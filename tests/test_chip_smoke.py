"""chip_smoke.py at a CPU-sized config: its phases and its refusal to run
without a TPU.  The script itself is run on the chip; these tests keep its
phases working between chip runs."""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import models
from repro.configs import get_smoke_config

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


@pytest.mark.parametrize("where", ["in_repo", "alone_in_a_directory"])
def test_exits_nonzero_without_tpu(tmp_path, where):
    script = SCRIPT
    if where == "alone_in_a_directory":
        script = tmp_path / "chip_smoke.py"
        script.write_text(SCRIPT.read_text())
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=300, cwd=tmp_path, env=_cpu_env())
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    if where == "in_repo":
        assert "no TPU" in r.stderr


def test_serve_phase_fills_every_slot():
    cs = _load()
    cfg = get_smoke_config(cs.ARCH)
    srv, rep = cs.serve(cfg, max_len=64)
    assert rep["tokens"] == cs.MAX_BATCH * cs.MAX_NEW
    assert rep["params"] == cs.n_params(models.init_params(
        cfg, jax.random.PRNGKey(0)))
    assert rep["compile_s"] > 0
    prompt = cs.prompts(cfg, 1, cs.REF_PROMPT_LEN, cs.SEED + 1)[0]
    assert cs.forward_error(cfg, srv.params, prompt) <= cs.LOGITS_TOL


def test_logits_tolerance_rejects_other_weights():
    # the error of a wrong model is of order 1, far above the tolerance
    cs = _load()
    cfg = get_smoke_config(cs.ARCH)
    tokens = jnp.asarray(cs.prompts(cfg, 1, cs.REF_PROMPT_LEN, cs.SEED + 1))
    a, b = (models.forward(cfg, models.init_params(
        cfg, jax.random.PRNGKey(seed)), tokens)[0] for seed in (0, 1))
    assert cs.rel_l2(a, b) > 10 * cs.LOGITS_TOL


def test_four_chip_phases_on_four_host_devices():
    code = textwrap.dedent(f"""
        import importlib.util, jax
        from repro.configs import get_smoke_config
        spec = importlib.util.spec_from_file_location("cs", {str(SCRIPT)!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        devs = jax.devices()
        assert len(devs) == 4
        cfg = get_smoke_config(cs.ARCH)
        a = cs.server_step_errors(cfg, devs, max_len=64)
        b = cs.picnic_errors(cfg, devs, max_len=64)
        assert set(a) == {{"kv_rel_l2", "token_deficit"}}
        assert set(b) == {{"logits_rel_l2", "kv_rel_l2", "one_chip_vs_f32",
                          "picnic_vs_f32"}}
        assert cs.within_tolerance(a) and cs.within_tolerance(b), (a, b)
        print("ERRS", a, b)
    """)
    env = _cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "ERRS" in r.stdout
