"""Process-level JAX set-up (``repro.jax_setup``), the Auto-axis mesh
builder every launcher uses, and the JAX APIs the distributed paths call
directly (``jax.shard_map``, ``Compiled.cost_analysis``).

Fast lane: the only subprocess pins a fresh process to host devices."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro import jax_setup
from repro.jax_setup import force_host_devices, use_compile_cache
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.sharding import ShardingCtx, shard_hint, use_sharding

ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# meshes: Auto axes, so shard_hint's with_sharding_constraint accepts them
# ---------------------------------------------------------------------------

def test_make_host_mesh_axes_are_auto():
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    assert mesh.devices.size == len(jax.devices())


def test_shard_hint_under_jit_on_host_mesh():
    mesh = make_host_mesh()
    ctx = ShardingCtx(mesh, {"act_btd": P("data", "model")})

    def f(x):
        with use_sharding(ctx):
            return shard_hint(x * 2, "act_btd")

    x = jnp.arange(8.0).reshape(2, 4)
    assert jnp.array_equal(jax.jit(f)(x), x * 2)


def test_shard_hint_rejects_explicit_axes():
    # why make_mesh exists: JAX's own default axes are Explicit, and
    # with_sharding_constraint refuses them
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    assert AxisType.Explicit in mesh.axis_types
    ctx = ShardingCtx(mesh, {"act_btd": P("data")})

    def f(x):
        with use_sharding(ctx):
            return shard_hint(x, "act_btd")

    with pytest.raises(Exception, match="Auto"):
        jax.jit(f)(jnp.ones((2, 4)))


def test_shard_map_runs_on_single_device_mesh():
    mesh = make_mesh((1,), ("model",))
    fn = jax.shard_map(lambda x: jax.lax.psum(x, "model"), mesh=mesh,
                       in_specs=P(), out_specs=P(), check_vma=False)
    assert float(jax.jit(fn)(jnp.float32(2.0))) == 2.0


def test_cost_analysis_on_real_compiled():
    # dryrun/collective_capture index the result as a dict
    f = jax.jit(lambda x: x @ x)
    c = f.lower(jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    d = c.cost_analysis()
    assert isinstance(d, dict) and d.get("flops", 0) > 0


# ---------------------------------------------------------------------------
# force_host_devices: CPU studies with many host devices
# ---------------------------------------------------------------------------

@pytest.fixture
def clean_env(monkeypatch):
    # setenv first so monkeypatch records the pre-test state (delenv on an
    # absent var records nothing and the writes under test would leak)
    for var in ("XLA_FLAGS", "JAX_PLATFORMS"):
        monkeypatch.setenv(var, "sentinel")
        monkeypatch.delenv(var)
    return monkeypatch


def test_force_host_devices_appends(clean_env):
    clean_env.setenv("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false")
    force_host_devices(8)
    assert os.environ["XLA_FLAGS"] == (
        "--xla_cpu_multi_thread_eigen=false "
        "--xla_force_host_platform_device_count=8")


def test_force_host_devices_respects_existing_count(clean_env):
    clean_env.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    force_host_devices(8)
    assert os.environ["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=4"


def test_force_host_devices_sets_when_unset(clean_env):
    force_host_devices(8)
    assert os.environ["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=8"


@pytest.mark.parametrize("inherited", [None, "tpu", "tpu,cpu", "cpu"])
def test_force_host_devices_pins_cpu_backend(clean_env, inherited):
    if inherited is not None:
        clean_env.setenv("JAX_PLATFORMS", inherited)
    force_host_devices(8)
    assert os.environ["JAX_PLATFORMS"] == "cpu"


def test_dryrun_import_pins_cpu_with_512_devices():
    # asked for the TPU, the dry-run still compiles on the host: importing
    # it pins the CPU backend before JAX loads
    env = dict(os.environ, JAX_PLATFORMS="tpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    code = ("import repro.launch.dryrun, jax; "
            "print(jax.devices()[0].platform, len(jax.devices()))")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.split()[-2:] == ["cpu", "512"]


# ---------------------------------------------------------------------------
# use_compile_cache: placed from outside when asked, else one in-repo path
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_config(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "sentinel")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_left_to_env_var(cache_config, tmp_path):
    cache_config.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_repo_path(cache_config):
    first = use_compile_cache()
    second = use_compile_cache()
    assert first == second == ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(first)


def test_compile_cache_dir_is_gitignored():
    ignored = (ROOT / ".gitignore").read_text().split()
    assert jax_setup.COMPILE_CACHE_DIR.relative_to(ROOT).as_posix() + "/" \
        in ignored
