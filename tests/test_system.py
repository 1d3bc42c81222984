"""End-to-end system behaviour tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_training_improves_loss(tmp_path):
    """A short real training run on the synthetic pipeline must reduce
    loss (end-to-end: data -> model -> optimizer -> checkpoints)."""
    from repro.launch.train import main
    losses = main(["--arch", "smollm-360m", "--smoke", "--steps", "20",
                   "--batch", "4", "--seq-len", "128",
                   "--ckpt-dir", str(tmp_path), "--save-every", "10",
                   "--log-every", "100"])
    assert losses[-1] < losses[0] - 0.1


def test_training_resumes_from_checkpoint(tmp_path):
    from repro.launch.train import main
    main(["--arch", "smollm-360m", "--smoke", "--steps", "10",
          "--batch", "2", "--seq-len", "64", "--ckpt-dir", str(tmp_path),
          "--save-every", "5", "--log-every", "100"])
    # second invocation starts from step 10's checkpoint and continues
    losses = main(["--arch", "smollm-360m", "--smoke", "--steps", "14",
                   "--batch", "2", "--seq-len", "64",
                   "--ckpt-dir", str(tmp_path), "--save-every", "5",
                   "--log-every", "100"])
    assert len(losses) == 4          # only steps 11..14 executed


def test_serving_continuous_batching():
    from repro.launch.serve import Server
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("smollm-360m")
    srv = Server(cfg, max_batch=3, max_len=64)
    rng = np.random.default_rng(0)
    for rid in range(3):
        assert srv.admit(rid, rng.integers(2, cfg.vocab_size, size=4))
    assert not srv.admit(99, rng.integers(2, cfg.vocab_size, size=4))
    for _ in range(6):
        srv.decode_round()
    assert all(len(s.generated) == 6 for s in srv.slots)


def test_server_places_params_and_cache_on_its_mesh():
    from repro.launch.serve import Server
    from repro.configs import get_smoke_config
    srv = Server(get_smoke_config("smollm-360m"), max_batch=2, max_len=32)
    for tree in (srv.params, srv.cache, srv.tokens):
        for x in jax.tree.leaves(tree):
            assert x.sharding.mesh == srv.ctx.mesh


def test_serve_main_names_the_device(capsys, monkeypatch, tmp_path):
    from repro.launch.serve import main
    # set, the variable leaves JAX's cache alone (JAX read it at import)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    main(["--arch", "smollm-360m", "--smoke", "--n-requests", "2",
          "--max-new", "3", "--max-len", "32"])
    out = capsys.readouterr().out
    dev = jax.devices()[0]
    assert f"{dev.platform} device(s), {dev.device_kind}" in out
    assert "CPU device(s)" not in out
    assert out.strip().endswith("OK")


def test_benchmark_harness_runs():
    """Every paper-table benchmark executes and emits its derived value."""
    import benchmarks.run as br
    rows = br.bench_table_ii()
    assert len(rows) == 9
    t3 = br.bench_table_iii()
    assert t3[0]["platform"].startswith("PICNIC")
    t4 = br.bench_table_iv()
    assert "_tile" in t4
    f8 = br.bench_fig8_ccpg()
    assert len(f8) == 3


def test_dryrun_artifacts_complete():
    """The committed dry-run sweep covers all 40 cells x 2 meshes for both
    variants with zero errors."""
    import json
    from pathlib import Path
    art = Path(__file__).resolve().parents[1] / "artifacts" / "dryrun"
    if not art.exists():
        pytest.skip("dry-run artifacts not generated yet")
    recs = [json.loads(f.read_text()) for f in art.glob("*.json")]
    assert not [r for r in recs if r["status"] == "error"]
    base1 = [r for r in recs if r["mesh"] == "pod1"
             and r.get("variant") == "baseline"]
    assert len(base1) == 40
    ok = sum(r["status"] == "ok" for r in base1)
    sk = sum(r["status"] == "skipped" for r in base1)
    assert (ok, sk) == (33, 7)
    # every ok cell has the three roofline terms + dominant
    for r in recs:
        if r["status"] == "ok":
            assert set(r["roofline"]) == {"compute_s", "memory_s",
                                          "collective_s"}
            assert r["dominant"] in ("compute_s", "memory_s",
                                     "collective_s")
