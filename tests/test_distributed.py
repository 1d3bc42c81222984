"""Multi-device numerical-equivalence tests.

These spawn subprocesses with ``--xla_force_host_platform_device_count=8``
(the main test process must keep the real single-device CPU view).  Each
subprocess asserts that the sharded/shard_map execution paths produce the
SAME numerics as the single-device reference:

  * picnic decode (sequence-sharded KV + partial-softmax psum) == baseline
  * sp_attention (shard_map ring-lite) == single-device flash
  * sharded train_step loss == unsharded loss
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_sub(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, {src!r})
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import make_mesh
        assert len(jax.devices()) == 8
    """).format(src=SRC) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_picnic_decode_matches_baseline():
    run_sub("""
    import dataclasses
    from jax.sharding import PartitionSpec as P
    from repro import models
    from repro.configs import get_smoke_config
    from repro.sharding import ShardingCtx, use_sharding
    from repro.sharding import specs as sp

    cfg = dataclasses.replace(get_smoke_config("yi-34b"), dtype="float32")
    mesh = make_mesh((2, 4), ("data", "model"))
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 4, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    _, _, cache = models.forward(cfg, params, toks[:, :-1],
                                 collect_cache=True, kv_max=S)
    ref_logits, _ = models.decode_step(cfg, params, toks[:, -1:], cache,
                                       jnp.int32(S))

    rules = sp.activation_rules(cfg, mesh, "decode")
    ctx = ShardingCtx(mesh, rules, {
        "picnic_decode": True, "seq_axes": ("model",), "dp_axes": ("data",)})
    def step(params, cache, tok, n):
        with use_sharding(ctx):
            return models.decode_step(cfg, params, tok, cache, n)
    out, _ = jax.jit(step)(params, cache, toks[:, -1:], jnp.int32(S))
    err = float(jnp.max(jnp.abs(out - ref_logits)))
    rel = err / float(jnp.max(jnp.abs(ref_logits)))
    assert rel < 1e-4, rel
    print("picnic decode rel err", rel)
    """)


@pytest.mark.slow
def test_sp_attention_matches_flash():
    run_sub("""
    from repro.models.attention import flash_attention, sp_flash_attention
    mesh = make_mesh((2, 4), ("data", "model"))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    ref = flash_attention(q, k, v, causal=True)
    out = jax.jit(lambda q, k, v: sp_flash_attention(
        q, k, v, mesh=mesh, dp_axes=("data",), seq_axes=("model",),
        causal=True, q_chunk=8, kv_chunk=16))(q, k, v)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-4, err
    print("sp attention err", err)

    # sliding window variant
    refw = flash_attention(q, k, v, causal=True, window=24)
    outw = jax.jit(lambda q, k, v: sp_flash_attention(
        q, k, v, mesh=mesh, dp_axes=("data",), seq_axes=("model",),
        causal=True, window=24, q_chunk=8, kv_chunk=16))(q, k, v)
    assert float(jnp.max(jnp.abs(outw - refw))) < 1e-4
    """)


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    run_sub("""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.launch.steps import init_train_state, make_train_step
    from repro.sharding import ShardingCtx, use_sharding
    from repro.sharding import specs as sp

    cfg = dataclasses.replace(get_smoke_config("smollm-360m"),
                              dtype="float32")
    mesh = make_mesh((2, 4), ("data", "model"))
    params, opt = init_train_state(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    step = make_train_step(cfg)
    _, _, m_ref = jax.jit(step)(params, opt, batch)

    rules = sp.activation_rules(cfg, mesh, "train")
    ctx = ShardingCtx(mesh, rules, {
        "sp_attention": True, "seq_axes": ("model",), "dp_axes": ("data",)})
    params2, opt2 = init_train_state(cfg, jax.random.PRNGKey(0))
    pspecs = sp.param_specs(cfg, jax.eval_shape(lambda: params2), mesh,
                            "train")
    def wrapped(p, o, b):
        with use_sharding(ctx):
            return step(p, o, b)
    fn = jax.jit(wrapped, in_shardings=(sp.to_named(pspecs, mesh),
                                        None, None))
    _, _, m_sh = fn(params2, opt2, batch)
    d = abs(float(m_sh["loss"]) - float(m_ref["loss"]))
    assert d < 2e-3, (float(m_sh["loss"]), float(m_ref["loss"]))
    print("sharded loss delta", d)
    """)


@pytest.mark.slow
def test_compressed_psum_matches_exact():
    run_sub("""
    from repro.runtime import compressed_allreduce
    mesh = make_mesh((8,), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 128)) * 1e-3

    out, _ = jax.jit(lambda g, e: compressed_allreduce(
        {"g": g}, {"g": e}, mesh, "data"))(g, jnp.zeros_like(g))
    exact = jnp.sum(g, axis=0, keepdims=True)
    rel = float(jnp.linalg.norm(out["g"][:1] - exact)
                / jnp.linalg.norm(exact))
    assert rel < 0.02, rel
    print("compressed psum rel err", rel)
    """)


@pytest.mark.slow
def test_pipeline_parallel_matches_reference():
    """GPipe-over-pod-axis: pipelined loss == single-device loss, and a
    few PP train steps reduce it (bwd pipeline via shard_map autodiff)."""
    run_sub("""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.launch.steps import make_loss_fn
    from repro.launch.pipeline import pp_forward, make_pp_train_step
    from repro import models
    from repro.optim import make_optimizer

    cfg = dataclasses.replace(get_smoke_config("smollm-360m"),
                              dtype="float32", n_layers=4, remat=False)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                              cfg.vocab_size)
    ref_loss, _ = make_loss_fn(cfg)(
        params, {"tokens": toks, "labels": jnp.roll(toks, -1, 1)})
    pl, _ = jax.jit(lambda p, t: pp_forward(
        cfg, p, t, mesh=mesh, stage_axis="pod", n_micro=4,
        dp_axes=("data",)))(params, toks)
    assert abs(float(ref_loss) - float(pl)) < 1e-4

    opt_init, _ = make_optimizer(cfg.optimizer)
    step = jax.jit(make_pp_train_step(cfg, mesh, stage_axis="pod",
                                      n_micro=4, base_lr=2e-3, warmup=0,
                                      total_steps=100))
    opt = opt_init(params)
    losses = []
    for _ in range(3):
        params, opt, m = step(params, opt, toks)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    print("pp losses", losses)
    """)
