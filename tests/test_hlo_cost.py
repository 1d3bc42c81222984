"""Regression tests for the trip-count-aware HLO cost parser — the
methodological backbone of the roofline numbers (EXPERIMENTS.md §Dry-run).

Runs in a subprocess with 4 host devices so the main process keeps its
single-device view."""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_sub(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, {src!r})
        import jax
        import jax.numpy as jnp
        from repro.launch.mesh import make_mesh
    """).format(src=SRC) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-2000:]}"
    return r.stdout


@pytest.mark.slow
def test_scan_flops_counted_with_trip_count():
    run_sub("""
    from repro.launch import hlo_cost

    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    ws = jax.ShapeDtypeStruct((10, 512, 512), jnp.bfloat16)
    x = jax.ShapeDtypeStruct((64, 512), jnp.bfloat16)
    c = jax.jit(f).lower(ws, x).compile()
    # the raw xla number undercounts by the trip count...
    raw = c.cost_analysis()["flops"]
    analytic = 10 * 2 * 64 * 512 * 512
    assert raw < 0.2 * analytic
    # ...the parser does not
    cost = hlo_cost.analyze(c.as_text(), 4)
    assert abs(cost.flops / analytic - 1) < 0.05, cost.flops
    """)


@pytest.mark.slow
def test_collectives_and_tp_flops_exact():
    run_sub("""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import hlo_cost
    mesh = make_mesh((4,), ("model",))

    def g(w, x):
        return x @ w

    w = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
    x = jax.ShapeDtypeStruct((64, 512), jnp.bfloat16)
    fn = jax.jit(g, in_shardings=(NamedSharding(mesh, P("model", None)),
                                  NamedSharding(mesh, P())),
                 out_shardings=NamedSharding(mesh, P()))
    c = fn.lower(w, x).compile()
    cost = hlo_cost.analyze(c.as_text(), 4)
    assert cost.flops == 2 * 64 * 512 * 512 / 4       # per-chip
    assert "all-reduce" in cost.coll
    ar = cost.coll["all-reduce"]
    # ring all-reduce of the (64,512) f32 output: 2*(g-1)/g*bytes
    expect = 2 * (3/4) * 64 * 512 * 4
    assert abs(ar["wire_bytes"] / expect - 1) < 0.05
    """)


@pytest.mark.slow
def test_nested_scan_trip_products():
    run_sub("""
    from repro.launch import hlo_cost

    def f(ws, x):
        def outer(x, w):
            def inner(x, _):
                return jnp.tanh(x @ w), None
            y, _ = jax.lax.scan(inner, x, None, length=5)
            return y, None
        y, _ = jax.lax.scan(outer, x, ws)
        return y

    ws = jax.ShapeDtypeStruct((6, 256, 256), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 256), jnp.float32)
    c = jax.jit(f).lower(ws, x).compile()
    cost = hlo_cost.analyze(c.as_text(), 4)
    analytic = 6 * 5 * 2 * 32 * 256 * 256
    assert abs(cost.flops / analytic - 1) < 0.1, (cost.flops, analytic)
    """)
