"""The comparison that decides ``correct`` fails what it must, at sizes
the CPU holds: the float8 control, and the timed path broken underneath a
whole run (a decode step that returns its cache unchanged, half of the
batch left out of a step, a served token altered where it is produced).
The exchange between chips is not among them: every cell is one chip."""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH_DIR, CELLS


def limit(workload):
    return json.loads((BENCH_DIR / "limits" / f"{workload}.json").read_text())[
        "token_deficit"]["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_float8_control_fails_where_the_program_passes(control_root, workload):
    import calibrate
    for seed in (3, 2**31 + 5, 12345):
        r = calibrate.readings(control_root, workload, seed, control=True)
        assert r["served_max"] <= limit(workload) < r["control_max"], r


def _fault_unchanged_cache(decode, V):
    def step(p, c, t, n):
        kept = jax.tree.map(jnp.copy, c)
        tok, _ = decode(p, c, t, n)
        return tok, kept
    return step


def _fault_half_batch(decode, V):
    def step(p, c, t, n):
        tok, c = decode(p, c, t, n)
        half = tok.shape[0] // 2
        return tok.at[half:].set(0), c
    return step


def _fault_altered_token(decode, V):
    def step(p, c, t, n):
        tok, c = decode(p, c, t, n)
        return (tok + 1) % V, c
    return step


@pytest.mark.parametrize("fault", [_fault_unchanged_cache, _fault_half_batch,
                                   _fault_altered_token])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, workload, fault):
    import harness
    cell = harness.Cell(tiny_root, workload)
    engine = cell.module(cell.traffic["engine"]).Engine
    init = engine.__init__

    def broken_init(self, *a, **k):
        init(self, *a, **k)
        self.decode = fault(self.decode, self.V)

    monkeypatch.setattr(engine, "__init__", broken_init)
    result = harness.run_cell(Path(tiny_root), workload, seed=2**31 + 77, seconds=0.3,
                              trace=False, devices=jax.devices(),
                              t_start=time.perf_counter(), kind="TPU v5 lite")
    assert not result["correct"], result["checks"]
