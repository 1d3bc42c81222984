"""Cell grids through :func:`repro.launch.sweep`: every cell's report and
``kv_stats`` must equal the reference path's on the same cell — the
object recorder and an unmemoized `CycleModel`, as in
``test_fastpath._engine_pair`` — over mixed, randomized, paged,
overlap, dynamic-CCPG, deadline and prefill-heavy grids."""
import copy
import dataclasses

import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core import CycleModel, PicnicSimulator
from repro.launch import ServingConfig, SweepCell, sweep
from repro.launch.serving_engine import (ContinuousBatchingEngine,
                                         poisson_trace)
from repro.runtime.kv_cache import KVCacheConfig, kv_bytes_per_token


@pytest.fixture(scope="module")
def cfg():
    return get_config("llama3.2-1b")


def _hexdict(obj) -> dict:
    d = dataclasses.asdict(obj)
    return {k: (v.hex() if isinstance(v, float) else v) for k, v in d.items()}


def _reference_run(cell: SweepCell):
    """This cell alone on the reference path: a fresh simulator with an
    unmemoized cycle model (carrying the cell's DRAM-hub setting) and
    the object recorder."""
    sim = PicnicSimulator(cycle_model=CycleModel(memoize=False))
    if cell.sim is not None and cell.sim.ccpg_model.include_dram_hub:
        sim.ccpg_model.include_dram_hub = True
    eng = ContinuousBatchingEngine(
        cell.cfg, sim=sim,
        engine=dataclasses.replace(cell.engine, columnar_timeline=False))
    rep = eng.run([copy.copy(r) for r in cell.trace])
    return rep, eng.kv_stats


def _assert_cell_identical(res, cell: SweepCell):
    rep, kv = _reference_run(cell)
    assert res.key == cell.key
    assert _hexdict(res.report) == _hexdict(rep), cell.key
    if kv is None:
        assert res.kv_stats is None
    else:
        assert res.kv_stats.row() == kv.row(), cell.key


# ---------------------------------------------------------------------------
# sweep() == the reference path, per cell
# ---------------------------------------------------------------------------

def test_sweep_matches_scalar_mixed_grid(cfg):
    """A grid mixing batch sizes, CCPG and chunked prefill: every cell's
    report is byte-identical to the reference path's."""
    cells = []
    for mb in (1, 4, 8):
        for ccpg in (False, True):
            trace = poisson_trace(24, 40.0, seed=3, prompt_len=384,
                                  max_new=48)
            cells.append(SweepCell(
                key=f"b{mb}_g{int(ccpg)}", cfg=cfg, trace=trace,
                engine=ServingConfig(max_batch=mb, ccpg=ccpg,
                                     chunked_prefill_tokens=256)))
    results = sweep(cells)
    assert len(results) == len(cells)
    for res, cell in zip(results, cells):
        _assert_cell_identical(res, cell)


def test_sweep_single_cell_and_empty_grid(cfg):
    assert sweep([]) == []
    cell = SweepCell("only", cfg, poisson_trace(8, 30.0, seed=1,
                                                max_new=32))
    (res,) = sweep([cell])
    _assert_cell_identical(res, cell)


@settings(max_examples=6, deadline=None)
@given(rate=st.sampled_from([15.0, 45.0, 90.0]),
       mb=st.integers(min_value=1, max_value=8),
       ccpg=st.booleans(),
       chunk=st.sampled_from([0, 128]),
       seed=st.integers(min_value=0, max_value=5))
def test_sweep_property_random_cells(cfg, rate, mb, ccpg, chunk, seed):
    """Randomized 3-cell grids (shared default sim, varying prompt
    regimes) stay bit-identical to the reference path."""
    cells = [
        SweepCell(f"c{i}", cfg,
                  poisson_trace(10, rate, seed=seed + i,
                                prompt_len=pl, max_new=mn),
                  engine=ServingConfig(max_batch=mb, ccpg=ccpg,
                                       chunked_prefill_tokens=chunk))
        for i, (pl, mn) in enumerate(((128, 16), (512, 64), (96, 96)))
    ]
    for res, cell in zip(sweep(cells), cells):
        _assert_cell_identical(res, cell)


@settings(max_examples=6, deadline=None)
@given(n_blocks=st.integers(min_value=6, max_value=24),
       bt=st.sampled_from([16, 64, 256]),
       dram=st.sampled_from([0, 16]),
       share=st.booleans(),
       seed=st.integers(min_value=0, max_value=3))
def test_sweep_property_paged_cells(cfg, n_blocks, bt, dram, share, seed):
    """Paged/prefix cells: kv_stats rows (preemptions, spills, COW
    forks) match the reference path bit-for-bit."""
    kvc = KVCacheConfig(n_blocks=n_blocks, block_tokens=bt,
                        dram_blocks=dram, prefix_sharing=share,
                        bytes_per_token=kv_bytes_per_token(cfg))
    sim = PicnicSimulator()
    sim.ccpg_model.include_dram_hub = dram > 0
    trace = poisson_trace(12, 50.0, seed=seed, prompt_len=256, max_new=64,
                          prefix_len=192 if share else 0, prefix_frac=0.75)
    cell = SweepCell("paged", cfg, trace, sim=sim,
                     engine=ServingConfig(max_batch=4, ccpg=True,
                                          kv_cache=kvc))
    (res,) = sweep([cell])
    assert res.kv_stats is not None
    _assert_cell_identical(res, cell)


def test_sweep_shared_trace_object_not_mutated(cfg):
    """Grid builders reuse one trace list across cells; sweep() serves
    copies (TrackedRequest is mutable bookkeeping)."""
    trace = poisson_trace(8, 40.0, seed=0, max_new=24)
    snap = [dataclasses.astuple(r) for r in trace]
    cells = [SweepCell(f"c{i}", cfg, trace,
                       engine=ServingConfig(max_batch=1 + i))
             for i in range(3)]
    for res, cell in zip(sweep(cells), cells):
        _assert_cell_identical(res, cell)
    assert [dataclasses.astuple(r) for r in trace] == snap


# ---------------------------------------------------------------------------
# overlap / dynamic CCPG / TTFT deadlines
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine_kw, trace_kw", [
    (dict(overlap=0.5), {}),
    (dict(overlap=1.0), {}),
    (dict(ccpg=True, dynamic_ccpg=True), {}),
    (dict(), dict(deadline_ttft=0.25)),
    (dict(), dict(deadline_ttft=0.05)),
    (dict(overlap=0.25, ccpg=True, dynamic_ccpg=True),
     dict(deadline_ttft=0.1)),
])
def test_sweep_lifted_lanes_vectorized(cfg, engine_kw, trace_kw):
    """Overlap, dynamic CCPG and TTFT deadlines beside a default cell in
    one grid: both cells match the reference path."""
    trace = poisson_trace(8, 30.0, seed=2, max_new=24, **trace_kw)
    cell = SweepCell("fb", cfg, trace, engine=ServingConfig(**engine_kw))
    vanilla = SweepCell("ok", cfg, poisson_trace(8, 30.0, seed=2,
                                                 max_new=24))
    lifted, ok = sweep([cell, vanilla])
    _assert_cell_identical(lifted, cell)
    _assert_cell_identical(ok, vanilla)


@settings(max_examples=8, deadline=None)
@given(overlap=st.sampled_from([0.0, 0.25, 1.0]),
       dyn=st.booleans(),
       ttft=st.sampled_from([None, 0.05, 0.3]),
       chunk=st.sampled_from([0, 128]),
       mb=st.integers(min_value=1, max_value=6),
       seed=st.integers(min_value=0, max_value=4))
def test_sweep_property_lifted_lane_cells(cfg, overlap, dyn, ttft, chunk,
                                          mb, seed):
    """Randomized cells over overlap, dynamic CCPG, TTFT deadlines and
    chunking match the reference path."""
    trace_kw = {} if ttft is None else dict(deadline_ttft=ttft)
    trace = poisson_trace(10, 60.0, seed=seed, prompt_len=192, max_new=40,
                          **trace_kw)
    cell = SweepCell(
        "lift", cfg, trace,
        engine=ServingConfig(max_batch=mb, overlap=overlap,
                             ccpg=dyn, dynamic_ccpg=dyn,
                             chunked_prefill_tokens=chunk))
    (res,) = sweep([cell])
    _assert_cell_identical(res, cell)


@settings(max_examples=6, deadline=None)
@given(dyn=st.booleans(),
       overlap=st.sampled_from([0.0, 0.5]),
       ttft=st.sampled_from([None, 0.2]),
       seed=st.integers(min_value=0, max_value=3))
def test_sweep_property_paged_lifted_cells(cfg, dyn, overlap, ttft, seed):
    """Paged KV with overlap, dynamic CCPG and TTFT deadlines: block
    growth can preempt and change the queue head under a deadline."""
    kvc = KVCacheConfig(n_blocks=12, block_tokens=64, dram_blocks=8,
                        bytes_per_token=kv_bytes_per_token(cfg))
    sim = PicnicSimulator()
    sim.ccpg_model.include_dram_hub = True
    trace_kw = {} if ttft is None else dict(deadline_ttft=ttft)
    trace = poisson_trace(10, 50.0, seed=seed, prompt_len=256, max_new=64,
                          **trace_kw)
    cell = SweepCell("pl", cfg, trace, sim=sim,
                     engine=ServingConfig(max_batch=4, ccpg=True,
                                          dynamic_ccpg=dyn, overlap=overlap,
                                          kv_cache=kvc))
    (res,) = sweep([cell])
    assert res.kv_stats is not None
    _assert_cell_identical(res, cell)


def test_sweep_prefill_cruise_identical(cfg):
    """Prefill-dominated cells (long prompts streamed in 128-token
    chunks, tiny generation, spaced arrivals) match the reference."""
    trace = poisson_trace(6, 4.0, seed=9, prompt_len=8192, max_new=2)
    for kw in (dict(), dict(ccpg=True, dynamic_ccpg=True),
               dict(overlap=0.5)):
        cell = SweepCell("pf", cfg, trace,
                         engine=ServingConfig(chunked_prefill_tokens=128,
                                              **kw))
        (res,) = sweep([cell])
        _assert_cell_identical(res, cell)
