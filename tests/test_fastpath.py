"""Fast simulation core (ISSUE 5): the columnar TimelineIR recorder, the
SoA serving loop and the memoized CycleModel must be BIT-IDENTICAL to
the reference object path — property-tested on random traces, locked on
the committed golden, and exercised through every consumer (reports,
kv_stats, chrome traces, O(1) aggregate queries)."""
import copy
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core import (C2CTransfer, ClusterWake, ComputeSpan, CycleModel,
                        EnergySample, PicnicSimulator, Timeline, TokenEmit)
from repro.core.scheduling import allocate_chiplets
from repro.launch.serving_engine import (ContinuousBatchingEngine,
                                         ServingConfig, poisson_trace,
                                         replay_trace)
from repro.runtime.kv_cache import KVCacheConfig, kv_bytes_per_token

GOLDEN = json.loads(
    (Path(__file__).parent / "golden" / "timeline_golden.json").read_text())


def _hexdict(obj) -> dict:
    d = dataclasses.asdict(obj)
    d.pop("queue_depth", None)
    # per-node attribution (ISSUE 9 fleet) stays None outside a fleet and
    # is absent from the committed golden — drop it exactly when unset
    for k in ("node_id", "pool"):
        if k in d and d[k] is None:
            d.pop(k)
    return {k: (v.hex() if isinstance(v, float) else v) for k, v in d.items()}


@pytest.fixture(scope="module")
def cfg():
    return get_config("llama3.2-1b")


def _engine_pair(cfg, **engine_kw):
    """(fast, reference): identical policy/config, different recorders."""
    fast = ContinuousBatchingEngine(
        cfg, sim=PicnicSimulator(), engine=ServingConfig(**engine_kw))
    ref = ContinuousBatchingEngine(
        cfg, sim=PicnicSimulator(cycle_model=CycleModel(memoize=False)),
        engine=ServingConfig(columnar_timeline=False, **engine_kw))
    return fast, ref


# ---------------------------------------------------------------------------
# Columnar recorder == object recorder
# ---------------------------------------------------------------------------

def _drive(tl: Timeline) -> None:
    tl.compute(1e-3, kind="prefill", power_W=4.0, cycles=123, batch=2,
               name="p0")
    tl.c2c(4096, phase="prefill", t0=0.0, dur_s=1e-6)
    tl.token(3, request_id=7)
    tl.compute(2e-3, kind="decode", power_W=4.0, cycles=456, batch=3)
    tl.token_each([1, 2, 5])
    tl.wake(1e-4, power_W=2.0, cycles=99, cluster=1)
    tl.c2c(128, dur_s=5e-7, phase="kv_fetch", advance=True, power_W=3.0)
    tl.sleep(5e-4, power_W=0.5)
    tl.sleep(1e-3, t0=0.0, advance=False, power_W=9.0)
    tl.sample(1.25)


def test_columnar_matches_object_recorder_exactly():
    col, obj = Timeline(columnar=True), Timeline(columnar=False)
    assert col.columnar and not obj.columnar
    _drive(col)
    _drive(obj)
    # materialized dataclass stream, cursor and every running integral
    assert col.events == obj.events
    assert col.n_events == obj.n_events == len(obj.events)
    for attr in ("now", "energy_J", "busy_s", "idle_s", "c2c_bytes",
                 "tokens", "occupancy_s"):
        assert getattr(col, attr) == getattr(obj, attr), attr
    # O(1) aggregate queries agree between modes (and with a raw scan)
    for cls in (ComputeSpan, C2CTransfer, ClusterWake, EnergySample,
                TokenEmit):
        assert col.count(cls) == obj.count(cls)
    for kind in (None, "prefill", "decode"):
        assert col.cycles(ComputeSpan, kind=kind) \
            == obj.cycles(ComputeSpan, kind=kind) \
            == sum(e.cycles for e in obj.events
                   if isinstance(e, ComputeSpan)
                   and (kind is None or e.kind == kind))
        assert col.span_seconds(ComputeSpan, kind=kind) \
            == obj.span_seconds(ComputeSpan, kind=kind)
    assert col.cycles(ClusterWake) == obj.cycles(ClusterWake) == 99
    assert col.power_trace() == obj.power_trace()
    assert col.total_energy_J() == obj.total_energy_J()
    # chrome export byte-identical across modes
    assert json.dumps(col.to_chrome_trace()) \
        == json.dumps(obj.to_chrome_trace())


def test_columnar_events_cache_extends_incrementally():
    tl = Timeline()
    tl.compute(1e-3, kind="decode", cycles=1)
    first = tl.events
    assert len(first) == 2                    # span + auto sample
    tl.token(1, request_id=0)
    again = tl.events
    assert again is first and len(again) == 3  # same cache, extended
    assert again[:2] == first[:2]


def test_column_accessor_matches_events(cfg):
    for columnar in (True, False):
        tl = Timeline(columnar=columnar)
        PicnicSimulator().run(cfg, 256, 32, ccpg=True, timeline=tl)
        durs = tl.column(ComputeSpan, "dur_s")
        assert durs == [e.dur_s for e in tl.events
                        if isinstance(e, ComputeSpan)]
        assert tl.column(TokenEmit, "n") == \
            [e.n for e in tl.events if isinstance(e, TokenEmit)]
        with pytest.raises(KeyError):
            tl.column(ComputeSpan, "nbytes")


def test_simulator_identical_on_both_recorders(cfg):
    for kw in ({}, {"ccpg": True}, {"ccpg": True, "dynamic_ccpg": True},
               {"overlap": 0.5}):
        col, obj = Timeline(columnar=True), Timeline(columnar=False)
        r_col = PicnicSimulator().run(cfg, 384, 64, timeline=col, **kw)
        r_obj = PicnicSimulator(cycle_model=CycleModel(memoize=False)) \
            .run(cfg, 384, 64, timeline=obj, **kw)
        assert _hexdict(r_col) == _hexdict(r_obj)
        assert col.events == obj.events


# ---------------------------------------------------------------------------
# Golden byte-identity with the columnar recorder (and the object one)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("columnar", [True, False])
def test_serving_golden_byte_identical_both_recorders(cfg, columnar):
    """The committed golden (captured from the seed code) is reproduced
    byte-for-byte by BOTH recording modes of the SoA engine."""
    for key in sorted(GOLDEN["serving"]):
        eng = ContinuousBatchingEngine(
            cfg, engine=ServingConfig(max_batch=4, ccpg=(key == "ccpg=True"),
                                     columnar_timeline=columnar))
        rep = eng.run(poisson_trace(24, rate_rps=40, seed=0, prompt_len=256,
                                    max_new=32))
        assert eng.timeline.columnar == columnar
        assert _hexdict(rep) == GOLDEN["serving"][key]


def test_table_ii_golden_byte_identical_columnar():
    for key in sorted(GOLDEN["table_ii"]):
        arch, ctx, cc = key.split("/")
        tl = Timeline(columnar=True)
        r = PicnicSimulator().run(get_config(arch), int(ctx), int(ctx),
                                  ccpg=(cc == "ccpg=True"), timeline=tl)
        assert _hexdict(r) == GOLDEN["table_ii"][key]


# ---------------------------------------------------------------------------
# SoA engine == reference engine on randomized traces
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(n=st.integers(2, 20), batch=st.integers(1, 8),
       rate=st.floats(10.0, 200.0), seed=st.integers(0, 99),
       ccpg=st.booleans())
def test_fast_engine_matches_reference_on_poisson(n, batch, rate, seed,
                                                  ccpg):
    cfg = get_config("llama3.2-1b")
    fast, ref = _engine_pair(cfg, max_batch=batch, ccpg=ccpg)
    trace = poisson_trace(n, rate_rps=rate, seed=seed, prompt_len=192,
                          max_new=24)
    r_fast = fast.run(list(trace))
    r_ref = ref.run(list(trace))
    assert _hexdict(r_fast) == _hexdict(r_ref)
    assert r_fast.queue_depth == r_ref.queue_depth
    assert fast.timeline.events == ref.timeline.events
    assert fast.events == ref.events


@settings(max_examples=6, deadline=None)
@given(n=st.integers(2, 10), seed=st.integers(0, 99),
       n_blocks=st.integers(24, 60), dram=st.integers(0, 60),
       chunk=st.sampled_from([0, 64]))
def test_fast_engine_matches_reference_paged(n, seed, n_blocks, dram,
                                             chunk):
    """Randomized PAGED traces: identical reports, kv_stats AND engine
    event logs through preemption/spill/chunked-prefill paths."""
    cfg = get_config("llama3.2-1b")
    rng = np.random.default_rng(seed)
    rows = [(float(rng.uniform(0, 0.05)), int(rng.integers(16, 300)),
             int(rng.integers(1, 40))) for _ in range(n)]
    kvc = KVCacheConfig(n_blocks=n_blocks, block_tokens=16,
                        dram_blocks=dram,
                        bytes_per_token=kv_bytes_per_token(cfg))
    kw = dict(max_batch=4, ccpg=True, kv_cache=kvc,
              chunked_prefill_tokens=chunk)
    fast, ref = _engine_pair(cfg, **kw)
    r_fast = fast.run(replay_trace(rows))
    r_ref = ref.run(replay_trace(rows))
    assert _hexdict(r_fast) == _hexdict(r_ref)
    assert fast.kv_stats.row() == ref.kv_stats.row()
    assert fast.events == ref.events
    assert fast.timeline.events == ref.timeline.events


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 99), n_blocks=st.integers(32, 80),
       dram=st.sampled_from([0, 40]), chunk=st.sampled_from([0, 64]),
       prefix_len=st.sampled_from([96, 120]))
def test_fast_engine_matches_reference_prefix_sharing(seed, n_blocks,
                                                      dram, chunk,
                                                      prefix_len):
    """Randomized PREFIX-SHARING traces (adopt/COW/register on top of
    preemption/spill/chunked prefill): the SoA fast core and the
    reference recorder must stay bit-identical — reports, kv_stats
    (including the new sharing counters) and both event streams."""
    cfg = get_config("llama3.2-1b")
    kvc = KVCacheConfig(n_blocks=n_blocks, block_tokens=16,
                        dram_blocks=dram,
                        bytes_per_token=kv_bytes_per_token(cfg),
                        prefix_sharing=True)
    kw = dict(max_batch=4, ccpg=True, kv_cache=kvc,
              chunked_prefill_tokens=chunk)
    trace = poisson_trace(10, rate_rps=80, seed=seed, prompt_len=160,
                          max_new=24, prefix_len=prefix_len,
                          prefix_frac=0.8, prefix_groups=2)
    fast, ref = _engine_pair(cfg, **kw)
    r_fast = fast.run(list(trace))
    r_ref = ref.run(list(trace))
    assert _hexdict(r_fast) == _hexdict(r_ref)
    assert fast.kv_stats.row() == ref.kv_stats.row()
    assert fast.events == ref.events
    assert fast.timeline.events == ref.timeline.events


def test_fast_engine_matches_reference_with_deadlines(cfg):
    rows = [(0.0, 256, 64), (0.01, 64, 8, 0.02), (0.02, 32, 4, None),
            (0.03, 128, 16, 0.5)]
    fast, ref = _engine_pair(cfg, max_batch=2, decode_quantum=64)
    r_fast = fast.run(replay_trace(rows))
    r_ref = ref.run(replay_trace(rows))
    assert _hexdict(r_fast) == _hexdict(r_ref)
    assert fast.events == ref.events


def _kv(cfg, n_blocks, dram_blocks=0, prefix_sharing=False):
    return KVCacheConfig(n_blocks=n_blocks, block_tokens=16,
                         dram_blocks=dram_blocks, prefix_sharing=prefix_sharing,
                         bytes_per_token=kv_bytes_per_token(cfg))


_UNSORTED_ROWS = [(0.3, 64, 8), (0.0, 128, 16, 0.05), (0.2, 32, 4),
                  (0.1, 256, 8), (0.05, 16, 2)]


@pytest.mark.parametrize("arch, engine_kw, trace, witness", [
    pytest.param(
        "llama3.2-1b", lambda c: dict(max_batch=4, decode_quantum=1),
        lambda: poisson_trace(16, 80.0, seed=1, prompt_len=256, max_new=32),
        lambda rep, kv: rep.finished == 16, id="decode_quantum_1"),
    pytest.param(
        "llama3.2-1b", lambda c: dict(max_batch=4, decode_quantum=16),
        lambda: poisson_trace(16, 80.0, seed=1, prompt_len=256, max_new=32),
        lambda rep, kv: rep.max_queue_depth > 8, id="decode_quantum_16"),
    pytest.param(
        "llama3.2-1b", lambda c: dict(max_batch=2, queue_limit=2),
        lambda: poisson_trace(24, 400.0, seed=2, prompt_len=512, max_new=32),
        lambda rep, kv: rep.rejected > 0, id="queue_limit_rejects"),
    pytest.param(
        "llama3.2-1b", lambda c: dict(max_batch=4, kv_cache=_kv(c, 12)),
        lambda: poisson_trace(10, 80.0, seed=3, prompt_len=64, max_new=64),
        lambda rep, kv: kv.preemptions > 0, id="paged_preempts"),
    pytest.param(
        "llama3.2-1b",
        lambda c: dict(max_batch=4, ccpg=True, kv_cache=_kv(c, 16, 64)),
        lambda: poisson_trace(10, 80.0, seed=4, prompt_len=96, max_new=64),
        lambda rep, kv: kv.spilled_blocks > 0 and kv.dram_read_bytes > 0,
        id="dram_spills"),
    pytest.param(
        "llama3.2-1b",
        lambda c: dict(max_batch=4,
                       kv_cache=_kv(c, 64, prefix_sharing=True)),
        lambda: poisson_trace(12, 80.0, seed=5, prompt_len=160, max_new=24,
                              prefix_len=120, prefix_frac=0.8,
                              prefix_groups=2, deadline_ttft=0.05),
        lambda rep, kv: kv.cow_forks > 0 and kv.prefix_hits > 0,
        id="prefix_cow_deadlines"),
    pytest.param(
        "llama3.2-1b",
        lambda c: dict(max_batch=4, overlap=1.0, kv_cache=_kv(c, 24, 16)),
        lambda: poisson_trace(10, 60.0, seed=6, prompt_len=128, max_new=48),
        lambda rep, kv: kv.preemptions > 0, id="overlap_1_paged"),
    pytest.param(
        "llama3.2-1b",
        lambda c: dict(max_batch=4, ccpg=True, kv_cache=_kv(c, 24, 16)),
        lambda: poisson_trace(10, 60.0, seed=7, prompt_len=128, max_new=48),
        lambda rep, kv: kv.spilled_blocks > 0, id="static_ccpg_paged"),
    pytest.param(
        "llama3.2-1b",
        lambda c: dict(max_batch=4, ccpg=True, dynamic_ccpg=True,
                       overlap=0.5, kv_cache=_kv(c, 24, 16)),
        lambda: poisson_trace(10, 60.0, seed=8, prompt_len=128, max_new=48),
        lambda rep, kv: kv.spilled_blocks > 0,
        id="dynamic_ccpg_overlap_half_paged"),
    pytest.param(
        "llama3.2-1b",
        lambda c: dict(max_batch=4, chunked_prefill_tokens=64,
                       kv_cache=_kv(c, 64, prefix_sharing=True)),
        lambda: poisson_trace(12, 80.0, seed=9, prompt_len=320, max_new=16,
                              prefix_len=256, prefix_frac=0.8),
        lambda rep, kv: kv.prefix_hits > 0, id="chunk_64_prefix_sharing"),
    pytest.param(
        "llama3.2-1b", lambda c: dict(max_batch=16),
        lambda: poisson_trace(48, 2000.0, seed=10, prompt_len=256,
                              max_new=64, deadline_ttft=0.02),
        lambda rep, kv: rep.max_queue_depth > 16, id="batch_16_saturated"),
    pytest.param(
        "llama3.2-1b", lambda c: dict(max_batch=4),
        lambda: list(reversed(replay_trace(_UNSORTED_ROWS))),
        lambda rep, kv: rep.finished == len(_UNSORTED_ROWS),
        id="replay_unsorted_arrivals"),
    pytest.param(
        "llama3-8b",
        lambda c: dict(max_batch=4, ccpg=True, chunked_prefill_tokens=128),
        lambda: poisson_trace(8, 20.0, seed=11, prompt_len=256, max_new=32),
        lambda rep, kv: rep.finished == 8, id="llama3_8b_chunked_ccpg"),
])
def test_fast_engine_matches_reference_on_settings(arch, engine_kw, trace,
                                                   witness):
    """The fast engine (columnar recorder, memoized cycle model, inline
    affine decode pricing over a running context sum) against the
    reference path on settings the cell grids of tests/test_engine_grid.py
    leave out.  ``witness`` checks that each case reaches the path it
    names.  The defect each case fails at:

    * decode_quantum_1, decode_quantum_16, queue_limit_rejects,
      batch_16_saturated, replay_unsorted_arrivals: a deferred finish
      that releases its slot before syncing the request's final context,
      so the running context sum drifts;
    * paged_preempts: a preempted resident's context left in the sum;
    * dram_spills: the columnar recorder dropping the power sample of an
      advancing remote KV read;
    * prefix_cow_deadlines: the sum seeded from the prompt length instead
      of the admitted request's context;
    * overlap_1_paged: the affine price, which serializes every C2C
      cycle, used at overlap > 0;
    * static_ccpg_paged: the affine price without the static-CCPG wake
      residue;
    * dynamic_ccpg_overlap_half_paged: the columnar recorder losing a
      ClusterWake's cluster;
    * chunk_64_prefix_sharing, llama3_8b_chunked_ccpg: the prefill memo
      keyed without the cached context before the chunk.
    """
    cfg = get_config(arch)
    fast, ref = _engine_pair(cfg, **engine_kw(cfg))
    reqs = trace()
    r_fast = fast.run([copy.copy(r) for r in reqs])
    r_ref = ref.run([copy.copy(r) for r in reqs])
    assert witness(r_fast, fast.kv_stats)
    assert _hexdict(r_fast) == _hexdict(r_ref)
    assert r_fast.queue_depth == r_ref.queue_depth
    if fast.kv_stats is not None:
        assert fast.kv_stats.row() == ref.kv_stats.row()
    assert fast.events == ref.events
    assert fast.timeline.events == ref.timeline.events


# ---------------------------------------------------------------------------
# Memoized CycleModel == direct walk
# ---------------------------------------------------------------------------

def test_memoized_decode_costs_match_direct_walk(cfg):
    alloc = allocate_chiplets(cfg)
    memo, direct = CycleModel(), CycleModel(memoize=False)
    rng = np.random.default_rng(0)
    for _ in range(40):
        b = int(rng.integers(1, 12))
        contexts = [int(rng.integers(1, 4096)) for _ in range(b)]
        for overlap in (0.0, 0.37):
            assert memo.batched_token_decode_cycles(
                cfg, alloc, contexts, overlap=overlap) \
                == direct.batched_token_decode_cycles(
                    cfg, alloc, contexts, overlap=overlap)
    assert memo.batched_token_decode_cycles(cfg, alloc, []) == (0, 0)


def test_memoized_prefill_costs_match_direct_walk(cfg):
    alloc = allocate_chiplets(cfg)
    memo, direct = CycleModel(), CycleModel(memoize=False)
    for chunk, before in [(1, 0), (512, 0), (512, 512), (100, 3),
                          (2048, 0), (64, 8192)]:
        for _ in range(2):      # second call = cache hit
            assert memo.prefill_chunk_cycles(cfg, alloc, chunk, before) \
                == direct.prefill_chunk_cycles(cfg, alloc, chunk, before)
    assert memo.prefill_cycles(cfg, alloc, 777) \
        == direct.prefill_cycles(cfg, alloc, 777)


def test_calibration_mutation_invalidates_memo(cfg):
    """Mutating any calibrated constant (calibrate() does this to alpha)
    must never serve a stale cached cost."""
    alloc = allocate_chiplets(cfg)
    cm = CycleModel()
    before = cm.batched_token_decode_cycles(cfg, alloc, [512] * 4)
    p_before = cm.prefill_cycles(cfg, alloc, 512)
    cm.alpha = 0.5
    cm.ctx_cycles_per_pos = 100.0
    after = cm.batched_token_decode_cycles(cfg, alloc, [512] * 4)
    p_after = cm.prefill_cycles(cfg, alloc, 512)
    fresh = CycleModel(alpha=0.5, ctx_cycles_per_pos=100.0,
                       memoize=False)
    assert after == fresh.batched_token_decode_cycles(cfg, alloc, [512] * 4)
    assert p_after == fresh.prefill_cycles(cfg, alloc, 512)
    assert after != before and p_after != p_before


def test_recalibration_between_runs_of_one_engine(cfg):
    """One engine run twice on one simulator, with ``alpha`` and the
    per-layer overhead changed on its cycle model between the runs: the
    second run prices with the new constants, exactly as a fresh engine
    built with them does.  Fails when a calibration change leaves a
    decode or prefill memo entry in use."""
    trace = poisson_trace(10, 40.0, seed=5, prompt_len=384, max_new=32)
    sim = PicnicSimulator()
    eng = ContinuousBatchingEngine(
        cfg, sim=sim, engine=ServingConfig(max_batch=8,
                                           chunked_prefill_tokens=128))
    before = eng.run([copy.copy(r) for r in trace])
    cm = sim.cycle_model
    cm.alpha = cm.alpha * 0.5
    cm.layer_fixed_cycles = cm.layer_fixed_cycles * 2.0
    after = eng.run([copy.copy(r) for r in trace])
    fresh_sim = PicnicSimulator()
    fresh_sim.cycle_model.alpha = cm.alpha
    fresh_sim.cycle_model.layer_fixed_cycles = cm.layer_fixed_cycles
    fresh = ContinuousBatchingEngine(
        cfg, sim=fresh_sim, engine=eng.engine).run(
            [copy.copy(r) for r in trace])
    assert after.wall_s != before.wall_s
    assert _hexdict(after) == _hexdict(fresh)


def test_engines_sharing_simulator_and_allocation_match_solo(cfg):
    """Engines of two models on one simulator, two of them on one
    allocation, run in turn: each reports what it reports alone on a
    simulator of its own.  Fails when a decode or prefill memo key omits
    the model or the allocation, so one model is priced at another's
    costs."""
    other = get_config("smollm-360m")
    sim = PicnicSimulator()
    alloc = allocate_chiplets(cfg, sim.tile)
    trace = poisson_trace(10, 40.0, seed=6, prompt_len=256, max_new=24)
    plans = [(cfg, alloc, dict(max_batch=2)),
             (other, None, dict(max_batch=4, chunked_prefill_tokens=64)),
             (cfg, alloc, dict(max_batch=4, chunked_prefill_tokens=64)),
             (other, None, dict(max_batch=2))]
    shared = [ContinuousBatchingEngine(c, sim=sim, alloc=a,
                                       engine=ServingConfig(**kw))
              for c, a, kw in plans]
    for eng in shared:
        assert eng.sim is sim
    assert shared[0].alloc is shared[2].alloc
    for (c, _, kw), eng in zip(plans, shared):
        got = eng.run([copy.copy(r) for r in trace])
        solo = ContinuousBatchingEngine(
            c, sim=PicnicSimulator(), engine=ServingConfig(**kw)).run(
                [copy.copy(r) for r in trace])
        assert _hexdict(got) == _hexdict(solo)


def test_nonaffine_subclass_falls_back_to_walk(cfg):
    """A subclass whose per-layer cost is NOT affine in ctx_sum must be
    detected by the cache-fill probes and served by the direct walk."""
    class Quadratic(CycleModel):
        def layer_decode_cycles_batched(self, ld, ctx_sum, b):
            base = super().layer_decode_cycles_batched(ld, ctx_sum, b)
            if ld.kind == "attn":
                base += int(0.001 * ctx_sum * ctx_sum)
            return base

    alloc = allocate_chiplets(cfg)
    memo, direct = Quadratic(), Quadratic(memoize=False)
    for ctxs in ([100], [512, 2048], [7, 7, 7, 7]):
        assert memo.batched_token_decode_cycles(cfg, alloc, ctxs) \
            == direct.batched_token_decode_cycles(cfg, alloc, ctxs)
    assert memo.decode_affine(cfg, alloc, 2) is None


def test_engine_fallback_hands_subclass_real_contexts(cfg):
    """A CycleModel subclass may legitimately ITERATE the contexts
    sequence (the documented signature).  The engine's non-affine
    fallback must hand it the real per-request values — reconstructed
    from the SoA offsets, exactly matching the request objects'
    contexts at that round."""
    seen = []

    class PerRequest(CycleModel):
        def layer_decode_cycles_batched(self, ld, ctx_sum, b):
            base = super().layer_decode_cycles_batched(ld, ctx_sum, b)
            return base + (7 if ld.kind == "attn" else 0) * b * b

        def batched_token_decode_cycles_split(self, cfg_, alloc, contexts):
            contexts = [int(c) for c in contexts]      # iterates!
            seen.append(tuple(contexts))
            return super().batched_token_decode_cycles_split(
                cfg_, alloc, contexts)

    rows = [(0.0, 40, 12), (0.001, 60, 6), (0.002, 20, 9)]

    def run(cm):
        eng = ContinuousBatchingEngine(
            cfg, sim=PicnicSimulator(cycle_model=cm),
            engine=ServingConfig(max_batch=3, decode_quantum=1))
        return eng.run(replay_trace(rows))

    r_sub = run(PerRequest())                # memoized: probes -> affine?
    assert seen, "subclass walk never saw a contexts sequence"
    # the per-b*b term IS affine in ctx_sum at fixed b, so also pin the
    # memoize=False configuration, which always takes the fallback
    seen.clear()
    r_direct = run(PerRequest(memoize=False))
    assert _hexdict(r_sub) == _hexdict(r_direct)
    # contexts handed to the walk are the true per-request values:
    # strictly positive, and each round's batch sums consistently
    assert all(c > 0 for ctxs in seen for c in ctxs)
    assert any(len(ctxs) > 1 for ctxs in seen)        # batched rounds ran


def test_decode_affine_reproduces_model_exactly(cfg):
    """The affine export the SoA engine inlines == the full model call,
    including a non-unit alpha (the int truncation point)."""
    alloc = allocate_chiplets(cfg)
    for alpha in (1.0, 0.6180339887):
        cm = CycleModel(alpha=alpha)
        for b in (1, 3, 8):
            base, n_attn, c2c_bytes, cpp, a, ver = \
                cm.decode_affine(cfg, alloc, b)
            assert a == alpha and ver == cm._cal_ver
            for ctx_sum in (b, 513, 16384):
                contexts = [ctx_sum // b] * (b - 1) \
                    + [ctx_sum - (ctx_sum // b) * (b - 1)]
                want = cm.batched_token_decode_cycles(cfg, alloc, contexts)
                got = (int((base + n_attn * int(cpp * ctx_sum)) * a),
                       c2c_bytes)
                assert got == want


# ---------------------------------------------------------------------------
# Trace construction: sort-once + monotonic-arrival handling
# ---------------------------------------------------------------------------

def test_replay_trace_sorts_once_at_construction():
    rows = [(0.5, 16, 2), (0.1, 32, 4), (0.3, 8, 1)]
    trace = replay_trace(rows)
    assert [r.arrival for r in trace] == sorted(r[0] for r in rows)
    # ids were assigned in ROW order before sorting (stable identity)
    assert [r.request_id for r in trace] == [1, 2, 0]


def test_run_handles_hand_built_unsorted_trace(cfg):
    from repro.launch.serving_engine import TrackedRequest
    unsorted_trace = [
        TrackedRequest(arrival=0.4, request_id=0, prompt_len=16, max_new=2),
        TrackedRequest(arrival=0.0, request_id=1, prompt_len=16, max_new=2),
    ]
    eng = ContinuousBatchingEngine(cfg, engine=ServingConfig(max_batch=2))
    rep = eng.run(unsorted_trace)
    assert rep.finished == 2
    prefills = {rid: t for t, k, rid in eng.events if k.value == "prefill"}
    assert prefills[1] <= prefills[0]       # earlier arrival served first


def test_rerun_after_construction_sort_is_idempotent(cfg):
    trace = replay_trace([(0.2, 32, 4), (0.0, 64, 8)])
    eng = ContinuousBatchingEngine(cfg, engine=ServingConfig(max_batch=2))
    assert eng.run(trace).row() == eng.run(trace).row()


# ---------------------------------------------------------------------------
# Streaming chrome-trace export
# ---------------------------------------------------------------------------

def test_dump_chrome_trace_streams_identical_json(cfg, tmp_path):
    for columnar in (True, False):
        tl = Timeline(columnar=columnar)
        PicnicSimulator().run(cfg, 256, 32, ccpg=True, dynamic_ccpg=True,
                              timeline=tl)
        path = tmp_path / f"trace_{columnar}.json"
        tl.dump_chrome_trace(path)
        streamed = json.loads(path.read_text())
        assert streamed == tl.to_chrome_trace()
        assert len(streamed["traceEvents"]) > tl.n_events  # + metadata


def test_engine_streamed_trace_has_all_categories(cfg, tmp_path):
    eng = ContinuousBatchingEngine(
        cfg, engine=ServingConfig(max_batch=2, ccpg=True, dynamic_ccpg=True))
    eng.run(replay_trace([(0.0, 32, 4), (0.5, 32, 4)]))
    path = tmp_path / "eng.json"
    eng.timeline.save_chrome_trace(path)        # alias of dump_
    d = json.loads(path.read_text())
    cats = {e.get("cat") for e in d["traceEvents"] if e.get("cat")}
    assert {"ComputeSpan", "C2CTransfer", "ClusterWake", "ClusterSleep",
            "EnergySample", "TokenEmit"} <= cats
