"""Public serving API (ISSUE 9 redesign): the versioned keyword-only
config schema, the deprecation shim over the old ``EngineConfig``
constructor, the unified :class:`Trace` surface and the
``repro.launch`` facade."""
import copy
import dataclasses

import pytest

from repro.configs import get_config
from repro.core import PicnicSimulator
from repro.core.interconnect import MeasuredTraffic
from repro.launch import (FleetConfig, ServingConfig, SweepCell, Trace,
                          fleet, poisson_trace, replay_trace, serve, sweep)
from repro.launch.serving_engine import ContinuousBatchingEngine
from repro.runtime.kv_cache import KVCacheConfig


# ---------------------------------------------------------------------------
# ServingConfig / FleetConfig schema contract
# ---------------------------------------------------------------------------

def test_serving_config_round_trip():
    c = ServingConfig(max_batch=4, ccpg=True, overlap=0.5,
                      chunked_prefill_tokens=128)
    d = c.to_dict()
    assert d["schema"] == ServingConfig.SCHEMA_VERSION
    assert ServingConfig.from_dict(d) == c


def test_serving_config_round_trip_nested_kv_cache():
    kvc = KVCacheConfig(n_blocks=32, block_tokens=16, dram_blocks=8,
                        bytes_per_token=2048, prefix_sharing=True)
    c = ServingConfig(max_batch=8, kv_cache=kvc)
    d = c.to_dict()
    assert isinstance(d["kv_cache"], dict)      # JSON-serializable
    c2 = ServingConfig.from_dict(d)
    assert c2 == c and c2.kv_cache == kvc


def test_fleet_config_round_trip_nested():
    fc = FleetConfig(n_prefill=3, n_decode=1, autoscale=True,
                     engine=ServingConfig(max_batch=4, ccpg=True),
                     measured_handoff=MeasuredTraffic(
                         prefill_bytes=1e6, decode_bytes_per_token=128.0),
                     handoff_bytes_per_token=4096)
    d = fc.to_dict()
    assert d["schema"] == FleetConfig.SCHEMA_VERSION
    assert isinstance(d["engine"], dict)
    assert isinstance(d["measured_handoff"], dict)
    fc2 = FleetConfig.from_dict(d)
    assert fc2 == fc
    assert fc2.n_nodes == 4


def test_from_dict_rejects_unknown_keys():
    d = ServingConfig().to_dict()
    d["max_batchh"] = 4                          # the typo'd knob
    with pytest.raises(ValueError, match="max_batchh"):
        ServingConfig.from_dict(d)
    fd = FleetConfig().to_dict()
    fd["n_prefll"] = 2
    with pytest.raises(ValueError, match="n_prefll"):
        FleetConfig.from_dict(fd)
    kd = ServingConfig(kv_cache=KVCacheConfig(n_blocks=4)).to_dict()
    kd["kv_cache"]["n_blockss"] = 4
    with pytest.raises(ValueError, match="n_blockss"):
        ServingConfig.from_dict(kd)


def test_from_dict_rejects_newer_schema():
    d = ServingConfig().to_dict()
    d["schema"] = ServingConfig.SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="schema"):
        ServingConfig.from_dict(d)
    fd = FleetConfig().to_dict()
    fd["schema"] = FleetConfig.SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="schema"):
        FleetConfig.from_dict(fd)


def test_configs_are_keyword_only():
    with pytest.raises(TypeError):
        ServingConfig(4)                         # noqa: positional
    with pytest.raises(TypeError):
        FleetConfig(2, 2)                        # noqa: positional


# ---------------------------------------------------------------------------
# EngineConfig deprecation shim
# ---------------------------------------------------------------------------

def test_engine_config_warns_and_maps_keywords():
    from repro.launch.serving_engine import EngineConfig
    with pytest.warns(DeprecationWarning, match="ServingConfig"):
        c = EngineConfig(max_batch=4, ccpg=True)
    assert isinstance(c, ServingConfig)
    assert c.max_batch == 4 and c.ccpg is True
    # dataclass __eq__ is class-strict; the field values are what the
    # shim must preserve
    assert dataclasses.asdict(c) \
        == dataclasses.asdict(ServingConfig(max_batch=4, ccpg=True))


def test_engine_config_accepts_legacy_positional_form():
    from repro.launch.serving_engine import EngineConfig
    # the old dataclass field order: max_batch, queue_limit,
    # decode_quantum, ccpg, ...
    with pytest.warns(DeprecationWarning):
        c = EngineConfig(4, 128, 2, True)
    assert (c.max_batch, c.queue_limit, c.decode_quantum, c.ccpg) \
        == (4, 128, 2, True)
    with pytest.warns(DeprecationWarning), \
            pytest.raises(TypeError):
        EngineConfig(*range(20))                 # too many positionals


# ---------------------------------------------------------------------------
# Trace surface
# ---------------------------------------------------------------------------

def test_trace_poisson_matches_legacy_function():
    a = Trace.poisson(16, rate_rps=40, seed=3, prompt_len=256, max_new=8)
    b = poisson_trace(16, rate_rps=40, seed=3, prompt_len=256, max_new=8)
    assert isinstance(a, Trace) and isinstance(b, Trace)
    assert len(a) == len(b) == 16
    for x, y in zip(a, b):
        assert dataclasses.asdict(x) == dataclasses.asdict(y)


def test_trace_replay_matches_legacy_function():
    rows = [(0.1, 64, 4), {"arrival_s": 0.05, "prompt_len": 32,
                           "max_new": 2, "deadline_ttft": 0.5}]
    a = Trace.replay(rows)
    b = replay_trace(rows)
    assert [dataclasses.asdict(r) for r in a] \
        == [dataclasses.asdict(r) for r in b]
    assert a[0].arrival == 0.05                  # sorted by arrival


# ---------------------------------------------------------------------------
# Facade entry points
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cfg():
    return get_config("llama3.2-1b")


def test_serve_facade_matches_engine_run(cfg):
    trace = Trace.poisson(8, rate_rps=40, seed=0, prompt_len=256,
                          max_new=8)
    sc = ServingConfig(max_batch=4, ccpg=True)
    r1 = serve(cfg, [copy.copy(r) for r in trace], config=sc,
               sim=PicnicSimulator())
    eng = ContinuousBatchingEngine(cfg, sim=PicnicSimulator(), engine=sc)
    r2 = eng.run([copy.copy(r) for r in trace])
    assert r1.row() == r2.row()


def test_fleet_facade_matches_engine_run(cfg):
    from repro.launch.fleet_engine import FleetEngine
    trace = Trace.poisson(8, rate_rps=40, seed=0, prompt_len=256,
                          max_new=8)
    fc = FleetConfig(engine=ServingConfig(max_batch=4))
    r1 = fleet(cfg, [copy.copy(r) for r in trace], config=fc,
               sim=PicnicSimulator())
    r2 = FleetEngine(cfg, fc, sim=PicnicSimulator()).run(
        [copy.copy(r) for r in trace])
    assert r1.row() == r2.row()


def test_sweep_facade_matches_per_cell_engines(cfg):
    trace = Trace.poisson(6, rate_rps=40, seed=0, prompt_len=256, max_new=8)
    cells = [SweepCell(f"b{b}", cfg, trace, ServingConfig(max_batch=b))
             for b in (1, 4)]
    results = sweep(cells)
    assert [r.key for r in results] == ["b1", "b4"]
    for res, cell in zip(results, cells):
        eng = ContinuousBatchingEngine(cfg, sim=PicnicSimulator(),
                                       engine=cell.engine)
        assert res.report.row() == eng.run(
            [copy.copy(r) for r in trace]).row()
        assert res.kv_stats is None


def test_serving_report_row_attribution_fields(cfg):
    """node_id/pool stay OUT of row() on single-node runs (artifact
    byte-identity) and appear once a fleet sets them."""
    trace = Trace.poisson(4, rate_rps=40, seed=0, prompt_len=128,
                          max_new=4)
    rep = serve(cfg, list(trace), config=ServingConfig(max_batch=4))
    row = rep.row()
    assert "node_id" not in row and "pool" not in row
    rep.node_id, rep.pool = 2, "decode"
    row = rep.row()
    assert row["node_id"] == 2 and row["pool"] == "decode"
