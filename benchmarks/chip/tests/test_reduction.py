"""The trace reduction: on hand-made intervals, and on a small trace
recorded on a TPU v5e (``data/lockstep_tiny.xplane.pb``, written by
``record_trace.py`` for the tiny olmo-1b decode cell: one wave of 4 rows,
prompt 8, 6 tokens each, then part of the next)."""
import json

import pytest

import reduction as R
from conftest import BENCH_DIR

DATA = BENCH_DIR / "tests" / "data"


def test_union_merges_overlaps_and_clips_to_the_window():
    busy, gaps = R.union_ns([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], (1, 25))
    assert busy == (3 - 1) + (12 - 5) + (25 - 20)
    assert gaps == [(3, 5), (12, 20)]


def test_union_of_nothing_is_one_gap():
    assert R.union_ns([], (0, 10)) == (0.0, [(0, 10)])


def test_clock_offset_is_the_middle_of_what_dispatch_and_completion_allow():
    # two executions 100 ns long; the host dispatched them at 1000 and 2000
    # and heard of their ends at 1500 and 2600: the shift is at least
    # max(1000 - 100, 2000 - 1000) and at most min(1500 - 200, 2600 - 1100)
    modules = {"bench_decode": [(100, 200, 7), (1000, 1100, 8)]}
    off = R.clock_offset({"bench_decode": [1000, 2000]}, modules, {7: 1500, 8: 2600})
    assert off == (1000 + 1300) / 2


def test_span_index_names_the_span_that_covers_most_of_a_gap():
    idx = R.SpanIndex([("bench_window", 0, 100), ("token_readback", 10, 20),
                       ("decode_dispatch", 18, 40), ("wave_prep", 60, 61)])
    assert idx.at((12, 19)) == "token_readback"
    assert idx.at((19, 30)) == "decode_dispatch"
    assert idx.at((45, 55)) == R.NO_SPAN


def test_module_and_op_names():
    assert R.module_name("jit_bench_decode(16025998350676621146)") == "bench_decode"
    assert R.op_name("%convolution_tanh_fusion.2 = bf16[64,2048]{1,0} fusion(x)") == \
        "convolution_tanh_fusion.2"


@pytest.fixture(scope="module")
def recorded():
    from engines.lockstep import HOST_SPANS
    red = R.reduce_file(DATA / "lockstep_tiny.xplane.pb", host_spans=HOST_SPANS)
    calls = json.loads((DATA / "lockstep_tiny.calls.json").read_text())
    return red, calls


def test_recorded_busy_and_idle_fill_the_window(recorded):
    red, _ = recorded
    idle = sum(ns for _, ns in red.idle_gaps)
    assert red.chips == 1
    assert 0 < red.busy_ns < red.window[1] - red.window[0]
    assert red.busy_ns + idle == pytest.approx(red.window[1] - red.window[0])


def test_recorded_programs_are_found_by_the_benchmark_names(recorded):
    red, calls = recorded
    for program in ("prefill", "decode"):
        n = sum(c[0] == program for c in calls["calls"])
        assert n > 0
        assert len(red.module_ns(f"bench_{program}")) == n
    # every execution starts after the host dispatched it, on the host clock
    assert red.offset_ns != 0
    assert all(0 < ns < 1e9 for ns in red.module_ns("bench_decode"))


def test_recorded_op_time_is_the_busy_time(recorded):
    red, _ = recorded
    assert sum(red.op_ns.values()) >= red.busy_ns * (1 - 1e-9)
    names = [n for n, _ in red.top_ops(10)]
    assert len(names) == len(set(names)) and all(" = " not in n for n in names)


def test_recorded_idle_gaps_are_named_by_host_spans(recorded):
    red, _ = recorded
    spans = {"wave_prep", "prefill_dispatch", "decode_dispatch", "token_readback",
             R.NO_SPAN}
    assert {s for s, _ in red.idle_gaps} <= spans
    named = sum(ns for s, ns in red.idle_gaps if s != R.NO_SPAN)
    assert named > 0.5 * sum(ns for _, ns in red.idle_gaps)
    breakdown = red.idle_breakdown(10)
    assert len(breakdown) <= 10 and breakdown[0][0].startswith("sum:")
