"""The split of traced executions by layer scope, and the naming of long
idle gaps (``scopes.py``, ``trace_split.report``): on hand-made events,
on the trace of the unscoped programs (``data/lockstep_tiny.xplane.pb``)
and on one of the scoped programs (``data/lockstep_tiny_scoped.xplane.pb``),
both recorded on a TPU v5e by ``record_trace.py`` for the tiny olmo-1b
decode cell."""
import json
from types import SimpleNamespace as NS

import pytest

import reduction as R
import scopes as S
from conftest import BENCH_DIR

DATA = BENCH_DIR / "tests" / "data"
TRACES = {"unscoped": "lockstep_tiny", "scoped": "lockstep_tiny_scoped"}


def _load(name):
    import jax
    from engines.lockstep import HOST_SPANS
    path = DATA / f"{TRACES[name]}.xplane.pb"
    red = R.reduce_file(path, host_spans=HOST_SPANS)
    profile = jax.profiler.ProfileData.from_file(str(path))
    names = S.op_names(path)
    split = S.split_profile(profile, names, red, host_spans=HOST_SPANS)
    calls = json.loads((DATA / f"{TRACES[name]}.calls.json").read_text())["calls"]
    return NS(path=path, red=red, profile=profile, names=names, split=split,
              calls=calls)


@pytest.fixture(scope="module")
def traces():
    return {name: _load(name) for name in TRACES}


@pytest.fixture(params=sorted(TRACES))
def trace(request, traces):
    return traces[request.param]


def _ops(profile):
    plane = next(p for p in profile.planes if p.name == "/device:TPU:0")
    line = next(x for x in plane.lines if x.name == "XLA Ops")
    return sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in line.events), key=lambda o: (o[0], -o[1]))


def test_self_time_takes_nested_ops_off_their_parent():
    # a loop (0-10) holding two ops, one of which holds another; then an op
    # alone; a child that runs 1 ns past its parent counts to its parent's end
    ops = [(0, 10), (1, 4), (2, 3), (5, 9), (10, 12), (20, 25), (24, 26)]
    assert S.self_times(ops) == [10 - 3 - 4, 3 - 1, 1, 4, 2, 5 - 1, 2]


def test_scope_path_keeps_the_scope_names_outermost_first():
    name = ("jit(bench_decode)/jit(wrapped)/layers/while/body/closed_call/"
            "attention/attend/bhgd,bkhd->bhgk/dot_general:")
    assert S.scope_path(name) == ("layers", "attention", "attend")
    assert S.scope_path("jit(bench_prefill)/jit(_take)/gather:") == ()
    assert S.scope_path(None) == ()


def test_every_op_falls_in_exactly_one_execution(trace):
    assert trace.split.orphan_ops == 0 and trace.split.straddling_ops == 0
    assert len(trace.split.executions) == len(trace.calls)


def test_every_computing_op_of_the_trace_has_its_op_name(trace):
    # XLA's own copies and the loop op itself come with no op_name
    table = trace.names["/device:TPU:0"]
    assert {e.program for e in trace.split.executions} == {"bench_prefill",
                                                          "bench_decode"}
    programs = {p for p, _ in table}
    assert len(programs) == 2
    computing = [name for _, _, name in _ops(trace.profile)
                 if not R.op_name(name).startswith(("copy", "while"))]
    assert computing
    for name in computing:
        assert any((p, name) in table for p in programs), name


def test_executions_are_the_ones_the_reduction_times(trace):
    for program in ("bench_prefill", "bench_decode"):
        window = [(e.start, e.end) for e in trace.split.executions
                  if e.in_window and e.program == program]
        assert window == trace.red.modules[program]


def test_self_times_add_up_to_each_execution_without_counting_twice(trace):
    ops = _ops(trace.profile)
    off = trace.red.offset_ns
    for e in trace.split.executions:
        inside = [(s + off, t + off) for s, t, _ in ops if e.start <= s + off < e.end]
        union, _ = R.union_ns(inside, (e.start, e.end))
        op_self = sum(e.self_ns.values())
        assert op_self == pytest.approx(union, abs=2)      # times are rounded to the ns
        assert op_self + e.between_ns == pytest.approx(e.device_ns, rel=0.01)
        assert e.between_ns < 0.1 * e.device_ns


def test_a_while_holds_only_its_own_time(trace):
    ops = _ops(trace.profile)
    own = S.self_times([(s, t) for s, t, _ in ops])
    loops = [(i, o) for i, o in enumerate(ops) if R.op_name(o[2]).startswith("while")]
    assert loops
    for i, (s, t, _) in loops:
        nested = [o for o in ops if s <= o[0] and o[1] <= t and o != ops[i]]
        assert nested
        assert own[i] < 0.1 * (t - s)


def test_a_trace_without_scopes_reads_no_layer(traces):
    split = traces["unscoped"].split
    readings = S.readings(split)
    assert readings == dict.fromkeys(readings)        # None where no scope, not 0
    assert {k.split("/")[1] for k, _ in split.device_scopes()} == \
        {S.UNSCOPED, S.BETWEEN_OPS}
    assert None not in S.readings(traces["scoped"].split).values()


def test_the_scoped_decode_step_is_split_by_layer(traces):
    scoped = traces["scoped"]
    from engines.lockstep import HOST_SPANS
    import trace_split
    out = trace_split.report(scoped.path, host_spans=HOST_SPANS, chips=[0],
                             calls=scoped.calls)
    scopes = dict(out["breakdown"]["device_scopes"])
    n = sum(c[0] == "decode" for c in scoped.calls)
    rest = sum(scopes.get(f"bench_decode/{k}", 0.0) for k in
               ("embed", "head", "sample", S.UNSCOPED, S.BETWEEN_OPS)) / n * 1e3
    layers = out["decode_attention_ms"] + out["decode_mlp_ms"] + out["decode_carry_ms"]
    assert layers + rest == pytest.approx(out["decode_ms"], rel=1e-6)
    # ``sample``'s argmax is fused into the head's op, which names the fusion
    for k in ("embed", "layers", "attention", "attend", "kv_write", "mlp", "head"):
        assert f"bench_decode/{k}" in scopes
    prefill = sum(v for k, v in scopes.items() if k.startswith("bench_prefill/"))
    n = sum(c[0] == "prefill" for c in scoped.calls)
    assert prefill / n * 1e3 == pytest.approx(out["prefill_ms"], rel=1e-6)


def _profile(lines):
    """A hand-made profile: one host plane of {line name: [(name, start, end)]}."""
    return NS(planes=[NS(name="/host:CPU", lines=[
        NS(name=line, events=[NS(name=n, start_ns=s, duration_ns=e - s)
                              for n, s, e in evs])
        for line, evs in lines.items()])])


def test_idle_causes_name_the_innermost_event_covering_most_of_a_gap():
    profile = _profile({
        "python3": [("bench_window", 0, 1000), ("token_readback", 0, 100),
                    ("$lockstep.py:84 wave", 0, 1000), ("$numpy asarray", 10, 95),
                    ("gc", 20, 80), ("np.asarray(jax.Array)", 10, 95)],
        "main/291": [("CommonPjRtLoadedExecutable::Execute", 0, 100),
                     ("Wait for usage holds", 30, 70)],
        "pjrt-tpu-tasks/326": [("D2H Dispatch", 40, 48),
                               ("tpu::System::TransferFromDevice", 60, 75)],
        "futex-default-SDomainT/427": [("CompleteCallbacks", 500, 510),
                                       ("ReadSyncFlag", 500_000, 500_100)],
    })
    events = S.host_events(profile, ["token_readback"])
    causes = S.idle_causes(*events, [(25, 75), (300, 400)])
    # pjrt-tpu-tasks covers at most 15 of the first gap's 50 ns: left out;
    # no runtime event starts in the second gap
    assert causes == [
        ("token_readback|gc|main:Wait for usage holds|"
         "python3:np.asarray(jax.Array)|first+0.000ms:main:Wait for usage holds", 50),
        (f"{R.NO_SPAN}|$lockstep.py:84 wave", 100),
    ]
    # a gap no event covers: the host noticed it 0.465 ms in
    later = S.idle_causes(*events, [(35_000, 5_000_000)])
    assert later[0][0] == f"{R.NO_SPAN}|-|first+0.465ms:futex-default-SDomainT:ReadSyncFlag"
    split = S.Split([], 0, 0, {}, causes, [])
    assert split.idle_breakdown(1) == [[causes[1][0], pytest.approx(100e-9)]]


def test_only_gaps_of_ten_ms_or_more_are_named(trace):
    ops = _ops(trace.profile)
    off = trace.red.offset_ns
    _, gaps = R.union_ns(((s + off, t + off) for s, t, _ in ops), trace.red.window)
    long_gaps = [g for g in gaps if g[1] - g[0] >= S.LONG_GAP_NS]
    assert len(trace.split.idle_causes) == len(long_gaps)
    assert sum(ns for _, ns in trace.red.idle_gaps) == \
        pytest.approx(sum(g[1] - g[0] for g in gaps))
