"""Unified event-timeline simulation core — TimelineIR.

Every cost producer in the repo appends typed events to ONE `Timeline`:

  * ``PicnicSimulator.run``            — analytic prefill/decode/C2C spans
    (or `MeasuredTraffic`-sourced C2C transfers),
  * ``ContinuousBatchingEngine``       — per-round prefill/decode spans,
    idle (`ClusterSleep`) gaps, per-token `TokenEmit`s,
  * ``CCPGModel`` (dynamic mode)       — real `ClusterWake` latency on
    cluster transitions instead of a folded-in residue constant.

Every consumer derives its numbers from the same event stream:
`InferenceResult` (cycle/byte sums), `ServingReport` (percentiles,
tok/s, tok/J via the span-integrated energy), and the Chrome-trace
exporter (`chrome://tracing` / Perfetto JSON).

Energy is INTEGRATED over spans — ``sum(duration * power)`` in append
order — instead of multiplying one average power by the wall clock.
The paper's CCPG and interconnect claims are time-resolved effects
(cluster wake-up, bursty C2C, idle retention) that average-power models
cannot show; see PAPERS.md on CIM power-gating surveys.

Cursor semantics: *advancing* appends (`compute` / `wake` / `sleep`)
move ``now`` and integrate energy; *concurrent* appends (`c2c` /
`token` / `sample`, or any append with ``advance=False``) annotate the
stream at a given instant without advancing time — C2C bursts overlap
compute, token emits are instantaneous.

Recording modes
---------------
``columnar=True`` (the default, the fast simulation core) stores each
event class as growable parallel columns of scalars — no per-event
Python object is built on the hot append path, and the existing
dataclass events are materialized **lazily** (and cached) only when a
consumer actually reads ``timeline.events`` (golden-file comparisons,
``TrafficTrace.from_timeline``).  ``columnar=False`` keeps the original
one-dataclass-per-append recorder; both modes run the same float
arithmetic in the same order, so they are byte-identical — locked by
tests/test_fastpath.py.

Aggregate queries (`cycles()` / `span_seconds()` / `count()` /
`total_energy_J()`) read running per-(class, kind) sums maintained on
append — O(1) instead of an O(E) event scan — in both modes.
"""
from __future__ import annotations

import json
from collections import defaultdict
from dataclasses import dataclass
from typing import (Dict, Iterator, List, Optional, Sequence, Tuple, Type,
                    Union)

from .interconnect import LinkSpec, OPTICAL, c2c_average_power


# ---------------------------------------------------------------------------
# Event taxonomy
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ComputeSpan:
    """A busy span of the active cluster(s): one prefill, one (batched)
    decode iteration, or one sampled chunk of the analytic decode walk."""
    t0: float
    dur_s: float
    kind: str                # "prefill" | "decode"
    power_W: float = 0.0
    cycles: int = 0          # exact cycle count (ints sum losslessly)
    batch: int = 1           # co-scheduled requests riding this span
    name: str = ""


@dataclass(frozen=True)
class C2CTransfer:
    """Photonic/electrical chip-to-chip burst.  Concurrent with compute
    (the link runs under the compute wave unless `overlap` < 1 exposes
    part of it — the exposed part is inside the owning ComputeSpan)."""
    t0: float
    dur_s: float
    nbytes: int
    phase: str = ""          # "prefill" | "decode"
    source: str = "analytic"  # "analytic" | MeasuredTraffic.source


@dataclass(frozen=True)
class ClusterWake:
    """Exposed cluster power-up latency (CCPG).  Static mode folds the
    pre-wake residue into decode cycles; dynamic mode emits the full
    regulator-settle walk as real timeline latency."""
    t0: float
    dur_s: float
    cycles: int = 0
    cluster: int = -1        # -1: aggregate walk over all transitions


@dataclass(frozen=True)
class ClusterSleep:
    """Idle/retention span: scratchpads only (CCPG) or full active burn
    (no gating path).  ``advance=False`` appends mark background sleepers
    concurrent with compute (their power is already inside the span's
    aggregate) and carry no energy of their own."""
    t0: float
    dur_s: float
    power_W: float = 0.0


@dataclass(frozen=True)
class EnergySample:
    """Instantaneous power sample (W) — the Fig-8-style power trace.
    Emitted automatically at every advancing span start; contributes no
    energy (spans carry the integral)."""
    t0: float
    power_W: float


@dataclass(frozen=True)
class TokenEmit:
    """``n`` tokens produced at instant ``t0`` (request_id -1: aggregate
    analytic walk, otherwise the serving engine's per-request emits)."""
    t0: float
    n: int = 1
    request_id: int = -1


@dataclass(frozen=True)
class NodeFail:
    """A fleet node dies at instant ``t0`` holding its in-flight KV
    (fault injection, launch/config.FaultConfig).  Appended to the
    failing node's own timeline; the router's recovery actions
    (re-routes, recompute prefills, retransmits) land on the survivors'
    timelines as ordinary spans/transfers."""
    t0: float
    node: int = -1


@dataclass(frozen=True)
class NodeRecover:
    """The node rejoins the fleet at ``t0`` after ``downtime_s`` of
    being dead (its timeline is padded with a zero-power sleep over the
    gap — a dead node burns nothing)."""
    t0: float
    node: int = -1
    downtime_s: float = 0.0


Event = Union[ComputeSpan, C2CTransfer, ClusterWake, ClusterSleep,
              EnergySample, TokenEmit, NodeFail, NodeRecover]

# The core categories every full trace contains; the fault kinds only
# appear when fault injection is on, so they live in their own tuple
# (trace-completeness checks iterate EVENT_CATEGORIES).
EVENT_CATEGORIES: Tuple[Type, ...] = (
    ComputeSpan, C2CTransfer, ClusterWake, ClusterSleep, EnergySample,
    TokenEmit)
FAULT_EVENT_CATEGORIES: Tuple[Type, ...] = (NodeFail, NodeRecover)
ALL_EVENT_CATEGORIES: Tuple[Type, ...] = \
    EVENT_CATEGORIES + FAULT_EVENT_CATEGORIES

# columnar class ids, in ALL_EVENT_CATEGORIES order
_COMPUTE, _C2C, _WAKE, _SLEEP, _SAMPLE, _TOKEN, _FAIL, _RECOVER = range(8)


# ---------------------------------------------------------------------------
# Accumulator
# ---------------------------------------------------------------------------

class Timeline:
    """Append-only event stream with a time cursor and running integrals.

    The integrals (`energy_J`, `busy_s`, `idle_s`, `occupancy_s`) are
    accumulated in append order with one multiply-add per span, so a
    producer that previously charged ``energy += dt * power`` inline
    reproduces its floats bit-for-bit by appending the same spans in the
    same order.  The same holds for the per-(class, kind) cycle / span /
    count aggregates behind `cycles()` / `span_seconds()` / `count()`.
    """

    def __init__(self, link: LinkSpec = OPTICAL, *, columnar: bool = True):
        self.link = link
        self.columnar = columnar
        self.now = 0.0
        self.energy_J = 0.0        # span-integrated chip energy
        self.busy_s = 0.0
        self.idle_s = 0.0
        self.c2c_bytes = 0
        self.tokens = 0
        self.occupancy_s = 0.0     # integral of batch occupancy over busy
        # running aggregates behind the O(1) derived queries; float sums
        # run in append order, exactly as the old O(E) scans did
        self._cycles: Dict[Tuple[str, Optional[str]], int] = \
            defaultdict(int)
        self._span_s: Dict[Tuple[str, Optional[str]], float] = \
            defaultdict(float)
        if columnar:
            # per-class parallel columns + one global class-id sequence;
            # dataclass events are materialized lazily from these
            self._seq: List[int] = []
            self._cols: Tuple[Tuple[list, ...], ...] = tuple(
                tuple([] for _ in range(n))
                for n in (7, 5, 4, 3, 2, 3, 2, 3))
            self._mat: List[Event] = []        # lazy materialization cache
            self._cursors = [0] * 8            # per-class materialize pos
        else:
            self._events: List[Event] = []

    # -- advancing producers ------------------------------------------
    def compute(self, dur_s: float, *, kind: str, power_W: float = 0.0,
                cycles: int = 0, batch: int = 1, name: str = "") -> float:
        now = self.now
        if self.columnar:
            seq = self._seq
            seq.append(_COMPUTE)
            c = self._cols[_COMPUTE]
            c[0].append(now)
            c[1].append(dur_s)
            c[2].append(kind)
            c[3].append(power_W)
            c[4].append(cycles)
            c[5].append(batch)
            c[6].append(name)
            seq.append(_SAMPLE)               # auto power sample (inline)
            c = self._cols[_SAMPLE]
            c[0].append(now)
            c[1].append(power_W)
        else:
            self._events.append(ComputeSpan(now, dur_s, kind, power_W,
                                            cycles, batch, name))
            self._events.append(EnergySample(now, power_W))
        span = self._span_s
        span["ComputeSpan", None] += dur_s
        span["ComputeSpan", kind] += dur_s
        if cycles:
            cyc = self._cycles
            cyc["ComputeSpan", None] += cycles
            cyc["ComputeSpan", kind] += cycles
        self.busy_s += dur_s
        self.energy_J += dur_s * power_W
        self.occupancy_s += dur_s * batch
        self.now = now + dur_s
        return self.now

    def wake(self, dur_s: float, *, power_W: float = 0.0, cycles: int = 0,
             cluster: int = -1) -> float:
        now = self.now
        if self.columnar:
            seq = self._seq
            seq.append(_WAKE)
            c = self._cols[_WAKE]
            c[0].append(now)
            c[1].append(dur_s)
            c[2].append(cycles)
            c[3].append(cluster)
            seq.append(_SAMPLE)
            c = self._cols[_SAMPLE]
            c[0].append(now)
            c[1].append(power_W)
        else:
            self._events.append(ClusterWake(now, dur_s, cycles, cluster))
            self._events.append(EnergySample(now, power_W))
        self._span_s["ClusterWake", None] += dur_s
        if cycles:
            self._cycles["ClusterWake", None] += cycles
        self.busy_s += dur_s
        self.energy_J += dur_s * power_W
        self.now = now + dur_s
        return self.now

    def sleep(self, dur_s: float, *, power_W: float = 0.0,
              t0: Optional[float] = None, advance: bool = True) -> float:
        at = self.now if t0 is None else t0
        if self.columnar:
            self._seq.append(_SLEEP)
            c = self._cols[_SLEEP]
            c[0].append(at)
            c[1].append(dur_s)
            c[2].append(power_W)
        else:
            self._events.append(ClusterSleep(at, dur_s, power_W))
        self._span_s["ClusterSleep", None] += dur_s
        if advance:
            if self.columnar:
                self._seq.append(_SAMPLE)
                c = self._cols[_SAMPLE]
                c[0].append(at)
                c[1].append(power_W)
            else:
                self._events.append(EnergySample(at, power_W))
            self.idle_s += dur_s
            self.energy_J += dur_s * power_W
            self.now += dur_s
        return self.now

    # -- concurrent annotations ---------------------------------------
    def c2c(self, nbytes: int, *, dur_s: float = 0.0, phase: str = "",
            t0: Optional[float] = None, source: str = "analytic",
            advance: bool = False, power_W: float = 0.0) -> None:
        """``advance=True`` serializes the burst (cursor moves past it) —
        the Fig-10 layer-boundary handoff view; the default treats it as
        concurrent with the surrounding compute (any exposed transfer
        time is already inside the owning ComputeSpan's cycles).
        ``power_W`` charges chip power over an *advancing* burst (the
        chiplets do not stop burning while stalled on a remote KV read);
        concurrent bursts carry no energy of their own."""
        nbytes = int(nbytes)
        at = self.now if t0 is None else t0
        if self.columnar:
            self._seq.append(_C2C)
            c = self._cols[_C2C]
            c[0].append(at)
            c[1].append(dur_s)
            c[2].append(nbytes)
            c[3].append(phase)
            c[4].append(source)
        else:
            self._events.append(C2CTransfer(at, dur_s, nbytes, phase,
                                            source))
        self._span_s["C2CTransfer", None] += dur_s
        self.c2c_bytes += nbytes
        if advance:
            if power_W:
                if self.columnar:
                    self._seq.append(_SAMPLE)
                    c = self._cols[_SAMPLE]
                    c[0].append(self.now)
                    c[1].append(power_W)
                else:
                    self._events.append(EnergySample(self.now, power_W))
                self.energy_J += dur_s * power_W
            self.busy_s += dur_s
            self.now += dur_s

    def token(self, n: int = 1, *, request_id: int = -1,
              t0: Optional[float] = None) -> None:
        n = int(n)
        at = self.now if t0 is None else t0
        if self.columnar:
            self._seq.append(_TOKEN)
            c = self._cols[_TOKEN]
            c[0].append(at)
            c[1].append(n)
            c[2].append(request_id)
        else:
            self._events.append(TokenEmit(at, n, request_id))
        self.tokens += n

    def token_each(self, request_ids: Sequence[int], *,
                   t0: Optional[float] = None) -> None:
        """Batched emit: ONE single-token `TokenEmit` per request id, all
        at the same instant — the serving engine's per-decode-round
        batch, appended with C-level column extends instead of one
        `token()` call per resident.  Event-stream equivalent to
        ``for rid in request_ids: token(1, request_id=rid)``."""
        b = len(request_ids)
        if not b:
            return
        at = self.now if t0 is None else t0
        if self.columnar:
            self._seq.extend([_TOKEN] * b)
            c = self._cols[_TOKEN]
            c[0].extend([at] * b)
            c[1].extend([1] * b)
            c[2].extend(request_ids)
        else:
            self._events.extend(
                TokenEmit(at, 1, rid) for rid in request_ids)
        self.tokens += b

    def node_fail(self, node: int = -1, *,
                  t0: Optional[float] = None) -> None:
        """Concurrent instant: this node crashed (fault injection)."""
        at = self.now if t0 is None else t0
        if self.columnar:
            self._seq.append(_FAIL)
            c = self._cols[_FAIL]
            c[0].append(at)
            c[1].append(node)
        else:
            self._events.append(NodeFail(at, node))

    def node_recover(self, node: int = -1, *, downtime_s: float = 0.0,
                     t0: Optional[float] = None) -> None:
        """Concurrent instant: this node rejoined after a crash."""
        at = self.now if t0 is None else t0
        if self.columnar:
            self._seq.append(_RECOVER)
            c = self._cols[_RECOVER]
            c[0].append(at)
            c[1].append(node)
            c[2].append(downtime_s)
        else:
            self._events.append(NodeRecover(at, node, downtime_s))

    def sample(self, power_W: float) -> None:
        if self.columnar:
            self._seq.append(_SAMPLE)
            c = self._cols[_SAMPLE]
            c[0].append(self.now)
            c[1].append(power_W)
        else:
            self._events.append(EnergySample(self.now, power_W))

    # -- event materialization ----------------------------------------
    @property
    def n_events(self) -> int:
        """Event count without materializing anything — O(1)."""
        return len(self._seq) if self.columnar else len(self._events)

    @property
    def events(self) -> List[Event]:
        """The dataclass event stream.  In columnar mode this is a lazy,
        incrementally extended materialization cache: appends after a
        read only materialize the new tail on the next read."""
        if not self.columnar:
            return self._events
        if len(self._mat) < len(self._seq):
            mat, cur, cols = self._mat, self._cursors, self._cols
            ctors = (ComputeSpan, C2CTransfer, ClusterWake, ClusterSleep,
                     EnergySample, TokenEmit, NodeFail, NodeRecover)
            for cid in self._seq[len(mat):]:
                i = cur[cid]
                mat.append(ctors[cid](*(col[i] for col in cols[cid])))
                cur[cid] = i + 1
        return self._mat

    def _iter_events(self) -> Iterator[Event]:
        """Yield events one at a time WITHOUT caching a materialized list
        (columnar mode) — the streaming export path for million-event
        traces."""
        if not self.columnar:
            yield from self._events
            return
        ctors = (ComputeSpan, C2CTransfer, ClusterWake, ClusterSleep,
                 EnergySample, TokenEmit, NodeFail, NodeRecover)
        cur = [0] * 8
        cols = self._cols
        for cid in self._seq:
            i = cur[cid]
            yield ctors[cid](*(col[i] for col in cols[cid]))
            cur[cid] = i + 1

    _FIELDS = {
        "ComputeSpan": ("t0", "dur_s", "kind", "power_W", "cycles",
                        "batch", "name"),
        "C2CTransfer": ("t0", "dur_s", "nbytes", "phase", "source"),
        "ClusterWake": ("t0", "dur_s", "cycles", "cluster"),
        "ClusterSleep": ("t0", "dur_s", "power_W"),
        "EnergySample": ("t0", "power_W"),
        "TokenEmit": ("t0", "n", "request_id"),
        "NodeFail": ("t0", "node"),
        "NodeRecover": ("t0", "node", "downtime_s"),
    }

    def column(self, cls: Type, field: str) -> list:
        """One raw column of ``cls`` (e.g. ``column(ComputeSpan, "dur_s")``)
        in append order — the zero-copy analysis path in columnar mode."""
        name = cls.__name__
        fields = self._FIELDS[name]
        if field not in fields:
            raise KeyError(f"{name} has no field {field!r}")
        if self.columnar:
            return list(self._cols[self._CIDS[name]][fields.index(field)])
        return [getattr(e, field) for e in self._events
                if isinstance(e, cls)]

    # -- derived queries (O(1): running aggregates) --------------------
    def cycles(self, cls: Type = ComputeSpan,
               kind: Optional[str] = None) -> int:
        """Exact integer cycle sum over events of ``cls`` (optionally a
        ComputeSpan ``kind``) — the lossless bridge back to the cycle
        model's arithmetic."""
        return self._cycles.get((cls.__name__, kind), 0)

    def span_seconds(self, cls: Type = ComputeSpan,
                     kind: Optional[str] = None) -> float:
        return self._span_s.get((cls.__name__, kind), 0.0)

    _CIDS = {"ComputeSpan": _COMPUTE, "C2CTransfer": _C2C,
             "ClusterWake": _WAKE, "ClusterSleep": _SLEEP,
             "EnergySample": _SAMPLE, "TokenEmit": _TOKEN,
             "NodeFail": _FAIL, "NodeRecover": _RECOVER}

    def count(self, cls: Type) -> int:
        if self.columnar:
            return len(self._cols[self._CIDS[cls.__name__]][0])
        return sum(1 for e in self._events if isinstance(e, cls))

    def c2c_energy_J(self, wall_s: Optional[float] = None) -> float:
        """Link energy for the delivered bytes: average power at the
        delivered rate (bursty traffic, duty-cycled laser bias) over the
        wall clock."""
        wall = max(self.now if wall_s is None else wall_s, 1e-12)
        return c2c_average_power(self.c2c_bytes / wall, self.link) * wall

    def total_energy_J(self) -> float:
        return self.energy_J + self.c2c_energy_J()

    def power_trace(self) -> List[Tuple[float, float]]:
        """(t, W) steps from the EnergySample stream."""
        if self.columnar:
            t0s, ws = self._cols[_SAMPLE]
            return list(zip(t0s, ws))
        return [(e.t0, e.power_W) for e in self._events
                if isinstance(e, EnergySample)]

    # -- Chrome trace export ------------------------------------------
    _TIDS = {"ComputeSpan": 1, "C2CTransfer": 2, "ClusterWake": 3,
             "ClusterSleep": 4, "TokenEmit": 5}
    # fault lanes: their thread metadata is emitted ONLY when such
    # events exist, so zero-fault traces stay byte-identical
    _FAULT_TIDS = {"NodeFail": 6, "NodeRecover": 7}

    def iter_chrome_events(self, *, process_name: str = "picnic",
                           pid: int = 0) -> Iterator[Dict]:
        """Yield `chrome://tracing` event dicts one at a time (metadata
        first), without holding the whole trace in memory.  ``pid``
        attributes every event to one trace process — fleet runs export
        each NODE's timeline under its own pid (see
        :func:`merge_chrome_traces`); the default 0 keeps single-node
        output byte-identical to the pre-fleet exporter."""
        yield {"ph": "M", "pid": pid, "name": "process_name",
               "args": {"name": process_name}}
        for lane, tid in sorted(self._TIDS.items(), key=lambda kv: kv[1]):
            yield {"ph": "M", "pid": pid, "tid": tid,
                   "name": "thread_name", "args": {"name": lane}}
        for lane, tid in sorted(self._FAULT_TIDS.items(),
                                key=lambda kv: kv[1]):
            if self.count(ALL_EVENT_CATEGORIES[tid]) > 0:
                yield {"ph": "M", "pid": pid, "tid": tid,
                       "name": "thread_name", "args": {"name": lane}}

        def span(cat, name, e, args):
            return {"ph": "X", "pid": pid, "tid": self._TIDS[cat],
                    "cat": cat, "name": name, "ts": e.t0 * 1e6,
                    "dur": e.dur_s * 1e6, "args": args}

        for e in self._iter_events():
            ts = e.t0 * 1e6                     # chrome wants microseconds
            if isinstance(e, ComputeSpan):
                yield span("ComputeSpan", e.name or e.kind, e,
                           {"kind": e.kind, "cycles": e.cycles,
                            "batch": e.batch, "power_W": e.power_W})
            elif isinstance(e, C2CTransfer):
                yield span("C2CTransfer", f"c2c:{e.phase or 'burst'}",
                           e, {"bytes": e.nbytes, "phase": e.phase,
                               "source": e.source})
            elif isinstance(e, ClusterWake):
                yield span("ClusterWake", "wake", e,
                           {"cycles": e.cycles, "cluster": e.cluster})
            elif isinstance(e, ClusterSleep):
                yield span("ClusterSleep", "sleep", e,
                           {"power_W": e.power_W})
            elif isinstance(e, EnergySample):
                yield {"ph": "C", "pid": pid, "cat": "EnergySample",
                       "name": "power_W", "ts": ts,
                       "args": {"power_W": e.power_W}}
            elif isinstance(e, TokenEmit):
                yield {"ph": "i", "pid": pid,
                       "tid": self._TIDS["TokenEmit"],
                       "cat": "TokenEmit", "name": f"tok x{e.n}",
                       "ts": ts, "s": "t",
                       "args": {"n": e.n, "request_id": e.request_id}}
            elif isinstance(e, NodeFail):
                yield {"ph": "i", "pid": pid,
                       "tid": self._FAULT_TIDS["NodeFail"],
                       "cat": "NodeFail", "name": "node_fail",
                       "ts": ts, "s": "p", "args": {"node": e.node}}
            elif isinstance(e, NodeRecover):
                yield {"ph": "i", "pid": pid,
                       "tid": self._FAULT_TIDS["NodeRecover"],
                       "cat": "NodeRecover", "name": "node_recover",
                       "ts": ts, "s": "p",
                       "args": {"node": e.node,
                                "downtime_s": e.downtime_s}}

    def to_chrome_trace(self, *, process_name: str = "picnic",
                        pid: int = 0) -> Dict:
        """`chrome://tracing` / Perfetto JSON: one thread lane per event
        category, power as a counter track, tokens as instant events."""
        return {"traceEvents":
                list(self.iter_chrome_events(process_name=process_name,
                                             pid=pid)),
                "displayTimeUnit": "ms"}

    def dump_chrome_trace(self, path, *, process_name: str = "picnic",
                          pid: int = 0) -> None:
        """Stream the Chrome trace to ``path`` one event at a time —
        constant memory, so ``--trace-out`` stays usable on
        million-event traces."""
        with open(path, "w") as f:
            f.write('{"displayTimeUnit": "ms", "traceEvents": [\n')
            first = True
            for ev in self.iter_chrome_events(process_name=process_name,
                                              pid=pid):
                if not first:
                    f.write(",\n")
                json.dump(ev, f)
                first = False
            f.write("\n]}\n")

    def save_chrome_trace(self, path, *, process_name: str = "picnic",
                          pid: int = 0) -> None:
        self.dump_chrome_trace(path, process_name=process_name, pid=pid)


def merge_chrome_traces(named_timelines) -> Dict:
    """One `chrome://tracing` document from several timelines: each
    ``(name, timeline)`` pair becomes its own trace PROCESS (pid = list
    position, process_name = name) — per-node attribution for fleet
    runs, where every node's events keep their own lanes but share the
    global clock."""
    events: List[Dict] = []
    for pid, (name, tl) in enumerate(named_timelines):
        events.extend(tl.iter_chrome_events(process_name=name, pid=pid))
    return {"traceEvents": events, "displayTimeUnit": "ms"}
