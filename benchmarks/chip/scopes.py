"""Split each traced execution of a step program by the model's layer
scopes, and name what the host did in each long idle gap of the device.

Reads the same ``.xplane.pb`` as ``reduction.py``, beside a
:class:`reduction.Reduction` of it (its window and clock offset), and
changes none of that module's readings.

Scopes.  The program wraps its layers in ``jax.named_scope``; the names
reach the trace as each XLA op's ``op_name`` (``tf_op`` in the device
plane's event metadata, e.g. ``jit(bench_decode)/jit(wrapped)/layers/while/
body/closed_call/attention/attend/dot_general``).  ``ProfileData`` does not
expose event metadata, so :func:`op_names` reads it from the file's
protobuf.  Each op is assigned to the execution (``XLA Modules`` event)
that holds it in time on its device, since op names repeat between
programs.  Its self time is its duration less that of the ops nested in
it (a ``while`` keeps only its own time), and it is counted under the
scopes its ``op_name`` holds; an op with none is ``unscoped`` (on a TPU v5e,
XLA's own copies and the ``while`` op carry no ``op_name``).  Device time
of an execution in which no op ran is ``between_ops``.  So within each
execution the self times of all scope paths add up to its device time.

Idle causes.  For each gap of ``LONG_GAP_NS`` or more in which no op ran,
what the host was doing: the benchmark span (named as ``idle_gaps`` names
it), the innermost Python event (``$...``) or ``gc`` span on the
``python3`` line, and the runtime event on each host thread, each the
innermost event that covers more than half of the gap; and when the first
runtime event started in the gap.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

import reduction as R

SCOPES = ("embed", "layers", "attention", "kv_write", "attend", "mlp", "moe",
          "mamba", "head", "sample")
BLOCKS = ("attention", "mlp", "moe", "mamba")   # what the layer scan computes
UNSCOPED = "unscoped"
BETWEEN_OPS = "between_ops"
GC_SPAN = "gc"
LONG_GAP_NS = 10e6          # a clean decode step's longest gap is about 3 ms
PROGRAM_ID = re.compile(r"\((\d+)\)$")
THREAD = re.compile(r"/\d+$")

ScopePath = Tuple[str, ...]     # the scope names in an op_name, outermost first


# --- event metadata from the .xplane.pb (XSpace protobuf) ---------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an int
    for varints, a memoryview for length-delimited and fixed fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_entries(entries) -> Dict[int, memoryview]:
    out = {}
    for entry in entries:
        kv = dict(_fields(entry))
        out[kv.get(1, 0)] = kv.get(2, memoryview(b""))
    return out


def op_names(path) -> Dict[str, Dict[Tuple[str, str], str]]:
    """Device plane name -> {(program id, op event name): op_name}, from each
    device plane's event metadata (stats ``program_id`` and ``tf_op``)."""
    data = memoryview(open(path, "rb").read())
    out: Dict[str, Dict[Tuple[str, str], str]] = {}
    for field, plane in _fields(data):
        if field != 1:                              # XSpace.planes
            continue
        name, events, stats = "", [], []
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:                            # XPlane.event_metadata
                events.append(v)
            elif f == 5:                            # XPlane.stat_metadata
                stats.append(v)
        if not R.DEVICE_PLANE.match(name):
            continue
        stat_names = {k: bytes(dict(_fields(v)).get(2, b"")).decode()
                      for k, v in _map_entries(stats).items()}
        table = {}
        for meta in _map_entries(events).values():
            ev_name, program, tf_op = "", None, None
            for f, v in _fields(meta):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:                        # XEventMetadata.stats
                    stat = dict(_fields(v))
                    key = stat_names.get(stat.get(1))
                    if key == "program_id":
                        program = str(stat.get(3, stat.get(4)))
                    elif key == "tf_op":
                        if 7 in stat:               # a reference to a stat name
                            tf_op = stat_names.get(stat[7])
                        else:
                            tf_op = bytes(stat.get(5, stat.get(6, b""))).decode()
            if program is not None and tf_op:
                table[(program, ev_name)] = tf_op
        out[name] = table
    return out


def program_id(module_event: str) -> str:
    """``jit_bench_decode(1437...)`` -> ``1437...``, the ``program_id`` of
    its ops' metadata."""
    m = PROGRAM_ID.search(module_event)
    return m.group(1) if m else ""


def scope_path(op_name: Optional[str]) -> ScopePath:
    """The scope names in an ``op_name``, outermost first."""
    if not op_name:
        return ()
    return tuple(p for p in op_name.rstrip(":").split("/") if p in SCOPES)


# --- the split ------------------------------------------------------------------

@dataclasses.dataclass
class Execution:
    """One execution of a program on the host's clock (ns), and the self
    time of its ops by scope path; ``()`` is ``unscoped``."""
    program: str
    start: float
    end: float
    self_ns: Dict[ScopePath, float]
    between_ns: float = 0.0
    in_window: bool = False

    @property
    def device_ns(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Split:
    executions: List[Execution]             # every execution on the first chip
    orphan_ops: int                         # ops that start in no execution
    straddling_ops: int                     # ops that end past their execution
    unscoped_ops: Dict[Tuple[str, str], float]   # (program, op) -> self ns in the window
    idle_causes: List[Tuple[str, float]]    # (cause, ns) of each long gap
    gc_ns: List[float]                      # each gc span that started in the window

    def _window(self, program: str) -> List[Execution]:
        return [e for e in self.executions if e.in_window and e.program == program]

    def mean_ms(self, program: str, under: str,
                outside: Sequence[str] = ()) -> Optional[float]:
        """Mean self time (ms) a window execution of ``program`` spends in
        ops under scope ``under`` (nested scopes included) and under none of
        ``outside``; None where no op of the program is under ``under``."""
        execs = self._window(program)
        paths = {p for e in execs for p in e.self_ns if under in p}
        if not paths:
            return None
        keep = [p for p in paths if not set(outside) & set(p)]
        total = sum(e.self_ns.get(p, 0.0) for e in execs for p in keep)
        return total / len(execs) * 1e-6

    def device_scopes(self, n: Optional[int] = None) -> List[List]:
        """Self seconds of the window's executions by
        ``<program>/<innermost scope>``, longest first."""
        total: Dict[str, float] = defaultdict(float)
        for e in self.executions:
            if not e.in_window:
                continue
            for path, ns in e.self_ns.items():
                total[f"{e.program}/{path[-1] if path else UNSCOPED}"] += ns
            total[f"{e.program}/{BETWEEN_OPS}"] += e.between_ns
        out = sorted(total.items(), key=lambda kv: -kv[1])
        return [[k, v * 1e-9] for k, v in out[:n]]

    def top_unscoped(self, n: int = 10) -> List[List]:
        out = sorted(self.unscoped_ops.items(), key=lambda kv: -kv[1])[:n]
        return [[f"{p}/{op}", ns * 1e-9] for (p, op), ns in out]

    def idle_breakdown(self, n: int = 10) -> List[List]:
        longest = sorted(self.idle_causes, key=lambda c: -c[1])[:n]
        return [[cause, ns * 1e-9] for cause, ns in longest]


def self_times(ops: Sequence[Tuple[float, float]]) -> List[float]:
    """Each op's duration less that of the ops nested in it, for ops on one
    line sorted by (start, -end); a child that runs past its parent's end is
    taken off its parent only up to that end."""
    own = [e - s for s, e in ops]
    stack: List[int] = []
    for i, (s, e) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            own[parent] -= min(e, ops[parent][1]) - s
        stack.append(i)
    return own


def split_profile(profile, names: Dict[str, Dict[Tuple[str, str], str]],
                  red: "R.Reduction", *, host_spans: Iterable[str],
                  chips: Sequence[int] = (0,)) -> Split:
    """Split the first chip's executions of ``profile`` by scope, with the
    window and clock offset of its reduction ``red``, and name each long
    idle gap of that chip."""
    first = min(chips)
    plane = next(p for p in profile.planes if p.name == f"/device:TPU:{first}")
    table = names.get(plane.name, {})
    modules, ops = [], []
    for line in plane.lines:
        if line.name == "XLA Modules":
            modules = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events]
        elif line.name == "XLA Ops":
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in line.events]
    modules.sort()
    ops.sort(key=lambda o: (o[0], -o[1]))
    off, (w0, w1) = red.offset_ns, red.window
    execs = [Execution(R.module_name(n), s + off, e + off, defaultdict(float),
                       in_window=w0 <= s + off < w1) for s, e, n in modules]
    ids = [program_id(n) for _, _, n in modules]
    starts = [s for s, _, _ in modules]
    own = self_times([(s, e) for s, e, _ in ops])
    orphans = straddling = 0
    op_sum = [0.0] * len(execs)
    unscoped: Dict[Tuple[str, str], float] = defaultdict(float)
    for (s, e, name), ns in zip(ops, own):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= modules[i][1]:
            orphans += 1
            continue
        straddling += e > modules[i][1] + 2     # times are rounded to the ns
        path = scope_path(table.get((ids[i], name)))
        execs[i].self_ns[path] += ns
        op_sum[i] += ns
        if not path and execs[i].in_window:
            unscoped[(execs[i].program, R.op_name(name))] += ns
    for x, busy in zip(execs, op_sum):
        x.self_ns = dict(x.self_ns)
        x.between_ns = max(0.0, x.device_ns - busy)
    _, gaps = R.union_ns(((s + off, e + off) for s, e, _ in ops), red.window)
    spans, python, runtime = host_events(profile, host_spans)
    causes = idle_causes(spans, python, runtime,
                         [g for g in gaps if g[1] - g[0] >= LONG_GAP_NS])
    gc_ns = [e - s for s, e, n in python if n == GC_SPAN and w0 <= s < w1]
    return Split(execs, orphans, straddling, dict(unscoped), causes, gc_ns)


# --- idle causes ----------------------------------------------------------------

class Line:
    """The events of one host line (or one group of them), to find what
    covered a gap."""

    def __init__(self, events: Sequence[Tuple[float, float, str]]):
        self.names = [n for _, _, n in events]
        self.start = np.array([s for s, _, _ in events], float)
        self.end = np.array([e for _, e, _ in events], float)

    def covering(self, gap: R.Interval) -> Optional[str]:
        """The innermost event that covers more than half of ``gap``, or
        None."""
        if not self.names:
            return None
        cover = np.minimum(self.end, gap[1]) - np.maximum(self.start, gap[0])
        most = np.flatnonzero(cover > (gap[1] - gap[0]) / 2)
        if not len(most):
            return None
        return self.names[int(most[np.argmin((self.end - self.start)[most])])]


def host_events(profile, host_spans: Iterable[str]):
    """The host plane's events, on the host's clock, as (name, start, end)
    for the benchmark's spans and (start, end, name) for the rest: the
    spans, the ``python3`` line's Python events and ``gc`` spans, and each
    thread's runtime events (those of ``python3`` included)."""
    named = set(host_spans)
    spans, python = [], []
    runtime: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            thread = THREAD.sub("", line.name)
            for ev in line.events:
                item = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                if line.name != "python3":
                    runtime[thread].append(item)
                elif ev.name in named:
                    spans.append((ev.name, item[0], item[1]))
                elif ev.name.startswith("$") or ev.name == GC_SPAN:
                    python.append(item)
                elif ev.name != R.WINDOW_SPAN:
                    runtime[thread].append(item)
    return spans, python, dict(runtime)


def idle_causes(spans, python, runtime,
                gaps: Sequence[R.Interval]) -> List[Tuple[str, float]]:
    """``(<span>|<python event>|<thread>:<runtime event>|...|first+<ms>:
    <thread>:<event>, ns)`` for each gap, from :func:`host_events`: the
    span as ``idle_gaps`` names it; on each line the innermost event that
    covers more than half of the gap (``-`` for Python where none does; a
    thread where none does is left out); and the first runtime event to
    start in the gap, with how long after the gap's start (the host's
    first sign of noticing that the device went idle)."""
    index = R.SpanIndex(spans)
    python_line = Line(python)
    threads = {t: Line(evs) for t, evs in sorted(runtime.items())}
    starts = sorted((s, t, n) for t, evs in runtime.items() for s, _, n in evs)
    out = []
    for gap in gaps:
        parts = [index.at(gap), python_line.covering(gap) or "-"]
        for thread, line in threads.items():
            event = line.covering(gap)
            if event:
                parts.append(f"{thread}:{event}")
        i = bisect.bisect_left(starts, (gap[0],))
        if i < len(starts) and starts[i][0] < gap[1]:
            s, thread, event = starts[i]
            parts.append(f"first+{(s - gap[0]) * 1e-6:.3f}ms:{thread}:{event}")
        out.append(("|".join(parts), gap[1] - gap[0]))
    return out


def split_file(path, red: "R.Reduction", *, host_spans: Iterable[str],
               chips: Sequence[int] = (0,)) -> Split:
    import jax
    return split_profile(jax.profiler.ProfileData.from_file(str(path)),
                         op_names(path), red, host_spans=host_spans, chips=chips)


# --- the readings a benchmark metric would take -------------------------------

def readings(split: Split) -> Dict[str, Optional[float]]:
    """Mean ms per execution of each layer of the two step programs."""
    return {
        "decode_attention_ms": split.mean_ms("bench_decode", "attention"),
        "decode_mlp_ms": split.mean_ms("bench_decode", "mlp"),
        "decode_carry_ms": split.mean_ms("bench_decode", "layers", outside=BLOCKS),
        "prefill_attention_ms": split.mean_ms("bench_prefill", "attention"),
        "prefill_mlp_ms": split.mean_ms("bench_prefill", "mlp"),
    }
