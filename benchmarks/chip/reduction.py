"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What a TPU trace holds (one TPU v5e, JAX 0.9.0):
  * plane ``/device:TPU:<i>``: line ``XLA Modules`` has one event per
    execution of a compiled program, named ``jit_<function>(<hash>)``, with
    a ``run_id`` stat; line ``XLA Ops`` has one event per HLO operation,
    named by its HLO text (``%fusion.3 = bf16[...] fusion(...)``).
  * plane ``/host:CPU``: line ``python3`` holds the host spans
    (``jax.profiler.TraceAnnotation``) and one ``PjitFunction(<function>)``
    event per dispatch of a jitted function; other lines hold the runtime's
    ``CompleteCallbacks`` events, each with the ``run_id`` of the
    execution whose end it reports.

Device and host events are given on one clock, but the device's is off by
a millisecond or two.  The offset is bounded per execution: the device
cannot start a program before the host dispatched it, nor end it after the
host was told it had ended.  The reduction shifts device times by the
middle of the tightest such bound, so that each idle gap of the device can
be named by the host span it fell in.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_NAME = re.compile(r"^(?:jit_)?(.+?)(?:\(\d+\))?$")
WINDOW_SPAN = "bench_window"
NO_SPAN = "no_host_span"

Interval = Tuple[float, float]  # (start_ns, end_ns)


@dataclasses.dataclass
class Reduction:
    """What one traced window held, on the host's clock (ns)."""
    window: Interval
    chips: int
    busy_ns: float                          # union of op intervals, mean over chips
    modules: Dict[str, List[Interval]]      # program name -> its executions
    op_ns: Dict[str, float]                 # HLO op name -> device time
    idle_gaps: List[Tuple[str, float]]      # (host span, ns), every gap
    offset_ns: float                        # added to device times

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def module_ns(self, name: str) -> List[float]:
        return [e - s for s, e in self.modules.get(name, [])]

    def top_ops(self, n: int = 10) -> List[List]:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in ops]

    def idle_breakdown(self, n: int = 10) -> List[List]:
        """Idle time summed by the host span it fell in (``sum:<span>``),
        then the single longest gaps (``longest:<span>``), ``n`` in all."""
        total: Dict[str, float] = defaultdict(float)
        for span, ns in self.idle_gaps:
            total[span] += ns
        out = [[f"sum:{k}", v * 1e-9]
               for k, v in sorted(total.items(), key=lambda kv: -kv[1])][:n]
        longest = sorted(self.idle_gaps, key=lambda g: -g[1])
        out += [[f"longest:{k}", v * 1e-9] for k, v in longest[:n - len(out)]]
        return out


def union_ns(intervals: Iterable[Interval], clip: Interval) -> Tuple[float, List[Interval]]:
    """Length of the union of ``intervals`` inside ``clip``, and the gaps
    of ``clip`` that no interval covers."""
    lo, hi = clip
    busy = 0.0
    gaps: List[Interval] = []
    cur = lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
            busy += e - s
            cur = e
        elif e > cur:
            busy += e - cur
            cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return busy, gaps


def module_name(event_name: str) -> str:
    return MODULE_NAME.match(event_name).group(1)


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def clock_offset(dispatch: Dict[str, List[float]],
                 modules: Dict[str, List[Tuple[float, float, Optional[int]]]],
                 completed: Dict[int, float]) -> float:
    """The shift that puts device events on the host's clock: the middle of
    [max(dispatch - device start), min(host completion - device end)] over
    every execution whose dispatch or completion the host recorded."""
    lo, hi = [], []
    for name, execs in modules.items():
        starts = dispatch.get(name, [])
        if len(starts) == len(execs):
            lo += [h - s for h, (s, _, _) in zip(starts, execs)]
        for s, e, run_id in execs:
            if run_id in completed:
                hi.append(completed[run_id] - e)
    if not lo and not hi:
        return 0.0
    if not lo:
        return min(hi)
    if not hi:
        return max(lo)
    a, b = max(lo), min(hi)
    return (a + b) / 2 if a <= b else sorted(lo)[len(lo) // 2]


def _stats(event) -> Dict[str, str]:
    return {k: v for k, v in event.stats}


def reduce_profile(profile, *, host_spans: Iterable[str],
                   chips: Sequence[int] = (0,)) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Reduction` over
    the host span ``bench_window``; idle gaps are named by ``host_spans``."""
    named = set(host_spans) | {WINDOW_SPAN}
    spans: List[Tuple[str, float, float]] = []
    dispatch: Dict[str, List[float]] = defaultdict(list)
    completed: Dict[int, float] = {}
    device = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if int(m.group(1)) in chips:
                device[int(m.group(1))] = plane
            continue
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if line.name == "python3":
                    if name.startswith("PjitFunction("):
                        dispatch[name[len("PjitFunction("):-1]].append(ev.start_ns)
                    elif not name.startswith("$") and name in named:
                        spans.append((name, ev.start_ns, ev.start_ns + ev.duration_ns))
                elif name == "CompleteCallbacks":
                    run_id = _stats(ev).get("run_id")
                    if run_id is not None:
                        completed[int(run_id)] = ev.start_ns
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} '{WINDOW_SPAN}' spans, not 1")
    if sorted(device) != sorted(chips):
        raise ValueError(f"trace holds device planes {sorted(device)}, not {list(chips)}")
    window = windows[0]

    per_chip_modules = {}
    per_chip_ops = {}
    for chip, plane in device.items():
        mods: Dict[str, List[Tuple[float, float, Optional[int]]]] = defaultdict(list)
        ops: List[Tuple[str, float, float]] = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    run_id = _stats(ev).get("run_id")
                    mods[module_name(ev.name)].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         int(run_id) if run_id is not None else None))
            elif line.name == "XLA Ops":
                ops += [(op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        per_chip_modules[chip] = mods
        per_chip_ops[chip] = ops

    first = min(device)
    offset = clock_offset(dispatch, per_chip_modules[first], completed)
    busy_total = 0.0
    op_ns: Dict[str, float] = defaultdict(float)
    idle: List[Tuple[str, float]] = []
    for chip in device:
        shifted = [(n, s + offset, e + offset) for n, s, e in per_chip_ops[chip]]
        busy, gaps = union_ns(((s, e) for _, s, e in shifted), window)
        busy_total += busy
        for n, s, e in shifted:
            d = min(e, window[1]) - max(s, window[0])
            if d > 0:
                op_ns[n] += d / len(device)
        if chip == first:
            index = SpanIndex(spans)
            idle = [(index.at(g), g[1] - g[0]) for g in gaps]
    modules = {n: [(s + offset, e + offset) for s, e, _ in execs
                   if window[0] <= s + offset < window[1]]
               for n, execs in per_chip_modules[first].items()}
    return Reduction(window=window, chips=len(device),
                     busy_ns=busy_total / len(device), modules=modules,
                     op_ns=dict(op_ns), idle_gaps=idle, offset_ns=offset)


class SpanIndex:
    """Host spans (other than the window), to name what the host was doing
    during a gap: the span that covers most of it, the shorter one where
    two cover it equally."""

    def __init__(self, spans: Sequence[Tuple[str, float, float]]):
        self.spans = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
        self.starts = [s for s, _, _ in self.spans]
        self.longest = max((e - s for s, e, _ in self.spans), default=0.0)

    def at(self, gap: Interval) -> str:
        best, best_cover, best_len = NO_SPAN, 0.0, float("inf")
        i = bisect.bisect_left(self.starts, gap[1]) - 1
        while i >= 0 and self.starts[i] > gap[0] - self.longest:
            s, e, name = self.spans[i]
            cover = min(e, gap[1]) - max(s, gap[0])
            if cover > best_cover or (cover == best_cover and cover > 0
                                      and e - s < best_len):
                best, best_cover, best_len = name, cover, e - s
            i -= 1
        return best


def reduce_file(path, *, host_spans: Iterable[str],
                chips: Sequence[int] = (0,)) -> Reduction:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(str(path)),
                          host_spans=host_spans, chips=chips)
