"""One run of one benchmark cell: everything after the look for a chip.

The harness is driven by ``BENCHMARK.json`` and by files found by the names
in it, under the benchmark's directory (``paths[0]``):
  * ``configs``' ``file``: the configuration as run, with modules, by
    path, for the ``program`` (its config and weight layout in the program
    under test), its plain ``reference`` and its work ``counts``;
  * ``traffic/<traffic>.json``: the mix, with ``engine`` (a module by
    path) and its parameters;
  * ``limits/<workload>.json``: each compared number's limit and the
    readings it was set from;
  * ``metrics/<metric>.py``: one reader per metric, ``read(run)`` returning
    a number or ``None`` where it finds nothing to read.
A new cell, configuration, mix or metric is new files and new entries.
An engine serves ``Request`` records and logs each device call as
(program, batch, length); the checks and the readers see nothing else of it.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

import check

def load_module(path: Path):
    """Import a benchmark file by path, under a name unique to the path."""
    path = Path(path).resolve()
    name = "bench_" + hashlib.sha1(str(path).encode()).hexdigest()[:12]
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` and every file it names."""

    def __init__(self, root: Path, workload: str):
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / self.bench["paths"][0]
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
        self.name = workload
        self.cell = cells[workload]
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.conf = json.loads((self.root / configs[self.cell["config"]]["file"]).read_text())
        self.traffic = json.loads(
            (self.dir / "traffic" / f"{self.cell['traffic']}.json").read_text())
        self.limits = json.loads((self.dir / "limits" / f"{workload}.json").read_text())

    def metrics(self, trace: bool) -> List[Dict]:
        """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
        group = self.bench["per_layer" if trace else "end_to_end"]
        return [m for m in group if self.name in m.get("workloads", [self.name])]

    def module(self, rel: str):
        return load_module(self.dir / rel)


class Compiles:
    """Counts, from JAX's monitoring events, backend compilations and
    persistent-cache hits and misses, and the compilations while entered
    (there should be none inside the measured window)."""

    PREFIX = "/jax/compilation_cache/"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.counts = {"backend": 0, "cache_hits": 0, "cache_misses": 0}
        self.in_window = 0
        self._on = False
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kwargs):
        name = event[len(self.PREFIX):] if event.startswith(self.PREFIX) else None
        if name in self.counts:
            self.counts[name] += 1

    def _duration(self, event, duration, **kwargs):
        if event == self.BACKEND:
            self.counts["backend"] += 1
            self.in_window += self._on

    def __enter__(self):
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False


@dataclasses.dataclass
class Request:
    """One request as an engine served it."""
    slot: int                       # the batch row that served it
    submit: float                   # host time it was handed over
    prompt: np.ndarray              # (P,)
    tokens: np.ndarray              # (n,): the tokens served so far
    times: np.ndarray               # (n,): host time each token arrived
    want: int                       # the tokens it asked for

    @property
    def done(self) -> bool:
        return len(self.tokens) == self.want


@dataclasses.dataclass
class Run:
    """What one run produced, as the metric readers see it."""
    cell: Cell
    start: float                    # window, host perf_counter seconds
    end: float
    requests: List[Request]
    calls: list                     # (program, batch, length) dispatched in the window
    setup_s: float
    peaks: Dict[str, float]
    counts: object                  # the configuration's counts module
    reduction: Optional[object] = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def work(self, program: str) -> List[Dict[str, float]]:
        """Needed FLOPs and bytes of each call of ``program`` in the window."""
        c = self.cell.conf
        if program == "prefill":
            return [self.counts.prefill(c, batch=b, prompt=n)
                    for p, b, n in self.calls if p == "prefill"]
        return [self.counts.decode(c, batch=b, context=n)
                for p, b, n in self.calls if p == "decode"]

    def floor_s(self, w: Dict[str, float]) -> float:
        return max(w["flops"] / self.peaks["bf16_flops_per_s"],
                   w["bytes"] / self.peaks["hbm_bytes_per_s"])

    def device_ms(self, program: str) -> Optional[List[float]]:
        """Device time (ms) of each traced ``bench_<program>`` execution, or
        None where the trace does not hold exactly the calls dispatched."""
        if self.reduction is None:
            return None
        ns = self.reduction.module_ns(f"bench_{program}")
        if not ns or len(ns) != sum(c[0] == program for c in self.calls):
            return None
        return [x * 1e-6 for x in ns]


def peaks_for(bench_dir: Path, kind: str) -> Dict[str, float]:
    table = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program stored however fast it compiled.  The directory is the
    benchmark's own: where JAX evicts by size, one entry without its access
    time (as another program's cache may hold) makes every write fail."""
    import jax
    cache = root / ".bench_cache" / "jax"
    cache.mkdir(parents=True, exist_ok=True)   # JAX writes no entry into a missing directory
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(root: Path, workload: str, *, seed: int, seconds: float, trace: bool,
             devices, t_start: float, kind: Optional[str] = None) -> Dict:
    """Run the cell and return its result line (a dict)."""
    import jax
    compiles = Compiles()
    cell = Cell(root, workload)
    sys.path.insert(0, str(cell.dir))
    kind = kind or devices[0].device_kind
    peaks = peaks_for(cell.dir, kind)
    program = cell.module(cell.conf["program"])
    ref = cell.module(cell.conf["reference"])
    engine_mod = cell.module(cell.traffic["engine"])
    rng = np.random.default_rng(seed % 2**64)
    phases = {"start": time.perf_counter() - t_start}
    engine = engine_mod.Engine(program, cell.conf, cell.traffic, seed, ref)
    phases["engine"] = time.perf_counter() - t_start
    engine.warm_up(np.random.default_rng([seed % 2**64, 1]))
    phases["warm_up"] = time.perf_counter() - t_start
    tdir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace else None
    if trace:
        jax.profiler.start_trace(str(tdir))
    setup_s = time.perf_counter() - t_start
    with compiles:
        start, end, requests = engine.run(rng, seconds)
    calls = list(engine.calls)
    if trace:
        jax.profiler.stop_trace()
    used = devices[:cell.cell["chips"]]
    stats = [d.memory_stats() or {} for d in used]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    engine.close()
    del engine
    reduction = None
    if trace:
        from reduction import reduce_file
        xplane = next(tdir.rglob("*.xplane.pb"))
        reduction = reduce_file(xplane, chips=[d.id for d in used],
                                host_spans=engine_mod.HOST_SPANS)
        shutil.rmtree(tdir, ignore_errors=True)
        phases["trace_reduced"] = time.perf_counter() - t_start

    run = Run(cell, start, end, requests, calls, setup_s, peaks,
              cell.module(cell.conf["counts"]), reduction)
    metrics = {}
    for m in cell.metrics(trace):
        value = cell.module(f"metrics/{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    verdict = check.compare(cell, ref, seed, requests)
    phases["checked"] = time.perf_counter() - t_start
    print("seconds since start: " + " ".join(f"{k} {v:.3f}" for k, v in phases.items()),
          file=sys.stderr)
    print(f"compiles_in_window {compiles.in_window} in_run "
          + " ".join(f"{k} {v}" for k, v in compiles.counts.items()), file=sys.stderr)
    for name, c in verdict.checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": verdict.correct, "attempted": verdict.attempted,
              "failed": verdict.failed, "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = {"device_ops": reduction.top_ops(10),
                               "idle_gaps": reduction.idle_breakdown(10)}
    result["checks"] = verdict.checks
    return result
