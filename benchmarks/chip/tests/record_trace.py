#!/usr/bin/env python3
"""Record the small chip trace that ``test_reduction.py`` reads.

  python3 benchmarks/chip/tests/record_trace.py <root> <workload> <out-dir>

Run on a TPU, from a checkout whose ``<root>/BENCHMARK.json`` names a cell
at a tiny size (the tests' rehearsal root).  It warms the cell's engine up,
traces a window of 0.05 s, and writes ``lockstep_tiny.xplane.pb`` and
``lockstep_tiny.calls.json`` (the programs dispatched in the window, in
order, as (program, batch, length)) to ``<out-dir>``.
"""
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


def main(root: str, workload: str, out: str) -> int:
    sys.path.insert(0, str(BENCH_DIR))
    sys.path.insert(0, str(Path.cwd() / "src"))
    import jax
    import numpy as np
    import harness
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: the recorded trace has to come from the chip")
    cell = harness.Cell(Path(root), workload)
    ref = cell.module(cell.conf["reference"])
    engine = cell.module(cell.traffic["engine"]).Engine(
        cell.module(cell.conf["program"]), cell.conf, cell.traffic, 7, ref)
    engine.warm_up(np.random.default_rng(1))
    tdir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    jax.profiler.start_trace(str(tdir))
    start, end, requests = engine.run(np.random.default_rng(7), 0.05)
    jax.profiler.stop_trace()
    out = Path(out)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(next(tdir.rglob("*.xplane.pb")), out / "lockstep_tiny.xplane.pb")
    shutil.rmtree(tdir)
    (out / "lockstep_tiny.calls.json").write_text(json.dumps(
        {"calls": engine.calls, "requests": len(requests),
         "tokens_per_request": [len(r.times) for r in requests]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
