#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

  python3 benchmarks/chip/run.py --workload olmo-1b.decode.b192 \\
      --seed 1234 --seconds 20 --trace 0

Run from the root of a checkout.  With ``--trace 0`` the result holds the
cell's end-to-end metrics; with ``--trace 1`` a profiler trace of the
window is reduced to its per-layer metrics.  The last line of standard
output is the result, one JSON object; the last lines of standard error
are each compared number beside its limit.  With no TPU, or fewer chips
than the cell asks for, it exits non-zero before any work and prints no
result.
"""
import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = Path.cwd()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    import repro  # noqa: F401  the system under test; absent, the run stops here
    import harness

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}.get(args.workload)
    if chips is None:
        sys.exit(f"unknown workload {args.workload!r}")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"no TPU: JAX found {devices[0].platform}; nothing was run")
    if len(devices) < chips:
        sys.exit(f"the cell needs {chips} chips, JAX found {len(devices)}")
    harness.use_compile_cache(ROOT)
    result = harness.run_cell(ROOT, args.workload, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              devices=devices, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
