#!/usr/bin/env python3
"""Readings that a cell's ``token_deficit`` limit is set from.

  python3 benchmarks/chip/calibrate.py --workload olmo-1b.decode.b192 \\
      --seeds 101,102,103 --control-seeds 101,102,103

For each seed, in one process: build the cell's engine, warm it up, serve
the run's first wave through the timed path (the same compiled programs,
batch and lengths as a run), free the program's state, and read the
reference gaps of the sample that a run with that seed would compare
(``check.sample``).  For the control seeds, also read the gaps of the
tokens that the float8 forward puts first at the same positions.  One
JSON line per seed on standard output.  The benchmark's own runs never run
this.  It runs on whatever device JAX finds, and says which.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = Path.cwd()


def readings(root: Path, workload: str, seed: int, control: bool) -> dict:
    import jax
    import numpy as np
    import check
    import harness
    t0 = time.perf_counter()
    cell = harness.Cell(root, workload)
    ref = cell.module(cell.conf["reference"])
    engine = cell.module(cell.traffic["engine"]).Engine(
        cell.module(cell.conf["program"]), cell.conf, cell.traffic, seed, ref)
    engine.warm_up(np.random.default_rng([seed % 2**64, 1]))
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    wave = engine.wave(np.random.default_rng(seed % 2**64), end=None)
    wave_s = time.perf_counter() - t0
    engine.close()
    del engine
    chosen = check.sample(wave, cell.traffic["check_requests"], seed)
    served = np.stack([r.tokens for r in chosen])
    t0 = time.perf_counter()
    gaps = ref.token_gaps(cell.conf, seed, np.stack([r.prompt for r in chosen]), served,
                          control=control)
    out = {"workload": workload, "seed": seed, "setup_s": setup_s, "wave_s": wave_s,
           "reference_s": time.perf_counter() - t0,
           "platform": jax.devices()[0].platform,
           "served_tokens": int(served.size),
           "served_max": float(gaps["served"].max()),
           "served_p99": float(np.percentile(gaps["served"], 99)),
           "served_nonzero": int((gaps["served"] > 0).sum())}
    if control:
        out.update(control_max=float(gaps["control"].max()),
                   control_p99=float(np.percentile(gaps["control"], 99)),
                   control_nonzero=int((gaps["control"] > 0).sum()))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", default="", help="comma-separated")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    harness.use_compile_cache(ROOT)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in seeds + sorted(controls - set(seeds)):
        print(json.dumps(readings(ROOT, args.workload, seed, seed in controls)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
