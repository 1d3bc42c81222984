"""Simulator-speed microbench: wall-clock throughput of the simulation
core itself (ISSUE 5) — simulated-tokens-per-wall-second and
events-per-wall-second for the serving, paged and Table-II paths, fast
path (columnar TimelineIR + SoA engine + memoized CycleModel, the
defaults) vs the reference object path (``columnar_timeline=False`` +
``CycleModel(memoize=False)``).

The two paths are asserted REPORT-IDENTICAL in-run before any number is
recorded, so the speedup can never be bought with a behavior change.

Emits ``artifacts/bench/BENCH_speed.json``:

  * ``metrics.speedup.*``            — fast/reference wall ratio per path
    (machine-portable: both sides run on the same host in the same
    process) — gated by benchmarks/check_regression.py as
    higher-is-better headline metrics;
  * ``metrics.wall_ms.*``            — absolute wall clocks, gated as
    LOWER-is-better but only when the recorded ``host_ops_per_s``
    calibration matches the baseline's host (cross-machine wall clocks
    are not comparable);
  * ``metrics.sim_tokens_per_wall_s.* / events_per_wall_s.*`` —
    informational trajectory numbers.

  python benchmarks/microbench.py                  # full: what CI runs
  #                                                  and what the committed
  #                                                  baseline was made from
  python benchmarks/microbench.py --min-speedup 3  # CI's hard floor
  python benchmarks/microbench.py --smoke          # quick local iteration
  #   (NB: smoke runs are never gated against a full-workload baseline —
  #    check_regression skips wall-clock docs whose `smoke` flag differs)
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

ART = ROOT / "artifacts" / "bench"


def _host_calibration() -> float:
    """Fixed pure-Python workload timed once: a machine-speed fingerprint
    stored next to the wall clocks, so the regression gate can tell
    "slower code" apart from "slower host"."""
    t0 = time.perf_counter()
    acc = 0
    for i in range(2_000_000):
        acc += i * 3
    dt = time.perf_counter() - t0
    assert acc  # keep the loop un-optimizable
    return 2_000_000 / dt


def _best_wall(fn, repeats: int):
    """(best_wall_s, last_result): min over repeats — the standard
    microbench estimator for a deterministic workload."""
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def _engines(cfg, engine_kw):
    """(fast, reference) engine pair over identical configs."""
    from repro.core import CycleModel, PicnicSimulator
    from repro.launch import ServingConfig
    from repro.launch.serving_engine import ContinuousBatchingEngine
    fast = ContinuousBatchingEngine(
        cfg, sim=PicnicSimulator(),
        engine=ServingConfig(**engine_kw))
    ref = ContinuousBatchingEngine(
        cfg, sim=PicnicSimulator(cycle_model=CycleModel(memoize=False)),
        engine=ServingConfig(columnar_timeline=False, **engine_kw))
    return fast, ref


def _engine_case(name, cfg, trace, engine_kw, repeats):
    """Measure one serving-path case; assert fast == reference first."""
    fast, ref = _engines(cfg, engine_kw)
    rep_fast = fast.run(list(trace))
    rep_ref = ref.run(list(trace))
    assert rep_fast.row() == rep_ref.row(), \
        f"{name}: fast path diverged from reference"
    if fast.kv_stats is not None:
        assert fast.kv_stats.row() == ref.kv_stats.row(), \
            f"{name}: fast path kv_stats diverged from reference"

    wall_fast, _ = _best_wall(lambda: fast.run(list(trace)), repeats)
    wall_ref, _ = _best_wall(lambda: ref.run(list(trace)), repeats)
    tokens = rep_fast.tokens_generated + rep_fast.tokens_prefilled
    return {
        "name": name,
        "sim_tokens": tokens,
        "events": fast.timeline.n_events,
        "wall_fast_s": wall_fast,
        "wall_reference_s": wall_ref,
        "speedup": wall_ref / wall_fast,
        "tokens_per_wall_s_fast": tokens / wall_fast,
        "tokens_per_wall_s_reference": tokens / wall_ref,
        "events_per_wall_s_fast": fast.timeline.n_events / wall_fast,
    }


def bench_serving_path(smoke: bool, repeats: int):
    from repro.configs import get_config
    from repro.launch import Trace
    cfg = get_config("llama3.2-1b")
    n = 24 if smoke else 64
    trace = Trace.poisson(n, rate_rps=40, seed=0, prompt_len=512,
                          max_new=64)
    return _engine_case("serving", cfg, trace, dict(max_batch=8, ccpg=True),
                        repeats)


def bench_paged_path(smoke: bool, repeats: int):
    from repro.configs import get_config
    from repro.launch import Trace
    from repro.runtime.kv_cache import kv_cache_from_model
    cfg = get_config("llama3.2-1b")
    kvc = kv_cache_from_model(cfg, kv_frac=0.5, dram_frac=1.0)
    n = 8 if smoke else 16
    trace = Trace.poisson(n, rate_rps=60, seed=0, prompt_len=2048,
                          max_new=256)
    return _engine_case("paged", cfg, trace,
                        dict(max_batch=8, ccpg=True, kv_cache=kvc,
                             chunked_prefill_tokens=512), repeats)


def bench_table_ii_path(smoke: bool, repeats: int):
    """The analytic Table-II walk: columnar vs object TimelineIR (the
    cycle-model memo hits across the 9-row sweep's repeated shapes)."""
    from repro.configs import get_config
    from repro.core import CycleModel, PicnicSimulator, Timeline
    table_ii = [("llama3.2-1b", 512), ("llama3.2-1b", 1024),
                ("llama3.2-1b", 2048), ("llama3-8b", 512),
                ("llama3-8b", 1024), ("llama3-8b", 2048),
                ("llama2-13b", 512), ("llama2-13b", 1024),
                ("llama2-13b", 2048)]
    rows = table_ii[:3] if smoke else table_ii
    cfgs = {arch: get_config(arch) for arch, _ in rows}

    def run_fast():
        sim = PicnicSimulator()
        tl = Timeline()
        for arch, ctx in rows:
            sim.run(cfgs[arch], ctx, ctx, timeline=tl)
        return tl

    def run_ref():
        sim = PicnicSimulator(cycle_model=CycleModel(memoize=False))
        tl = Timeline(columnar=False)
        for arch, ctx in rows:
            sim.run(cfgs[arch], ctx, ctx, timeline=tl)
        return tl

    wall_fast, tl_fast = _best_wall(run_fast, repeats)
    wall_ref, tl_ref = _best_wall(run_ref, repeats)
    assert tl_fast.events == tl_ref.events, \
        "table_ii: columnar timeline diverged from object recorder"
    tokens = sum(2 * ctx for _, ctx in rows)
    return {
        "name": "table_ii",
        "sim_tokens": tokens,
        "events": tl_fast.n_events,
        "wall_fast_s": wall_fast,
        "wall_reference_s": wall_ref,
        "speedup": wall_ref / wall_fast,
        "tokens_per_wall_s_fast": tokens / wall_fast,
        "tokens_per_wall_s_reference": tokens / wall_ref,
        "events_per_wall_s_fast": tl_fast.n_events / wall_fast,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small traces, single repeat (CI fast lane)")
    ap.add_argument("--repeats", type=int, default=None,
                    help="wall-clock repeats (best-of); default 2 smoke / 5")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="fail if any path's fast-vs-reference speedup "
                         "falls below this floor (host-independent gate)")
    ap.add_argument("--out", type=Path, default=ART / "BENCH_speed.json")
    args = ap.parse_args()
    repeats = args.repeats or (2 if args.smoke else 5)

    cal = _host_calibration()
    cases = [
        bench_serving_path(args.smoke, repeats),
        bench_paged_path(args.smoke, repeats),
        bench_table_ii_path(args.smoke, repeats),
    ]

    doc = {
        "bench": "speed", "schema": 1, "smoke": args.smoke,
        "repeats": repeats,
        # host fingerprint: the regression gate compares wall_ms only
        # when this matches the baseline's host (see check_regression)
        "host_ops_per_s": round(cal, 1),
        "metrics": {
            "speedup": {c["name"]: round(c["speedup"], 3) for c in cases},
            "wall_ms": {f"{c['name']}_fast":
                        round(c["wall_fast_s"] * 1e3, 3) for c in cases},
            "sim_tokens_per_wall_s": {
                f"{c['name']}_fast":
                    round(c["tokens_per_wall_s_fast"], 1) for c in cases} | {
                f"{c['name']}_reference":
                    round(c["tokens_per_wall_s_reference"], 1)
                for c in cases},
            "events_per_wall_s": {
                c["name"]: round(c["events_per_wall_s_fast"], 1)
                for c in cases},
        },
        "rows": cases,
    }
    args.out.parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)

    print("path,speedup,tokens_per_wall_s_fast,tokens_per_wall_s_reference,"
          "events_per_wall_s")
    for c in cases:
        print(f"{c['name']},{c['speedup']:.2f},"
              f"{c['tokens_per_wall_s_fast']:.0f},"
              f"{c['tokens_per_wall_s_reference']:.0f},"
              f"{c['events_per_wall_s_fast']:.0f}")
    print(f"wrote {args.out}")

    if args.min_speedup is not None:
        slow = [c for c in cases if c["speedup"] < args.min_speedup]
        if slow:
            print(f"SPEED REGRESSION: "
                  f"{[(c['name'], round(c['speedup'], 1)) for c in slow]} "
                  f"below the fast-vs-reference floor (--min-speedup "
                  f"{args.min_speedup})")
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
