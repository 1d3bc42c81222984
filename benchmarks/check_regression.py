"""Bench-regression gate: fail CI when a headline metric regresses >10%
vs the committed baseline.

Compares ``artifacts/bench/BENCH_*.json`` (produced by ``benchmarks/run.py``
and ``benchmarks/microbench.py`` in the same CI run) against
``artifacts/bench/baseline/BENCH_*.json`` (committed to the repo).  Only
*headline* metrics are gated, DIRECTION-AWARE:

  * higher-is-better families (throughput tok/s, efficiency tok/J,
    speedups) fail when the current value drops >tolerance below the
    baseline;
  * lower-is-better families (``wall_ms`` wall clocks from the simulator
    microbench) fail when the current value rises >tolerance ABOVE the
    baseline.

Wall-clock benches (any doc carrying a ``host_ops_per_s`` calibration,
i.e. ``BENCH_speed.json``) are only compared when the baseline was
recorded on a similar-speed host (within ``HOST_TOL``) AND on the same
workload size (``smoke`` flag) — a slower CI runner is not a code
regression.  On foreign hosts the microbench's own ``--min-speedup``
floor is the (host-independent) gate.

Individual metrics can override the tolerance via ``TOLERANCE_OVERRIDES``
(longest key-prefix match per bench file) — so a noisy harness wall
clock gates loose while deterministic simulated outputs in the same doc
gate tight.

Everything else (latency percentiles, byte counts, error percentages) is
informational.  The simulator itself is deterministic, so a >10% drop in
a simulated metric is a real modeling/scheduling regression, not noise.

  python benchmarks/check_regression.py             # gate (exit 1 on fail)
  python benchmarks/check_regression.py --refresh   # accept current as baseline
  python benchmarks/check_regression.py --tolerance 0.05

A new bench with no committed baseline is reported but does not fail the
gate (commit its baseline with --refresh); a *missing* current file for a
baselined bench DOES fail — the bench silently disappearing is exactly
the kind of regression the gate exists to catch.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "artifacts" / "bench"
BASELINE_DIR = BENCH_DIR / "baseline"

# higher-is-better headline families (substring match on the metric key)
HEADLINE = ("tokens_per_s", "tokens_per_J", "throughput_tok_s",
            "efficiency_tok_J", "speedup", "eff_impr",
            "paged_vs_infinite_tput", "availability",
            "goodput_retention")
# lower-is-better families: real wall clocks (see microbench.py)
LOWER_IS_BETTER = ("wall_ms",)
# max relative host-calibration mismatch for wall-clock comparability
HOST_TOL = 0.30
# measured wall clocks jitter far more than the deterministic simulated
# metrics even on one host (scheduler noise, neighbors, cache state —
# observed same-host best-of-5 swings up to ~35%): wall-clock benches
# gate at this floor tolerance instead — wide enough to ignore
# run-to-run noise, tight enough to catch "the fast path lost its
# speedup" (a real regression there is 3-15x, not 50%)
WALL_BENCH_TOL = 0.50

# Per-metric tolerance overrides: (bench artifact name, flattened-key
# prefix) -> tolerance; the longest matching prefix wins, and an
# override beats both the CLI tolerance and the wall-clock widening.
# This lets one doc mix metric classes: a harness wall clock (loose) NEXT
# TO deterministic simulated outputs (tight), which the doc-level
# WALL_BENCH_TOL widening alone cannot express.
TOLERANCE_OVERRIDES = {
    # fleet doc (ISSUE 9): carries host_ops_per_s, so the doc-level
    # WALL_BENCH_TOL widening applies — pin the deterministic simulated
    # metrics back to tight and leave only the harness wall loose.
    # Documented in EXPERIMENTS.md §Disaggregation-sweep.
    ("BENCH_fleet.json", "wall_ms"): 0.50,
    ("BENCH_fleet.json", "tokens_per_s"): 0.10,
    ("BENCH_fleet.json", "tokens_per_J"): 0.10,
    ("BENCH_fleet.json", "fleet_best_tokens_per_J"): 0.10,
    ("BENCH_fleet.json", "disagg_vs_combined_eff_speedup"): 0.10,
    # chaos doc (ISSUE 10): same shape as the fleet doc — it carries
    # host_ops_per_s (doc-level WALL_BENCH_TOL widening), but every
    # availability/goodput number is a deterministic DES output of the
    # seeded fault schedule: pin them tight, leave only the harness
    # wall loose.  Documented in EXPERIMENTS.md §Chaos-sweep.
    ("BENCH_chaos.json", "wall_ms"): 0.50,
    ("BENCH_chaos.json", "availability"): 0.10,
    ("BENCH_chaos.json", "worst_availability"): 0.10,
    ("BENCH_chaos.json", "goodput_tokens_per_s"): 0.10,
    ("BENCH_chaos.json", "chaos_goodput_retention"): 0.10,
}


def metric_tolerance(bench: str, key: str, default: float) -> float:
    """Effective tolerance for one metric: longest-prefix override for
    ``(bench, key)`` if any, else ``default``."""
    best = None
    for (b, prefix), tol in TOLERANCE_OVERRIDES.items():
        if b == bench and key.startswith(prefix):
            if best is None or len(prefix) > best[0]:
                best = (len(prefix), tol)
    return default if best is None else best[1]


def _flatten(prefix: str, obj, out: dict) -> None:
    if isinstance(obj, dict):
        for k, v in obj.items():
            _flatten(f"{prefix}.{k}" if prefix else str(k), v, out)
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix] = float(obj)


def metric_direction(key: str) -> str:
    """'lower' | 'higher' | '' (not a gated headline metric)."""
    if any(h in key for h in LOWER_IS_BETTER):
        return "lower"
    if any(h in key for h in HEADLINE):
        return "higher"
    return ""


def headline_metrics(doc: dict) -> dict:
    flat: dict = {}
    _flatten("", doc.get("metrics", {}), flat)
    return {k: v for k, v in flat.items() if metric_direction(k)}


def hosts_comparable(base_doc: dict, cur_doc: dict) -> bool:
    """Wall clocks are only gated between runs on similar-speed hosts
    and identical workload sizes; benches that carry no calibration are
    always comparable (their metrics are simulated, not measured)."""
    b = base_doc.get("host_ops_per_s")
    c = cur_doc.get("host_ops_per_s")
    if b is None or c is None or b <= 0:
        return True
    if base_doc.get("smoke") != cur_doc.get("smoke"):
        return False
    return abs(c / b - 1.0) <= HOST_TOL


def compare(tolerance: float) -> int:
    if not BASELINE_DIR.is_dir():
        print(f"no baseline dir at {BASELINE_DIR}; nothing to gate")
        return 0
    failures, checked, new = [], 0, []
    for base_path in sorted(BASELINE_DIR.glob("BENCH_*.json")):
        cur_path = BENCH_DIR / base_path.name
        if not cur_path.exists():
            failures.append(f"{base_path.name}: current run produced no "
                            f"artifact (bench removed or failed?)")
            continue
        base_doc = json.loads(base_path.read_text())
        cur_doc = json.loads(cur_path.read_text())
        base = headline_metrics(base_doc)
        cur = headline_metrics(cur_doc)
        if not hosts_comparable(base_doc, cur_doc):
            # every metric in a wall-clock bench is host-sensitive
            # (speedup ratios included) — the microbench's own
            # --min-speedup floor gates foreign hosts instead
            print(f"{base_path.name}: host calibration / workload "
                  f"differs (host_ops_per_s "
                  f"{base_doc.get('host_ops_per_s')} vs "
                  f"{cur_doc.get('host_ops_per_s')}, smoke "
                  f"{base_doc.get('smoke')} vs {cur_doc.get('smoke')}); "
                  f"skipping its wall-clock gates")
            continue
        tol = tolerance
        if base_doc.get("host_ops_per_s") is not None:
            tol = max(tolerance, WALL_BENCH_TOL)
        for key, b in sorted(base.items()):
            direction = metric_direction(key)
            if key not in cur:
                failures.append(f"{base_path.name}:{key}: metric vanished")
                continue
            checked += 1
            c = cur[key]
            if b <= 0:
                continue
            tol_k = metric_tolerance(base_path.name, key, tol)
            if direction == "higher" and c < (1.0 - tol_k) * b:
                failures.append(
                    f"{base_path.name}:{key}: {c:.4g} < "
                    f"{(1 - tol_k) * b:.4g} "
                    f"(baseline {b:.4g}, -{100 * (1 - c / b):.1f}%)")
            elif direction == "lower" and c > (1.0 + tol_k) * b:
                failures.append(
                    f"{base_path.name}:{key}: {c:.4g} > "
                    f"{(1 + tol_k) * b:.4g} "
                    f"(baseline {b:.4g}, +{100 * (c / b - 1):.1f}% "
                    f"wall-clock slowdown)")
    for cur_path in sorted(BENCH_DIR.glob("BENCH_*.json")):
        if not (BASELINE_DIR / cur_path.name).exists():
            new.append(cur_path.name)
    if new:
        print(f"unbaselined benches (run --refresh to adopt): {new}")
    if failures:
        print(f"BENCH REGRESSION: {len(failures)} headline metric(s) "
              f"regressed more than {100 * tolerance:.0f}%:")
        for f in failures:
            print(f"  {f}")
        return 1
    print(f"bench gate ok: {checked} headline metrics within "
          f"{100 * tolerance:.0f}% of baseline")
    return 0


def refresh() -> int:
    BASELINE_DIR.mkdir(parents=True, exist_ok=True)
    n = 0
    for cur_path in sorted(BENCH_DIR.glob("BENCH_*.json")):
        shutil.copy2(cur_path, BASELINE_DIR / cur_path.name)
        n += 1
    print(f"baseline refreshed: {n} BENCH_*.json copied to {BASELINE_DIR}")
    return 0 if n else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--refresh", action="store_true",
                    help="adopt the current BENCH_*.json as the baseline")
    ap.add_argument("--tolerance", type=float, default=0.10,
                    help="allowed fractional regression (default 0.10)")
    args = ap.parse_args()
    return refresh() if args.refresh else compare(args.tolerance)


if __name__ == "__main__":
    sys.exit(main())
