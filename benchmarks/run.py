"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall time of the
producing computation on this host; derived = the headline quantity the
paper's table/figure reports).  Detailed tables go to artifacts/bench/;
headline benches additionally write ``BENCH_<name>.json`` artifacts
(throughput, tok/J, p50/p99 in one stable schema) so the perf trajectory
stays machine-readable across PRs (uploaded by CI).

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run table_ii   # one (alias: table2)
  python benchmarks/run.py table2 --trace-out /tmp/t.json   # + chrome trace
"""
from __future__ import annotations

import copy
import dataclasses
import itertools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

ART = ROOT / "artifacts" / "bench"

PAPER_TABLE_II = {
    ("llama3.2-1b", 512): (1503.8, 4.0520, 371.1),
    ("llama3.2-1b", 1024): (969.2, 4.0513, 239.2),
    ("llama3.2-1b", 2048): (566.4, 4.0507, 139.8),
    ("llama3-8b", 512): (386.5, 28.4018, 13.6),
    ("llama3-8b", 1024): (309.8, 28.4015, 10.9),
    ("llama3-8b", 2048): (221.9, 28.4010, 7.8),
    ("llama2-13b", 512): (228.9, 52.3014, 4.4),
    ("llama2-13b", 1024): (192.4, 52.3012, 3.7),
    ("llama2-13b", 2048): (146.2, 52.3009, 2.8),
}


def _emit(name, t0, derived):
    us = (time.time() - t0) * 1e6
    print(f"{name},{us:.0f},{derived}")


def _save(name, obj):
    ART.mkdir(parents=True, exist_ok=True)
    with open(ART / f"{name}.json", "w") as f:
        json.dump(obj, f, indent=1, default=str)


def _bench_artifact(name, metrics, rows=None, extra=None):
    """BENCH_<name>.json — one stable schema per bench across PRs so the
    perf trajectory is machine-diffable (CI uploads these).  ``extra``
    merges additional top-level keys (e.g. the ``host_ops_per_s``
    calibration fingerprint that check_regression.py uses to decide
    wall-clock comparability)."""
    ART.mkdir(parents=True, exist_ok=True)
    doc = {"bench": name, "schema": 1, "metrics": metrics}
    if rows is not None:
        doc["rows"] = rows
    if extra:
        doc.update(extra)
    with open(ART / f"BENCH_{name}.json", "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True, default=str)


@dataclasses.dataclass
class Cell:
    """One point of a benchmark sweep grid: ordered ``(axis, value)``
    pairs plus the abbreviation map used to render its artifact key."""
    axes: tuple
    abbrev: dict

    def __getitem__(self, name):
        for k, v in self.axes:
            if k == name:
                return v
        raise KeyError(name)

    def get(self, name, default=None):
        return next((v for k, v in self.axes if k == name), default)

    def key(self, *, without=()) -> str:
        """Stable artifact key: ``<abbrev><value>`` fragments joined by
        ``_`` in axis order.  Bools render as 0/1; a ``None`` axis value
        is skipped (sparse axes — e.g. ``prefix_sharing`` only appears
        on the prefix cells); ``without`` drops axes (the paged bench's
        tier-free keys)."""
        parts = []
        for name, value in self.axes:
            if name in without or value is None:
                continue
            if isinstance(value, bool):
                value = int(value)
            parts.append(f"{self.abbrev.get(name, name)}{value}")
        return "_".join(parts)


def cell_grid(axes, abbrev=None):
    """Cartesian product of named axes -> list of :class:`Cell` in
    row-major (last axis fastest) order.  Replaces the ad-hoc per-bench
    key builders (the `_prefix{0,1}` disambiguation pattern) with one
    stable naming scheme shared by every sweep bench."""
    abbrev = abbrev or {}
    names = list(axes)
    return [Cell(tuple(zip(names, vals)), abbrev)
            for vals in itertools.product(*axes.values())]


# ---------------------------------------------------------------------------

def bench_table_ii():
    """Table II: PICNIC LLM inference benchmark (9 rows) vs the paper."""
    from repro.configs import get_config
    from repro.core import PicnicSimulator
    t0 = time.time()
    sim = PicnicSimulator()
    rows, errs = [], []
    for (arch, ctx), (tput, power, eff) in PAPER_TABLE_II.items():
        r = sim.run(get_config(arch), ctx, ctx)
        err = r.throughput_tps / tput - 1
        errs.append(abs(err))
        rows.append({**r.row(), "paper_tput": tput, "paper_power": power,
                     "paper_eff": eff, "tput_err_%": round(100 * err, 1)})
    mean_err = 100 * float(np.mean(errs))
    _save("table_ii", rows)
    _bench_artifact("table_ii", {
        "mean_abs_tput_err_pct": round(mean_err, 3),
        "throughput_tok_s": {f"{r['model']}/{r['context']}":
                             r["throughput_tok_s"] for r in rows},
        "efficiency_tok_J": {f"{r['model']}/{r['context']}":
                             r["efficiency_tok_J"] for r in rows},
    }, rows=rows)
    _emit("table_ii", t0, f"mean_abs_tput_err_pct={mean_err:.2f}")
    return rows


def bench_table_iii():
    """Table III: platform comparison (Llama-8B 1024/1024, H100 baseline)."""
    from repro.configs import get_config
    from repro.core import PicnicSimulator, comparison_table
    t0 = time.time()
    sim = PicnicSimulator()
    r = sim.run(get_config("llama3-8b"), 1024, 1024, ccpg=True)
    rows = comparison_table(r)
    _save("table_iii", rows)
    _bench_artifact("table_iii", {
        "throughput_tok_s": rows[0]["throughput_tok_s"],
        "efficiency_tok_J": rows[0]["efficiency_tok_J"],
        "eff_impr_vs_h100": rows[0]["eff_impr_vs_h100"],
        "speedup_vs_h100": rows[0]["speedup_vs_h100"],
    })
    _emit("table_iii", t0,
          f"eff_impr_vs_h100={rows[0]['eff_impr_vs_h100']}x_paper=57x")
    return rows


def bench_table_iv():
    """Table IV: power & area breakdown of the PICNIC macros."""
    from repro.core import table_iv, TileSpec
    t0 = time.time()
    t = table_iv()
    ts = TileSpec()
    t["_tile"] = {"area_mm2": ts.tile_area_mm2,
                  "active_W": ts.tile_power_active,
                  "sleep_W": ts.tile_power_sleep}
    _save("table_iv", t)
    _emit("table_iv", t0,
          f"router_pe_pair_uW={t['Total (IPCN-PE)']['power_uW']:.0f}")
    return t


def bench_fig8_ccpg():
    """Fig 8: system power & efficiency with/without CCPG."""
    from repro.configs import get_config
    from repro.core import PicnicSimulator
    t0 = time.time()
    sim = PicnicSimulator()
    rows = []
    for arch in ("llama3.2-1b", "llama3-8b", "llama2-13b"):
        cfg = get_config(arch)
        r0 = sim.run(cfg, 1024, 1024, ccpg=False)
        r1 = sim.run(cfg, 1024, 1024, ccpg=True)
        rows.append({
            "model": arch,
            "power_W": round(r0.avg_power_W, 3),
            "power_ccpg_W": round(r1.avg_power_W, 3),
            "saving_%": round(100 * (1 - r1.avg_power_W / r0.avg_power_W), 1),
            "eff_tpj": round(r0.efficiency_tpj, 2),
            "eff_ccpg_tpj": round(r1.efficiency_tpj, 2),
            "tput_ratio": round(r1.throughput_tps / r0.throughput_tps, 4),
        })
    _save("fig8_ccpg", rows)
    saving_8b = [r for r in rows if r["model"] == "llama3-8b"][0]["saving_%"]
    _emit("fig8_ccpg", t0, f"llama8b_power_saving_pct={saving_8b}_paper=80")
    return rows


def bench_fig9_c2c():
    """Fig 9: average C2C power, electrical vs optical, per model/ctx."""
    from repro.configs import get_config
    from repro.core import ELECTRICAL, OPTICAL, PicnicSimulator
    from repro.core.interconnect import c2c_average_power
    t0 = time.time()
    sim = PicnicSimulator()
    rows = []
    for arch in ("llama3.2-1b", "llama3-8b", "llama2-13b"):
        for ctx in (512, 1024, 2048):
            r = sim.run(get_config(arch), ctx, ctx)
            rate = r.c2c_bytes_total / (r.prefill_s + r.decode_s)
            rows.append({
                "model": arch, "ctx": ctx,
                "c2c_rate_MBps": round(rate / 1e6, 2),
                "optical_mW": round(1e3 * c2c_average_power(rate, OPTICAL), 3),
                "electrical_mW": round(
                    1e3 * c2c_average_power(rate, ELECTRICAL), 3),
            })
    _save("fig9_c2c", rows)
    # the paper's two claims: optical < electrical, power falls with ctx
    ok1 = all(r["optical_mW"] < r["electrical_mW"] for r in rows)
    by_model = {}
    for r in rows:
        by_model.setdefault(r["model"], []).append(r["electrical_mW"])
    ok2 = all(v[0] >= v[-1] for v in by_model.values())
    _emit("fig9_c2c", t0, f"optical_lt_electrical={ok1}_falls_with_ctx={ok2}")
    return rows


def bench_fig10_timeline():
    """Fig 10: C2C transfer distribution over time (Llama-1B)."""
    from repro.configs import get_config
    from repro.core import PicnicSimulator
    t0 = time.time()
    sim = PicnicSimulator()
    trace = sim.c2c_trace(get_config("llama3.2-1b"), n_tokens=8, context=512)
    horizon = max(t + d for t, d, _ in trace.events) * 1.01
    bins = trace.binned(horizon, 100)
    out = {"utilization": trace.utilization(horizon),
           "n_bursts": len(trace.events), "bins_GBps": bins}
    _save("fig10_timeline", out)
    _emit("fig10_timeline", t0,
          f"link_utilization={out['utilization']:.4f}_bursty=True")
    return out


def bench_serving():
    """Continuous-batching serving engine: the same 64-request Poisson
    trace (Llama-1B 512/64) served 1-at-a-time vs batch-8, ccpg off/on.
    Headline: batched decode throughput at batch 8 vs sequential."""
    from repro.configs import get_config
    from repro.core import PicnicSimulator
    from repro.launch import ServingConfig, SweepCell, Trace, sweep
    t0 = time.time()
    cfg = get_config("llama3.2-1b")
    sim = PicnicSimulator()
    grid = cell_grid({"max_batch": (1, 8), "ccpg": (False, True)})
    cells = [SweepCell(c.key(), cfg,
                       Trace.poisson(64, rate_rps=40, seed=0,
                                     prompt_len=512, max_new=64),
                       ServingConfig(max_batch=c["max_batch"],
                                     ccpg=c["ccpg"]), sim=sim)
             for c in grid]
    results = sweep(cells)
    rows = [{"max_batch": c["max_batch"], **r.report.row()}
            for c, r in zip(grid, results)]
    tput = {(c["max_batch"], c["ccpg"]): r.report.tokens_per_s
            for c, r in zip(grid, results)}
    speedup = tput[(8, False)] / tput[(1, False)]
    _save("serving", rows)
    _bench_artifact("serving", {
        "batch8_vs_1_speedup": round(speedup, 3),
        "tokens_per_s": {f"b{r['max_batch']}_ccpg{int(r['ccpg'])}":
                         r["tokens_per_s"] for r in rows},
        "tokens_per_J": {f"b{r['max_batch']}_ccpg{int(r['ccpg'])}":
                         r["tokens_per_J"] for r in rows},
        "p50_latency_s": {f"b{r['max_batch']}_ccpg{int(r['ccpg'])}":
                          r["p50_latency_s"] for r in rows},
        "p99_latency_s": {f"b{r['max_batch']}_ccpg{int(r['ccpg'])}":
                          r["p99_latency_s"] for r in rows},
    }, rows=rows)
    _emit("serving", t0, f"batch8_vs_1at_a_time_tput={speedup:.2f}x")
    return rows


def bench_paged():
    """Paged KV-cache serving (ISSUE 4 tentpole): context length x
    arrival rate sweep over the FINITE scratchpad budget (blocks sized
    from the mapped model, DRAM-hub spill tier behind the photonic link,
    chunked prefill) vs the infinite-capacity engine that silently
    mispriced long contexts.  Headline: how much of the infinite-cache
    throughput the paged engine keeps at the longest context — plus the
    ISSUE 6 prefix-heavy cell, where copy-on-write prefix sharing
    recovers the batch occupancy that long shared system prompts cost."""
    from repro.configs import get_config
    from repro.core import PicnicSimulator
    from repro.launch import ServingConfig, SweepCell, Trace, sweep
    from repro.runtime.kv_cache import kv_cache_from_model
    t0 = time.time()
    arch = "llama3.2-1b"
    cfg = get_config(arch)
    kvc = kv_cache_from_model(cfg, kv_frac=0.5, dram_frac=1.0)
    abbrev = {"rate_rps": "r", "paged": "p", "prefix_sharing": "prefix"}
    grid = cell_grid({"ctx": (512, 2048, 8192), "rate_rps": (20, 60),
                      "paged": (False, True)}, abbrev=abbrev)
    # prefix-heavy cells (ISSUE 6): 90% of requests carry a long shared
    # system prefix (8064 of 8192 prompt tokens) at the capacity-bound
    # corner — without sharing each sharer pays the full footprint and
    # mean batch collapses to ~2.4; COW prefix sharing dedups the common
    # blocks and recovers most of the occupancy
    grid += cell_grid({"ctx": (8192,), "rate_rps": (60,), "paged": (True,),
                       "prefix_sharing": (False, True)}, abbrev=abbrev)
    sim_plain = PicnicSimulator()
    sim_hub = PicnicSimulator()
    sim_hub.ccpg_model.include_dram_hub = True
    cells = []
    for c in grid:
        share = c.get("prefix_sharing")
        if share is None:
            # max_new keeps residents decoding long enough to build
            # co-residency — the regime where capacity binds (short
            # decodes are prefill-serial and never stress the cache)
            kv = kvc if c["paged"] else None
            trace = Trace.poisson(16, rate_rps=c["rate_rps"], seed=0,
                                  prompt_len=c["ctx"], max_new=256)
        else:
            kv = dataclasses.replace(kvc, prefix_sharing=share)
            trace = Trace.poisson(24, rate_rps=60, seed=0, prompt_len=8192,
                                  max_new=512, prefix_len=8064,
                                  prefix_frac=0.9)
        cells.append(SweepCell(
            c.key(), cfg, trace,
            ServingConfig(max_batch=8, ccpg=True, kv_cache=kv,
                          chunked_prefill_tokens=512 if kv else 0),
            sim=sim_hub if c["paged"] else sim_plain))
    results = sweep(cells)

    rows, tput, mean_batch = [], {}, {}
    for c, res in zip(grid, results):
        rep, st = res.report, res.kv_stats
        share = c.get("prefix_sharing")
        if share is None:
            tput[(c["ctx"], c["rate_rps"], c["paged"])] = rep.tokens_per_s
            rows.append({
                "ctx": c["ctx"], "rate_rps": c["rate_rps"],
                "paged": c["paged"], **rep.row(),
                **({"kv": st.row()} if st is not None else {}),
            })
        else:
            mean_batch[share] = rep.mean_batch_occupancy
            rows.append({
                "ctx": c["ctx"], "rate_rps": c["rate_rps"], "paged": True,
                "prefix": True, "prefix_sharing": share,
                **rep.row(), "kv": st.row(),
            })
    keep = tput[(8192, 60, True)] / tput[(8192, 60, False)]
    recovery = mean_batch[True] / mean_batch[False]

    _save("paged", rows)
    keyed = list(zip((c.key() for c in grid), rows))
    tiered = [(c.key(without=("paged",)), r)
              for c, r in zip(grid, rows) if r["paged"]]
    _bench_artifact("paged", {
        "paged_vs_infinite_tput_at_8k": round(keep, 3),
        "prefix_batch_recovery_speedup": round(recovery, 3),
        "prefix_mean_batch": {"off": round(mean_batch[False], 2),
                              "on": round(mean_batch[True], 2)},
        "kv_blocks": kvc.n_blocks,
        "tokens_per_s": {k: r["tokens_per_s"] for k, r in keyed},
        "tokens_per_J": {k: r["tokens_per_J"] for k, r in keyed},
        "p99_latency_s": {k: r["p99_latency_s"] for k, r in keyed},
        "preemptions": {k: r["kv"]["preemptions"] for k, r in tiered},
        "spilled_MB": {k: round(r["kv"]["spilled_bytes"] / 1e6, 2)
                       for k, r in tiered},
    }, rows=rows)
    _emit("paged", t0, f"paged_vs_infinite_tput_at_8k={keep:.3f} "
                       f"prefix_batch_recovery_speedup={recovery:.2f}x")
    return rows


def bench_fleet():
    """Disaggregated prefill/decode fleet (ISSUE 9 tentpole): node count x
    prefill:decode split x arrival rate over launch/fleet_engine.py.  Each cell
    serves the same Poisson trace (Llama-1B 512/64, CCPG on) through a
    FleetEngine; disaggregated splits hand finished-prefill KV to a
    decode node as an inter-node C2CTransfer priced by
    core/interconnect.fleet_handoff_bytes.  The combined cells (handoff
    off, same node count) are the like-for-like baseline, so the
    headline is the tok/J-optimal disaggregation point and its
    efficiency ratio vs combined serving — honest even when < 1.  An
    autoscale pair at low arrival rate surfaces CCPG node wake counts
    (whole nodes sleep, scale-up pays real ClusterWake latency)."""
    from repro.configs import get_config
    from repro.core import PicnicSimulator
    from repro.launch import FleetConfig, ServingConfig, Trace
    from repro.launch.fleet_engine import FleetEngine
    try:
        from benchmarks.microbench import _host_calibration
    except ImportError:                     # `python benchmarks/run.py`
        from microbench import _host_calibration
    t0 = time.time()
    cfg = get_config("llama3.2-1b")
    cal = _host_calibration()
    ecfg = ServingConfig(max_batch=8, ccpg=True)

    # (n_prefill, n_decode, handoff): combined baselines keep the node
    # count so the ratio sweep is like-for-like
    shapes = {2: [(1, 1, False), (1, 1, True)],
              4: [(2, 2, False), (1, 3, True), (2, 2, True), (3, 1, True)]}
    rates = (60, 120)
    t_wall = time.perf_counter()
    rows, tput, eff = [], {}, {}
    for rate in rates:
        trace = Trace.poisson(48, rate_rps=rate, seed=0,
                              prompt_len=512, max_new=64)
        for n, splits in shapes.items():
            for (p, d, handoff) in splits:
                fc = FleetConfig(n_prefill=p, n_decode=d, handoff=handoff,
                                 engine=ecfg)
                eng = FleetEngine(cfg, fc, sim=PicnicSimulator())
                rep = eng.run([copy.copy(r) for r in trace])
                key = (f"n{n}_p{p}d{d}_"
                       f"{'dis' if handoff else 'comb'}_r{rate}")
                assert rep.finished == len(trace), \
                    f"fleet cell {key}: dropped requests"
                rows.append({"cell": key, **rep.row()})
                tput[key] = rep.tokens_per_s
                eff[(n, rate, handoff)] = max(
                    eff.get((n, rate, handoff), 0.0), rep.tokens_per_J)

    # autoscale pair: low arrival rate, 2+2 nodes — with autoscaling the
    # fleet parks idle nodes asleep and pays ClusterWake on scale-up
    wakes = {}
    trace = Trace.poisson(48, rate_rps=20, seed=0,
                          prompt_len=512, max_new=64)
    for auto in (False, True):
        fc = FleetConfig(n_prefill=2, n_decode=2, handoff=True,
                         engine=ecfg, autoscale=auto, min_awake=1,
                         scale_up_queue=2)
        rep = FleetEngine(cfg, fc, sim=PicnicSimulator()).run(
            [copy.copy(r) for r in trace])
        key = f"n4_p2d2_dis_r20_auto{int(auto)}"
        rows.append({"cell": key, **rep.row()})
        wakes[auto] = rep.wakes
    t_wall = time.perf_counter() - t_wall

    best_eff = max(v for (_, _, h), v in eff.items() if h)
    ratio = max(eff[(n, r, True)] / eff[(n, r, False)]
                for n in shapes for r in rates)
    _save("fleet", rows)
    _bench_artifact("fleet", {
        "fleet_best_tokens_per_J": round(best_eff, 2),
        "disagg_vs_combined_eff_speedup": round(ratio, 3),
        "autoscale_wakes": {"off": wakes[False], "on": wakes[True]},
        "tokens_per_s": {r["cell"]: r["tokens_per_s"] for r in rows},
        "tokens_per_J": {r["cell"]: r["tokens_per_J"] for r in rows},
        "handoff_MB": {r["cell"]: r["handoff_MB"] for r in rows},
        "p99_ttft_s": {r["cell"]: r["p99_ttft_s"] for r in rows},
        "wall_ms": round(t_wall * 1e3, 1),
    }, rows=rows, extra={"host_ops_per_s": round(cal, 1)})
    _emit("fleet", t0,
          f"disagg_vs_combined_eff={ratio:.3f}x "
          f"autoscale_wakes={wakes[True]}")
    return rows


def bench_chaos():
    """Deterministic fault injection over the fleet (ISSUE 10 tentpole):
    fault profile x node count through launch/fleet_engine.py with a
    seeded FaultConfig schedule.  Profiles: zero-fault baseline, link
    degradation windows (FEC/retransmit overhead on every handoff in
    the window), node crash + recovery (KV lost; survivors re-routed
    with recompute-from-prompt) and full chaos (links + crashes + CCPG
    wake failures).  Headlines: worst-case availability, chaos goodput
    retention vs the zero-fault baseline, and MTTR.  The zero-fault
    default is asserted hex-identical to an inert FaultConfig in-bench,
    so the fault machinery provably prices nothing when no fault is
    declared."""
    from repro.configs import get_config
    from repro.core import PicnicSimulator
    from repro.launch import FleetConfig, ServingConfig, Trace
    from repro.launch.config import FaultConfig
    from repro.launch.fleet_engine import FleetEngine
    try:
        from benchmarks.microbench import _host_calibration
    except ImportError:                     # `python benchmarks/run.py`
        from microbench import _host_calibration
    t0 = time.time()
    cfg = get_config("llama3.2-1b")
    cal = _host_calibration()
    ecfg = ServingConfig(max_batch=8, ccpg=True)

    def profile(name, n_nodes):
        if name == "none":
            return None
        if name == "links":
            return FaultConfig.seeded(seed=11, n_nodes=n_nodes,
                                      horizon_s=0.8, link_windows=2)
        if name == "crash":
            return FaultConfig.seeded(seed=12, n_nodes=n_nodes,
                                      horizon_s=0.8, node_crashes=1)
        return FaultConfig.seeded(seed=13, n_nodes=n_nodes,
                                  horizon_s=0.8, link_windows=2,
                                  node_crashes=2, wake_faults=1)

    def hexrow(row):
        return {k: (v.hex() if isinstance(v, float) else v)
                for k, v in row.items()}

    shapes = {2: (1, 1), 4: (2, 2)}
    profiles = ("none", "links", "crash", "chaos")
    t_wall = time.perf_counter()
    rows, avail, goodput, mttr = [], {}, {}, {}
    base_tput = {}
    for n, (p, d) in shapes.items():
        trace = Trace.poisson(48, rate_rps=60, seed=0,
                              prompt_len=512, max_new=64)
        for prof in profiles:
            fc = FleetConfig(n_prefill=p, n_decode=d, handoff=True,
                             engine=ecfg, fault=profile(prof, n))
            eng = FleetEngine(cfg, fc, sim=PicnicSimulator())
            rep = eng.run([copy.copy(r) for r in trace])
            key = f"n{n}_p{p}d{d}_{prof}"
            assert rep.finished + rep.rejected == rep.n_requests, \
                f"chaos cell {key}: silent request loss"
            row = rep.row()
            rows.append({"cell": key, **row})
            if prof == "none":
                base_tput[n] = rep.tokens_per_s
                # zero-fault identity: an INERT FaultConfig must price
                # nothing — hex-identical row to fault=None
                fc_inert = FleetConfig(n_prefill=p, n_decode=d,
                                       handoff=True, engine=ecfg,
                                       fault=FaultConfig())
                rep_i = FleetEngine(cfg, fc_inert,
                                    sim=PicnicSimulator()).run(
                    [copy.copy(r) for r in trace])
                assert hexrow(rep_i.row()) == hexrow(row), \
                    f"chaos cell {key}: inert FaultConfig not inert"
            else:
                avail[key] = row["availability"]
                goodput[key] = row["goodput_tokens_per_s"]
                if row["mttr_s"] is not None:
                    mttr[key] = row["mttr_s"]
    t_wall = time.perf_counter() - t_wall

    worst_avail = min(avail.values())
    retention = min(goodput[f"n{n}_p{p}d{d}_chaos"] / base_tput[n]
                    for n, (p, d) in shapes.items())
    _save("chaos", rows)
    _bench_artifact("chaos", {
        "worst_availability": round(worst_avail, 6),
        "chaos_goodput_retention": round(retention, 4),
        "availability": avail,
        "goodput_tokens_per_s": goodput,
        "mttr_s": mttr,
        "p99_ttft_s": {r["cell"]: r["p99_ttft_s"] for r in rows},
        "finished": {r["cell"]: r["finished"] for r in rows},
        "rejected": {r["cell"]: r["rejected"] for r in rows},
        "wall_ms": round(t_wall * 1e3, 1),
    }, rows=rows, extra={"host_ops_per_s": round(cal, 1)})
    _emit("chaos", t0,
          f"worst_availability={worst_avail:.4f} "
          f"goodput_retention={retention:.3f}")
    return rows


def bench_distributed():
    """Measured HLO collectives -> photonic cost model (ISSUE 2 tentpole).

    Lowers the sharded llama-1B prefill + decode cells (picnic variant:
    shard_map SP attention / partial-softmax decode) on a forced 8-host-
    device 1x8 (data x model) mesh in a subprocess, extracts per-collective
    wire bytes from the compiled HLO, and feeds them into the simulator as
    the photonic C2C traffic term — next to the default analytic path,
    which must keep reproducing the calibrated Table II row exactly."""
    from repro.configs import get_config
    from repro.core import PicnicSimulator
    from repro.launch.collective_capture import (capture_in_subprocess,
                                                 to_measured_traffic)
    t0 = time.time()
    arch, ctx = "llama3.2-1b", 512
    recs = capture_in_subprocess(arch, modes=("prefill", "decode"),
                                 seq_len=ctx, batch=1, mesh="1x8",
                                 variant="picnic")
    pre = next(r for r in recs if r["mode"] == "prefill")
    dec = next(r for r in recs if r["mode"] == "decode")
    mt = to_measured_traffic(pre, dec)

    sim = PicnicSimulator()
    cfg = get_config(arch)
    r_an = sim.run(cfg, ctx, ctx)                      # default: analytic
    r_me = sim.run(cfg, ctx, ctx, measured_c2c=mt)     # measured traffic
    # guard: the default b=1 path must still hit the calibrated Table II row
    paper_tput = PAPER_TABLE_II[(arch, ctx)][0]
    tput_err = abs(r_an.throughput_tps / paper_tput - 1)
    assert tput_err < 0.07, (r_an.throughput_tps, paper_tput)
    assert r_me.throughput_tps == r_an.throughput_tps  # traffic != timing

    out = {
        "arch": arch, "ctx": ctx, "mesh": dec["mesh"],
        "per_collective_decode": dec["collectives"],
        "per_collective_prefill": pre["collectives"],
        "measured": {
            "prefill_bytes": mt.prefill_bytes,
            "decode_bytes_per_token": mt.decode_bytes_per_token,
            "c2c_bytes_total": r_me.c2c_bytes_total,
            "c2c_power_W": r_me.c2c_avg_power_W,
            "c2c_source": r_me.c2c_source,
        },
        "analytic": {
            "c2c_bytes_total": r_an.c2c_bytes_total,
            "c2c_power_W": r_an.c2c_avg_power_W,
            "tput_err_vs_paper_%": round(100 * tput_err, 2),
        },
    }
    _save("distributed", out)
    ratio = r_me.c2c_bytes_total / max(r_an.c2c_bytes_total, 1)
    _emit("distributed", t0,
          f"measured_B_per_tok={mt.decode_bytes_per_token:.0f}_"
          f"measured_vs_analytic_c2c={ratio:.2f}x_tableII_err_pct="
          f"{100 * tput_err:.2f}")
    return out


def bench_kernels():
    """Microbenchmarks of the Pallas kernels (interpret mode on CPU: the
    number that matters here is allclose-to-oracle; wall time is recorded
    for harness completeness)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    key = jax.random.PRNGKey(0)
    results = []

    t0 = time.time()
    x = jax.random.normal(key, (256, 512)) * 3
    o = ops.pwl_softmax(x)
    err = float(jnp.max(jnp.abs(o - ref.ref_pwl_softmax(x))))
    _emit("kernel_pwl_softmax", t0, f"max_err={err:.2e}")
    results.append(("pwl_softmax", err))

    t0 = time.time()
    q = jax.random.normal(key, (1, 256, 4, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 2, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 2, 64))
    o = ops.flash_attention(q, k, v)
    r = ref.ref_flash_attention(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2))
    err = float(jnp.max(jnp.abs(o - r)))
    _emit("kernel_flash_attention", t0, f"max_err={err:.2e}")
    results.append(("flash_attention", err))

    t0 = time.time()
    x = jax.random.normal(key, (64, 512))
    w = jax.random.normal(jax.random.PRNGKey(3), (512, 128)) * 0.05
    ex = ref.ref_exact_matmul(x, w)
    o = ops.cim_matmul(x, w, block_m=64, block_n=128)
    rel = float(jnp.linalg.norm(o - ex) / jnp.linalg.norm(ex))
    _emit("kernel_cim_matmul", t0, f"rel_err_vs_exact={rel:.3f}")
    results.append(("cim_matmul", rel))

    t0 = time.time()
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 4, 64)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(16, 16, 2, 64)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(16, 16, 2, 64)), jnp.float32)
    tables = jnp.asarray([[0, 2, 4, 0], [1, 3, 0, 0]], jnp.int32)
    ctxs = jnp.asarray([50, 20], jnp.int32)
    o = ops.paged_attention(q, kc, vc, tables, ctxs)
    r = ref.ref_paged_attention(q, kc, vc, tables, ctxs)
    err = float(jnp.max(jnp.abs(o - r)))
    _emit("kernel_paged_attention", t0, f"max_err={err:.2e}")
    results.append(("paged_attention", err))

    t0 = time.time()
    xs = jax.random.normal(key, (1, 128, 2, 32))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(5), (1, 128, 2)))
    an = -jnp.exp(jax.random.normal(jax.random.PRNGKey(6), (2,)) * 0.2)
    B_ = jax.random.normal(jax.random.PRNGKey(7), (1, 128, 8)) * 0.3
    C_ = jax.random.normal(jax.random.PRNGKey(8), (1, 128, 8)) * 0.3
    o = ops.ssd_scan(xs, dt, an, B_, C_, chunk=32)
    err = float(jnp.max(jnp.abs(o - ref.ref_ssd(xs, dt, an, B_, C_,
                                                chunk=32))))
    _emit("kernel_ssd_scan", t0, f"max_err={err:.2e}")
    results.append(("ssd_scan", err))
    _save("kernels", results)
    return results


def bench_ablations():
    """Beyond-paper ablation: CIM ADC resolution and SCU PWL segment count
    vs numerical fidelity (the hardware knobs behind §II-A/§II-C)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    t0 = time.time()
    key = jax.random.PRNGKey(0)
    rows = []
    # ADC bits sweep on a transformer-shaped matmul
    x = jax.random.normal(key, (64, 1024))
    w = jax.random.normal(jax.random.PRNGKey(1), (1024, 256)) * 0.03
    ex = ref.ref_exact_matmul(x, w)
    for adc in (6, 8, 10, 12, 14):
        o = ops.cim_matmul(x, w, adc_bits=adc, block_m=64, block_n=256)
        rel = float(jnp.linalg.norm(o - ex) / jnp.linalg.norm(ex))
        rows.append({"knob": "adc_bits", "value": adc,
                     "rel_err_vs_fp": round(rel, 5)})
    # PWL softmax: top-1 agreement with exact softmax at attention scale
    s_ = jax.random.normal(jax.random.PRNGKey(2), (4096, 128)) * 4
    pwl = np.asarray(ops.pwl_softmax(s_))
    exact = np.asarray(ref.ref_softmax(s_))
    agree = float((pwl.argmax(-1) == exact.argmax(-1)).mean())
    maxdev = float(np.abs(pwl - exact).max())
    rows.append({"knob": "pwl_softmax_top1_agreement", "value": 8,
                 "rel_err_vs_fp": round(1 - agree, 5)})
    rows.append({"knob": "pwl_softmax_max_dev", "value": 8,
                 "rel_err_vs_fp": round(maxdev, 5)})
    _save("ablations", rows)
    adc12 = [r for r in rows if r["knob"] == "adc_bits"
             and r["value"] == 12][0]["rel_err_vs_fp"]
    _emit("ablations", t0,
          f"adc12_rel_err={adc12}_pwl_top1_agree={agree:.4f}")
    return rows


def export_trace(path):
    """--trace-out: export a chrome://tracing JSON of one dynamic-CCPG
    Llama-1B 512/64 walk — every TimelineIR category (ComputeSpan,
    C2CTransfer, ClusterWake, ClusterSleep, EnergySample, TokenEmit) in
    one trace.  Open with chrome://tracing or ui.perfetto.dev.  The
    export STREAMS (Timeline.dump_chrome_trace): no materialized event
    list, so million-event traces stay in constant memory."""
    from repro.configs import get_config
    from repro.core import PicnicSimulator, Timeline
    t0 = time.time()
    tl = Timeline()
    sim = PicnicSimulator()
    sim.run(get_config("llama3.2-1b"), 512, 64, ccpg=True,
            dynamic_ccpg=True, timeline=tl)
    tl.save_chrome_trace(path)
    _emit("trace_export", t0, f"events={tl.n_events}_path={path}")


BENCHES = {
    "table_ii": bench_table_ii,
    "table_iii": bench_table_iii,
    "table_iv": bench_table_iv,
    "fig8_ccpg": bench_fig8_ccpg,
    "fig9_c2c": bench_fig9_c2c,
    "fig10_timeline": bench_fig10_timeline,
    "serving": bench_serving,
    "paged": bench_paged,
    "fleet": bench_fleet,
    "chaos": bench_chaos,
    "distributed": bench_distributed,
    "kernels": bench_kernels,
    "ablations": bench_ablations,
}


# short CLI aliases for the paper-table benches
ALIASES = {"table2": "table_ii", "table3": "table_iii", "table4": "table_iv"}


def main() -> None:
    argv = sys.argv[1:]
    trace_out = None
    if "--trace-out" in argv:
        i = argv.index("--trace-out")
        try:
            trace_out = argv[i + 1]
        except IndexError:
            raise SystemExit("--trace-out requires a path argument")
        del argv[i:i + 2]
    which = [ALIASES.get(a, a) for a in argv] or list(BENCHES)
    print("name,us_per_call,derived")
    for name in which:
        BENCHES[name]()
    if trace_out is not None:
        export_trace(trace_out)


if __name__ == "__main__":
    main()
