#!/usr/bin/env python3
"""Trace one cell's window and split it by the model's layer scopes.

  python3 benchmarks/chip/trace_split.py --workload olmo-1b.decode.b192 \\
      --seed 1234 --seconds 20 [--out split.json]

Run on a TPU, from the root of a checkout.  It runs the cell as a
``run.py --trace 1`` run does (same engine, warm-up and window), with each
Python garbage collection recorded as a ``gc`` host span where the program
has ``repro.launch.tracing.gc_spans``, and checks no outputs.  It prints one
JSON object: the traced window's ``output_tok_s``; the step times that
``decode_step_ms`` and ``prefill_ms`` read; the layer readings of
``scopes.readings``; the ``device_scopes``, ``idle_causes`` and
``unscoped_ops`` breakdowns; ``reduction.py``'s ``device_ops`` and
``idle_gaps``; and the share of each program's device time that no op
ran in (``between_ops``) or no scope named (``unscoped``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = Path.cwd()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="also write the result to this file")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import numpy as np
    import harness
    try:
        from repro.launch.tracing import gc_spans
    except ImportError:         # a program without the helper: no gc spans
        gc_spans = contextlib.nullcontext
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"no TPU: JAX found {jax.devices()[0].platform}; nothing was run")
    harness.use_compile_cache(ROOT)
    cell = harness.Cell(ROOT, args.workload)
    sys.path.insert(0, str(cell.dir))
    ref = cell.module(cell.conf["reference"])
    engine_mod = cell.module(cell.traffic["engine"])
    engine = engine_mod.Engine(cell.module(cell.conf["program"]), cell.conf,
                               cell.traffic, args.seed, ref)
    engine.warm_up(np.random.default_rng([args.seed % 2**64, 1]))
    setup_s = time.perf_counter() - T_START
    tdir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    jax.profiler.start_trace(str(tdir))
    with gc_spans():
        start, end, requests = engine.run(np.random.default_rng(args.seed % 2**64),
                                          args.seconds)
    jax.profiler.stop_trace()
    calls = list(engine.calls)
    engine.close()

    xplane = next(tdir.rglob("*.xplane.pb"))
    chips = [d.id for d in jax.devices()[:cell.cell["chips"]]]
    result = {
        "workload": args.workload, "seed": args.seed,
        "device": {"kind": jax.devices()[0].device_kind, "count": len(jax.devices())},
        "setup_s": setup_s,
        "output_tok_s": sum(int((r.times <= end).sum()) for r in requests) / (end - start),
        **report(xplane, host_spans=engine_mod.HOST_SPANS, chips=chips, calls=calls),
    }
    shutil.rmtree(tdir, ignore_errors=True)
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


def report(xplane, *, host_spans, chips, calls) -> dict:
    """What one traced window shows, by layer: ``calls`` are the engine's
    (program, batch, length) records of the window."""
    import reduction
    import scopes
    red = reduction.reduce_file(xplane, host_spans=host_spans, chips=chips)
    split = scopes.split_file(xplane, red, host_spans=host_spans, chips=chips)
    out = {"window_s": red.window_s, "busy_s": red.busy_s,
           "device_idle_share": 100.0 * (1.0 - red.busy_s / red.window_s)}
    for program in ("decode", "prefill"):
        ns = red.module_ns(f"bench_{program}")
        if ns and len(ns) == sum(c[0] == program for c in calls):
            out[f"{program}_ms"] = sum(ns) / len(ns) * 1e-6
    out.update(scopes.readings(split))
    window = [e for e in split.executions if e.in_window]
    out["between_ops_share"] = {
        p: sum(e.between_ns for e in window if e.program == p)
        / sum(e.device_ns for e in window if e.program == p)
        for p in sorted({e.program for e in window})}
    out["unscoped_share"] = {
        p: sum(e.self_ns.get((), 0.0) for e in window if e.program == p)
        / sum(e.device_ns for e in window if e.program == p)
        for p in sorted({e.program for e in window})}
    out["gc_spans"] = len(split.gc_ns)
    out["gc_s"] = sum(split.gc_ns) * 1e-9
    out["orphan_ops"] = split.orphan_ops
    out["straddling_ops"] = split.straddling_ops
    out["trace_mb"] = Path(xplane).stat().st_size / 2**20
    out["breakdown"] = {"device_scopes": split.device_scopes(),
                        "idle_causes": split.idle_breakdown(10),
                        "unscoped_ops": split.top_unscoped(10),
                        "device_ops": red.top_ops(10),
                        "idle_gaps": red.idle_breakdown(10)}
    return out


if __name__ == "__main__":
    sys.exit(main())
