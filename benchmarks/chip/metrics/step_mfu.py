"""Whole-step model FLOP/s utilization (%): the FLOPs that every prefill
and decode call in the traced window needs (counted from shapes, not from
the compiled program), over the traced window times the chips' bf16
peak.  Device trace for the window."""


def read(run):
    r = run.reduction
    if r is None or not run.calls:
        return None
    flops = sum(w["flops"] for p in ("prefill", "decode") for w in run.work(p))
    return 100.0 * flops / (r.window_s * r.chips * run.peaks["bf16_flops_per_s"])
