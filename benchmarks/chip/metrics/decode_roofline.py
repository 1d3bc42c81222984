"""Decode step's share of its roofline (%): the least time the chip could
take for the work each decode call needs, max(FLOPs / bf16 peak, bytes /
HBM bandwidth), summed over the traced window's calls, over the device
time of their ``bench_decode`` executions.  Device trace."""


def read(run):
    ms = run.device_ms("decode")
    if not ms:
        return None
    floor_s = sum(run.floor_s(w) for w in run.work("decode"))
    return 100.0 * floor_s / (sum(ms) * 1e-3)
