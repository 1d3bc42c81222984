"""Mean device time of one decode step (ms): the traced window's
``bench_decode`` executions.  Device trace."""


def read(run):
    ms = run.device_ms("decode")
    return sum(ms) / len(ms) if ms else None
