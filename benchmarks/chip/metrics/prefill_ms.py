"""Mean device time of one prefill (ms): the traced window's
``bench_prefill`` executions.  Device trace."""


def read(run):
    ms = run.device_ms("prefill")
    return sum(ms) / len(ms) if ms else None
