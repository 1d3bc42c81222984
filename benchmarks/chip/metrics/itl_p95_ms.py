"""Inter-token latency, 95th percentile over requests (ms): each request's
mean gap between consecutive output tokens that reached the host inside
the window, (last - first) / (tokens - 1).  A request's gaps are averaged
before the tail is taken, so that each reading spans many steps of the
host clock and not one.  Host clock."""
import numpy as np


def read(run):
    per_request = []
    for r in run.requests:
        times = r.times[r.times <= run.end]
        if len(times) >= 2:
            per_request.append((times[-1] - times[0]) / (len(times) - 1))
    if not per_request:
        return None
    return float(np.percentile(per_request, 95)) * 1e3
