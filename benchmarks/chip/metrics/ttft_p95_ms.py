"""Time to first token, 95th percentile over requests (ms): from the
request's submission to its first token on the host, for every request
whose first token reached the host inside the window.  Host clock."""
import numpy as np


def read(run):
    ttft = [r.times[0] - r.submit for r in run.requests
            if len(r.times) and r.times[0] <= run.end]
    if not ttft:
        return None
    return float(np.percentile(ttft, 95)) * 1e3
