"""Device idle share (%): 100 * (1 - busy / window), where busy is the
union of the intervals in which an XLA operation ran on the device inside
the traced window, averaged over the chips used.  Device trace."""


def read(run):
    r = run.reduction
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
