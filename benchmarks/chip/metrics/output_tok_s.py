"""Output tokens per second: generated tokens that reached the host inside
the window, over the window's length.  Host clock."""


def read(run):
    n = sum(int((r.times <= run.end).sum()) for r in run.requests)
    return n / run.seconds
