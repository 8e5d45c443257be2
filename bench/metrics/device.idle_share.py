"""Device: the share of the traced window in which no op ran on the
device, 1 - busy / window (busy averaged over the cell's chips)."""


def read(r):
    busy = r.trace.busy_s
    return 1.0 - busy / r.trace.window_s if busy > 0 else None
