"""1 - (union of device operation intervals) / traced window."""


def read(run):
    t = run.trace
    if t is None or not t.window_s:
        return None
    return 1.0 - t.busy_s / t.window_s
