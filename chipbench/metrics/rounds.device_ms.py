"""Device time of every program other than decode (AND and score rounds,
top-k, accumulate, gathers) per batch that ran during the profiled
stretch of the window."""

DECODE = ("decode_tiles", "decode_worklist")


def read(run):
    t, n = run.trace, len(run.traced_batches())
    if t is None or not n:
        return None
    s = sum(v for k, v in t.programs.items()
            if not any(d in k for d in DECODE))
    return s * 1e3 / n
