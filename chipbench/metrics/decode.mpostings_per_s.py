"""Postings decoded on the device per second of decode device time, in
millions: (``decode_postings`` per window batch, from the engine's
counters) over (device time of the decode programs, fused
``decode_tiles`` and the arena work-list decode, per traced batch).  The
counter counts the real postings of the same programs' work-lists,
padding excluded.  None without a trace, or where the program has no such
counter."""

DECODE = ("decode_tiles", "decode_worklist")


def read(run):
    t, post = run.trace, run.counters.get("decode_postings")
    if t is None or post is None or not run.batches:
        return None
    traced = len(run.traced_batches())
    s = sum(v for k, v in t.programs.items() if any(d in k for d in DECODE))
    if not traced or s <= 0:
        return None
    return (post / len(run.batches)) / (s / traced) / 1e6
