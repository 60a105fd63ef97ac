"""Host work after the device per batch: the ``kernel/extract_ids`` and
``ranked/rescore`` spans of the window, over its batches (engine tracer on
in the traced run)."""

NAMES = ("kernel/extract_ids", "ranked/rescore")


def read(run):
    if not run.batches:
        return None
    total = sum(s.t1 - s.t0 for s in run.spans if s.name in NAMES)
    return total * 1e3 / len(run.batches)
