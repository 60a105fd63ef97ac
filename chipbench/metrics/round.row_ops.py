"""Device rows handled one at a time per batch of the window: rows cut out
of a decoded or uploaded matrix (``rows_sliced``) plus rows passed one by
one into a round's row matrix (``rows_stacked``), from the engine's
counters.  None where the program has no such counters."""


def read(run):
    c = run.counters
    if not run.batches or "rows_sliced" not in c or "rows_stacked" not in c:
        return None
    return (c["rows_sliced"] + c["rows_stacked"]) / len(run.batches)
