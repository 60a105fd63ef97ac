"""Small statistics shared by the metric readers."""

from __future__ import annotations

import math


def nearest_rank(sorted_vals, q: float) -> float:
    """The nearest-rank percentile (copied from ``repro.obs.metrics``):
    ``sorted_vals[min(max(ceil(q/100 * n), 1), n) - 1]``, never
    interpolated."""
    n = len(sorted_vals)
    if n == 0:
        raise ValueError("nearest_rank of an empty sample")
    rank = min(max(int(math.ceil(q / 100.0 * n)), 1), n)
    return float(sorted_vals[rank - 1])


def mean(vals):
    vals = list(vals)
    return sum(vals) / len(vals) if vals else None


# a request that never came back, or came back shed or failed, has a
# latency above any served one; JSON has no infinity, so it reads this
NEVER_MS = 1e9


def latencies_ms(records) -> list:
    """Due time -> delivery, in ms, for every record; ``NEVER_MS`` for one
    that was not served."""
    return sorted((r.t_done - r.t_due) * 1e3 if r.served else NEVER_MS
                  for r in records)
