"""One general traffic generator, driven by a traffic file's parameters.

A traffic file (``traffic/<name>.json``) holds only data:

``loop``            ``"open"`` (arrivals on a schedule) or ``"closed"``
                    (``clients`` callers, each sending its next request when
                    the previous one is answered)
``arrivals``        open loop: ``"poisson"`` or ``"gamma"`` (with
                    ``gamma_shape``; below 1 is burstier than Poisson)
``rate_qps``        open loop: offered rate
``clients``         closed loop: requests kept in flight (the queries are
                    an endless stream, taken in order)
``modes``           mode -> share of requests (``and``, ``or``,
                    ``and_scored``)
``k``               top-k of ranked requests
``lengths``         query length in terms -> share
``warm_seconds``    open loop: seconds of the same mix under the warm-up
                    sub-seed, served before the window
``warm_requests``   closed loop: requests of the same mix under the warm-up
                    sub-seed, served by ``clients`` callers before the window
``check_ranked``    ranked requests compared with the reference per run
``work_seed``       the draw of the queries themselves: every run's seed
                    serves these same queries, in another order
``reorder_groups``  closed loop: the run's seed reorders each run of this
                    many groups of ``clients`` consecutive queries (a batch
                    each), and the queries inside each group

Query terms are drawn independently for each query, uniformly over the
configuration's ``n_lists`` list ranks and without repeats inside a query,
and sent under the term ids that hold those ranks in the run
(``corpus.term_ids``).  Lengths and modes are dealt in exact proportions.
The queries come from ``work_seed``, and the run's seed only reorders
them: an open loop sends all ``round(rate * seconds)`` of them shuffled, on
arrivals drawn from the seed and scaled to the window; a closed loop's
window covers the same batches up to its last block.  So every seed asks
for the same sizes in another order.  The warm-up's queries come from a
stream of their own: the window meets queries that the server has not
seen.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# sub-streams of one --seed: the window's traffic (its arrival clock is
# stream + 16), the open loop's warm-up traffic, the ranked check sample
WINDOW, WARM, SAMPLE = 1, 2, 3
CHUNK = 256               # closed-loop queries dealt per proportion round


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


@dataclasses.dataclass(frozen=True)
class Query:
    terms: tuple
    mode: str
    k: int


def _deal(shares: dict, n: int, rng) -> list:
    """``n`` labels in exact proportion to ``shares`` (largest remainder),
    shuffled."""
    keys = list(shares)
    w = np.asarray([float(shares[x]) for x in keys])
    want = w / w.sum() * n
    got = np.floor(want).astype(int)
    for i in np.argsort(-(want - got), kind="stable")[:n - got.sum()]:
        got[i] += 1
    out = [keys[i] for i in range(len(keys)) for _ in range(got[i])]
    rng.shuffle(out)
    return out


def make_queries(traffic: dict, n_lists: int, n: int, rng) -> list:
    lengths = [int(x) for x in _deal(traffic["lengths"], n, rng)]
    modes = _deal(traffic["modes"], n, rng)
    k = int(traffic["k"])
    return [Query(tuple(int(t) for t in rng.choice(n_lists, ln,
                                                   replace=False)), m, k)
            for ln, m in zip(lengths, modes)]


# copied from repro.index.serve (poisson_offsets / bursty_offsets)
def poisson_offsets(n: int, rate_qps: float, rng) -> np.ndarray:
    return np.cumsum(rng.exponential(1.0 / rate_qps, n))


def gamma_offsets(n: int, rate_qps: float, rng,
                  shape: float = 0.25) -> np.ndarray:
    return np.cumsum(rng.gamma(shape, 1.0 / (rate_qps * shape), n))


def arrival_offsets(traffic: dict, seconds: float, rng,
                    rate_qps: float | None = None) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop:
    ``round(rate * seconds)`` arrivals whose gaps follow the traffic's
    process, scaled so that the rate over the window is the offered rate."""
    rate = float(traffic["rate_qps"] if rate_qps is None else rate_qps)
    n = max(1, int(round(rate * seconds)))
    if traffic["arrivals"] == "poisson":
        cum = poisson_offsets(n + 1, rate, rng)
    elif traffic["arrivals"] == "gamma":
        cum = gamma_offsets(n + 1, rate, rng, float(traffic["gamma_shape"]))
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    # the first request is due at 0, the (n+1)-th would be due at `seconds`
    return (cum[:n] - cum[0]) * (seconds / (cum[n] - cum[0]))


def query_stream(traffic: dict, n_lists: int, rng, chunk: int = CHUNK):
    """Endless queries of the mix, dealt ``chunk`` at a time in exact
    proportions."""
    while True:
        yield from make_queries(traffic, n_lists, chunk, rng)


def reordered(queries, group: int, groups: int, rng):
    """``queries`` (an iterator) taken ``group * groups`` at a time, each
    such block sent as its groups of ``group`` in a seeded order, and each
    group's queries in a seeded order."""
    while True:
        block = [next(queries) for _ in range(group * groups)]
        for g in rng.permutation(groups):
            part = block[g * group:(g + 1) * group]
            yield from (part[j] for j in rng.permutation(group))


def relabel(q: Query, terms) -> Query:
    return Query(tuple(int(terms[t]) for t in q.terms), q.mode, q.k)


def window_requests(traffic: dict, terms, seconds: float, seed: int,
                    stream: int = WINDOW, rate_qps: float | None = None):
    """``(queries, offsets)`` for one window: a list and its due times for
    an open loop, an endless iterator and None for a closed loop.
    ``terms[r]`` is the term id that holds the list of rank ``r``."""
    work = rng_for(int(traffic["work_seed"]), stream)
    order = rng_for(seed, stream)
    if traffic["loop"] == "open":
        offs = arrival_offsets(traffic, seconds, rng_for(seed, stream + 16),
                               rate_qps)
        qs = make_queries(traffic, len(terms), len(offs), work)
        return [relabel(qs[i], terms) for i in order.permutation(len(qs))], offs
    if traffic["loop"] == "closed":
        qs = reordered(query_stream(traffic, len(terms), work),
                       int(traffic["clients"]),
                       int(traffic["reorder_groups"]), order)
        return (relabel(q, terms) for q in qs), None
    raise ValueError(f"unknown loop {traffic['loop']!r}")
