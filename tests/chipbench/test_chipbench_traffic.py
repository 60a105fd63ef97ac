"""Every seed asks for the same work in another order: the traffic
generator's queries come from the mix's ``work_seed``, and the run's seed
only reorders them and names their terms."""

from __future__ import annotations

import itertools

import numpy as np
import pytest

from chipbench import corpus, traffic

CFG = {"shape": "gov2", "n_lists": 40, "n_terms_sampled": 2000}
CLOSED = {"loop": "closed", "clients": 8, "modes": {"and": 1.0}, "k": 10,
          "lengths": {"1": 0.2, "2": 0.4, "3": 0.3, "4": 0.1},
          "work_seed": 12, "reorder_groups": 8}
OPEN = {"loop": "open", "arrivals": "poisson", "rate_qps": 20.0,
        "modes": {"and": 1, "or": 1}, "k": 10,
        "lengths": {"1": 0.5, "3": 0.5}, "work_seed": 12}
SEEDS = (3, 2 ** 33 + 17)


def _ranks(q, terms):
    where = {int(t): r for r, t in enumerate(terms)}
    return tuple(sorted(where[t] for t in q.terms)), q.mode


def _closed_batches(seed, n):
    terms = corpus.term_ids(CFG, seed)
    qs, _ = traffic.window_requests(CLOSED, terms, 51.0, seed)
    qs = [_ranks(q, terms) for q in itertools.islice(qs, n)]
    return [tuple(sorted(qs[i:i + 8])) for i in range(0, n, 8)]


@pytest.mark.parametrize("blocks", [1, 3])
def test_closed_loop_blocks_hold_the_same_batches_for_every_seed(blocks):
    n = 64 * blocks
    a, b = (_closed_batches(s, n) for s in SEEDS)
    assert a != b                               # another order
    for i in range(0, len(a), 8):               # the same batches per block
        assert sorted(a[i:i + 8]) == sorted(b[i:i + 8])


def test_open_loop_sends_the_same_queries_in_another_order():
    got = []
    for s in SEEDS:
        terms = corpus.term_ids(CFG, s)
        qs, offs = traffic.window_requests(OPEN, terms, 10.0, s)
        assert len(qs) == len(offs) == 200 and offs[0] == 0.0
        got.append([_ranks(q, terms) for q in qs])
    assert got[0] != got[1] and sorted(got[0]) == sorted(got[1])


def test_term_ids_are_a_seeded_permutation():
    a, b = (corpus.term_ids(CFG, s) for s in SEEDS)
    assert sorted(a) == sorted(b) == list(range(CFG["n_lists"]))
    assert not np.array_equal(a, b)
    assert np.array_equal(a, corpus.term_ids(CFG, SEEDS[0]))
