"""The benchmark's reference against the served engine, and the check's
limits against perturbed answers and the control, at a tiny size."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import check, corpus
from chipbench.drive import Record
from chipbench.reference import Control, Reference
from chipbench.traffic import Query, make_queries, rng_for

CFG = {"name": "tiny", "shape": "gov2", "n_docs": 5000, "n_lists": 16,
       "n_terms_sampled": 2000, "zipf_s": 1.15, "lists_seed": 77,
       "doclen": 778}
MIX = {"modes": {"and": 1, "or": 1, "and_scored": 1}, "k": 10,
       "lengths": {"1": 0.2, "2": 0.4, "3": 0.3, "4": 0.1}}


@pytest.fixture(scope="module")
def served():
    """Every mode served by the engine at the host and device placements,
    with the reference built from the same seeded raw lists."""
    from repro.index.engine import QueryBatch, QueryEngine
    from repro.index.invindex import InvertedIndex
    doclen, postings = corpus.make_corpus(CFG, 2 ** 33 + 5)
    engine = QueryEngine(InvertedIndex.build(doclen, postings)).to_device(
        fused=True)
    queries = make_queries(MIX, CFG["n_lists"], 30, rng_for(9, 1))
    out = []
    for placement in ("host", "device", "fused"):
        for mode in MIX["modes"]:
            qs = [q for q in queries if q.mode == mode]
            got = engine.execute(engine.plan(
                QueryBatch([list(q.terms) for q in qs], mode=mode, k=10),
                placement=placement))
            out += [Record(q, 0.0, 0.0, 0.1, r) for q, r in zip(qs, got)]
    doclen, postings = corpus.make_corpus(CFG, 2 ** 33 + 5)
    return Reference(doclen, postings), Control(doclen, postings), out


def test_reference_matches_every_mode_and_placement(served):
    ref, _, recs = served
    read = check.readings(ref, recs, seed=3, n_ranked=len(recs))
    assert read == {"missing": 0, "and_wrong": 0, "ranked_gap": 0.0}
    ok, lines = check.verdict(read)
    assert ok and len(lines) == len(check.LIMITS)


def test_corpus_sizes_do_not_depend_on_the_seed():
    a = corpus.make_corpus(CFG, 1)[1]
    b = corpus.make_corpus(CFG, 2 ** 31 + 11)[1]
    assert sorted(a) == sorted(b) == list(range(CFG["n_lists"]))
    assert sorted(len(v[0]) for v in a.values()) == sorted(
        len(v[0]) for v in b.values())
    assert any(not np.array_equal(a[t][0], b[t][0]) for t in a)
    # the same lists, dealt to term ids by the seed
    ra, rb = corpus.term_ids(CFG, 1), corpus.term_ids(CFG, 2 ** 31 + 11)
    for r in range(CFG["n_lists"]):
        assert np.array_equal(a[int(ra[r])][0], b[int(rb[r])][0])


def _perturb(recs, mode, fn):
    out, done = [], False
    for r in recs:
        if not done and r.query.mode == mode and len(r.result):
            r = Record(r.query, r.t_due, r.t_submit, r.t_done, fn(r.result))
            done = True
        out.append(r)
    assert done
    return out


@pytest.mark.parametrize("mode,fn,number", [
    ("and", lambda a: a[:-1], "and_wrong"),
    ("or", lambda a: [(d, s * (1 + 1e-7)) for d, s in a], "ranked_gap"),
    ("and_scored", lambda a: a[:-1], "ranked_gap"),
    ("or", lambda a: [(a[0][0] + 1, a[0][1])] + list(a[1:]), "ranked_gap"),
])
def test_an_altered_answer_fails(served, mode, fn, number):
    ref, _, recs = served
    read = check.readings(ref, _perturb(recs, mode, fn), seed=3,
                          n_ranked=len(recs))
    assert read[number] > check.LIMITS[number]
    assert not check.verdict(read)[0]


def test_a_request_not_served_fails(served):
    ref, _, recs = served
    r = recs[0]
    lost = [Record(r.query, r.t_due, r.t_submit, r.t_done, None,
                   "rejected: deadline")] + recs[1:]
    read = check.readings(ref, lost, seed=3, n_ranked=len(recs))
    assert read["missing"] == 1 and not check.verdict(read)[0]


def test_the_control_fails(served):
    ref, ctl, recs = served
    read = check.control_readings(ref, ctl, recs, seed=3, n_ranked=len(recs))
    assert read["and_wrong"] > 0
    assert read["ranked_gap"] > check.LIMITS["ranked_gap"]
    assert not check.verdict(read)[0]


def test_ranked_sample_keeps_the_longest_and_is_seeded():
    recs = [Record(Query(tuple(range(1 + i % 4)), "or", 10), 0.0, 0.0, 0.1,
                   []) for i in range(40)]
    a = check.ranked_sample(recs, 8, seed=5)
    assert a == check.ranked_sample(recs, 8, seed=5)
    assert len(a) == 8 and any(len(r.query.terms) == 4 for r in a)
