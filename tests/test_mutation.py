"""Stateful differential harness for the streaming mutable index.

The acceptance gate of the LSM mutation subsystem (``repro.index.segments`` +
the generation-aware engine): a randomized interleaving of insert / delete /
compact / query steps runs against a plain-dict numpy oracle, and EVERY query
step asserts bit-identical results — docids for ``and``, (docid, score) pairs
for ``or`` / ``and_scored`` — across the host, device, and fused placements
versus a from-scratch rebuild (``InvertedIndex.build(doclen_now,
live_postings)`` served by a fresh host engine).  The device engines persist
across the whole run, so generation swaps, tombstone epochs, and cache keying
are exercised exactly the way a serving process would hit them; the zero
-sync contract (no per-round candidate/score downloads, tombstone gating is
upload-only) is asserted at the end of every run.

Under real ``hypothesis`` the same model also runs as a
``RuleBasedStateMachine``; under the conftest shim (no stateful API) the
seeded interleaving loops below are the workhorse — they execute well over
200 randomized steps per run by construction (``N_STEPS``).
"""

from __future__ import annotations

import numpy as np
import pytest

import hypothesis

from repro.index.invindex import InvertedIndex
from repro.index.engine import QueryBatch, QueryEngine

N_STEPS = 240           # per seeded run; the ISSUE acceptance floor is 200
QUERY_EVERY = 6         # differential check cadence within a run
MODES = ("and", "or", "and_scored")
K = 5


class MutationModel:
    """The differential model: a mutable index under test, three persistent
    engines (host / device / fused), and a plain-dict oracle of the live
    corpus that can be rebuilt from scratch at any step."""

    def __init__(self, doclen, postings, codec, n_terms, device=True):
        self.codec = codec
        self.n_terms = n_terms
        self.idx = InvertedIndex.build(doclen, postings, codec=codec)
        # oracle truth: docid -> {term: tf} for LIVE docs (every base doc is
        # live at the start, postings or not); docid -> last-set doclen for
        # every docid ever seen (deletes don't erase doclens)
        self.live: dict = {d: {} for d in range(len(doclen))}
        self.dl: dict = {d: int(l) for d, l in enumerate(doclen)}
        for t, (ids, tfs) in postings.items():
            for d, f in zip(ids.tolist(), tfs.tolist()):
                self.live[int(d)][int(t)] = int(f)
        self.base_docs = len(doclen)
        self.engines = [("host", QueryEngine(self.idx))]
        if device:
            self.engines += [
                ("device", QueryEngine(self.idx).to_device(fused=False)),
                ("fused", QueryEngine(self.idx).to_device(fused=True))]
        self.steps = 0

    # ---- mutation rules ----------------------------------------------------- #

    def insert(self, docid, terms, doclen):
        self.idx.insert(docid, terms, doclen)
        self.live[docid] = dict(terms)
        self.dl[docid] = int(doclen)
        self.steps += 1

    def delete(self, docid):
        got = self.idx.delete(docid)
        if docid in self.live:
            assert got, f"delete({docid}) missed a live doc"
        # the converse is NOT asserted: a postings-less docid inside the
        # append-only doc space reports True once per generation (its doclen
        # survives compaction, so the index — exactly like a from-scratch
        # rebuild — cannot distinguish it from a live doc with no postings);
        # query parity below is the authoritative liveness check
        self.live.pop(docid, None)
        self.steps += 1

    def compact(self):
        gid = self.idx.gen.gid
        gen = self.idx.compact()
        assert gen.gid == gid + 1
        assert not self.idx.mutated
        self.steps += 1

    # ---- the differential query step ---------------------------------------- #

    def oracle(self):
        """Rebuild the index from scratch from the oracle dicts — the bitwise
        parity target for every placement and mode."""
        space = max(max(self.dl, default=-1) + 1, self.base_docs)
        doclen = np.zeros(space, np.int64)
        for d, l in self.dl.items():
            doclen[d] = l
        postings: dict = {}
        for d in sorted(self.live):
            for t, f in self.live[d].items():
                postings.setdefault(t, ([], []))
                postings[t][0].append(d)
                postings[t][1].append(f)
        postings = {t: (np.asarray(ids, np.uint32), np.asarray(tfs, np.uint32))
                    for t, (ids, tfs) in postings.items()}
        return QueryEngine(InvertedIndex.build(doclen, postings,
                                               codec=self.codec))

    def check_queries(self, queries):
        """Assert bit-identical results vs the rebuilt oracle for every mode
        on every placement."""
        ora = self.oracle()
        for mode in MODES:
            batch = QueryBatch(queries, mode=mode, k=K)
            want = ora.execute(ora.plan(batch))
            for name, eng in self.engines:
                got = eng.execute(eng.plan(batch))
                for q, w, g in zip(queries, want, got):
                    where = f"{name}/{mode}/{q} @step {self.steps}"
                    if mode == "and":
                        np.testing.assert_array_equal(g, w, err_msg=where)
                        assert g.dtype == np.uint32, where
                    else:
                        # bitwise: float equality, order, and docid ties
                        assert g == w, f"{where}: {g} != {w}"
        self.steps += 1

    def assert_zero_syncs(self):
        """The resident paths must not have added ANY per-round host syncs
        under mutation: tombstone gating is upload-only."""
        for name, eng in self.engines:
            if name == "host":
                continue
            assert eng.dev_stats["score_syncs"] == 0, name
            assert eng.dev_stats["final_syncs"] > 0, name
            assert eng.dev_stats["tomb_gates"] > 0, name


def _seed_corpus(rng, n_docs, n_terms):
    doclen = rng.integers(20, 200, n_docs).astype(np.int64)
    postings = {}
    for t in range(n_terms):
        df = int(rng.integers(5, max(6, n_docs // 2)))
        ids = np.sort(rng.choice(n_docs, df, replace=False)).astype(np.uint32)
        postings[t] = (ids, rng.geometric(0.4, df).astype(np.uint32))
    return doclen, postings


def _random_doc(rng, n_terms):
    terms = {int(t): int(rng.integers(1, 6))
             for t in rng.choice(n_terms, int(rng.integers(1, 4)),
                                 replace=False)}
    return terms, int(rng.integers(5, 120))


def _random_queries(rng, n_terms, nq=4):
    return [rng.choice(n_terms, size=int(rng.integers(1, 4)),
                       replace=False).tolist() for _ in range(nq)]


def _run_interleaving(model, rng, n_steps):
    """The seeded fallback for hypothesis' stateful driver: a weighted random
    interleaving of the model's rules, with a differential query check every
    ``QUERY_EVERY`` steps and once more at the end."""
    next_docid = model.base_docs
    while model.steps < n_steps:
        op = rng.random()
        if model.steps % QUERY_EVERY == QUERY_EVERY - 1:
            model.check_queries(_random_queries(rng, model.n_terms))
        elif op < 0.40:
            # mix of fresh docids, upserts of base docs, upserts of delta docs
            r = rng.random()
            if r < 0.5:
                d, next_docid = next_docid, next_docid + 1
            elif r < 0.8:
                d = int(rng.integers(0, model.base_docs))
            else:
                d = int(rng.integers(model.base_docs, next_docid + 1))
            terms, dl = _random_doc(rng, model.n_terms)
            model.insert(d, terms, dl)
        elif op < 0.70:
            model.delete(int(rng.integers(0, next_docid + 2)))
        elif op < 0.78 and model.idx.mutated:
            model.compact()
        else:
            model.delete(int(rng.integers(0, model.base_docs)))
    model.check_queries(_random_queries(rng, model.n_terms))


@pytest.mark.parametrize("codec,seed", [("group_simple", 0),
                                        ("group_pfd", 1)])
def test_stateful_mutation_differential(codec, seed):
    """The acceptance harness: >= 200 randomized insert/delete/compact/query
    steps, every query step bit-identical to the rebuild-from-scratch oracle
    across host/device/fused and all three modes — including the exception
    -bearing ``group_pfd`` codec — with zero per-round syncs preserved."""
    rng = np.random.default_rng(seed)
    doclen, postings = _seed_corpus(rng, n_docs=400, n_terms=8)
    model = MutationModel(doclen, postings, codec, n_terms=8)
    _run_interleaving(model, rng, N_STEPS)
    assert model.steps >= 200
    model.assert_zero_syncs()


def test_delta_only_corpus_all_placements():
    """A corpus living ENTIRELY in the delta segment (the generation has docs
    but zero terms): every mode and placement must serve it bit-identically
    to the rebuilt oracle, before and after its first compaction."""
    rng = np.random.default_rng(7)
    model = MutationModel(np.full(10, 25, np.int64), {}, "group_pfd",
                          n_terms=5)
    for _ in range(30):
        terms, dl = _random_doc(rng, 5)
        model.insert(int(rng.integers(0, 40)), terms, dl)
    model.check_queries([[0, 1], [2], [3, 4, 0], [1, 2, 3]])
    model.compact()
    model.check_queries([[0, 1], [2], [3, 4, 0], [1, 2, 3]])
    model.assert_zero_syncs()


def test_tombstone_only_mutation():
    """Deletes with an empty delta segment: the pure live-bitmap-gate path
    (no delta union at all), checked across all placements and modes."""
    rng = np.random.default_rng(3)
    doclen, postings = _seed_corpus(rng, n_docs=300, n_terms=6)
    model = MutationModel(doclen, postings, "group_simple", n_terms=6)
    for d in rng.choice(300, 40, replace=False).tolist():
        model.delete(int(d))
    assert not model.idx.delta and model.idx.tomb
    model.check_queries(_random_queries(rng, 6, nq=5))
    model.assert_zero_syncs()


# --------------------------------------------------------------------------- #
# generation pinning
# --------------------------------------------------------------------------- #


def _pin_fixture():
    rng = np.random.default_rng(11)
    doclen, postings = _seed_corpus(rng, n_docs=350, n_terms=6)
    idx = InvertedIndex.build(doclen, postings, codec="group_pfd")
    return rng, idx


@pytest.mark.parametrize("fused", [False, True])
def test_plan_pins_generation_across_compact(fused):
    """A plan built before ``compact()`` keeps executing bit-identically
    against its pinned generation + epoch, while a fresh plan (same engine)
    serves the new generation."""
    rng, idx = _pin_fixture()
    eng = QueryEngine(idx).to_device(fused=fused)
    queries = [[0, 1], [2, 3, 4], [1, 5], [0, 2]]
    for mode in MODES:
        plans = {mode: eng.plan(QueryBatch(queries, mode=mode, k=K))}
    plans = {m: eng.plan(QueryBatch(queries, mode=m, k=K)) for m in MODES}
    before = {m: eng.execute(plans[m]) for m in MODES}
    # mutate + compact underneath the pinned plans
    for d in (3, 50, 51, 120):
        idx.delete(d)
    idx.insert(5, {0: 4, 1: 1}, 30)
    idx.insert(360, {2: 2}, 15)
    old_gid = plans["and"].ctx.gen.gid
    idx.compact()
    assert idx.gen.gid == old_gid + 1
    for m in MODES:
        after = eng.execute(plans[m])        # pinned: pre-mutation results
        for w, g in zip(before[m], after):
            if m == "and":
                np.testing.assert_array_equal(g, w)
            else:
                assert g == w
    # a fresh plan sees the new generation and the post-compact truth
    fresh = eng.plan(QueryBatch(queries, mode="and"))
    assert fresh.ctx.gen.gid == old_gid + 1
    ref = QueryEngine(idx)
    want = ref.execute(ref.plan(QueryBatch(queries, mode="and")))
    for w, g in zip(want, eng.execute(fresh)):
        np.testing.assert_array_equal(g, w)


def test_plan_pins_mutation_epoch_without_compact():
    """Pinning is per epoch, not just per generation: a plan snapshots the
    delta/tombstone state at plan time, so later writes don't leak in."""
    rng, idx = _pin_fixture()
    eng = QueryEngine(idx).to_device(fused=False)
    idx.delete(10)
    idx.insert(400, {0: 2, 3: 1}, 20)
    queries = [[0, 3], [1, 2], [0, 1, 2]]
    plan = eng.plan(QueryBatch(queries, mode="and_scored", k=K))
    before = eng.execute(plan)
    idx.delete(0)                   # post-plan writes...
    idx.insert(401, {0: 9}, 10)
    assert eng.execute(plan) == before   # ...invisible to the pinned plan
    live_now = eng.execute(eng.plan(QueryBatch(queries, mode="and_scored",
                                               k=K)))
    assert live_now != before       # docid 0 had term-0 postings in seed df


@pytest.mark.parametrize("fused", [False, True])
def test_tombstone_only_ranked_superset_contract(fused):
    """Ranked top-k under tombstones WITHOUT compaction: the device candidate
    set (quantization-margin superset, live-gated) must still contain the
    true top-k — results bit-identical to the rebuilt oracle — and deleted
    docs must never appear."""
    rng, idx = _pin_fixture()
    dead = sorted(int(d) for d in rng.choice(350, 60, replace=False))
    for d in dead:
        idx.delete(d)
    eng = QueryEngine(idx).to_device(fused=fused)
    queries = [[0, 1, 2], [3, 4], [1, 5], [2, 4, 5]]
    # rebuild-from-scratch oracle (host) for the same tombstoned corpus
    model = MutationModel(np.zeros(0, np.int64), {}, "group_pfd", 6,
                          device=False)
    model.idx = idx
    ora = None
    doclen = np.asarray(idx.doclen_now())
    postings = {}
    deadset = set(dead)
    for t in range(6):
        ids, tfs = idx.gen.decode_term(t)
        keep = [j for j, d in enumerate(ids.tolist()) if d not in deadset]
        if keep:
            postings[t] = (ids[keep], tfs[keep])
    ora = QueryEngine(InvertedIndex.build(doclen, postings, codec="group_pfd"))
    for mode in ("or", "and_scored"):
        want = ora.execute(ora.plan(QueryBatch(queries, mode=mode, k=K)))
        got = eng.execute(eng.plan(QueryBatch(queries, mode=mode, k=K)))
        assert got == want, mode
        for res in got:
            assert not any(d in deadset for d, _ in res)
    assert eng.dev_stats["score_syncs"] == 0
    assert eng.dev_stats["tomb_gates"] > 0


# --------------------------------------------------------------------------- #
# generation-keyed caches (the stale-cache regression)
# --------------------------------------------------------------------------- #


def test_caches_keyed_by_generation_not_stale_after_compact():
    """The (term, block) LRU and the score cache must be keyed by generation
    / epoch: after a ``compact()`` that rewrites a term's blocks in place
    (same term id, same block index, different postings), a warm engine must
    serve the NEW postings.  Single-generation keying fails this test by
    serving the evicted generation's decoded blocks and score vectors."""
    rng, idx = _pin_fixture()
    eng = QueryEngine(idx)
    queries = [[0, 1], [0], [1, 2]]
    eng.execute(eng.plan(QueryBatch(queries, mode="and")))      # block cache
    eng.execute(eng.plan(QueryBatch(queries, mode="or", k=K)))  # score cache
    gid0 = idx.gen.gid
    keys0 = set(eng.cache.keys())
    assert keys0 and all(k[-1] == gid0 for k in keys0)
    # rewrite term 0's first block: delete some of its early postings and
    # insert a brand-new doc carrying term 0, then compact
    t0_ids = idx.gen.decode_term(0)[0]
    for d in t0_ids[:5].tolist():
        idx.delete(int(d))
    idx.insert(500, {0: 3, 1: 1}, 40)
    idx.compact()
    ref = QueryEngine(idx)
    want = ref.execute(ref.plan(QueryBatch(queries, mode="and")))
    got = eng.execute(eng.plan(QueryBatch(queries, mode="and")))
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)     # stale gen-0 blocks would differ
    assert any(k[-1] == gid0 + 1 for k in eng.cache.keys())
    want = ref.execute(ref.plan(QueryBatch(queries, mode="or", k=K)))
    assert eng.execute(eng.plan(QueryBatch(queries, mode="or", k=K))) == want
    # score-cache entries carry the full epoch key (term, gid, tomb_v, delta_v)
    assert any(k[1] == gid0 + 1 for k in eng.score_cache.keys())


def test_score_cache_keyed_by_tombstone_epoch():
    """Score vectors depend on live df/avdl, so even a tombstone WITHOUT
    compaction must miss the old cache entry."""
    rng, idx = _pin_fixture()
    eng = QueryEngine(idx)
    r0 = eng.or_query([0, 1], k=K)
    ids0 = idx.gen.decode_term(0)[0]
    idx.delete(int(ids0[0]))                # changes term 0's df and scores
    r1 = eng.or_query([0, 1], k=K)
    want = QueryEngine(idx).or_query([0, 1], k=K)
    assert r1 == want
    assert r1 != r0


# --------------------------------------------------------------------------- #
# hypothesis stateful machine (runs under real hypothesis; the conftest shim
# has no stateful API, so the seeded interleavings above are the fallback)
# --------------------------------------------------------------------------- #

if not getattr(hypothesis, "__is_repro_shim__", False):
    from hypothesis import settings, strategies as st
    from hypothesis.stateful import (RuleBasedStateMachine, initialize,
                                     invariant, rule)

    class MutationMachine(RuleBasedStateMachine):
        """hypothesis drives the same MutationModel the seeded loops use;
        host placement only (device jit per shrunken example is too slow for
        a stateful search) — the seeded loops cover the device placements."""

        @initialize()
        def setup(self):
            rng = np.random.default_rng(0)
            doclen, postings = _seed_corpus(rng, n_docs=60, n_terms=4)
            self.model = MutationModel(doclen, postings, "group_pfd",
                                       n_terms=4, device=False)
            self.next_docid = 60

        @rule(fresh=st.booleans(), docid=st.integers(0, 80),
              tf=st.integers(1, 5), dl=st.integers(1, 50),
              term=st.integers(0, 3))
        def insert(self, fresh, docid, tf, dl, term):
            if fresh:
                docid, self.next_docid = self.next_docid, self.next_docid + 1
            self.model.insert(docid, {term: tf}, dl)

        @rule(docid=st.integers(0, 90))
        def delete(self, docid):
            self.model.delete(docid)

        @rule()
        def compact(self):
            self.model.compact()

        @rule(q=st.lists(st.integers(0, 4), min_size=1, max_size=3))
        def query(self, q):
            self.model.check_queries([q, q[:1]])

        @invariant()
        def doc_space_is_append_only(self):
            assert self.model.idx.doc_space >= self.model.base_docs

    MutationMachine.TestCase.settings = settings(
        max_examples=15, stateful_step_count=25, deadline=None)
    TestMutationMachine = MutationMachine.TestCase
