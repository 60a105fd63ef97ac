"""Ranked retrieval (OR / and_scored) acceptance: quantized score arenas +
device-resident block-max top-k must match the host float-BM25 oracle — same
doc set, same scores, docid-tiebreak order — across host/device/fused
placements on >= 3 arena codecs including an exception-bearing one, with zero
per-round host syncs on the device ranked path; plus the ScoreArena
quantization contract (floor codes, consistent block-max/term-max/stripe
tables, sound theta0) and the Pallas score-unpack tile."""

import heapq

import numpy as np
import pytest

from repro.core import codec
from repro.index.engine import QueryBatch, QueryEngine
from repro.index.invindex import InvertedIndex
from repro.index import scores as scores_lib
from repro.index.scores import ScoreArena, bm25_scores, topk_select, unpack_words_np
from repro.kernels import topk as topk_kern

# three arena codecs incl. the exception-bearing PFD family (acceptance)
RANKED_CODECS = ["group_simple", "stream_vbyte", "group_pfd"]
assert all(codec.get(n).arena is not None for n in RANKED_CODECS)

RNG = np.random.default_rng(2024)
N_DOCS = 3000


def _corpus(heavy=False, ties=False):
    rng = np.random.default_rng(7 if heavy else (9 if ties else 5))
    n_docs = 40_000 if heavy else N_DOCS    # heavy gaps need docid headroom
    postings = {}
    dfs = [15, 40, 64, 300, 511, 512, 700, 1200, 900, 150]
    for t, df in enumerate(dfs):
        if heavy:
            gaps = rng.integers(1, 4, df).astype(np.int64)
            gaps[rng.random(df) < 0.03] += rng.integers(1 << 8, 1 << 10)
            ids = np.cumsum(gaps).astype(np.uint32)
            assert int(ids[-1]) < n_docs
        else:
            ids = np.sort(rng.choice(n_docs, df, replace=False)).astype(np.uint32)
        tfs = (np.ones(df, np.uint32) if ties
               else rng.geometric(0.4, df).astype(np.uint32))
        postings[t] = (ids, tfs)
    doclen = (np.full(n_docs, 120, np.int64) if ties
              else rng.integers(60, 400, n_docs).astype(np.int64))
    return doclen, postings


DOCLEN, POSTINGS = _corpus()
# term 10: rare AND docid-clustered (topical locality) — the shape that lets
# block-max pruning drop the common terms' blocks outside the cluster
POSTINGS[10] = (np.sort(RNG.choice(256, 20, replace=False)).astype(np.uint32),
                RNG.geometric(0.4, 20).astype(np.uint32))
HDOCLEN, HPOSTINGS = _corpus(heavy=True)
TDOCLEN, TPOSTINGS = _corpus(ties=True)

QUERIES = ([RNG.choice(10, size=int(RNG.integers(2, 5)), replace=False).tolist()
            for _ in range(16)]
           + [[0, 7],                   # rare + common (the WAND shape)
              [3], [5],                 # single term
              [0, 999],                 # unknown term ignored
              [999], []])               # all-unknown / empty


def brute_or_topk(doclen, postings, n_docs, terms, k):
    avdl = doclen.mean()
    acc = {}
    for t in terms:
        if t not in postings:
            continue
        ids, tfs = postings[t]
        sc = bm25_scores(tfs, doclen[ids], len(ids), n_docs, avdl)
        for d, s in zip(ids.tolist(), sc.tolist()):
            acc[d] = acc.get(d, 0.0) + s
    return heapq.nsmallest(k, acc.items(), key=lambda kv: (-kv[1], kv[0]))


@pytest.mark.parametrize("name", RANKED_CODECS)
def test_ranked_placement_parity_and_float_oracle(name):
    """Acceptance: or/and_scored top-k identical (docids, float scores,
    order) across host, device, and fused placements, and the OR results
    match an independent brute-force float oracle with docid tie-break."""
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec=name)
    host = QueryEngine(idx)
    for mode in ("or", "and_scored"):
        want = host.execute(host.plan(QueryBatch(QUERIES, mode=mode, k=7)))
        for fused in (False, True):
            eng = QueryEngine(idx).to_device(fused=fused)
            got = eng.execute(eng.plan(QueryBatch(QUERIES, mode=mode, k=7)))
            assert want == got, (name, mode, fused)
    top = host.execute(host.plan(QueryBatch(QUERIES, mode="or", k=7)))
    for q, res in zip(QUERIES, top):
        oracle = brute_or_topk(DOCLEN, POSTINGS, N_DOCS, q, 7)
        assert [(d, pytest.approx(s, rel=1e-12)) for d, s in oracle] == res, q


@pytest.mark.parametrize("name", RANKED_CODECS)
def test_ranked_heavy_tail_exception_corpus(name):
    """Exception-bearing blocks (PFD patch streams on the heavy-tailed
    corpus) flow through the score path with exact parity."""
    idx = InvertedIndex.build(HDOCLEN, HPOSTINGS, codec=name)
    if name == "group_pfd":
        assert any(encg.exceptions is not None and len(encg.exceptions)
                   for tp in idx.terms.values()
                   for _, encg, _ in tp.blocks), "corpus exercises no exceptions"
    host = QueryEngine(idx)
    for mode in ("or", "and_scored"):
        want = host.execute(host.plan(QueryBatch(QUERIES, mode=mode, k=9)))
        for fused in (False, True):
            eng = QueryEngine(idx).to_device(fused=fused)
            got = eng.execute(eng.plan(QueryBatch(QUERIES, mode=mode, k=9)))
            assert want == got, (name, mode, fused)


def test_ranked_quantization_ties_docid_tiebreak():
    """All-equal TFs and flat doclens collapse most quantized sums into
    ties: the margin + rescore contract must still reproduce the float
    oracle's docid-tiebreak order exactly."""
    idx = InvertedIndex.build(TDOCLEN, TPOSTINGS, codec="group_simple")
    host = QueryEngine(idx)
    for mode in ("or", "and_scored"):
        want = host.execute(host.plan(QueryBatch(QUERIES, mode=mode, k=11)))
        eng = QueryEngine(idx).to_device()
        got = eng.execute(eng.plan(QueryBatch(QUERIES, mode=mode, k=11)))
        assert want == got, mode


def test_ranked_device_path_zero_per_round_syncs():
    """Acceptance: the resident ranked path accumulates scores across >= 2
    device rounds with zero per-round host syncs — the only download is the
    single final candidate bitmap per batch."""
    queries = [q for q in QUERIES if len([t for t in q if t in POSTINGS]) >= 2]
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    for fused in (False, True):
        for mode, final in (("or", 1), ("and_scored", 1)):
            eng = QueryEngine(idx).to_device(fused=fused)
            eng.execute(eng.plan(QueryBatch(queries, mode=mode, k=5)))
            assert eng.dev_stats["score_rounds"] >= 2
            assert eng.dev_stats["score_syncs"] == 0
            assert eng.dev_stats["final_syncs"] == final, (mode, fused)
            assert eng.dev_stats["blocks_scored"] > 0
            if fused:
                assert eng.arena.stats["fused_calls"] > 0


def test_or_blockmax_pruning_fires_and_stays_exact():
    """The rare-clustered + common query shape prunes (term, block)
    work-list entries by upper bound, and pruned execution is still bitwise
    exact."""
    queries = [[10, 7], [10, 3], [10, 7, 5]] * 4
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    ref = QueryEngine(idx)
    want = ref.execute(ref.plan(QueryBatch(queries, mode="or", k=5)))
    eng = QueryEngine(idx).to_device()
    got = eng.execute(eng.plan(QueryBatch(queries, mode="or", k=5)))
    assert want == got
    assert eng.dev_stats["blocks_pruned"] > 0
    assert eng.dev_stats["blocks_scored"] > 0


def test_zero_posting_term_in_ranked_queries_on_device():
    """A term present in the index with zero postings must score 0 and not
    crash the ranked device path (regression: the block-lazy rescore indexed
    an empty skip table)."""
    postings = dict(POSTINGS)
    postings[99] = (np.zeros(0, np.uint32), np.zeros(0, np.uint32))
    idx = InvertedIndex.build(DOCLEN, postings, codec="group_simple")
    host = QueryEngine(idx)
    queries = [[99, 3, 7], [99], [3, 99, 5]]
    for mode in ("or", "and_scored"):
        want = host.execute(host.plan(QueryBatch(queries, mode=mode, k=5)))
        for fused in (False, True):
            eng = QueryEngine(idx).to_device(fused=fused)
            got = eng.execute(eng.plan(QueryBatch(queries, mode=mode, k=5)))
            assert want == got, (mode, fused)
    assert host.execute(host.plan(QueryBatch([[99]], mode="or", k=5))) == [[]]


def test_ranked_eviction_pressure_stays_exact():
    idx = InvertedIndex.build(HDOCLEN, HPOSTINGS, codec="group_pfd")
    host = QueryEngine(idx)
    tiny = QueryEngine(idx, cache_blocks=2, cache_score_terms=1).to_device()
    for mode in ("or", "and_scored"):
        want = host.execute(host.plan(QueryBatch(QUERIES, mode=mode, k=6)))
        got = tiny.execute(tiny.plan(QueryBatch(QUERIES, mode=mode, k=6)))
        assert want == got, mode
    assert tiny.cache.evictions > 0


# --------------------------------------------------------------------------- #
# ScoreArena quantization contract
# --------------------------------------------------------------------------- #


def test_score_arena_tables_consistent_with_codes():
    """block-max == max stored code, term-max == max block-max, stripe table
    bounds every posting's code, floor(build float block-max / delta) ==
    stored block-max (floor is monotone)."""
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    sa = ScoreArena.from_index(idx)
    tiles = np.asarray(sa.tiles)
    for t, tp in idx.terms.items():
        per_block = []
        for bi in range(len(tp.blocks)):
            ids, tfs = idx.decode_block(t, bi)
            codes = unpack_words_np(tiles[sa.slot[(t, bi)]], len(ids))
            sc = bm25_scores(tfs, np.asarray(idx.doclen)[ids], tp.df,
                             idx.n_docs, float(np.asarray(idx.doclen).mean()))
            np.testing.assert_array_equal(
                codes, np.minimum(np.floor(sc / sa.delta), 255))
            bm = int(sa.block_max[sa.slot[(t, bi)]])
            assert bm == int(codes.max(initial=0))
            assert bm == min(int(idx.impact_block_max(t)[bi] / sa.delta), 255)
            per_block.append(bm)
            stripe = sa.stripes[t][ids // sa.stripe_width]
            assert np.all(stripe >= codes.astype(np.int64))
        assert sa.term_max[t] == max(per_block, default=0)
        tops = sa.term_tops[t]
        assert np.all(tops[:-1] >= tops[1:])          # sorted descending
        assert len(tops) == min(tp.df, scores_lib.TOP_TABLE)


def test_theta0_is_a_sound_lower_bound():
    """k docs provably reach theta0: the k-th best true OR score of any
    query is >= theta0 * delta."""
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    sa = ScoreArena.from_index(idx)
    for q in ([0, 7], [3, 5, 8], [1, 2, 9]):
        k = 5
        oracle = brute_or_topk(DOCLEN, POSTINGS, N_DOCS, q, k)
        assert oracle[-1][1] >= sa.theta0(q, k) * sa.delta - 1e-12


def test_topk_select_docid_tiebreak_and_partial_sort():
    docs = np.array([5, 1, 9, 3, 7, 2], np.uint32)
    scores = np.array([1.0, 2.0, 2.0, 2.0, 0.5, 1.0])
    # ties at 2.0 resolve by ascending docid; ties at 1.0 straddle the cut
    assert topk_select(docs, scores, 4) == [(1, 2.0), (3, 2.0), (9, 2.0),
                                            (2, 1.0)]
    assert topk_select(docs, scores, 100) == [(1, 2.0), (3, 2.0), (9, 2.0),
                                              (2, 1.0), (5, 1.0), (7, 0.5)]
    assert topk_select(docs, scores, 0) == []
    assert topk_select(np.zeros(0, np.uint32), np.zeros(0), 3) == []


# --------------------------------------------------------------------------- #
# adaptive theta promotion + threshold/compact kernels
# --------------------------------------------------------------------------- #


def test_topk_threshold_k_exceeds_candidate_count():
    """k larger than the number of nonzero sums must degenerate to 0 —
    keep-everything, never a positive threshold that could drop real
    candidates."""
    import jax.numpy as jnp
    acc = jnp.zeros((3, 128), jnp.uint32)
    acc = acc.at[0, 3].set(9).at[0, 70].set(5)     # q0: two candidates
    acc = acc.at[1, 0].set(2)                      # q1: one candidate
    assert np.asarray(topk_kern.topk_threshold(acc, 5)).tolist() == [0, 0, 0]
    assert np.asarray(topk_kern.pooled_threshold(acc, 5)).tolist() == [0, 0, 0]
    # sanity: with k <= candidates the same kernels return the exact k-th
    assert np.asarray(topk_kern.topk_threshold(acc, 2)).tolist() == [5, 0, 0]
    assert np.asarray(topk_kern.topk_threshold(acc, 1)).tolist() == [9, 2, 0]


def test_candidate_bitmap_all_pruned_worklist():
    """A work-list whose every entry fails the promoted-theta upper-bound
    test scatters nothing, and the final compact returns an all-zero
    candidate bitmap (no candidates, no crash)."""
    import jax.numpy as jnp
    q, words, p, ow = 2, 4, 3, 8
    acc = jnp.zeros((q, words * 32), jnp.uint32)
    member = jnp.zeros((q, words), jnp.uint32)
    ids = jnp.tile(jnp.arange(ow, dtype=jnp.uint32), (p, 1))
    codes = jnp.ones((p, ow), jnp.uint32)
    qslot = jnp.array([0, 1, 0], jnp.int32)
    ns = jnp.full((p,), ow, jnp.int32)
    theta = jnp.array([7, 7], jnp.uint32)
    iq = jnp.full((q,), 1 << 16, jnp.uint32)       # identity scale
    ub = jnp.array([7, 3, 0], jnp.int32)           # all <= scaled theta
    acc, member = topk_kern.score_round(
        acc, member, ids, qslot, codes, ns, member, ub, theta, iq,
        gated=False)
    assert not np.asarray(acc).any() and not np.asarray(member).any()
    got = topk_kern.candidate_bitmap(acc, member, theta,
                                     jnp.zeros((q,), jnp.int32), iq)
    assert not np.asarray(got).any()


def test_theta_promotion_monotone_and_never_over_promotes():
    """The superset contract per round: the promoted theta is monotone
    nondecreasing and NEVER exceeds the k-th largest sum of the final
    accumulator — so a block dropped mid-flight (ub <= promoted theta) holds
    only docs that end below the final threshold, outside the top-k."""
    import jax.numpy as jnp
    rng = np.random.default_rng(42)
    q, width, k, rounds = 8, 256, 5, 6
    acc = jnp.zeros((q, width), jnp.uint32)
    theta = jnp.zeros((q,), jnp.uint32)
    trail = []
    for _ in range(rounds):
        add = ((rng.random((q, width)) < 0.08)
               * rng.integers(1, 200, (q, width)))
        acc = acc + jnp.asarray(add.astype(np.uint32))
        theta = jnp.maximum(theta, topk_kern.pooled_threshold(acc, k))
        trail.append(np.asarray(theta).copy())
    final_kth = np.sort(np.asarray(acc), axis=1)[:, -k]
    for r, th in enumerate(trail):
        assert np.all(th <= final_kth), r          # sound lower bound
        if r:
            assert np.all(th >= trail[r - 1]), r   # monotone promotion


# --------------------------------------------------------------------------- #
# density-adaptive bitmap blocks + adaptive theta: end-to-end parity
# --------------------------------------------------------------------------- #


def _dense_corpus():
    """Clustered postings (avg gap ~2.5 << DENSE_GAP): the build stores most
    blocks as raw 128-word bitmaps via the dense_bitmap capability."""
    rng = np.random.default_rng(13)
    n_docs = 6000
    postings = {}
    for t, df in enumerate([500, 512, 700, 1024, 300, 64]):
        gaps = rng.integers(1, 5, df).astype(np.int64)
        ids = (int(rng.integers(0, 900)) + np.cumsum(gaps)).astype(np.uint32)
        assert int(ids[-1]) < n_docs
        postings[t] = (ids, rng.geometric(0.4, df).astype(np.uint32))
    return rng.integers(60, 400, n_docs).astype(np.int64), postings


DENSE_QUERIES = ([[0, 1], [2, 3], [0, 3, 4], [1, 2, 5], [4], [0, 1, 2, 3],
                  [5, 3], [2, 4, 5]] * 2)


@pytest.mark.parametrize("name", RANKED_CODECS)
def test_dense_bitmap_corpus_ranked_parity(name):
    """The density-adaptive representation serves the ranked modes
    word-parallel with exact parity across all placements."""
    from repro.core import dense_bitmap
    doclen, postings = _dense_corpus()
    idx = InvertedIndex.build(doclen, postings, codec=name)
    assert any(encg.codec == dense_bitmap.NAME
               for tp in idx.terms.values()
               for _, encg, _ in tp.blocks), "corpus stores no dense blocks"
    host = QueryEngine(idx)
    for mode in ("or", "and_scored"):
        want = host.execute(host.plan(QueryBatch(DENSE_QUERIES, mode=mode, k=7)))
        for fused in (False, True):
            eng = QueryEngine(idx).to_device(fused=fused)
            got = eng.execute(eng.plan(QueryBatch(DENSE_QUERIES, mode=mode,
                                                  k=7)))
            assert want == got, (name, mode, fused)
            assert eng.dev_stats["blocks_dense"] > 0, (name, mode, fused)
        oracle_q = [q for q in DENSE_QUERIES]
        for q, res in zip(oracle_q, host.execute(
                host.plan(QueryBatch(oracle_q, mode="or", k=7)))):
            oracle = brute_or_topk(doclen, postings, len(doclen), q, 7)
            assert [(d, pytest.approx(s, rel=1e-12)) for d, s in oracle] == res


def test_adaptive_theta_corpus_parity_and_pruning():
    """The rare-clustered + common shape at a multi-round k=10: adaptive
    promotion engages (several rounds, armed theta) and stays bitwise exact
    while the static prune still drops blocks."""
    queries = [[10, 7, 5], [10, 3, 8], [10, 7], [10, 1, 4, 6]] * 4
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    ref = QueryEngine(idx)
    want = ref.execute(ref.plan(QueryBatch(queries, mode="or", k=10)))
    for fused in (False, True):
        eng = QueryEngine(idx).to_device(fused=fused)
        got = eng.execute(eng.plan(QueryBatch(queries, mode="or", k=10)))
        assert want == got, fused
        assert eng.dev_stats["blocks_pruned"] > 0
        assert eng.dev_stats["score_syncs"] == 0


def test_tombstone_only_epoch_keeps_pruning_armed_and_exact():
    """Deletes only raise idf, so the ranked path stays ARMED under a
    tombstone-only epoch (idf-ratio deflated thresholds): blocks still
    prune, and every placement matches a from-scratch rebuild of the live
    corpus bitwise."""
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    rng = np.random.default_rng(31)
    dead = set()
    for d in rng.choice(N_DOCS, 30, replace=False):
        idx.delete(int(d))
        dead.add(int(d))
    live = {}
    for t, (ids, tfs) in POSTINGS.items():
        keep = [j for j, d in enumerate(ids.tolist()) if d not in dead]
        if keep:
            live[t] = (ids[np.asarray(keep)], tfs[np.asarray(keep)])
    rebuilt = InvertedIndex.build(DOCLEN, live, codec="group_simple")
    queries = [[10, 7], [10, 3], [10, 7, 5], [0, 7], [3, 5, 8]] * 3
    for mode in ("or", "and_scored"):
        batch = QueryBatch(queries, mode=mode, k=6)
        ref, host = QueryEngine(rebuilt), QueryEngine(idx)
        want = ref.execute(ref.plan(batch))
        assert host.execute(host.plan(batch)) == want, mode
        for fused in (False, True):
            eng = QueryEngine(idx).to_device(fused=fused)
            got = eng.execute(eng.plan(QueryBatch(queries, mode=mode, k=6)))
            assert want == got, (mode, fused)
            assert eng.dev_stats["score_syncs"] == 0
            if mode == "or":
                assert eng.dev_stats["blocks_pruned"] > 0, fused


def test_unpack_codes_pallas_matches_host():
    rng = np.random.default_rng(0)
    import jax.numpy as jnp
    from repro.kernels.decode_fused import pack_gaps
    blocks = [rng.integers(0, 256, n).astype(np.uint32)
              for n in (512, 511, 100, 1, 0)]
    tiles = jnp.asarray(np.stack([pack_gaps(c, 8) for c in blocks]))
    slots = jnp.asarray(np.arange(len(blocks), dtype=np.int32))
    got = np.asarray(topk_kern.unpack_codes(tiles, slots))
    for j, c in enumerate(blocks):
        np.testing.assert_array_equal(got[j, :len(c)], c)
        np.testing.assert_array_equal(got[j, len(c):], 0)
