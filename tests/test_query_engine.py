"""Batched query engine: exact parity with brute force across all registered
codecs, the stream_vbyte short-list path, the decoded-block LRU (hit and
eviction paths), and the intersection kernels."""

import heapq

import numpy as np
import pytest

from repro.core import codec
from repro.index.invindex import SHORT, SHORT_CODEC, InvertedIndex
from repro.index.engine import B, K1, BlockCache, QueryBatch, QueryEngine
from repro.kernels import intersect

RNG = np.random.default_rng(11)
N_DOCS = 2000


def small_corpus():
    """Synthetic index inputs small enough to build with every codec,
    including the python-loop scalar baselines: 12 terms, df 10..900 (both
    short-list and multi-block terms)."""
    doclen = RNG.integers(50, 400, N_DOCS).astype(np.int64)
    postings = {}
    for t, df in enumerate([10, 20, 40, 63, 64, 120, 300, 500, 700, 900, 55, 250]):
        ids = np.sort(RNG.choice(N_DOCS, df, replace=False)).astype(np.uint32)
        tfs = RNG.geometric(0.4, df).astype(np.uint32)
        postings[t] = (ids, tfs)
    return doclen, postings


DOCLEN, POSTINGS = small_corpus()
QUERIES = [RNG.choice(12, size=int(RNG.integers(2, 4)), replace=False).tolist()
           for _ in range(24)]


def brute_and(postings, terms):
    out = None
    for t in terms:
        ids = postings[t][0]
        out = ids if out is None else np.intersect1d(out, ids)
    return out.astype(np.uint32)


def brute_or_topk(doclen, postings, n_docs, terms, k):
    avdl = doclen.mean()
    acc = {}
    for t in terms:
        ids, tfs = postings[t]
        df = len(ids)
        idf = np.log(1.0 + (n_docs - df + 0.5) / (df + 0.5))
        tf = tfs.astype(np.float64)
        sc = idf * tf * (K1 + 1) / (tf + K1 * (1 - B + B * doclen[ids] / avdl))
        for d, s in zip(ids.tolist(), sc.tolist()):
            acc[d] = acc.get(d, 0.0) + s
    return heapq.nlargest(k, acc.items(), key=lambda kv: kv[1])


@pytest.mark.parametrize("name", codec.names())
def test_batched_and_or_match_bruteforce(name):
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec=name)
    eng = QueryEngine(idx)
    got = eng.execute(eng.plan(QueryBatch(QUERIES, mode="and")))
    for q, res in zip(QUERIES, got):
        np.testing.assert_array_equal(res, brute_and(POSTINGS, q),
                                      err_msg=f"{name}/{q}")
        assert res.dtype == np.uint32
    top = eng.execute(eng.plan(QueryBatch(QUERIES[:6], mode="or", k=8)))
    for q, res in zip(QUERIES[:6], top):
        want = brute_or_topk(DOCLEN, POSTINGS, N_DOCS, q, 8)
        assert len(res) == len(want)
        np.testing.assert_allclose(sorted(s for _, s in res),
                                   sorted(s for _, s in want), rtol=1e-12)
        assert all(res[i][1] >= res[i + 1][1] for i in range(len(res) - 1))


def test_short_lists_use_stream_vbyte():
    from repro.core import dense_bitmap
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    for t, (ids, _) in POSTINGS.items():
        # per-block, build picks: dense bitmap past the density cutoff, the
        # short-list codec under the df cutoff, the requested codec otherwise
        for bi, (_, encg, _) in enumerate(idx.terms[t].blocks):
            if dense_bitmap.eligible(ids[bi * 512:(bi + 1) * 512]):
                assert encg.codec == dense_bitmap.NAME, (t, bi, len(ids))
            elif len(ids) < SHORT:
                assert encg.codec == SHORT_CODEC, (t, bi, len(ids))
            else:
                assert encg.codec == "group_simple", (t, bi, len(ids))
    # both the dense and the sparse arm are actually exercised
    codecs = {encg.codec for tp in idx.terms.values() for _, encg, _ in tp.blocks}
    assert dense_bitmap.NAME in codecs and "group_simple" in codecs
    # short-list-only AND goes entirely through the stream_vbyte path
    got = QueryEngine(idx).and_query([0, 1, 2])
    np.testing.assert_array_equal(got, brute_and(POSTINGS, [0, 1, 2]))


def test_one_shot_helpers_match_seed_reference():
    """Single-query plans on an uncached engine match brute force."""
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_pfd")

    def one(q, mode, k=10):
        eng = QueryEngine(idx, cache_blocks=0, cache_score_terms=0)
        return eng.execute(eng.plan(QueryBatch([q], mode=mode, k=k)))[0]

    for q in QUERIES:
        docs = one(q, "and")
        np.testing.assert_array_equal(docs, brute_and(POSTINGS, q))
        assert len(one(q, "and_scored", k=5)) == min(5, len(docs))
    # unknown terms are ignored, all-unknown -> empty
    assert len(one([999], "and")) == 0
    assert one([999], "or") == []


def test_block_cache_hit_and_eviction_paths():
    c = BlockCache(2)
    assert c.get((0, 0, 0)) is None
    c.put((0, 0, 0), "a")
    c.put((0, 1, 0), "b")
    assert c.get((0, 0, 0)) == "a"          # hit refreshes LRU order
    c.put((0, 2, 0), "c")                   # evicts (0,1,0), the LRU entry
    assert c.get((0, 1, 0)) is None
    assert c.get((0, 0, 0)) == "a"
    assert c.evictions == 1 and c.hits == 2
    # capacity 0 disables caching entirely
    c0 = BlockCache(0)
    c0.put("k", "v")
    assert c0.get("k") is None and len(c0) == 0


def test_engine_cache_reuse_and_eviction_correctness():
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    eng = QueryEngine(idx)
    r1 = eng.execute(eng.plan(QueryBatch(QUERIES, mode="and")))
    h1 = eng.cache.hits
    r2 = eng.execute(eng.plan(QueryBatch(QUERIES, mode="and")))
    assert eng.cache.hits > h1              # second pass served from cache
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a, b)
    # a pathologically small cache must evict constantly yet stay exact
    tiny = QueryEngine(idx, cache_blocks=2, cache_score_terms=1)
    r3 = tiny.execute(tiny.plan(QueryBatch(QUERIES, mode="and")))
    assert tiny.cache.evictions > 0
    for a, b in zip(r1, r3):
        np.testing.assert_array_equal(a, b)


def test_score_cache_eviction_and_recompute_on_miss():
    """cache_score_terms bounds the BM25 score-vector cache: a capacity-1
    cache under multi-term OR queries must evict, recompute evicted terms on
    the next miss, and stay exact throughout."""
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    ref = QueryEngine(idx)
    eng = QueryEngine(idx, cache_score_terms=1)
    queries = [[5, 9, 7], [9, 5], [7, 9, 5]] * 3
    want = ref.execute(ref.plan(QueryBatch(queries, mode="or", k=8)))
    got = eng.execute(eng.plan(QueryBatch(queries, mode="or", k=8)))
    assert want == got
    assert eng.score_cache.evictions > 0
    assert eng.score_cache.cost_used <= eng.score_cache.capacity
    # an evicted term recomputes on miss with an identical score vector
    ids0, sc0 = map(np.copy, eng.term_scores(5))
    eng.term_scores(9)                      # capacity 1: evicts term 5
    assert eng.score_cache.get(5) is None   # miss (recorded as such)
    misses = eng.score_cache.misses
    ids1, sc1 = eng.term_scores(5)          # recompute path
    assert eng.score_cache.misses == misses + 1
    np.testing.assert_array_equal(ids0, ids1)
    np.testing.assert_array_equal(sc0, sc1)
    # recomputed vectors serve OR queries exactly
    assert eng.or_query([5, 9], k=6) == ref.or_query([5, 9], k=6)


def test_score_cache_zero_capacity_always_recomputes():
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    eng = QueryEngine(idx, cache_score_terms=0)
    r1 = eng.or_query([5, 9, 2], k=5)
    r2 = eng.or_query([5, 9, 2], k=5)
    assert r1 == r2 == QueryEngine(idx).or_query([5, 9, 2], k=5)
    assert len(eng.score_cache) == 0 and eng.score_cache.hits == 0


def test_zero_posting_term_does_not_crash():
    postings = dict(POSTINGS)
    postings[99] = (np.zeros(0, np.uint32), np.zeros(0, np.uint32))
    idx = InvertedIndex.build(DOCLEN, postings, codec="group_simple")
    eng = QueryEngine(idx)
    assert len(eng.and_query([99])) == 0
    assert len(eng.and_query([99, 0])) == 0
    assert eng.or_query([99]) == []


def test_single_term_result_mutation_does_not_corrupt_cache():
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    eng = QueryEngine(idx)
    r = eng.and_query([5])
    assert r.flags.writeable                 # results are caller-owned
    r[0] = 12345
    np.testing.assert_array_equal(eng.and_query([5]), POSTINGS[5][0])
    np.testing.assert_array_equal(eng.and_query([5, 9]), brute_and(POSTINGS, [5, 9]))
    # cache-backed accessors hand out frozen arrays
    with pytest.raises(ValueError):
        eng.term_ids(5)[0] = 1


def test_batch_results_align_with_input_order():
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    eng = QueryEngine(idx)
    queries = [[9, 8], [0, 1], [9, 8], [5, 6, 7], [0, 1]]
    got = eng.execute(eng.plan(QueryBatch(queries, mode="and")))
    for q, res in zip(queries, got):
        np.testing.assert_array_equal(res, brute_and(POSTINGS, q))


# --------------------------------------------------------------------------- #
# intersection kernels
# --------------------------------------------------------------------------- #


def _sorted_unique(rng, n, hi):
    return np.sort(rng.choice(hi, size=min(n, hi), replace=False)).astype(np.uint32)


@pytest.mark.parametrize("na,nb,hi", [(0, 10, 100), (10, 0, 100), (5, 1000, 4000),
                                      (300, 400, 600), (1000, 1000, 1 << 20),
                                      (512, 4096, 5000)])
def test_intersection_kernels_match_intersect1d(na, nb, hi):
    rng = np.random.default_rng(na * 7919 + nb)
    a, b = _sorted_unique(rng, na, hi), _sorted_unique(rng, nb, hi)
    want = np.intersect1d(a, b)
    np.testing.assert_array_equal(intersect.gallop_intersect_np(a, b), want)
    np.testing.assert_array_equal(intersect.bitmap_intersect_np(a, b), want)
    np.testing.assert_array_equal(intersect.intersect_sorted(a, b), want)


def test_gallop_contains_jnp_matches_np():
    rng = np.random.default_rng(0)
    hay = _sorted_unique(rng, 500, 3000)
    needles = _sorted_unique(rng, 200, 3000)
    want = intersect.gallop_contains_np(hay, needles)
    import jax.numpy as jnp
    got = np.asarray(intersect.gallop_contains_jnp(jnp.asarray(hay), jnp.asarray(needles)))
    np.testing.assert_array_equal(got, want)


def test_bitmap_and_pallas_kernel_matches_host():
    rng = np.random.default_rng(1)
    for nwords in (7, 128, 300):
        wa = rng.integers(0, 1 << 32, nwords, dtype=np.uint64).astype(np.uint32)
        wb = rng.integers(0, 1 << 32, nwords, dtype=np.uint64).astype(np.uint32)
        got = intersect.bitmap_and_words(wa, wb, use_pallas=True)
        np.testing.assert_array_equal(got, wa & wb)
