"""Compressed data pipeline + inverted index behaviour tests."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import synth
from repro.data.pipeline import AdjacencyStore, BagStore, TokenStore, lm_batch_iter
from repro.index.engine import QueryBatch, QueryEngine
from repro.index.invindex import InvertedIndex
from repro.models.sampler import CSRGraph


def test_token_store_roundtrip_and_ratio():
    rng = np.random.default_rng(0)
    toks = np.minimum(rng.zipf(1.3, 200000), 49151).astype(np.uint32)
    st_ = TokenStore.build(toks, codec="bp128", block=4096)
    np.testing.assert_array_equal(st_.read(0, len(toks)), toks)
    np.testing.assert_array_equal(st_.read(5000, 1234), toks[5000:6234])
    assert st_.compressed_bytes() < st_.raw_bytes


def test_lm_batch_iter_deterministic_resume():
    toks = np.arange(100000, dtype=np.uint32) % 1000
    store = TokenStore.build(toks, codec="group_simple", block=8192)
    it = lm_batch_iter(store, batch=4, seq=16)
    b0, c = it(0)
    b0again, _ = it(0)
    np.testing.assert_array_equal(b0["tokens"], b0again["tokens"])
    np.testing.assert_array_equal(b0["labels"][:, :-1], b0["tokens"][:, 1:])


def test_adjacency_store_roundtrip():
    g = CSRGraph.random(500, 20000, 0)
    st_ = AdjacencyStore.build(g.indptr, g.indices, codec="group_pfd")
    for r in (0, 13, 499):
        want = np.sort(g.indices[g.indptr[r]:g.indptr[r + 1]])
        np.testing.assert_array_equal(st_.neighbors(r), want)
    assert st_.compressed_bytes() < st_.raw_bytes


def test_bag_store_roundtrip():
    rng = np.random.default_rng(1)
    bags = [rng.choice(10000, size=rng.integers(5, 60), replace=False) for _ in range(50)]
    st_ = BagStore.build(bags)
    for i in (0, 25, 49):
        np.testing.assert_array_equal(st_.read(i), np.sort(bags[i]))


def test_index_and_query_vs_bruteforce():
    doclen, postings = synth.make_corpus("wikipedia")
    idx = InvertedIndex.build(doclen, postings, codec="group_simple")
    t1, t2 = sorted(postings)[:2]
    eng = QueryEngine(idx)
    got, = eng.execute(eng.plan(QueryBatch([[t1, t2]], mode="and")))
    want = np.intersect1d(postings[t1][0], postings[t2][0])
    np.testing.assert_array_equal(np.sort(got), want)
    top, = eng.execute(eng.plan(QueryBatch([[t1, t2]], mode="or", k=5)))
    assert len(top) == 5
    assert top[0][1] >= top[-1][1]


def test_index_decode_term_with_skip():
    doclen, postings = synth.make_corpus("twitter")
    t = max(postings, key=lambda k: len(postings[k][0]))
    idx = InvertedIndex.build(doclen, postings, codec="bp128")
    ids_all, tfs_all = idx.decode_term(t)
    np.testing.assert_array_equal(ids_all, postings[t][0])
    np.testing.assert_array_equal(tfs_all, postings[t][1])
    mid = int(postings[t][0][len(postings[t][0]) // 2])
    ids_skip, _ = idx.decode_term(t, min_docid=mid)
    assert ids_skip[-1] == ids_all[-1]
    assert len(ids_skip) <= len(ids_all)
    assert mid in ids_skip or mid not in ids_all


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 500))
def test_property_index_sizes_consistent(df):
    docids = np.sort(np.random.default_rng(df).choice(10000, df, replace=False)).astype(np.uint32)
    tfs = np.ones(df, np.uint32)
    idx = InvertedIndex.build(np.full(10000, 100), {0: (docids, tfs)}, codec="group_simple")
    got, gtf = idx.decode_term(0)
    np.testing.assert_array_equal(got, docids)


def test_dataset_stats_match_paper_characteristics():
    for name in synth.DATASETS:
        stats = synth.dataset_stats(synth.make_dataset(name))
        assert stats["gap_fit8"] > 0.9 or stats["gap_mean"] < 300, (name, stats)
        assert stats["tf_fit8"] > 0.9, (name, stats)
