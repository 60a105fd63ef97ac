"""The final bitmap -> docid extraction expands only the nonzero words of
the batch's bitmap.  Every case is compared with a bit-by-bit oracle that
unpacks each row whole, over densities from empty to full, bits set past
``n_docs`` (row padding, or a shard narrower than its bitmap), and batches
of one row and of eight with empty rows between full ones."""

import numpy as np
import pytest

from repro.index.engine import QueryBatch, QueryEngine
from repro.index.invindex import InvertedIndex
from repro.kernels import intersect_rounds
from repro.obs import enable_tracing, get_tracer
from repro.obs.metrics import MetricsRegistry

N_BITS = 1_000_003          # n_docs of the padded rows: 3 bits in the last word


def _oracle(bm, n_docs):
    bits = np.unpackbits(np.ascontiguousarray(bm).view(np.uint8), axis=1,
                         bitorder="little")[:, :n_docs]
    return [np.flatnonzero(b).astype(np.uint32) for b in bits]


def _bitmap(densities, words, rng):
    """One row per density; every bit of the row, padding included, is set
    with that probability."""
    bits = rng.random((len(densities), words * 32)) < np.asarray(
        densities)[:, None]
    return np.packbits(bits, axis=1, bitorder="little").view(np.uint32)


@pytest.mark.parametrize("width", ["padded", "shard"])
@pytest.mark.parametrize("rows", [1, 8])
@pytest.mark.parametrize("density", [0.0, 1e-6, 1e-3, 0.05, 0.6, 1.0])
def test_extract_ids_matches_bitwise_oracle(density, rows, width):
    rng = np.random.default_rng([int(density * 1e6), rows, len(width)])
    words, _ = intersect_rounds.bitmap_geometry(N_BITS)
    # a shard's hi - lo is narrower than the bitmap it extracts from: whole
    # words and part of a word past it carry set bits
    n_docs = N_BITS if width == "padded" else N_BITS - 70_017
    # batches of 8: full rows with empty rows between them, and one sparse
    dens = [density] if rows == 1 else [density, 0, density, 0, 0, density,
                                        1e-4, density]
    bm = _bitmap(dens, words, rng)
    reg = MetricsRegistry()
    reg.counter("extract_words_nonzero")
    reg.counter("extract_ids")
    tr = enable_tracing(True)
    try:
        tr.clear()
        got = intersect_rounds.extract_ids(bm, n_docs, reg)
        span, = [s for s in tr.spans() if s.name == "kernel/extract_ids"]
    finally:
        enable_tracing(False)
        get_tracer().clear()
    want = _oracle(bm, n_docs)
    assert len(got) == rows
    for g, w in zip(got, want):
        assert g.dtype == np.uint32 and g.flags.writeable
        np.testing.assert_array_equal(g, w)
        assert np.all(np.diff(g.astype(np.int64)) > 0)
    # disjoint: writing one array changes no other
    before = [g.copy() for g in got]
    for i, g in enumerate(got):
        if len(g):
            g[0] ^= np.uint32(1)
            assert all(np.array_equal(o, b) for j, (o, b)
                       in enumerate(zip(got, before)) if j != i)
            g[0] ^= np.uint32(1)
    # the words the extraction covers are those below n_docs
    covered = bm[:, :-(-n_docs // 32)]
    n_ids = sum(len(w) for w in want)
    assert reg.value("extract_words_nonzero") == np.count_nonzero(covered)
    assert reg.value("extract_ids") == n_ids
    assert span.args["words_nonzero"] == np.count_nonzero(covered)
    assert span.args["ids"] == n_ids


# A corpus with one list denser than half the doc space: its 1-term query's
# bitmap row is nonzero in nearly every word.
N_DOCS = 60_000


def _corpus():
    rng = np.random.default_rng(17)

    def spread(df):
        return np.sort(rng.choice(N_DOCS, df, replace=False)).astype(np.uint32)

    lists = {0: spread(36_000), 1: spread(300), 2: spread(3_000),
             3: spread(20_000), 4: spread(45_000)}
    doclen = rng.integers(40, 300, N_DOCS).astype(np.int64)
    postings = {t: (ids, rng.geometric(0.4, len(ids)).astype(np.uint32))
                for t, ids in lists.items()}
    return doclen, postings


@pytest.fixture(scope="module")
def dense_index():
    doclen, postings = _corpus()
    return InvertedIndex.build(doclen, postings, codec="group_simple")


@pytest.mark.parametrize("placement,mode", [("device", "and"),
                                            ("fused", "and"),
                                            ("device", "and_scored")])
def test_dense_rows_match_host_oracle(dense_index, placement, mode):
    queries = [[0], [1], [0, 2], [4], [3, 1], [0, 4]]
    batch = QueryBatch(queries, mode=mode, k=7)
    want = QueryEngine(dense_index).execute(batch)
    eng = QueryEngine(dense_index).to_device(fused=placement == "fused")
    with eng.metrics.scoped() as s:
        got = eng.execute(eng.plan(batch, placement=placement))
    assert s.delta("final_syncs") == 1
    assert s.delta("extract_words_nonzero") > 0
    if mode == "and":
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        # more than half the doc space: the row is mostly nonzero words
        assert len(got[0]) > N_DOCS // 2
        assert s.delta("extract_ids") == sum(len(g) for g in got)
    else:
        assert want == got
