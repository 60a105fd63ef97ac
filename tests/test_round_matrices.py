"""A resident round's rows reach the accumulate kernels as bucketed device
matrices indexed on the device: one decoded matrix per source (codec, or
the numpy fallback) gathered by a row-index vector, and one fused part per
bit-width bucket, each with its own accumulate call.  Every case below is
bit-identical to the host oracle, and the row counters say how the rows
got there: none sliced or stacked one at a time on the arena path, every
sparse entry gathered, the bucket's remaining lanes padded with n = 0.

The corpus is sparse (average docid gap far above the dense-bitmap cutoff)
but for two lists of dense-bitmap blocks, whose 128-word windows the dense
kernels gather from the arena by row index in the same way."""

import numpy as np
import pytest

from repro.index.engine import QueryBatch, QueryEngine
from repro.index.invindex import InvertedIndex
from repro.obs import enable_tracing, get_tracer

N_DOCS = 500_000
GAPPED_AT = 20_000


def _corpus():
    rng = np.random.default_rng(2024)

    def spread(df):
        return np.sort(rng.choice(N_DOCS, df, replace=False)).astype(np.uint32)

    def packed(lo, hi, df):
        # average gap 5: dense-bitmap blocks, served as 128-word windows
        return np.sort(lo + rng.choice(hi - lo, df, replace=False)).astype(
            np.uint32)

    def gapped(ranges):
        # one 512-posting block per gap range: the block's bit width is the
        # range's, so its fused tile lands in a chosen bit-width bucket
        gaps = np.concatenate([rng.integers(lo, hi + 1, 512)
                               for lo, hi in ranges])
        return (GAPPED_AT + np.cumsum(gaps)).astype(np.uint32)

    lists = {
        0: spread(400),                 # one block over the whole doc range
        1: spread(7 * 512 - 5),         # 7 blocks
        2: spread(8 * 512),             # 8 blocks
        3: spread(9 * 512 - 1),         # 9 blocks
        4: spread(40),                  # short lists: their own codec
        5: spread(30),
        6: spread(5000),                # 10 blocks
        7: gapped([(9, 15)] * 2),                       # gaps of 4 bits
        8: gapped([(9, 15), (100, 255)]),               # 4 and 8 bits
        9: gapped([(9, 15), (100, 255), (300, 1000)]),  # 4, 8 and 10 bits
        10: spread(6500),               # 13 blocks
        11: packed(0, 100_000, 20_000),         # 40 dense blocks
        12: packed(100_000, 215_000, 23_000),   # 45 dense blocks
    }
    doclen = rng.integers(40, 300, N_DOCS).astype(np.int64)
    postings = {t: (ids, rng.geometric(0.4, len(ids)).astype(np.uint32))
                for t, ids in lists.items()}
    return doclen, postings


DOCLEN, POSTINGS = _corpus()


@pytest.fixture(scope="module")
def indexes():
    return {c: InvertedIndex.build(DOCLEN, POSTINGS, codec=c)
            for c in ("group_simple", "varbyte")}


def _mutated():
    """The group_simple corpus with tombstones and a delta segment."""
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    rng = np.random.default_rng(5)
    for d in rng.choice(POSTINGS[1][0], 300, replace=False).tolist():
        idx.delete(int(d))
    for d in range(N_DOCS, N_DOCS + 20):
        idx.insert(d, {1: 2, 3: 1, 6: 1}, 120)
    return idx


# name: (codec, placement, mode, queries, expected counter deltas, sources
# of the widest decode call, -1 where unchecked)
CASES = {
    "entries_1": ("group_simple", "device", "and", [[0]],
                  {"rows_gathered": 1, "rows_padded": 7}, 1),
    "entries_bucket_minus_1": ("group_simple", "device", "and", [[1]],
                               {"rows_gathered": 7, "rows_padded": 1}, 1),
    "entries_bucket": ("group_simple", "device", "and", [[2]],
                       {"rows_gathered": 8, "rows_padded": 0}, 1),
    "entries_bucket_plus_1": ("group_simple", "device", "and", [[3]],
                              {"rows_gathered": 9, "rows_padded": 7}, 1),
    "shared_block": ("group_simple", "device", "and",
                     [[1, 6], [1, 3], [1], [3, 6, 10]], {}, 1),
    "two_codecs": ("group_simple", "device", "and",
                   [[4, 6], [0, 3], [5, 1]], {}, 2),
    "host_fallback": ("varbyte", "device", "and", [[4, 6], [0, 3]], {}, 2),
    "fused_1_bucket": ("group_simple", "fused", "and", [[0, 7]],
                       {"fused_calls": 1}, 1),
    "fused_2_buckets": ("group_simple", "fused", "and", [[0, 8]],
                        {"fused_calls": 2}, 1),
    "fused_3_buckets": ("group_simple", "fused", "and", [[0, 9], [0, 7]],
                        {"fused_calls": 3}, 1),
    "dense_windows": ("group_simple", "device", "and",
                      [[3, 11], [0, 12], [11, 12], [6, 11, 12]], {}, 1),
    "mutated_epoch": ("mutated", "device", "and",
                      [[1, 6], [3, 1], [0, 1, 3], [4, 6]], {}, -1),
    "mutated_epoch_fused": ("mutated", "fused", "and",
                            [[1, 6], [3, 1], [0, 9]], {}, -1),
    "sharded_2": ("group_simple", "sharded", "and",
                  [[1, 6], [0, 3], [4, 10], [2]], {}, -1),
    "ranked_or": ("group_simple", "device", "or",
                  [[1, 6], [0, 3, 10], [4, 2]], {}, -1),
    "ranked_and_scored": ("group_simple", "device", "and_scored",
                          [[1, 6], [0, 3, 10], [4, 2]], {}, -1),
    "ranked_and_scored_fused": ("group_simple", "fused", "and_scored",
                                [[0, 9], [1, 6], [3, 10]], {}, -1),
    "ranked_dense": ("group_simple", "device", "and_scored",
                     [[3, 11], [0, 12, 6], [11, 12]], {}, -1),
    "ranked_or_dense": ("group_simple", "device", "or",
                        [[3, 11], [0, 12, 6], [11, 12]], {}, -1),
}


@pytest.mark.parametrize("case", list(CASES))
def test_round_matrices_match_host_oracle(indexes, case):
    codec, placement, mode, queries, want_deltas, n_sources = CASES[case]
    idx = _mutated() if codec == "mutated" else indexes[codec]
    batch = QueryBatch(queries, mode=mode, k=7)
    want = QueryEngine(idx).execute(batch)
    if placement == "sharded":
        eng = QueryEngine(idx).to_device(shards=2)
        plan = eng.plan(batch, placement="device")
    else:
        eng = QueryEngine(idx).to_device(fused=placement == "fused")
        plan = eng.plan(batch, placement=placement)
    widths = []
    if eng.arena is not None:
        decode_round = eng.arena.decode_round

        def counted(pairs):
            sources, decoded = decode_round(pairs)
            widths.append(len(sources))
            return sources, decoded

        eng.arena.decode_round = counted
    with eng.metrics.scoped() as s:
        got = eng.execute(plan)
    if mode == "and":
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g, err_msg=case)
    else:
        assert want == got, case
    if eng.arena is None:
        return
    # the arena path handles no row one at a time
    assert s.delta("rows_sliced") == 0
    assert s.delta("rows_stacked") == s.delta("blocks_host")
    for name, v in want_deltas.items():
        assert s.delta(name) == v, (case, name)
    if n_sources > 0:
        assert max(widths) == n_sources, case
    if case == "shared_block":
        assert s.delta("rows_gathered") > s.delta("worklist_decodes")
    if case == "host_fallback":
        assert s.delta("blocks_host") > 0 and s.delta("blocks_device") > 0


def test_new_lengths_inside_warm_buckets_compile_nothing(indexes):
    """Once a batch has warmed a bucket, work-lists of other exact lengths
    inside it compile no program.  A sparse round's programs take two
    buckets: its distinct blocks' (the decoded matrix) and its entries'
    (the row-index vector).  Device placement: seed rounds of 9, 10 and 13
    distinct blocks and probe rounds of 10 and 13 share bucket 16 for both;
    a seed round of 14 entries over 7 blocks shares (8, 16) with the warm
    16 entries over 8; dense probe rounds of 40 and 45 windows bucket 64.
    Fused placement: seed rounds as above, and fused parts of 1 to 3
    entries per bit-width bucket share bucket 8."""
    idx = indexes["group_simple"]
    eng = QueryEngine(idx).to_device(fused=True)
    warm = {"device": ([[3, 6]], [[3, 11]], [[2], [2]]),
            "fused": ([[3, 9]],)}
    new = {"device": ([[6, 10]], [[10]], [[1], [1]], [[3, 10]], [[6, 12]]),
           "fused": ([[6, 8]], [[10, 7]], [[1, 9], [1, 7]])}
    for placement, batches in warm.items():
        for queries in batches:
            eng.execute(eng.plan(QueryBatch(queries), placement=placement))
    tr = enable_tracing(True)
    try:
        tr.clear()
        for placement, batches in new.items():
            for queries in batches:
                eng.execute(eng.plan(QueryBatch(queries),
                                     placement=placement))
        compiled = [sp.args.get("fun_name") for sp in tr.spans()
                    if sp.name == "jax/compile"]
    finally:
        enable_tracing(False)
        get_tracer().clear()
    assert compiled == []
