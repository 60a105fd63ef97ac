"""Doc-range sharded serving: bitwise parity with the unsharded host oracle
on every mode and placement, shard-locality of the rounds (zero cross-shard
candidate syncs, ONE top-k merge collective per ranked batch), the per-shard
ranked superset contract, mutation epochs under shards (insert / delete /
compact with atomic per-generation shard sets), uneven and empty explicit
bounds, and the boundary-sliced tombstone upload.

The shards here are LOGICAL (the CI backend exposes one CPU device): every
shard runs on the default device through the exact same code path a mesh
placement uses, except the merge collective stacks host-side.  The one true
multi-device case runs in a subprocess with a forced 8-device CPU backend
(the ``test_distribution`` pattern) and goes through ``shard_map``.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.index.engine import QueryBatch, QueryEngine
from repro.index.invindex import InvertedIndex
from repro.index.shards import ShardSpec, TILE_DOCS, shard_generation
from repro.kernels.intersect_rounds import (bitmap_geometry, pack_live_words,
                                            pack_live_words_range)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_DOCS = 20_000
MODES = ("and", "or", "and_scored")


def _corpus(seed=0, n_terms=24):
    rng = np.random.default_rng(seed)
    doclen = rng.integers(5, 120, N_DOCS).astype(np.int64)
    postings = {}
    for t in range(n_terms):
        df = int(rng.integers(60, 6000))
        ids = np.sort(rng.choice(N_DOCS, df, replace=False)).astype(np.uint32)
        postings[t] = (ids, rng.integers(1, 8, df).astype(np.uint32))
    return doclen, postings


DOCLEN, POSTINGS = _corpus()
QUERIES = [[0, 1], [2, 3, 5], [7], [11, 13, 17, 19], [2, 4, 8], [1],
           [23, 6], []]


def _build(codec="group_simple"):
    return InvertedIndex.build(DOCLEN, POSTINGS, codec=codec)


def _assert_equal(ref, got, tag):
    for i, (a, b) in enumerate(zip(ref, got)):
        if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (tag, i)
        else:
            assert a == b, (tag, i, a, b)


def _sweep(host, sharded, tag, k=10, queries=QUERIES):
    for mode in MODES:
        b = QueryBatch([list(q) for q in queries], mode=mode, k=k)
        ref = host.execute(host.plan(b, placement="host"))
        got = sharded.execute(sharded.plan(b, placement="device"))
        _assert_equal(ref, got, (tag, mode))


# --------------------------------------------------------------------------- #
# parity: 1 shard == unsharded, multi-shard sweeps
# --------------------------------------------------------------------------- #

def test_one_shard_bitwise_equals_unsharded_every_mode_and_placement():
    idx = _build()
    host = QueryEngine(idx)
    dev = QueryEngine(idx).to_device(fused=True)
    sh1 = QueryEngine(idx).to_device(fused=True, shards=1)
    for mode in MODES:
        b = QueryBatch([list(q) for q in QUERIES], mode=mode, k=10)
        ref = host.execute(host.plan(b, placement="host"))
        for placement in ("device", "fused"):
            _assert_equal(ref, dev.execute(dev.plan(b, placement=placement)),
                          ("unsharded", mode, placement))
            _assert_equal(ref, sh1.execute(sh1.plan(b, placement=placement)),
                          ("1shard", mode, placement))


@pytest.mark.parametrize("codec", ["group_simple", "group_pfd"])
@pytest.mark.parametrize("n_shards", [2, 4])
def test_multi_shard_parity_sweep(codec, n_shards):
    idx = _build(codec)
    host = QueryEngine(idx)
    sh = QueryEngine(idx).to_device(shards=n_shards)
    _sweep(host, sh, (codec, n_shards))


def test_fused_placement_parity_under_shards():
    idx = _build("group_pfd")
    host = QueryEngine(idx)
    sh = QueryEngine(idx).to_device(fused=True, shards=3)
    for mode in MODES:
        b = QueryBatch([list(q) for q in QUERIES], mode=mode, k=10)
        ref = host.execute(host.plan(b, placement="host"))
        _assert_equal(ref, sh.execute(sh.plan(b, placement="fused")),
                      ("fused", mode))


def test_uneven_and_empty_explicit_bounds():
    idx = _build()
    host = QueryEngine(idx)
    # a deliberately lopsided split with an EMPTY middle shard and cuts not
    # aligned to bitmap tiles — correctness may not depend on where they fall
    sh = QueryEngine(idx).to_device(bounds=(0, 100, 100, 17_001, N_DOCS))
    _sweep(host, sh, "uneven")
    spec, engs, _ = sh._shard_engines(sh._ctx_now())
    assert spec.bounds == (0, 100, 100, 17_001, N_DOCS)
    assert engs[1] is None                  # empty shard gets no engine
    assert sum(e is not None for e in engs) == 3


# --------------------------------------------------------------------------- #
# shard locality + the single merge collective
# --------------------------------------------------------------------------- #

def test_zero_cross_shard_syncs_and_one_merge_per_ranked_batch():
    idx = _build()
    sh = QueryEngine(idx).to_device(shards=4)
    b = QueryBatch([list(q) for q in QUERIES], mode="or", k=10)
    with sh.metrics.scoped() as sample:
        sh.execute(sh.plan(b, placement="device"))
    assert sample.delta("merge_syncs") == 1         # ONE collective per batch
    assert sample.delta("collective_bytes") > 0
    spec, engs, _ = sh._shard_engines(sh._ctx_now())
    live = [e for e in engs if e is not None]
    assert live and spec.n_shards == 4
    for eng in live:                # rounds never sync scores
        assert eng.dev_stats["score_syncs"] == 0
    # each non-empty shard contributes exactly one final bitmap download
    assert sample.delta("shard_final_syncs") == len(live)
    with sh.metrics.scoped() as sample:
        sh.execute(sh.plan(QueryBatch([[0, 1], [2, 3]], mode="and"),
                           placement="device"))
    assert sample.delta("merge_syncs") == 0         # AND merges nothing


def test_plan_note_records_shard_topology():
    idx = _build()
    sh = QueryEngine(idx).to_device(shards=2)
    note = sh.plan(QueryBatch([[0, 1]] * 8, mode="or", k=10),
                   placement="device").note
    assert "sharded x2" in note and "bounds=" in note and "logical" in note


# --------------------------------------------------------------------------- #
# ranked superset contract, per shard
# --------------------------------------------------------------------------- #

def test_per_shard_candidates_superset_of_global_topk():
    idx = _build()
    host = QueryEngine(idx)
    sh = QueryEngine(idx).to_device(shards=4)
    queries = [list(q) for q in QUERIES if q]
    k = 10
    for mode in ("or", "and_scored"):
        b = QueryBatch(queries, mode=mode, k=k)
        ref = host.execute(host.plan(b, placement="host"))
        sh.execute(sh.plan(b, placement="device"))
        spec, engs, _ = sh._shard_engines(sh._ctx_now())
        shard_cands = sh._last_shard_cands
        ranges = [r for r, e in zip(spec.ranges(), engs) if e is not None]
        assert len(shard_cands) == len(ranges)
        for (lo, hi), cands in zip(ranges, shard_cands):
            for i, top in enumerate(ref):
                want = [d for d, _ in top if lo <= d < hi]
                got = set((cands[i] + np.uint32(lo)).tolist())
                assert got.issuperset(want), (mode, i, lo, hi)


# --------------------------------------------------------------------------- #
# mutation epochs under shards
# --------------------------------------------------------------------------- #

def test_mutation_epochs_and_atomic_generation_swap():
    rng = np.random.default_rng(9)
    idx = _build("group_pfd")
    host = QueryEngine(idx)
    sh = QueryEngine(idx).to_device(shards=3)
    gid0 = idx.gen.gid
    spec0, engs0, _ = sh._shard_engines(sh._ctx_now())
    assert all(e.idx.gid == gid0 for e in engs0 if e is not None)

    # tombstone-only epoch (pruning stays armed, per-shard sliced gates)
    for d in rng.choice(N_DOCS, 200, replace=False):
        idx.delete(int(d))
    _sweep(host, sh, "tomb-only")

    # delta-bearing epoch: fresh inserts served by the parent's delta scan
    for j in range(25):
        idx.insert(N_DOCS + j,
                   {int(t): int(rng.integers(1, 5))
                    for t in rng.choice(24, 4, replace=False)},
                   int(rng.integers(5, 100)))
    _sweep(host, sh, "delta")

    # pin a plan, compact underneath it: the pinned plan must keep serving
    # the OLD generation's shard set; fresh plans serve the new one
    b = QueryBatch([list(q) for q in QUERIES], mode="or", k=10)
    pinned = sh.plan(b, placement="device")
    ref_pinned = sh.execute(pinned)
    idx.compact()
    assert idx.gen.gid != gid0
    assert sh.execute(pinned) == ref_pinned         # epoch pinning holds
    _sweep(host, sh, "post-compact")
    # the new generation's shard set is a fresh atomic build, all on gid+1
    _, engs1, _ = sh._shard_engines(sh._ctx_now())
    gids = {e.idx.gid for e in engs1 if e is not None}
    assert gids == {idx.gen.gid}


# --------------------------------------------------------------------------- #
# shard building blocks
# --------------------------------------------------------------------------- #

def test_shard_spec_derive_covers_and_aligns():
    idx = _build()
    spec = ShardSpec.derive(idx.gen, 4)
    b = spec.bounds
    assert b[0] == 0 and b[-1] == N_DOCS and len(b) == 5
    assert all(x <= y for x, y in zip(b, b[1:]))
    assert all(x % TILE_DOCS == 0 for x in b[1:-1])     # interior cuts aligned
    assert spec.shard_of(0) == 0 and spec.shard_of(N_DOCS - 1) == 3
    for s, (lo, hi) in enumerate(spec.ranges()):
        if hi > lo:
            assert spec.shard_of(lo) == s and spec.shard_of(hi - 1) == s


def test_shard_generation_stats_fixed_to_parent():
    idx = _build()
    gen = idx.gen
    lo, hi = 4096, 12_288
    sg = shard_generation(gen, lo, hi)
    assert sg.gid == gen.gid and (sg.doc_lo, sg.doc_hi) == (lo, hi)
    assert sg.n_docs == hi - lo
    assert sg.stat_n_docs == gen.n_docs and sg.stat_avdl == gen.avdl
    for t, tp in sg.terms.items():
        assert tp.df == gen.terms[t].df             # GLOBAL df after fixup
        ids, tfs = sg.decode_term(t)
        gids_, gtfs = gen.decode_term(t)
        m = (gids_ >= lo) & (gids_ < hi)
        assert np.array_equal(ids.astype(np.int64) + lo,
                              gids_[m].astype(np.int64))
        assert np.array_equal(tfs, gtfs[m])


def test_pack_live_words_range_equals_sliced_translation():
    rng = np.random.default_rng(3)
    dead = np.sort(rng.choice(N_DOCS, 300, replace=False)).astype(np.int64)
    for lo, hi in ((0, N_DOCS), (4096, 12_288), (100, 17_001), (50, 51)):
        words, _ = bitmap_geometry(hi - lo)
        sub = dead[(dead >= lo) & (dead < hi)] - lo
        assert np.array_equal(pack_live_words_range(dead, lo, hi, words),
                              pack_live_words(sub, hi - lo, words))


def test_shard_spec_rejects_bad_bounds():
    with pytest.raises(ValueError):
        ShardSpec((5, 10))              # must start at 0
    with pytest.raises(ValueError):
        ShardSpec((0, 10, 5))           # must be non-decreasing
    with pytest.raises(ValueError):
        ShardSpec((0,))                 # needs at least (0, n_docs)
    with pytest.raises(ValueError):
        shard_generation(_build().gen, 10, 10)      # empty range


# --------------------------------------------------------------------------- #
# true multi-device mesh (subprocess, forced 8-device CPU backend)
# --------------------------------------------------------------------------- #

def test_mesh_sharded_parity_subprocess():
    body = textwrap.dedent("""
    import numpy as np, jax
    from repro.index.invindex import InvertedIndex
    from repro.index.engine import QueryEngine, QueryBatch
    from repro.launch.mesh import serving_mesh
    rng = np.random.default_rng(2)
    n_docs = 16000
    doclen = rng.integers(5, 120, n_docs).astype(np.int64)
    postings = {}
    for t in range(16):
        df = int(rng.integers(60, 4000))
        ids = np.sort(rng.choice(n_docs, df, replace=False)).astype(np.uint32)
        postings[t] = (ids, rng.integers(1, 8, df).astype(np.uint32))
    idx = InvertedIndex.build(doclen, postings, codec="group_simple")
    host = QueryEngine(idx)
    mesh = serving_mesh(4)
    assert mesh is not None and mesh.devices.size == 4
    sh = QueryEngine(idx).to_device(shards=4, mesh=mesh)
    queries = [[0, 1], [2, 3, 5], [7], [11, 13, 14, 15]]
    for mode in ("and", "or", "and_scored"):
        b = QueryBatch(queries, mode=mode, k=10)
        ref = host.execute(host.plan(b, placement="host"))
        got = sh.execute(sh.plan(b, placement="device"))
        for a, g in zip(ref, got):
            if mode == "and":
                assert np.array_equal(a, g)
            else:
                assert a == g
    note = sh.plan(QueryBatch(queries, mode="or", k=10),
                   placement="device").note
    assert "mesh-placed" in note
    assert sh.dev_stats["merge_syncs"] == 2
    print("MESH_PARITY_OK")
    """)
    code = ("import os\n"
            "os.environ['XLA_FLAGS']="
            "'--xla_force_host_platform_device_count=8'\n" + body)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=420)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "MESH_PARITY_OK" in r.stdout
