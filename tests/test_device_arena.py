"""Device/host parity for the posting arenas: batched arena decode and the
device-placed engine must be bit-identical to the numpy engine across every
registered group codec, including block-boundary (df == 512/513/1024) and
empty-intersection edge cases; the fused placement's resident rounds must
match the host intersection exactly, while a direct ``and_query`` on a fused
engine stays numpy; and the work-list discipline (<= 1 decode per distinct
(term, block) per round) must hold.

The native-decode sweep derives its codec list from the registry's *declared*
arena capabilities (``codec.get(name).arena``), so a codec gaining an
``ArenaLayout`` is parity-tested automatically — no hand-maintained list."""

import dataclasses

import numpy as np
import pytest

from repro.core import codec
from repro.index.device import DeviceArena
from repro.index.engine import ExecutionPlan, QueryBatch, QueryEngine
from repro.index.invindex import SHORT_CODEC, InvertedIndex
from repro.kernels import decode_fused, intersect_rounds

# every codec declaring the ArenaLayout capability decodes natively on device
# and is swept below; the registry lint (tools/registry_lint.py) cross-checks
# this derivation against the declarations
ARENA_CODECS = [n for n in codec.names() if codec.get(n).arena is not None]

RNG = np.random.default_rng(1234)
N_DOCS = 1500

# df values straddle the short-list cutoff (64) and the 512-posting block
# boundary; the last two are docid-disjoint so AND over them is empty
DFS = [12, 63, 64, 200, 512, 513, 1024, 300, 280]


def _corpus():
    doclen = RNG.integers(40, 300, N_DOCS).astype(np.int64)
    postings = {}
    for t, df in enumerate(DFS[:-2]):
        ids = np.sort(RNG.choice(N_DOCS, df, replace=False)).astype(np.uint32)
        postings[t] = (ids, RNG.geometric(0.4, df).astype(np.uint32))
    lo = np.sort(RNG.choice(N_DOCS // 2, DFS[-2], replace=False)).astype(np.uint32)
    hi = (np.sort(RNG.choice(N_DOCS // 2, DFS[-1], replace=False))
          + N_DOCS // 2).astype(np.uint32)
    postings[len(DFS) - 2] = (lo, RNG.geometric(0.4, DFS[-2]).astype(np.uint32))
    postings[len(DFS) - 1] = (hi, RNG.geometric(0.4, DFS[-1]).astype(np.uint32))
    return doclen, postings


DOCLEN, POSTINGS = _corpus()
NT = len(DFS)
QUERIES = ([RNG.choice(NT, size=int(RNG.integers(2, 4)), replace=False).tolist()
            for _ in range(12)]
           + [[NT - 2, NT - 1],          # disjoint -> empty intersection
              [4], [6],                  # single term, block-boundary terms
              [0, 999]])                 # unknown term ignored


def _engines(name, fused=False):
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec=name)
    return QueryEngine(idx), QueryEngine(idx).to_device(fused=fused)


def _brute_and(postings, q):
    known = [t for t in q if t in postings]
    out = postings[known[0]][0]
    for t in known[1:]:
        out = np.intersect1d(out, postings[t][0])
    return out.astype(np.uint32)


@pytest.mark.parametrize("name", codec.names(group_only=True))
def test_device_engine_matches_host_engine(name):
    host, dev = _engines(name)
    want = host.execute(host.plan(QueryBatch(QUERIES, mode="and")))
    got = dev.execute(dev.plan(QueryBatch(QUERIES, mode="and")))
    for q, a, b in zip(QUERIES, want, got):
        np.testing.assert_array_equal(a, b, err_msg=f"{name}/and/{q}")
        assert b.dtype == np.uint32
    for mode in ("or", "and_scored"):
        batch = QueryBatch(QUERIES[:5], mode=mode, k=7)
        assert (host.execute(host.plan(batch))
                == dev.execute(dev.plan(batch))), f"{name}/{mode}"


@pytest.mark.parametrize("name", ["group_simple", "bp128", "g_packed_binary",
                                  "group_pfd"])
def test_fused_placement_matches_host_engine(name):
    host, dev = _engines(name, fused=True)
    want = host.execute(host.plan(QueryBatch(QUERIES, mode="and")))
    plan = dev.plan(QueryBatch(QUERIES, mode="and"))
    assert plan.placement == "fused"
    got = dev.execute(plan)
    for q, a, b in zip(QUERIES, want, got):
        np.testing.assert_array_equal(a, b, err_msg=f"{name}/fused/{q}")
    assert dev.arena.stats["fused_calls"] > 0   # the kernel actually ran


@pytest.mark.parametrize("name", ["group_simple", "bp128", "g_packed_binary",
                                  "group_pfd"])
def test_direct_and_query_on_fused_engine_is_numpy(name):
    """A direct ``and_query`` on a fused engine intersects in numpy: the
    host reference never runs a Pallas kernel, so it stays independent of
    the device code it checks."""
    host, dev = _engines(name, fused=True)
    calls0 = dev.arena.stats["fused_calls"]
    for q in QUERIES:
        np.testing.assert_array_equal(dev.and_query(q),
                                      _brute_and(POSTINGS, q),
                                      err_msg=f"{name}/{q}")
        assert dev.and_query_scored(q, 5) == host.and_query_scored(q, 5)
    assert dev.arena.stats["fused_calls"] == calls0


@pytest.mark.parametrize("n_docs", [25_000, 77_824])   # 896 / 2432 words
def test_fused_resident_and_at_odd_bitmap_widths(n_docs):
    """The fused placement's resident rounds probe a candidate bitmap whose
    width is not a multiple of 2048 words, with postings up to the last
    doc, and answer 2- and 3-term AND batches like the host plan."""
    words, _ = intersect_rounds.bitmap_geometry(n_docs)
    assert words % 2048
    rng = np.random.default_rng(n_docs)
    postings = {}
    # sparse lists (mean gap far above the dense-bitmap cutoff), so every
    # block reaches the fused decode
    for t, df in enumerate([700, 1300, 520, 90]):
        ids = np.sort(rng.choice(n_docs - 1, df - 1, replace=False))
        ids = np.append(ids, n_docs - 1).astype(np.uint32)
        postings[t] = (ids, rng.geometric(0.4, df).astype(np.uint32))
    doclen = rng.integers(40, 300, n_docs).astype(np.int64)
    idx = InvertedIndex.build(doclen, postings, codec="group_simple")
    eng = QueryEngine(idx).to_device(fused=True)
    queries = [[0, 1], [1, 2], [2, 3], [0, 1, 2], [3, 1, 0], [0, 2, 3]]
    batch = QueryBatch(queries, mode="and")
    plan = eng.plan(batch)
    assert plan.placement == "fused"
    want = eng.execute(eng.plan(batch, placement="host"))
    got = eng.execute(plan)
    for q, a, b in zip(queries, want, got):
        np.testing.assert_array_equal(a, b, err_msg=f"{n_docs}/{q}")
        np.testing.assert_array_equal(b, _brute_and(postings, q))
    assert eng.arena.stats["fused_calls"] > 0


@pytest.mark.parametrize("repeat", [1, 40])
@pytest.mark.parametrize("name", ARENA_CODECS)
def test_arena_block_decode_matches_numpy_oracle(name, repeat):
    """``repeat`` 40 makes each codec's work-list longer than one decode
    chunk, so the chunked decode loop runs too."""
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec=name)
    arena = DeviceArena.from_index(idx, build_fused=False)
    entries = [(t, bi, f) for t in idx.terms
               for bi in range(idx.n_blocks(t)) for f in (0, 1)] * repeat
    got = arena.decode_blocks(entries)
    for (t, bi, f), a in zip(entries, got):
        want = idx.decode_block_ids(t, bi) if f == 0 else idx.decode_block_tfs(t, bi)
        np.testing.assert_array_equal(a, want, err_msg=f"{name}/{t}/{bi}/{f}")
    # full native coverage: the short-list codec declares an arena too, so no
    # block of this corpus falls back to the host oracle
    assert codec.get(SHORT_CODEC).arena is not None
    assert arena.stats["blocks_device"] == len(entries)
    assert arena.stats["blocks_host"] == 0


def test_non_arena_codec_falls_back_to_host_oracle():
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="varbyte")
    arena = DeviceArena.from_index(idx, build_fused=False)
    entries = [(t, bi, f) for t in idx.terms
               for bi in range(idx.n_blocks(t)) for f in (0, 1)]
    got = arena.decode_blocks(entries)
    for (t, bi, f), a in zip(entries, got):
        want = idx.decode_block_ids(t, bi) if f == 0 else idx.decode_block_tfs(t, bi)
        np.testing.assert_array_equal(a, want, err_msg=f"varbyte/{t}/{bi}/{f}")
    # varbyte declares no arena; its sparse blocks decode on host, while the
    # stream_vbyte short lists and the density-promoted bitmap blocks still
    # go native
    assert arena.stats["blocks_host"] > 0
    assert arena.stats["blocks_device"] > 0
    assert not arena.covers((2, 0, 0))       # df=64 sparse term -> varbyte
    assert arena.covers((0, 0, 0))           # df=12 term -> stream_vbyte


def test_plan_resolves_placement_and_term_caps():
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    host = QueryEngine(idx)
    p = host.plan(QueryBatch(QUERIES, mode="and"))
    assert isinstance(p, ExecutionPlan) and p.placement == "host"
    assert 999 not in p.terms                # unknown terms omitted
    assert p.terms[0].codec == SHORT_CODEC   # df=12 -> short-list fast path
    # df=512 over 1500 docs sits past the density cutoff, so build stored the
    # term's block as a raw bitmap — the caps surface the per-block decision
    assert p.terms[4].codec == "dense_bitmap"
    assert p.terms[4].arena and not p.terms[4].fused
    dev = QueryEngine(idx).to_device(fused=True)
    pf = dev.plan(QueryBatch(QUERIES, mode="and"))
    assert pf.placement == "fused" and pf.terms[4].fused
    # plans are snapshots: the host plan still executes on the host path and
    # reproduces the device results exactly
    for a, b in zip(host.execute(p), dev.execute(pf)):
        np.testing.assert_array_equal(a, b)


def test_execute_querybatch_shim_matches_plan_path():
    """Every placement's auto-placed plan reproduces the host plan of the
    same engine bit-identically."""
    batch = QueryBatch(QUERIES, mode="and")
    for name in ("group_simple", "stream_vbyte", "varbyte"):
        idx = InvertedIndex.build(DOCLEN, POSTINGS, codec=name)
        for eng, placement in ((QueryEngine(idx), "host"),
                               (QueryEngine(idx).to_device(), "device"),
                               (QueryEngine(idx).to_device(fused=True),
                                "fused")):
            plan = eng.plan(batch)
            assert plan.placement == placement
            want = eng.execute(eng.plan(batch, placement="host"))
            for a, b in zip(want, eng.execute(plan)):
                np.testing.assert_array_equal(a, b, err_msg=name)


def test_execute_rejects_a_query_batch():
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    for eng in (QueryEngine(idx), QueryEngine(idx).to_device()):
        with pytest.raises(TypeError, match=r"engine\.plan\(batch\)"):
            eng.execute(QueryBatch(QUERIES, mode="and"))


def test_plan_placement_mismatch_raises_clearly():
    """A device/fused plan executed on an engine without the matching arenas
    must fail with a clear error, not deep inside intersection."""
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    fused_plan = QueryEngine(idx).to_device(fused=True).plan(
        QueryBatch(QUERIES[:2], mode="and"))
    with pytest.raises(ValueError, match="to_device"):
        QueryEngine(idx).execute(fused_plan)
    with pytest.raises(ValueError, match="fused"):
        eng = QueryEngine(idx)
        eng.arena = idx.to_device(build_fused=False)
        eng.arena._pk = None
        eng.execute(fused_plan)
    # a host plan on a device engine is fine (host path works everywhere) and
    # stays pinned to host intersection: the fused kernel must not run
    host_plan = QueryEngine(idx).plan(QueryBatch(QUERIES[:2], mode="and"))
    dev = QueryEngine(idx).to_device(fused=True)
    calls0 = dev.arena.stats["fused_calls"]
    for a, b in zip(QueryEngine(idx).execute(host_plan), dev.execute(host_plan)):
        np.testing.assert_array_equal(a, b)
    assert dev.arena.stats["fused_calls"] == calls0
    assert dev._fused          # the engine's own configuration is untouched


def test_mismatched_bp_frame_layout_falls_back_to_host():
    """A bp128-named block at an alien frame size is outside the declared
    ArenaLayout (supports() says no) and must take the host oracle, exactly."""
    from repro.core import bp128 as bp128_lib
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="bp128")
    t = 6                                        # df=1024 -> two bp128 blocks
    first, encg, enct = idx.terms[t].blocks[0]
    gaps = codec.get(encg.codec).decode_np(encg)
    idx.terms[t].blocks[0] = (first, bp128_lib.encode(gaps, frame_quads=64), enct)
    arena = DeviceArena.from_index(idx, build_fused=False)
    assert not arena.covers((t, 0, 0))           # alien layout -> host oracle
    assert arena.covers((t, 1, 0))               # sibling block stays native
    got = arena.decode_blocks([(t, 0, 0), (t, 1, 0)])
    np.testing.assert_array_equal(got[0], idx.decode_block_ids(t, 0))
    np.testing.assert_array_equal(got[1], idx.decode_block_ids(t, 1))
    assert arena.stats["blocks_host"] == 1 and arena.stats["blocks_device"] == 1


def test_device_worklist_decodes_each_hot_block_once():
    """Each distinct (term, block) of a resident round decodes once in that
    round, however many of the batch's queries share it, and a repeated
    batch is served by the round memo without decoding again.  The decodes
    are read off the slots each codec's decode call is handed, not off the
    counters the decode path keeps itself."""
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    eng = QueryEngine(idx).to_device()
    calls = []
    for name, g in eng.arena._groups.items():
        def counted(slots, _name=name, _decode=g.decode_rows):
            calls.append([(_name, int(s)) for s in slots])
            return _decode(slots)
        g.decode_rows = counted
    with eng.metrics.scoped() as first:
        r0 = eng.execute(eng.plan(QueryBatch(QUERIES, mode="and")))
    assert calls
    for c in calls:                     # no slot twice inside one call
        assert len(c) == len(set(c)), c
    lanes = sum(len(c) for c in calls)
    assert first.delta("worklist_decodes") == lanes
    assert first.delta("blocks_device") == lanes
    assert first.delta("blocks_host") == first.delta("fallback_decodes") == 0
    # queries share blocks: more entries are served than blocks decoded
    assert first.delta("rows_gathered") > lanes
    assert first.delta("worklist_refs") >= lanes
    n_calls = len(calls)
    with eng.metrics.scoped() as again:
        r1 = eng.execute(eng.plan(QueryBatch(QUERIES, mode="and")))
    assert again.delta("worklist_decodes") == 0 and len(calls) == n_calls
    ref = QueryEngine(idx)
    want = ref.execute(ref.plan(QueryBatch(QUERIES, mode="and")))
    for w, a, b in zip(want, r0, r1):
        np.testing.assert_array_equal(w, a)
        np.testing.assert_array_equal(w, b)


def test_device_engine_eviction_pressure_stays_exact():
    # a sparse corpus (average docid gap far above the density cutoff) so
    # every block is served through the decode path — dense-bitmap blocks
    # never touch the block cache and would defuse the eviction pressure
    # this test is about
    rng = np.random.default_rng(77)
    n = 60000
    doclen = rng.integers(40, 300, n).astype(np.int64)
    postings = {}
    for t, df in enumerate([900, 1100, 1300, 700]):
        ids = np.sort(rng.choice(n, df, replace=False)).astype(np.uint32)
        postings[t] = (ids, rng.geometric(0.4, df).astype(np.uint32))
    idx = InvertedIndex.build(doclen, postings, codec="bp128")
    host = QueryEngine(idx)
    tiny = QueryEngine(idx, cache_blocks=2, cache_score_terms=1).to_device()
    queries = [[0, 1], [1, 2], [2, 3], [0, 3], [1, 3], [0, 2], [0, 1, 2]]
    want = host.execute(host.plan(QueryBatch(queries, mode="and")))
    got = tiny.execute(tiny.plan(QueryBatch(queries, mode="and")))
    # the resident rounds decode into per-round matrices and leave the block
    # cache alone; the host placement decodes through it and evicts
    assert len(tiny.cache) == 0
    cached = tiny.execute(tiny.plan(QueryBatch(queries, mode="and"),
                                    placement="host"))
    assert tiny.cache.evictions > 0
    for a, b, c in zip(want, got, cached):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_zero_posting_term_and_empty_results_on_device():
    postings = dict(POSTINGS)
    postings[99] = (np.zeros(0, np.uint32), np.zeros(0, np.uint32))
    idx = InvertedIndex.build(DOCLEN, postings, codec="group_simple")
    eng = QueryEngine(idx).to_device(fused=True)
    res = eng.execute(eng.plan(QueryBatch([[99], [99, 0], [NT - 2, NT - 1]],
                                          mode="and")))
    for r in res:
        assert len(r) == 0 and r.dtype == np.uint32 and r.flags.writeable
    assert eng.or_query([99]) == []


def test_term_concat_empty_is_frozen_and_consistent():
    postings = dict(POSTINGS)
    postings[99] = (np.zeros(0, np.uint32), np.zeros(0, np.uint32))
    idx = InvertedIndex.build(DOCLEN, postings, codec="group_simple")
    eng = QueryEngine(idx)
    v = eng.term_ids(99)
    assert len(v) == 0 and v.dtype == np.uint32
    # same contract as every other accessor: cache-backed arrays are frozen
    assert not v.flags.writeable
    assert not eng.term_tfs(99).flags.writeable
    np.testing.assert_array_equal(v, eng.term_ids(99))
    # but and_query results stay caller-owned
    assert eng.and_query([99]).flags.writeable


def test_invalid_mode_raises_on_both_paths():
    """Unknown modes fail with a ValueError that lists MODES and suggests
    the nearest name (the ``codec.get`` convention), on plan and execute."""
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    for eng in (QueryEngine(idx), QueryEngine(idx).to_device()):
        with pytest.raises(ValueError, match="did you mean 'and'"):
            eng.plan(QueryBatch([[0, 1]], mode="And"))
        bad = dataclasses.replace(eng.plan(QueryBatch([[0, 1]] * 2)),
                                  mode="And")
        with pytest.raises(ValueError, match="and, or, and_scored"):
            eng.execute(bad)
    host = QueryEngine(idx)
    bad = dataclasses.replace(host.plan(QueryBatch([[0, 1]])), mode="bm25")
    with pytest.raises(ValueError, match="unknown query mode"):
        host.execute(bad)


def test_fused_arena_buckets_by_block_bit_width():
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    arena = idx.to_device()
    # the corpus mixes dense (df=1024) and sparse (df=64) terms, so blocks
    # must land in more than one width bucket and every block must be covered
    assert len(arena._pk) > 1
    assert set(arena._pk) <= set(decode_fused.BW_BUCKETS)
    covered = set(arena._pk_slot)
    assert covered == {(t, bi) for t in idx.terms
                       for bi in range(idx.n_blocks(t))}


def test_to_device_upgrades_unfused_arena_in_place():
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    a1 = idx.to_device(build_fused=False)
    assert a1._pk is None
    a2 = idx.to_device(build_fused=True)     # cached arena gains fused tiles
    assert a2 is a1 and a1._pk is not None
    eng = QueryEngine(idx).to_device(fused=True)
    # sparse terms only (df 12/63/64): dense-bitmap blocks are served
    # word-parallel and would never reach the fused decode kernel
    eng.execute(eng.plan(QueryBatch([[0, 1], [1, 2], [0, 2], [0, 1, 2]],
                                    mode="and")))
    assert eng.arena.stats["fused_calls"] > 0


def test_to_device_is_cached_and_idempotent():
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    a1 = idx.to_device()
    a2 = idx.to_device()
    assert a1 is a2
    eng = QueryEngine(idx).to_device()
    assert eng.arena is a1
    assert eng.to_device(fused=True) is eng and eng._fused


# --------------------------------------------------------------------------- #
# exception-bearing arena codecs + device-resident rounds
# --------------------------------------------------------------------------- #

EXC_CODECS = ["group_afor", "group_vse", "group_pfd", "group_optpfd"]


def _heavy_corpus():
    """Heavy-tailed postings: big docid-gap outliers drive the PFD family to
    emit non-empty exception streams, and the dfs straddle the 512-posting
    block boundary so frame/exception state crosses blocks."""
    rng = np.random.default_rng(77)
    n_docs = 400_000
    postings = {}
    for t, df in enumerate([511, 512, 513, 1024, 700, 300]):
        gaps = rng.integers(1, 12, df).astype(np.int64)
        gaps[rng.random(df) < 0.02] += rng.integers(1 << 10, 1 << 14)
        ids = np.cumsum(gaps)
        assert ids[-1] < n_docs
        postings[t] = (ids.astype(np.uint32),
                       rng.geometric(0.4, df).astype(np.uint32))
    doclen = np.full(n_docs, 100, np.int64)
    return doclen, postings


HDOCLEN, HPOSTINGS = _heavy_corpus()
HQUERIES = [[0, 1], [1, 2, 3], [0, 3, 4, 5], [2, 4], [3], [5, 1, 0]]


@pytest.mark.parametrize("name", EXC_CODECS)
def test_exception_codecs_decode_natively_no_oracle_fallback(name):
    """Acceptance: the AFOR/PFD/VSE families decode in the device arena with
    no numpy-oracle fallback on their blocks, bit-identical to decode_np."""
    idx = InvertedIndex.build(HDOCLEN, HPOSTINGS, codec=name)
    if name in ("group_pfd", "group_optpfd"):
        # the corpus actually exercises the exception path
        assert any(encg.exceptions is not None and len(encg.exceptions)
                   for tp in idx.terms.values()
                   for _, encg, _ in tp.blocks), "corpus has no exceptions"
    arena = DeviceArena.from_index(idx, build_fused=False)
    entries = [(t, bi, f) for t in idx.terms
               for bi in range(idx.n_blocks(t)) for f in (0, 1)]
    got = arena.decode_blocks(entries)
    for (t, bi, f), a in zip(entries, got):
        want = idx.decode_block_ids(t, bi) if f == 0 else idx.decode_block_tfs(t, bi)
        np.testing.assert_array_equal(a, want, err_msg=f"{name}/{t}/{bi}/{f}")
    assert arena.stats["blocks_host"] == 0
    assert arena.stats["blocks_device"] == len(entries)


@pytest.mark.parametrize("name", EXC_CODECS)
def test_exception_codecs_eviction_and_block_boundary_parity(name):
    """Device engine under pathological cache eviction pressure stays exact
    across the 511/512/513/1024 block boundaries for the new arena codecs."""
    idx = InvertedIndex.build(HDOCLEN, HPOSTINGS, codec=name)
    host = QueryEngine(idx)
    tiny = QueryEngine(idx, cache_blocks=2, cache_score_terms=1).to_device()
    want = host.execute(host.plan(QueryBatch(HQUERIES, mode="and")))
    got = tiny.execute(tiny.plan(QueryBatch(HQUERIES, mode="and")))
    assert len(tiny.cache) == 0         # resident rounds skip the block cache
    cached = tiny.execute(tiny.plan(QueryBatch(HQUERIES, mode="and"),
                                    placement="host"))
    assert tiny.cache.evictions > 0
    for q, a, b, c in zip(HQUERIES, want, got, cached):
        np.testing.assert_array_equal(a, b, err_msg=f"{name}/{q}")
        np.testing.assert_array_equal(a, c, err_msg=f"{name}/{q}")


def test_multi_round_device_and_is_resident_with_zero_cand_syncs():
    """Acceptance: a >= 3-term AND batch executes with zero host candidate
    syncs between rounds, on both device and fused placements, with exact
    result parity against the host placement."""
    queries = [q for q in HQUERIES if len(q) >= 3] * 2
    for name in ("group_pfd", "group_simple"):
        idx = InvertedIndex.build(HDOCLEN, HPOSTINGS, codec=name)
        ref = QueryEngine(idx)
        want = ref.execute(ref.plan(QueryBatch(queries, mode="and")))
        for fused in (False, True):
            eng = QueryEngine(idx).to_device(fused=fused)
            got = eng.execute(eng.plan(QueryBatch(queries, mode="and")))
            for q, a, b in zip(queries, want, got):
                np.testing.assert_array_equal(
                    a, b, err_msg=f"{name}/fused={fused}/{q}")
                assert b.dtype == np.uint32 and b.flags.writeable
            # >= 2 intersect rounds ran device-resident; candidates came
            # back to the host exactly once (the final result copy)
            assert eng.dev_stats["resident_rounds"] >= 2
            assert eng.dev_stats["final_syncs"] == 1
            if fused:
                assert eng.arena.stats["fused_calls"] > 0


def test_plan_auto_places_tiny_batches_on_host():
    """engine.plan() places batches of <= HOST_BATCH_MAX queries on the host
    even when device arenas exist, and records why in the plan's repr."""
    from repro.index.engine import HOST_BATCH_MAX
    idx = InvertedIndex.build(DOCLEN, POSTINGS, codec="group_simple")
    dev = QueryEngine(idx).to_device(fused=True)
    tiny = dev.plan(QueryBatch(QUERIES[:1], mode="and"))
    assert tiny.placement == "host"
    assert "HOST_BATCH_MAX" in tiny.note and tiny.note in repr(tiny)
    big = dev.plan(QueryBatch(QUERIES, mode="and"))
    assert big.placement == "fused" and big.note == ""
    assert len(QUERIES) > HOST_BATCH_MAX
    # the demoted plan still executes correctly on the device engine
    ref = QueryEngine(idx)
    want = ref.execute(ref.plan(QueryBatch(QUERIES[:1], mode="and")))
    for a, b in zip(want, dev.execute(tiny)):
        np.testing.assert_array_equal(a, b)
