"""Latency-governed serving: batcher edge cases, bitwise parity with the
offline plan/execute oracle, per-tenant weighted admission, epoch pinning
across a racing ``compact()``, and auto-placement by batch size alone.

The server runs a real asyncio event loop per test (``asyncio.run`` inside
the sync test body — no plugin dependency); every stream is tiny and seeded,
so the suite stays tier-1 fast."""

import asyncio
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.index.engine import HOST_BATCH_MAX, QueryBatch, QueryEngine
from repro.index.invindex import InvertedIndex
from repro.index.serve import (IndexServer, Rejected, Request, ServeConfig,
                               bursty_offsets, poisson_offsets, serve_stream,
                               tenant_cap, weighted_fill)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(77)
N_DOCS = 2000


def _corpus():
    doclen = RNG.integers(40, 300, N_DOCS).astype(np.int64)
    postings = {}
    for t, df in enumerate([50, 180, 420, 700, 260, 90]):
        ids = np.sort(RNG.choice(N_DOCS, df, replace=False)).astype(np.uint32)
        postings[t] = (ids, RNG.geometric(0.4, df).astype(np.uint32))
    return doclen, postings


DOCLEN, POSTINGS = _corpus()


def _engine(device=False):
    idx = InvertedIndex.build(DOCLEN, POSTINGS)
    eng = QueryEngine(idx)
    return eng.to_device() if device else eng


def _serve(engine, reqs, offsets=None, **cfg_kw):
    cfg_kw.setdefault("max_batch", 4)
    cfg_kw.setdefault("max_wait_ms", 2.0)
    cfg_kw.setdefault("warm_terms", 4)
    if offsets is None:
        offsets = np.zeros(len(reqs))
    return serve_stream(engine, reqs, offsets, ServeConfig(**cfg_kw))


# --------------------------------------------------------------------------- #
# batcher edge cases
# --------------------------------------------------------------------------- #

def test_expired_at_enqueue_is_rejected_immediately():
    results, stats = _serve(_engine(), [Request([0, 1], deadline_ms=0),
                                        Request([0, 1], deadline_ms=-5.0),
                                        Request([0, 1], deadline_ms=500)])
    assert isinstance(results[0], Rejected) and results[0].reason == "expired"
    assert isinstance(results[1], Rejected) and results[1].reason == "expired"
    assert not isinstance(results[2], Rejected)
    assert stats.rejected_expired == 2 and stats.served == 1
    # rejected traces stop at enqueue but still record the outcome
    dead = [tr for tr in stats.traces if tr.outcome == "rejected_expired"]
    assert len(dead) == 2 and all(tr.stages() == (tr.t_enqueue,) for tr in dead)


def test_batch_of_one_bitwise_parity_with_offline_plan():
    engine = _engine()
    results, stats = _serve(engine, [Request([0, 2], deadline_ms=500)])
    assert stats.served == 1 and len(stats.batches) == 1
    b = stats.batches[0]
    assert len(b.queries) == 1
    oracle = engine.execute(engine.plan(
        QueryBatch([list(b.queries[0])], mode=b.mode, k=b.k),
        placement=b.placement))
    assert np.array_equal(np.asarray(results[0]), np.asarray(oracle[0]))


def test_mixed_modes_never_cobatched():
    engine = _engine()
    reqs = [Request([0, 2], mode="and" if i % 2 == 0 else "or",
                    deadline_ms=1000) for i in range(8)]
    results, stats = _serve(engine, reqs, max_batch=8, max_wait_ms=5.0)
    assert stats.served == 8
    assert all(not isinstance(r, Rejected) for r in results)
    # each batch carries exactly one (mode, k); and/or landed in different ones
    modes_by_batch = {b.batch_id: b.mode for b in stats.batches}
    for tr in stats.traces:
        assert modes_by_batch[tr.batch_id] == tr.mode
    assert {b.mode for b in stats.batches} == {"and", "or"}
    # different k never co-batches either
    reqs = [Request([0, 2], k=5 + (i % 2) * 5, mode="or", deadline_ms=1000)
            for i in range(6)]
    _, stats2 = _serve(engine, reqs, max_batch=8, max_wait_ms=5.0)
    assert all(len({tr.k for tr in stats2.traces
                    if tr.batch_id == b.batch_id}) == 1
               for b in stats2.batches)


def test_flush_on_idle_queue_beats_full_deadline():
    """A lone request on an idle queue must flush after ``max_wait_ms``, not
    sit until its (much longer) deadline closes the batch."""
    engine = _engine()
    results, stats = _serve(engine, [Request([0, 1], deadline_ms=10_000)],
                            max_batch=64, max_wait_ms=5.0)
    assert stats.served == 1
    tr = stats.traces[-1]
    # closed by the max_wait flush: far sooner than the 10s deadline
    assert (tr.t_close - tr.t_enqueue) < 1.0
    assert stats.batches[0].queries == (tuple([0, 1]),)


def test_compact_between_plan_and_execute_serves_pinned_epoch():
    """A ``compact()`` landing between plan and execute must not change the
    served results (the plan pins its epoch) and the trace must carry the
    pre-compact epoch key."""
    idx = InvertedIndex.build(DOCLEN, POSTINGS)
    idx.delete(int(POSTINGS[0][0][0]))           # make compaction non-trivial
    engine = QueryEngine(idx)
    oracle_plan = engine.plan(QueryBatch([[0, 2]], mode="and"))
    pinned_key = oracle_plan.ctx.skey
    oracle = engine.execute(oracle_plan)

    server = IndexServer(engine, ServeConfig(max_batch=4, max_wait_ms=2.0,
                                             warm_terms=2))
    compacted = []

    def boom(plan):
        assert plan.ctx.skey == pinned_key
        compacted.append(idx.compact())

    server._after_plan = boom

    async def go():
        await server.start()
        try:
            return await server.submit(Request([0, 2], deadline_ms=2000))
        finally:
            await server.stop()

    got = asyncio.run(go())
    assert compacted and idx.epoch != pinned_key
    assert np.array_equal(np.asarray(got), np.asarray(oracle[0]))
    tr = [t for t in server.stats.traces if t.outcome == "served"][-1]
    assert tr.epoch == pinned_key


def test_queue_full_backpressure_sheds_explicitly():
    engine = _engine()

    async def go():
        server = IndexServer(engine, ServeConfig(queue_cap=3))
        # batcher not started: nothing drains, so the cap must bite
        futs = [server.submit_nowait(Request([0, 1], deadline_ms=1000))
                for _ in range(5)]
        out = [f.result() if f.done() else None for f in futs]
        for f in futs:            # the queued futures never resolve; drop them
            f.cancel()
        return out, server.stats

    out, stats = asyncio.run(go())
    rejected = [r for r in out if isinstance(r, Rejected)]
    assert len(rejected) == 2
    assert all(r.reason == "queue_full" for r in rejected)
    assert stats.rejected_queue_full == 2


# --------------------------------------------------------------------------- #
# per-tenant weighted admission
# --------------------------------------------------------------------------- #

def test_tenant_cap_is_weighted_share():
    assert tenant_cap(100, {}, "anyone") == 100
    assert tenant_cap(90, {"a": 2.0, "b": 1.0}, "a") == 60
    assert tenant_cap(90, {"a": 2.0, "b": 1.0}, "b") == 30
    # unknown tenant weighs 1.0 against the configured total
    assert tenant_cap(80, {"a": 3.0}, "ghost") == 20
    assert tenant_cap(4, {"a": 100.0, "b": 0.001}, "b") >= 1


def test_weighted_fill_is_proportional_and_skips_incompatible():
    queues = {"a": [("and", i) for i in range(8)],
              "b": [("and", 10 + i) for i in range(8)]}
    got = weighted_fill(queues, {"a": 2.0, "b": 1.0},
                        lambda e: e[0] == "and", 6)
    by_tenant = {"a": sum(1 for e in got if e[1] < 10),
                 "b": sum(1 for e in got if e[1] >= 10)}
    assert by_tenant == {"a": 4, "b": 2}
    # an incompatible head must not block a tenant's later compatible entries
    queues = {"a": [("or", 0), ("and", 1)]}
    got = weighted_fill(queues, {}, lambda e: e[0] == "and", 4)
    assert got == [("and", 1)]
    assert queues["a"] == [("or", 0)]


def test_weighted_fill_carries_credit_across_batches():
    credit = {}
    queues = {"a": [1] * 10, "b": [2] * 10}
    first = weighted_fill(queues, {"a": 3.0, "b": 1.0}, lambda e: True, 4,
                          credit)
    second = weighted_fill(queues, {"a": 3.0, "b": 1.0}, lambda e: True, 4,
                           credit)
    both = first + second
    assert both.count(1) == 6 and both.count(2) == 2


# --------------------------------------------------------------------------- #
# auto-placement
# --------------------------------------------------------------------------- #

def test_plan_static_fallback_when_baseline_absent():
    engine = _engine(device=True)
    tiny = engine.plan(QueryBatch([[0, 1]], mode="and"))
    assert tiny.placement == "host"
    assert f"HOST_BATCH_MAX={HOST_BATCH_MAX}" in tiny.note


_PLAN_IN_A_FRESH_PROCESS = textwrap.dedent("""
    import json
    import numpy as np
    from repro.index.engine import HOST_BATCH_MAX, QueryBatch, QueryEngine
    from repro.index.invindex import InvertedIndex
    rng = np.random.default_rng(0)
    postings = {t: (np.sort(rng.choice(500, df, replace=False)).astype(np.uint32),
                    np.ones(df, np.uint32)) for t, df in enumerate([40, 90])}
    idx = InvertedIndex.build(np.full(500, 100, np.int64), postings)
    eng = QueryEngine(idx).to_device()
    print(json.dumps({
        mode: [[p.placement, p.note] for p in (
            eng.plan(QueryBatch([[0, 1]] * n, mode=mode))
            for n in (HOST_BATCH_MAX, HOST_BATCH_MAX + 1))]
        for mode in ("and", "or", "and_scored")}))
    """)


@pytest.fixture(scope="module")
def plans_beside_a_device_winning_baseline(tmp_path_factory):
    """Placements and notes of batches of ``HOST_BATCH_MAX`` and
    ``HOST_BATCH_MAX + 1`` queries per mode, planned in a fresh process
    whose environment names a CPU qps baseline file in which the device
    wins at every batch size (a fresh process, so nothing an earlier test
    loaded can stand in for the file)."""
    curves = {"host": {"1": 1.0, "16": 1.0}, "device": {"1": 99.0, "16": 99.0}}
    path = tmp_path_factory.mktemp("baseline") / "BENCH_query.json"
    path.write_text(json.dumps({
        "backend": "cpu",
        "host_qps": curves["host"], "device_qps": curves["device"],
        "mode_qps": {m: curves for m in ("and", "or", "and_scored")}}))
    env = dict(os.environ, BENCH_QUERY_JSON=str(path), JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", _PLAN_IN_A_FRESH_PROCESS],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mode", ["and", "or", "and_scored"])
def test_plan_places_by_batch_size_alone(plans_beside_a_device_winning_baseline,
                                         mode):
    """Placement reads no file: a qps baseline for the running backend in
    which the device wins at every batch size changes nothing."""
    (small, small_note), (big, big_note) = \
        plans_beside_a_device_winning_baseline[mode]
    assert small == "host"
    assert f"HOST_BATCH_MAX={HOST_BATCH_MAX}" in small_note
    assert big == "device" and big_note == ""


def test_plan_explicit_placement_bypasses_demotion():
    engine = _engine(device=True)
    plan = engine.plan(QueryBatch([[0, 1]], mode="and"), placement="device")
    assert plan.placement == "device" and "pinned by caller" in plan.note
    host_only = _engine(device=False)
    with pytest.raises(ValueError, match="needs device arenas"):
        host_only.plan(QueryBatch([[0, 1]], mode="and"), placement="device")
    with pytest.raises(ValueError, match="fused tile arenas"):
        engine.plan(QueryBatch([[0, 1]], mode="and"), placement="fused")
    with pytest.raises(ValueError, match="unknown placement"):
        engine.plan(QueryBatch([[0, 1]], mode="and"), placement="gpu")


# --------------------------------------------------------------------------- #
# streams, warm-up, stats
# --------------------------------------------------------------------------- #

def test_open_loop_stream_parity_and_stats():
    engine = _engine(device=True)
    n = 16
    reqs = [Request([0, 2] if i % 2 == 0 else [1, 3], deadline_ms=2000,
                    tenant=f"t{i % 2}") for i in range(n)]
    offsets = poisson_offsets(n, rate_qps=2000.0, seed=5)
    results, stats = _serve(engine, reqs, offsets, max_batch=4,
                            max_wait_ms=3.0, tenants={"t0": 1.0, "t1": 2.0})
    assert stats.served == n and stats.shed == 0
    snap = stats.snapshot()
    assert snap["shed_rate"] == 0.0
    assert snap["latency_ms"]["p50"] <= snap["latency_ms"]["p99"] <= \
        snap["latency_ms"]["p999"]
    assert sum(stats.per_tenant[t]["served"] for t in ("t0", "t1")) == n
    assert sum(n_b * size for hist in snap["batch_hist"].values()
               for size, n_b in hist.items()) == n
    # every batch replays bitwise through the offline oracle
    for b in stats.batches:
        oracle = engine.execute(engine.plan(
            QueryBatch([list(q) for q in b.queries], mode=b.mode, k=b.k),
            placement=b.placement))
        for off, rid in zip(oracle, b.rids):
            assert np.array_equal(np.asarray(off), np.asarray(results[rid]))
    # trace stage stamps are monotone (the lint's contract)
    for tr in stats.traces:
        s = tr.stages()
        assert all(a <= b2 for a, b2 in zip(s, s[1:]))


def test_arrival_processes_are_seeded_and_distinct():
    a = poisson_offsets(64, 500.0, seed=9)
    b = poisson_offsets(64, 500.0, seed=9)
    assert np.array_equal(a, b)
    g = bursty_offsets(64, 500.0, seed=9, shape=0.25)
    assert not np.array_equal(a, g)
    # same mean rate, heavier clumping: larger interarrival variance
    assert np.diff(g, prepend=0.0).var() > np.diff(a, prepend=0.0).var()
    assert np.all(np.diff(a) >= 0) and np.all(np.diff(g) >= 0)


def test_warmup_populates_hot_term_score_cache():
    engine = _engine(device=True)
    server = IndexServer(engine, ServeConfig(warm_terms=3, max_batch=2))

    async def go():
        await server.start()
        await server.stop()

    asyncio.run(go())
    assert server.stats.warmup_s > 0.0
    gen = engine.idx.gen
    hot = sorted(gen.terms, key=lambda t: -gen.terms[t].df)[:3]
    skey = engine._cur().skey
    for t in hot:
        assert engine.score_cache.get((t,) + skey) is not None


def test_shed_at_batch_close_when_deadline_passed():
    """A request whose deadline expires while queued is shed with an
    explicit Rejected at batch close, not silently stalled.  With
    ``slack_ms=0`` a lone under-sized batch waits until exactly the seed's
    deadline before closing, so the close stamp lands strictly after the
    deadline and the shed branch must fire."""
    engine = _engine()

    async def go():
        server = IndexServer(engine, ServeConfig(
            max_batch=4, max_wait_ms=1000.0, slack_ms=0.0, warm_terms=2))
        await server.start()
        try:
            return await server.submit(Request([0, 1], deadline_ms=5.0))
        finally:
            await server.stop()

    got = asyncio.run(go())
    assert isinstance(got, Rejected) and got.reason == "deadline"
