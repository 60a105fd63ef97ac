"""Observability layer (``repro.obs``): span tracer integrity, the Chrome
trace-event export contract, the typed metrics registry (scoped sampling,
read-only ``dev_stats`` view, Prometheus exposition, nearest-rank
percentiles), and the perf-regression gate's self-test guarantees.

The trace-integrity tests drive REAL serve streams (host, device, fused and
a 2-shard engine) and assert the full admission -> done span chain, nesting
discipline, and that the exported JSON round-trips ``json.loads`` with the
documented schema."""

import json
import math

import numpy as np
import pytest

from repro.index.device import _bucket
from repro.index.engine import QueryBatch, QueryEngine
from repro.index.invindex import InvertedIndex
from repro.index.serve import (Request, ServeConfig, ServerStats, TraceRecord,
                               serve_stream)
from repro.obs import (DevStatsView, MetricsRegistry, Span, Tracer,
                       enable_tracing, get_tracer, nearest_rank, regress,
                       to_chrome_trace, trace_coverage)

RNG = np.random.default_rng(91)
N_DOCS = 2500


def _corpus():
    doclen = RNG.integers(40, 300, N_DOCS).astype(np.int64)
    postings = {}
    for t, df in enumerate([60, 200, 450, 800, 300, 120]):
        ids = np.sort(RNG.choice(N_DOCS, df, replace=False)).astype(np.uint32)
        postings[t] = (ids, RNG.geometric(0.4, df).astype(np.uint32))
    return doclen, postings


DOCLEN, POSTINGS = _corpus()


def _engine(device=False, fused=False):
    idx = InvertedIndex.build(DOCLEN, POSTINGS)
    eng = QueryEngine(idx)
    return eng.to_device(fused=fused) if device or fused else eng


def _serve(engine, n=6, **cfg_kw):
    cfg_kw.setdefault("max_batch", 4)
    cfg_kw.setdefault("max_wait_ms", 2.0)
    cfg_kw.setdefault("warm_terms", 4)
    reqs = [Request([t % 4, (t + 1) % 4], deadline_ms=2000) for t in range(n)]
    return serve_stream(engine, reqs, np.zeros(n), ServeConfig(**cfg_kw))


# --------------------------------------------------------------------------- #
# tracer primitives
# --------------------------------------------------------------------------- #

def test_span_nesting_and_monotone_clocks():
    tr = Tracer(enabled=True)
    with tr.span("outer", lane="t") as outer:
        with tr.span("inner", lane="t", r=1) as inner:
            pass
    spans = {s.name: s for s in tr.spans()}
    assert spans["inner"].parent_sid == spans["outer"].sid
    assert spans["outer"].parent_sid == 0
    for s in spans.values():
        assert s.t1 >= s.t0
    # children are bracketed by their parent
    assert spans["outer"].t0 <= spans["inner"].t0
    assert spans["inner"].t1 <= spans["outer"].t1
    assert spans["inner"].args == {"r": 1}
    assert inner.sid != outer.sid


def test_disabled_tracer_is_noop_and_none_safe():
    tr = Tracer(enabled=False)
    with tr.span("x", lane="t") as sp:
        assert sp is None
    sp = tr.begin("y")
    assert sp is None
    tr.end(sp)                      # None-safe
    tr.fence(object())              # no-op when disabled
    assert tr.spans() == []


def test_detached_begin_end_with_explicit_stamps():
    tr = Tracer(enabled=True)
    sp = tr.begin("detached", lane="t", t0=10.0, rid=3)
    assert sp.t1 is None and sp.dur == 0.0
    tr.end(sp, t1=12.5, outcome="done")
    assert (sp.t0, sp.t1) == (10.0, 12.5)
    assert sp.args == {"rid": 3, "outcome": "done"}
    assert tr.spans() == [sp]


def test_span_buffer_bounded():
    tr = Tracer(enabled=True, max_spans=3)
    for i in range(5):
        with tr.span(f"s{i}", lane="t"):
            pass
    assert len(tr.spans()) == 3 and tr.dropped == 2
    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0


def test_global_tracer_toggle():
    tr = get_tracer()
    assert tr.enabled is False      # engine/kernel spans off by default
    enable_tracing(True)
    try:
        assert get_tracer().enabled is True
    finally:
        enable_tracing(False)
        get_tracer().clear()


# --------------------------------------------------------------------------- #
# chrome trace export (documented schema)
# --------------------------------------------------------------------------- #

def test_chrome_trace_round_trips_with_schema():
    tr = Tracer(enabled=True)
    with tr.span("serve/batch", lane="serve", nq=2):
        with tr.span("serve/plan", lane="serve"):
            pass
    blob = json.dumps(to_chrome_trace(tr))
    doc = json.loads(blob)
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in meta} == {"process_name", "thread_name"}
    assert len(spans) == 2
    for e in spans:
        assert e["pid"] == 1 and e["tid"] >= 1
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["cat"] == e["name"].split("/", 1)[0]
        assert {"sid", "parent_sid"} <= set(e["args"])
    by_name = {e["name"]: e for e in spans}
    assert (by_name["serve/plan"]["args"]["parent_sid"]
            == by_name["serve/batch"]["args"]["sid"])
    # lane -> named thread track
    lanes = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert lanes == {"serve"}


def test_chrome_trace_merges_multiple_sources():
    a, b = Tracer(enabled=True), Tracer(enabled=True)
    with a.span("x", lane="la"):
        pass
    with b.span("y", lane="lb"):
        pass
    doc = to_chrome_trace(a, b)
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert names == {"x", "y"}


def test_trace_coverage_math():
    tr = Tracer(enabled=True)
    b = tr.begin("serve/batch", lane="serve", t0=0.0)
    tr.end(b, t1=10.0)
    c = tr.begin("serve/plan", lane="serve", parent=b, t0=0.0)
    tr.end(c, t1=4.0)
    assert trace_coverage(tr.spans()) == pytest.approx(0.4)
    # unrelated spans don't count
    d = tr.begin("serve/plan", lane="serve", t0=0.0)     # no parent
    tr.end(d, t1=10.0)
    assert trace_coverage(tr.spans()) == pytest.approx(0.4)


# --------------------------------------------------------------------------- #
# trace integrity on real serve streams
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("placement", ["host", "device", "fused"])
def test_full_span_chain_per_placement(placement):
    engine = _engine(device=True, fused=(placement == "fused"))
    results, stats = _serve(engine, n=6, placement=placement)
    assert stats.served == 6
    spans = stats.tracer.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    # the full chain: every request spans admission -> done; every batch has
    # plan/execute/deliver children that tile it exactly
    assert len(by_name["serve/request"]) == 6
    assert len(by_name["serve/batch"]) == len(stats.batches)
    batches = {s.sid: s for s in by_name["serve/batch"]}
    for child in ("serve/plan", "serve/execute", "serve/deliver"):
        assert {c.parent_sid for c in by_name[child]} == set(batches)
    assert trace_coverage(spans) >= 0.9
    # TraceRecord stamps are a view over the same spans
    req = {s.args["rid"]: s for s in by_name["serve/request"]}
    for tr in stats.traces:
        assert tr.outcome == "served"
        s = req[tr.rid]
        assert s.t0 == tr.t_enqueue and s.t1 == tr.t_done
        assert s.args["outcome"] == "served"
        stamps = tr.stages()
        assert all(b >= a for a, b in zip(stamps, stamps[1:]))
    for b in stats.batches:
        bs = next(s for s in by_name["serve/batch"]
                  if s.args["bid"] == b.batch_id)
        assert bs.t0 == b.t_close and bs.t1 == b.t_done


def test_span_chain_two_shard_engine():
    engine = _engine()
    # explicit bounds: derived mass-balanced splits collapse to one shard
    # on a corpus this small
    engine.to_device(fused=True, bounds=(0, N_DOCS // 2, N_DOCS))
    enable_tracing(True)
    try:
        get_tracer().clear()
        results, stats = _serve(engine, n=4, placement="device")
        deep = get_tracer().spans()
    finally:
        enable_tracing(False)
        get_tracer().clear()
    assert stats.served == 4
    lanes = {s.lane for s in deep}
    assert {"shard0", "shard1"} <= lanes
    # the export merges server + engine tracers and keeps one track per lane
    doc = to_chrome_trace(stats.tracer, deep)
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"serve", "shard0", "shard1"} <= tracks
    json.loads(json.dumps(doc))


def test_rejected_and_shed_requests_close_their_spans():
    engine = _engine()
    reqs = [Request([0, 1], deadline_ms=0),          # rejected at enqueue
            Request([0, 1], deadline_ms=2000)]
    results, stats = serve_stream(
        engine, reqs, np.zeros(2),
        ServeConfig(max_batch=4, max_wait_ms=2.0, warm_terms=2))
    outcomes = {s.args["rid"]: s.args["outcome"]
                for s in stats.tracer.spans() if s.name == "serve/request"}
    assert outcomes[0] == "rejected_expired"
    assert outcomes[1] == "served"
    assert all(s.t1 is not None for s in stats.tracer.spans())


def test_engine_spans_disabled_by_default():
    engine = _engine(device=True)
    get_tracer().clear()
    engine.execute(engine.plan(QueryBatch([[0, 1]]), placement="device"))
    assert get_tracer().spans() == []


# --------------------------------------------------------------------------- #
# compile spans, round spans and round counters
# --------------------------------------------------------------------------- #

def _obs_listeners() -> list:
    from jax._src import monitoring
    return [cb for cb in monitoring.get_event_duration_listeners()
            if getattr(cb, "__module__", "") == "repro.obs.trace"]


def _fresh_program(c: int):
    """A jitted function JAX has not compiled before (a new constant)."""
    import jax
    return jax.jit(lambda x: x * c + 1)


def test_compile_spans_nest_under_the_open_span():
    import jax.numpy as jnp
    tr = enable_tracing(True)
    try:
        tr.clear()
        with tr.span("outer", lane="engine") as outer:
            _fresh_program(7919)(jnp.arange(5)).block_until_ready()
        spans = tr.spans()
    finally:
        enable_tracing(False)
        get_tracer().clear()
    jax_spans = [s for s in spans if s.name.startswith("jax/")]
    assert {"jax/trace", "jax/lower", "jax/compile"} <= {
        s.name for s in jax_spans}
    for s in jax_spans:
        assert s.parent_sid == outer.sid and s.lane == "engine"
        assert outer.t0 <= s.t0 <= s.t1 <= outer.t1
        assert "fun_name" in s.args
    assert any(s.args["fun_name"] == "<lambda>" for s in jax_spans
               if s.name == "jax/trace")


def test_compile_listener_registered_only_while_tracing():
    import jax.numpy as jnp
    assert _obs_listeners() == []
    get_tracer().clear()
    _fresh_program(7927)(jnp.arange(5)).block_until_ready()
    assert get_tracer().spans() == []
    enable_tracing(True)
    enable_tracing(True)                # enabling twice registers once
    try:
        assert len(_obs_listeners()) == 1
    finally:
        enable_tracing(False)
        get_tracer().clear()
    assert _obs_listeners() == []


def _round_spans(spans) -> dict:
    by_sid = {s.sid: s for s in spans}
    out: dict = {}
    for s in spans:
        if s.name.startswith("round/"):
            out.setdefault(s.name, []).append(by_sid[s.parent_sid].name)
    return out


@pytest.mark.parametrize("fused", [False, True])
def test_round_spans_and_counters_on_the_and_path(fused):
    engine = _engine(device=True, fused=fused)
    idx, ar = engine.idx, engine.arena
    placement = "fused" if fused else "device"
    tr = enable_tracing(True)
    try:
        # single-term queries run the seed round alone, on the arena decode:
        # its work-list is every block of the distinct terms
        tr.clear()
        with engine.metrics.scoped() as s:
            engine.execute(engine.plan(QueryBatch([[0], [1], [0], [5], [2]]),
                                       placement=placement))
        seed_spans = tr.spans()
        # probe rounds over sparse blocks only (terms 2 and 3 are dense)
        tr.clear()
        with engine.metrics.scoped() as m:
            engine.execute(engine.plan(
                QueryBatch([[0, 1, 4], [5, 4], [1, 5]]), placement=placement))
        spans = tr.spans()
    finally:
        enable_tracing(False)
        get_tracer().clear()
    sparse = [(t, bi) for t in (0, 1, 5) for bi in range(idx.n_blocks(t))]
    assert not any(b in ar.dense_slot for b in sparse)
    assert s.delta("decode_postings") == sum(
        idx.terms[t].blocks[bi][1].n for t, bi in sparse)
    # the seed's four sparse entries reach the kernel by row index into one
    # decoded matrix per codec (term 0 is a short list, the others are not):
    # nothing is sliced or stacked one row at a time, and each matrix's
    # bucket of 8 pads six lanes
    assert s.delta("device_calls") == 2
    assert s.delta("rows_sliced") == 0
    assert s.delta("rows_stacked") == 0
    assert s.delta("rows_gathered") == 4
    assert s.delta("rows_padded") == 12
    rows = [sp for sp in seed_spans if sp.name == "round/rows"]
    assert [sp.args for sp in rows] == [{"entries": 4, "decoded": 3}]
    parents = _round_spans(seed_spans)
    assert parents["round/rows"] == ["and/seed"]
    assert parents["round/launch"] == ["and/seed"]
    assert sorted(parents["round/stack"]) == ["and/seed", "and/seed"]
    # probe rounds: every round span nests under a round, and the counters
    # add up what the spans say each call handled
    parents = _round_spans(spans)
    assert set(parents) == {"round/rows", "round/stack", "round/launch"}
    assert set(parents["round/launch"]) == {"and/seed", "and/round"}
    assert set(parents["round/rows"] + parents["round/stack"]) <= {
        "and/seed", "and/round"}
    decoded = [sp for sp in spans if sp.name.startswith("decode/")]
    assert m.delta("decode_postings") == sum(sp.args["postings"]
                                             for sp in decoded) > 0
    assert m.delta("rows_sliced") == 0 and m.delta("rows_stacked") == 0
    stacks = [sp for sp in spans if sp.name == "round/stack"]
    assert m.delta("rows_gathered") == sum(sp.args["rows"] for sp in stacks)
    fused_spans = [sp for sp in decoded if sp.name == "decode/fused"]
    # padding: the sparse sources' buckets, then each fused bit-width part
    # padded to its own bucket
    assert m.delta("rows_padded") == (
        sum(sp.args["bucket"] - sp.args["rows"] for sp in stacks)
        + sum(_bucket(sp.args["blocks"]) - sp.args["blocks"]
              for sp in fused_spans))
    assert bool(fused_spans) == fused
    # fused rounds decode every entry, repeats included: (1, 0) twice and
    # (4, 0) in round 1, (4, 0) in round 2
    if fused:
        n = {t: idx.terms[t].blocks[0][1].n for t in (1, 4)}
        assert sum(sp.args["postings"] for sp in fused_spans) == (
            2 * n[1] + 2 * n[4])
        assert all(set(sp.args) == {"bw", "blocks", "postings"}
                   for sp in fused_spans)


def test_round_spans_nest_under_ranked_rounds():
    engine = _engine(device=True)
    queries = [[0, 1], [2, 3, 4], [5, 1]]
    tr = enable_tracing(True)
    try:
        for mode in ("and_scored", "or"):
            tr.clear()
            engine._round_cache.clear()
            engine.cache = type(engine.cache)(4096)
            engine.execute(engine.plan(QueryBatch(queries, mode=mode, k=5),
                                       placement="device"))
            parents = _round_spans(tr.spans())
            want = {"ranked/round"} | ({"and/seed", "and/round"}
                                       if mode == "and_scored" else set())
            assert set(parents["round/launch"]) == want, mode
            assert set(parents["round/rows"] + parents["round/stack"]) <= want
            if mode == "or":        # and_scored reuses the AND rounds' rows
                assert "ranked/round" in parents["round/rows"]
                assert "ranked/round" in parents["round/stack"]
            ranked = [sp for sp in tr.spans() if sp.name == "ranked/round"]
            assert ranked and all({"r", "mode", "plain", "fused", "dense"}
                                  <= set(sp.args) for sp in ranked)
    finally:
        enable_tracing(False)
        get_tracer().clear()


def test_tracing_off_adds_no_listener_and_no_host_copy(monkeypatch):
    """The resident AND path copies one array to the host per batch (the
    final bitmaps), with tracing off as on, and tracing off leaves no
    ``jax.monitoring`` listener of ``repro.obs`` behind."""
    import jax
    from jax._src import array as jax_array
    from repro.index import device as device_mod
    from repro.index import engine as engine_mod

    copies = []

    class _CountingNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(a, *k, **kw):
            copies.append(isinstance(a, jax.Array))
            return np.asarray(a, *k, **kw)

        @staticmethod
        def array(a, *k, **kw):
            copies.append(isinstance(a, jax.Array))
            return np.array(a, *k, **kw)

    value = jax_array.ArrayImpl._value

    def counted_value(self):
        copies.append(True)
        return value.fget(self)

    engine = _engine(device=True, fused=True)
    batch = QueryBatch([[0, 1, 2], [5, 3], [4, 2], [1]])
    engine.execute(engine.plan(batch, placement="fused"))     # compile
    monkeypatch.setattr(engine_mod, "np", _CountingNumpy())
    monkeypatch.setattr(device_mod, "np", _CountingNumpy())
    monkeypatch.setattr(jax_array.ArrayImpl, "_value",
                        property(counted_value))
    for on in (False, True):
        enable_tracing(on)
        try:
            assert len(_obs_listeners()) == int(on)
            copies.clear()
            engine._round_cache.clear()
            engine.cache = type(engine.cache)(4096)
            with engine.metrics.scoped() as s:
                engine.execute(engine.plan(batch, placement="fused"))
            assert sum(copies) == 1, on
            assert s.delta("final_syncs") == 1 and s.delta("cand_syncs") == 0
        finally:
            enable_tracing(False)
            get_tracer().clear()
    assert _obs_listeners() == []


# --------------------------------------------------------------------------- #
# metrics registry
# --------------------------------------------------------------------------- #

def test_registry_duplicate_and_label_vocabulary():
    reg = MetricsRegistry(namespace="t")
    reg.counter("a_counter")
    with pytest.raises(ValueError):
        reg.counter("a_counter")
    with pytest.raises(ValueError):
        reg.counter("bad", labelnames=("nope",))
    with pytest.raises(ValueError):
        MetricsRegistry(const_labels={"nope": "x"})
    with pytest.raises(ValueError):
        reg.get("a_counter").inc(-1)


def test_scoped_sampling_deltas():
    eng = _engine(device=True)
    eng.execute(eng.plan(QueryBatch([[0, 1]]), placement="device"))
    with eng.metrics.scoped() as s:
        eng.execute(eng.plan(QueryBatch([[0, 1]]), placement="device"))
    # the work-list decode already happened in the priming batch: the scoped
    # delta isolates the second batch without hand-rolled subtraction
    assert s.delta("worklist_decodes") == 0
    assert s.delta("resident_rounds") >= 1
    with pytest.raises(KeyError):
        s.delta("no_such_counter")
    assert s.deltas()["final_syncs"] == 1


def test_dev_stats_view_read_only_live():
    eng = _engine(device=True)
    assert eng.dev_stats["worklist_decodes"] == 0
    eng.execute(eng.plan(QueryBatch([[0, 1]]), placement="device"))
    assert eng.dev_stats["worklist_decodes"] >= 1
    assert set(eng.dev_stats) == set(dict(eng.dev_stats))
    with pytest.raises(TypeError):
        eng.dev_stats["worklist_decodes"] = 0
    with pytest.raises(KeyError):
        eng.dev_stats["not_a_counter"]
    assert isinstance(eng.dev_stats, DevStatsView)


def test_prometheus_exposition_format():
    reg = MetricsRegistry(namespace="t", const_labels={"engine": "q0"})
    reg.counter("reqs", "requests", labelnames=("outcome",))
    reg.inc("reqs", outcome="served")
    reg.inc("reqs", 2, outcome="shed")
    reg.gauge("warm", "warmup").set(1.5)
    reg.histogram("lat", "latency", buckets=(1.0, 10.0, float("inf")))
    reg.get("lat").observe(0.5)
    reg.get("lat").observe(5.0)
    text = reg.to_prometheus()
    assert "# TYPE t_reqs counter" in text
    assert 't_reqs{engine="q0",outcome="served"} 1' in text
    assert 't_reqs{engine="q0",outcome="shed"} 2' in text
    assert 't_warm{engine="q0"} 1.5' in text
    assert 't_lat_bucket{engine="q0",le="1"} 1' in text
    assert 't_lat_bucket{engine="q0",le="10"} 2' in text
    assert 't_lat_bucket{engine="q0",le="+Inf"} 2' in text
    assert 't_lat_count{engine="q0"} 2' in text


def test_server_stats_prometheus_snapshot():
    results, stats = _serve(_engine(), n=3)
    snap = stats.snapshot(prometheus=True)
    assert "repro_serve_requests_total" in snap["prometheus"]
    assert 'outcome="served"' in snap["prometheus"]
    assert "prometheus" not in stats.snapshot()     # opt-in only


def test_engine_registries_independent_and_labelled():
    a, b = _engine(), _engine()
    a.metrics.inc("worklist_refs", 5)
    assert b.dev_stats["worklist_refs"] == 0
    assert a.metrics.const_labels["engine"] != b.metrics.const_labels["engine"]
    assert a.metrics.schema() == b.metrics.schema()


# --------------------------------------------------------------------------- #
# nearest-rank percentiles
# --------------------------------------------------------------------------- #

def test_nearest_rank_rule():
    # n == 1: the single sample for every q
    assert nearest_rank([7.0], 50) == 7.0
    assert nearest_rank([7.0], 99.9) == 7.0
    # n == 2: p50 -> first, p99/p999 -> second; monotone in q
    assert nearest_rank([1.0, 9.0], 50) == 1.0
    assert nearest_rank([1.0, 9.0], 99) == 9.0
    assert nearest_rank([1.0, 9.0], 99.9) == 9.0
    # n == 10: ceil(q/100 * 10) ranks, never interpolated
    vals = [float(i) for i in range(1, 11)]
    assert nearest_rank(vals, 50) == 5.0
    assert nearest_rank(vals, 99) == 10.0
    assert nearest_rank(vals, 10) == 1.0
    assert nearest_rank(vals, 100) == 10.0
    qs = [1, 10, 50, 90, 99, 99.9]
    got = [nearest_rank(vals, q) for q in qs]
    assert got == sorted(got)
    with pytest.raises(ValueError):
        nearest_rank([], 50)


def test_snapshot_percentiles_tiny_n():
    for n in (1, 2, 10):
        stats = ServerStats()
        for i in range(n):
            stats.record(TraceRecord(
                i, "t", "and", 10, "served", deadline=1e9,
                t_enqueue=0.0, t_close=0.0, t_plan=0.0, t_execute=0.0,
                t_done=(i + 1) * 1e-3, on_time=True))
        lat = sorted((i + 1.0) for i in range(n))
        pct = stats.snapshot()["latency_ms"]
        for name, q in (("p50", 50.0), ("p99", 99.0), ("p999", 99.9)):
            r = min(max(math.ceil(q / 100.0 * n), 1), n)
            assert pct[name] == pytest.approx(lat[r - 1])
        assert pct["p50"] <= pct["p99"] <= pct["p999"] == pct["max"]


# --------------------------------------------------------------------------- #
# the regression gate
# --------------------------------------------------------------------------- #

_QUERY_REPORT = {
    "dataset": "gov2", "codec": "group_simple", "backend": "cpu",
    "n_queries": 20,
    "host_qps": {"1": 100.0, "16": 400.0},
    "decodes_per_hot_block": 1.0,
    "placements": {"device": {"host_syncs_per_query": 0},
                   "fused": {"host_syncs_per_query": 0}},
    "ranked": {"or": {"qps": {"host": 50.0}, "host_syncs_per_query": 0,
                      "blocks_pruned": 12}},
}


def test_gate_identity_passes_and_2x_regression_fails():
    tol = regress.load_tolerances(None)
    v, n = regress.compare_reports("query", _QUERY_REPORT, _QUERY_REPORT, tol)
    assert not v and n == 3          # host_qps x2 + ranked or qps
    bad = regress.synthesize_regression(_QUERY_REPORT, factor=0.5)
    assert bad["host_qps"]["1"] == 50.0
    assert bad["decodes_per_hot_block"] == 1.0       # non-qps leaf untouched
    assert bad["ranked"]["or"]["blocks_pruned"] == 12
    v, _ = regress.compare_reports("query", bad, _QUERY_REPORT, tol)
    assert len(v) == 3 and all(x.kind == "ratio" for x in v)


def test_gate_min_ratio_override_and_disable():
    tol = {"defaults": {"min_ratio": 0.55},
           "overrides": [{"artifact": "query", "pattern": "host_qps.*",
                          "min_ratio": 0}]}
    bad = regress.synthesize_regression(_QUERY_REPORT, factor=0.5)
    v, n = regress.compare_reports("query", bad, _QUERY_REPORT, tol)
    paths = {x.path for x in v}
    assert paths == {"ranked.or.qps.host"}           # host_qps ungated
    assert n == 1


def test_gate_workload_stamp_mismatch_refuses():
    other = dict(_QUERY_REPORT, n_queries=256)
    v = regress.check_workload(
        "query", ("dataset", "codec", "backend", "n_queries"),
        other, _QUERY_REPORT)
    assert len(v) == 1 and v[0].kind == "workload" and v[0].path == "n_queries"


def test_gate_hard_invariants():
    ok, n = regress.check_invariants("query", _QUERY_REPORT)
    assert not ok and n >= 4
    broken = json.loads(json.dumps(_QUERY_REPORT))
    broken["placements"]["device"]["host_syncs_per_query"] = 3
    broken["ranked"]["or"]["blocks_pruned"] = 0
    v, _ = regress.check_invariants("query", broken)
    assert {x.path for x in v} == {"placements.device.host_syncs_per_query",
                                   "ranked.or.blocks_pruned"}
    mut = {"tombstone_qps": {"0.01": {"cand_syncs": 0, "qps": 5.0}},
           "ranked_tomb_1pct": {"score_syncs": 0, "blocks_pruned": 3}}
    v, _ = regress.check_invariants("mutation", mut)
    assert not v
    mut["ranked_tomb_1pct"]["blocks_pruned"] = 0
    v, _ = regress.check_invariants("mutation", mut)
    assert [x.path for x in v] == ["ranked_tomb_1pct.blocks_pruned"]
    srv = {"arrivals": {"poisson": {"host": {"shed_rate": 0.0,
                                             "parity_ok": True}},
                        "bursty": {"host": {"shed_rate": 0.25,
                                            "parity_ok": False}}}}
    v, _ = regress.check_invariants("serving", srv)
    # bursty shed is allowed (overload by design); bursty parity is not
    assert [x.path for x in v] == ["arrivals.bursty.host.parity_ok"]


def test_gate_missing_fresh_report_is_a_violation(tmp_path):
    base = tmp_path / "base"
    fresh = tmp_path / "fresh"
    base.mkdir()
    fresh.mkdir()
    (base / "BENCH_query.json").write_text(json.dumps(_QUERY_REPORT))
    res = regress.run_gate(str(fresh), str(base))
    assert not res.passed
    assert res.violations[0].kind == "workload"
    # with the fresh report present, identity passes end to end
    (fresh / "BENCH_query.json").write_text(json.dumps(_QUERY_REPORT))
    res = regress.run_gate(str(fresh), str(base))
    assert res.passed and res.checked_ratios == 3


def test_committed_tolerances_keep_selftest_teeth():
    """The committed floors must stay in (0.5, 1.0] or the CI self-test's
    synthetic 2x regression would slip through."""
    import os
    tol = regress.load_tolerances(
        os.path.join(os.path.dirname(__file__), "..",
                     regress.TOLERANCES_FILE))
    floors = [float(tol["defaults"]["min_ratio"])]
    floors += [float(ov["min_ratio"]) for ov in tol["overrides"]
               if float(ov.get("min_ratio", 1)) > 0]
    assert all(0.5 < f <= 1.0 for f in floors), floors
