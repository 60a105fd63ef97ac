"""The readers of the engine's round spans, compile spans and row and
postings counters, on hand-built run records."""

from __future__ import annotations

import types

import pytest

from chipbench import run, spec, trace_reduce

BENCH = spec.Bench()
ROUND_SPANS = {"round.rows_ms": "round/rows", "round.stack_ms": "round/stack",
               "round.launch_ms": "round/launch"}


def _span(name, t0, t1, sid=0, parent_sid=0):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1, sid=sid,
                                 parent_sid=parent_sid)


def _batch(t_close, t_done):
    return types.SimpleNamespace(t_close=t_close, t_done=t_done)


def _run(spans=(), batches=2, counters=None, compiles=0, trace=None):
    bs = [_batch(i, i + 0.5) for i in range(batches)]
    return run.RunData(seconds=10.0, t0=0.0, records=[], traces=[],
                       batches=bs, spans=list(spans),
                       counters=dict(counters or {}), compiles=compiles,
                       setup_s=1.0, postings=1, index_bytes=0,
                       serving_bytes=0, trace=trace, trace_t0=0.0,
                       trace_t1=1.6)


def test_the_new_metrics_are_declared_for_the_cell():
    names = {m["name"]: m for m in BENCH.doc["per_layer"]}
    for n in (*ROUND_SPANS, "round.row_ops", "compile.ms",
              "decode.mpostings_per_s"):
        assert names[n]["workloads"] == ["gov2.and.saturated"]
        assert names[n]["moves"] == "qps"


@pytest.mark.parametrize("metric", sorted(ROUND_SPANS))
def test_round_span_self_time_per_batch(metric):
    name = ROUND_SPANS[metric]
    spans = [_span(name, 0.0, 1.0, sid=10),
             # children overlap: covered once, 0.2-0.6
             _span("decode/group_simple", 0.2, 0.5, sid=11, parent_sid=10),
             _span("jax/compile", 0.4, 0.6, sid=12, parent_sid=10),
             # a server span whose parent sid equals the round span's
             _span("serve/execute", -1.0, 2.0, sid=3, parent_sid=10),
             _span(name, 2.0, 2.5, sid=20),
             _span("other", 0.0, 3.0, sid=30)]
    read = BENCH.reader(metric)
    # (1.0 - 0.4) + 0.5 seconds over 2 batches
    assert read(_run(spans, batches=2)) == pytest.approx(550.0)
    assert read(_run(spans, batches=4)) == pytest.approx(275.0)
    assert read(_run(spans, batches=0)) is None
    # a program that records no such span
    assert read(_run([s for s in spans if s.name != name])) is None


def test_row_ops_per_batch():
    read = BENCH.reader("round.row_ops")
    c = {"rows_sliced": 30, "rows_stacked": 50}
    assert read(_run(batches=4, counters=c)) == pytest.approx(20.0)
    assert read(_run(batches=4, counters=dict(c, rows_sliced=0))) == 12.5
    assert read(_run(batches=0, counters=c)) is None
    assert read(_run(batches=4, counters={"rows_sliced": 30})) is None
    assert read(_run(batches=4)) is None


def test_compile_ms_counts_nested_spans_once():
    read = BENCH.reader("compile.ms")
    spans = [_span("jax/trace", 0.0, 1.0), _span("jax/trace", 0.2, 0.4),
             _span("jax/lower", 1.0, 1.5), _span("jax/compile", 1.5, 3.0),
             _span("round/launch", 0.0, 9.0)]
    assert read(_run(spans, batches=3, compiles=1)) == pytest.approx(1000.0)
    assert read(_run(batches=3, compiles=0)) == 0.0
    # programs compiled, but the program records no compile span
    assert read(_run(batches=3, compiles=2)) is None
    assert read(_run(spans, batches=0, compiles=1)) is None


def test_decode_rate_from_postings_and_device_time():
    read = BENCH.reader("decode.mpostings_per_s")
    trace = trace_reduce.Summary(
        busy_s=1.0, window_s=2.0, gaps={}, n_chips=1,
        programs={"jit_decode_tiles": 0.002, "jit__decode_worklist": 0.002,
                  "jit_round_accumulate": 5.0})
    c = {"decode_postings": 8_000_000}
    # traced stretch [0, 1.6] holds the batches ending at 0.5 and 1.5 of 4;
    # 2e6 postings per batch over 0.002 device seconds per traced batch
    r = _run(batches=4, counters=c, trace=trace)
    assert len(r.traced_batches()) == 2
    assert read(r) == pytest.approx(1000.0)
    assert read(_run(batches=4, counters=c)) is None            # no trace
    assert read(_run(batches=4, trace=trace)) is None           # no counter
    assert read(_run(batches=0, counters=c, trace=trace)) is None
    no_decode = trace_reduce.Summary(1.0, 2.0, {"jit_round": 1.0}, {}, 1)
    assert read(_run(batches=4, counters=c, trace=no_decode)) is None
