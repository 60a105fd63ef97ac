"""The trace reduction: interval union, clock alignment, gap labels, and a
small trace recorded on a TPU v5e (``chipbench/testdata``)."""

from __future__ import annotations

import json
import os
import types

import numpy as np
import pytest

from chipbench import trace_reduce
from conftest import ROOT

DATA = os.path.join(ROOT, "chipbench", "testdata")


def test_union_merges_overlaps_and_keeps_gaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [12, 13]], float)
    assert trace_reduce.union(iv).tolist() == [[0, 3], [5, 9], [12, 13]]
    assert trace_reduce.union(np.zeros((0, 2))).shape == (0, 2)


def _event(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def _span(name, t0, t1):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1)


def test_reduce_aligns_host_spans_and_labels_gaps():
    # trace clock = monotonic ns - 1e9 + 500; window [1.0, 1.0 + 1e-6] s
    off = 500 - 1e9
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        _line("XLA Ops", [_event("fusion", 600, 100), _event("add", 650, 100),
                          _event("copy", 1200, 100)]),
        _line("XLA Modules", [_event("jit_decode_tiles(17)", 600, 150),
                              _event("jit_round(3)", 1200, 100)])])
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        _line("python3", [_event("chipbench/sync", 500, 10)])])
    pd = types.SimpleNamespace(planes=[host, dev])
    t = lambda ns: (ns - off) / 1e9        # trace ns -> monotonic s
    spans = [_span("serve/execute", t(700), t(1350)),
             _span("kernel/extract_ids", t(900), t(1150)),
             _span("serve/request", t(0), t(5000))]
    s = trace_reduce.reduce(pd, sync_mono=1.0, t0_mono=t(500),
                            t1_mono=t(1500), spans=spans)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(250e-9)
    assert s.programs == pytest.approx({"jit_decode_tiles": 150e-9,
                                        "jit_round": 100e-9})
    # gaps: [500,600) before any span, [750,1200) inside extract_ids at
    # its midpoint 975, [1300,1500) mid 1400 after the execute span
    assert s.gaps == pytest.approx({trace_reduce.IDLE_HOST: 100e-9 + 200e-9,
                                    "kernel/extract_ids": 450e-9})
    bd = s.breakdown()
    assert bd["device_ops"][0] == ["jit_decode_tiles", pytest.approx(150e-9)]


def test_a_recorded_chip_trace_reduces_to_what_the_run_reported():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "trace.json")) as f:
        side = json.load(f)
    pd = ProfileData.from_file(os.path.join(DATA, "trace.xplane.pb"))
    spans = [_span(n, a, b) for n, a, b in side["spans"]]
    s = trace_reduce.reduce(pd, side["sync_mono"], side["t0"], side["t1"],
                            spans)
    want = side["summary"]
    assert s.window_s == pytest.approx(want["window_s"])
    assert s.busy_s == pytest.approx(want["busy_s"])
    assert 0 < s.busy_s < s.window_s
    assert s.programs == pytest.approx(want["programs"])
    assert s.gaps == pytest.approx(want["gaps"])
    assert sum(s.gaps.values()) == pytest.approx(s.window_s - s.busy_s)
