"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

* busy: the union of the intervals in which an operation ran on a TPU
  (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), clipped to the
  window and averaged over the chips;
* device time per jitted program (line ``XLA Modules``), by program name
  with the trailing ``(<id>)`` dropped;
* idle gaps: the complement of busy inside the window, each labelled with
  the innermost host span (server and engine spans, ``time.monotonic``)
  open at the gap's midpoint, summed per label.

Host spans are put on the trace's clock by one marker: the harness opens a
``jax.profiler.TraceAnnotation`` named ``chipbench/sync`` right after it
reads ``time.monotonic()``; the marker's start in the trace minus that
reading is the offset between the two clocks.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
IDLE_HOST = "no host span open"
# per-request spans cover queueing, not what the host is doing
SKIP_SPANS = ("serve/request",)
TOP = 10


@dataclasses.dataclass
class Summary:
    busy_s: float                   # mean over chips of the busy union
    window_s: float
    programs: dict                  # program name -> device seconds
    gaps: dict                      # host label -> idle seconds
    n_chips: int

    def breakdown(self) -> dict:
        ops = sorted(self.programs.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def latest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def program_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted ``(n, 2)`` intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[last]], axis=1)


def _clip(iv: np.ndarray, w0: float, w1: float) -> np.ndarray:
    iv = np.clip(iv, w0, w1)
    return iv[iv[:, 1] > iv[:, 0]]


def read_planes(pd, mark: str):
    """``(ops, modules, mark_ns)``: per TPU plane an ``(n, 2)`` array of op
    intervals in ns; ``(name, start_ns, end_ns)`` of every program run; and
    the start of the first host event named ``mark`` (None if there is
    none).  A host line is read only up to its marker."""
    ops, modules, mark_ns = [], [], None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            iv, mod_iv = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    iv += [(e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        modules.append((program_name(e.name), e.start_ns,
                                        e.start_ns + e.duration_ns))
                        mod_iv.append((e.start_ns,
                                       e.start_ns + e.duration_ns))
            ops.append(np.asarray(iv or mod_iv, np.float64).reshape(-1, 2))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == mark:
                        if mark_ns is None or e.start_ns < mark_ns:
                            mark_ns = e.start_ns
                        break
    return ops, modules, mark_ns


def reduce(pd, sync_mono: float, t0_mono: float, t1_mono: float,
           spans, mark: str = "chipbench/sync") -> Summary:
    ops, modules, mark_ns = read_planes(pd, mark)
    if not ops:
        raise ValueError("the trace holds no TPU plane; planes: "
                         f"{[p.name for p in pd.planes]}")
    if mark_ns is None:
        raise ValueError(f"the trace holds no {mark!r} marker")
    off = mark_ns - sync_mono * 1e9        # trace ns - monotonic ns
    w0, w1 = t0_mono * 1e9 + off, t1_mono * 1e9 + off
    busy = []
    merged0 = None
    for iv in ops:
        m = union(_clip(iv, w0, w1))
        busy.append(float((m[:, 1] - m[:, 0]).sum()))
        if merged0 is None:
            merged0 = m
    programs: dict = {}
    for name, a, b in modules:
        d = min(b, w1) - max(a, w0)
        if d > 0:
            programs[name] = programs.get(name, 0.0) + d / 1e9
    # idle gaps of the first chip, labelled by host spans
    edges = np.concatenate([[w0], merged0.reshape(-1), [w1]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    labels = _label(gaps, spans, off)
    by_label: dict = {}
    for (a, b), lab in zip(gaps, labels):
        by_label[lab] = by_label.get(lab, 0.0) + (b - a) / 1e9
    return Summary(busy_s=sum(busy) / len(busy) / 1e9,
                   window_s=(w1 - w0) / 1e9, programs=programs,
                   gaps=by_label, n_chips=len(ops))


def _label(gaps: np.ndarray, spans, off: float) -> list:
    """The innermost host span open at each gap's midpoint."""
    mids = (gaps[:, 0] + gaps[:, 1]) / 2
    order = np.argsort(mids)
    smid = mids[order]
    owner = np.full(len(mids), -1)
    span_iv = [(s.t0 * 1e9 + off, s.t1 * 1e9 + off, s.name) for s in spans
               if s.t1 is not None and s.name not in SKIP_SPANS]
    # longest first, so the innermost (shortest) open span is written last
    span_iv.sort(key=lambda x: -(x[1] - x[0]))
    for j, (a, b, _) in enumerate(span_iv):
        i0, i1 = np.searchsorted(smid, a), np.searchsorted(smid, b)
        owner[i0:i1] = j
    out = [IDLE_HOST] * len(mids)
    for pos, j in zip(order, owner):
        if j >= 0:
            out[pos] = span_iv[j][2]
    return out


def reduce_dir(trace_dir: str, sync_mono: float, t0_mono: float,
               t1_mono: float, spans) -> Summary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(latest_xplane(trace_dir)),
                  sync_mono, t0_mono, t1_mono, spans)
