"""Streaming serving benchmark: open-loop latency/goodput/shed under the
dynamic batcher (``repro.index.serve``), per arrival process and placement.

Offline qps (``bench_query.py``) measures how fast the engine chews a batch
it was handed; this harness measures what a request *stream* experiences:
requests arrive on an open-loop clock (arrivals never wait for responses),
the :class:`~repro.index.serve.IndexServer` forms batches under a
deadline-or-size policy, and every request's five-stage trace is recorded.
Two arrival processes at the same mean rate — Poisson (exponential
interarrivals) and bursty (Gamma interarrivals, shape < 1, so the same load
clumps) — cross ≥ 2 placements (host / device, plus fused when arenas carry
tiles), and each cell reports p50/p99/p999 latency, goodput (on-time served
qps), shed rate, and the achieved batch-size histogram.

Every cell is also *audited*: each batch the server formed is replayed
through the host numpy oracle (``plan(..., placement="host")``) and the
served results must be bitwise identical (``parity_ok``).  Under the
Poisson smoke load the shed rate must be exactly 0 — the CI-tracked
guarantee that admission + batching never drops a request the engine had
budget for.

Arrivals, corpus, and query workload all come from fixed RNG seeds, so two
runs measure the identical stream (timings vary, the workload does not).
Results go to ``BENCH_serving.json`` (override the path with the
``BENCH_SERVING_JSON`` env var); a baseline from a seeded run is committed
at the repo root.
"""

from __future__ import annotations

import json
import os

import numpy as np
import jax

from repro.data import synth
from repro.index.invindex import InvertedIndex
from repro.index.engine import QueryBatch, QueryEngine
from repro.index.serve import (Rejected, Request, ServeConfig,
                               bursty_offsets, poisson_offsets, serve_stream)
from .bench_query import git_sha, make_queries
from .util import emit


def bitwise_equal(a, b) -> bool:
    """Recursive exact comparison: nested lists/tuples of arrays, or bare
    arrays — the shapes the engine's per-mode results take."""
    if isinstance(a, (tuple, list)) and isinstance(b, (tuple, list)):
        return (len(a) == len(b)
                and all(bitwise_equal(x, y) for x, y in zip(a, b)))
    return np.array_equal(np.asarray(a), np.asarray(b))


def audit_parity(engine: QueryEngine, stats, results: list) -> list:
    """Replay every batch the server formed through the host numpy oracle
    (``plan(..., placement="host")`` / ``execute``) and return ``(mode,
    batch_id, rid)`` for every served result that is not bitwise the
    oracle's — empty when all match.  Replaying at the batch's own
    placement would let a device path that is wrong the same way every time
    pass.  ``results[rid]`` must be the stream's result for request ``rid``
    (true for ``serve_stream``'s submission-order list)."""
    bad = []
    for b in stats.batches:
        plan = engine.plan(QueryBatch([list(q) for q in b.queries],
                                      mode=b.mode, k=b.k),
                           placement="host")
        for want, rid in zip(engine.execute(plan), b.rids):
            if not bitwise_equal(want, results[rid]):
                bad.append((b.mode, b.batch_id, rid))
    return bad


def _drive(engine: QueryEngine, queries: list, offsets, deadline_ms: float,
           placement: str, max_batch: int, max_wait_ms: float,
           tenants: int = 2) -> tuple:
    """One benchmark cell: serve the stream, return (snapshot, parity_ok).

    The stream runs twice and only the second pass is recorded — the same
    ``warmup=1`` discipline as every ``timeit`` suite here.  Dynamic batch
    composition decides which jit worklist buckets get hit, so no synthetic
    priming can cover them all; the unrecorded first pass compiles whatever
    this exact stream forms, and the measured pass reports steady-state
    serving latency rather than first-seen compile stalls (which on the
    CPU-interpret backend run hundreds of ms each)."""
    reqs = [Request(list(q), mode="and", k=10,
                    tenant=f"t{i % tenants}", deadline_ms=deadline_ms)
            for i, q in enumerate(queries)]
    cfg = ServeConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms, slack_ms=2.0,
        # roomy admission: backpressure behaviour has its own tests; the
        # benchmark measures latency/goodput, not cap-induced shedding
        queue_cap=max(256, 4 * len(queries)),
        default_deadline_ms=deadline_ms,
        tenants={f"t{i}": 1.0 + i for i in range(tenants)},
        placement=placement, warm_terms=32, warm_modes=("and",),
        warm_queries=queries[:max_batch])
    serve_stream(engine, reqs, offsets, cfg)          # unrecorded warm pass
    results, stats = serve_stream(engine, reqs, offsets, cfg)
    served = [r for r in results if not isinstance(r, Rejected)]
    parity = not audit_parity(engine, stats, results) if served else True
    return stats.snapshot(), parity


def run(n_requests: int = 192, dataset: str = "gov2",
        codec: str = "group_simple", seed: int = 0, rate_qps: float = 200.0,
        deadline_ms: float = 2500.0, smoke: bool = False) -> None:
    """Poisson + bursty open-loop streams across placements; writes
    ``BENCH_serving.json``.  ``smoke`` additionally *asserts* the two
    CI-tracked guarantees (Poisson shed rate 0, bitwise parity)."""
    doclen, postings = synth.make_corpus(dataset, seed)
    queries = make_queries(postings, n_requests, seed=3 + seed)
    idx = InvertedIndex.build(doclen, postings, codec=codec)
    idx.to_device(build_fused=True)
    engine = QueryEngine(idx).to_device(fused=True)

    max_batch, max_wait_ms = 16, 4.0
    arrivals = {
        "poisson": poisson_offsets(n_requests, rate_qps, seed=41 + seed),
        "bursty": bursty_offsets(n_requests, rate_qps, seed=43 + seed,
                                 shape=0.25),
    }
    placements = ("host", "device", "fused")
    report = {
        "dataset": dataset, "codec": codec, "backend": jax.default_backend(),
        "git_sha": git_sha(), "n_requests": n_requests,
        "rate_qps": rate_qps, "deadline_ms": deadline_ms,
        "config": {"max_batch": max_batch, "max_wait_ms": max_wait_ms,
                   "slack_ms": 2.0, "tenants": 2},
        "arrivals": {},
    }
    for arrival, offsets in arrivals.items():
        report["arrivals"][arrival] = {}
        for placement in placements:
            snap, parity = _drive(engine, queries, offsets, deadline_ms,
                                  placement, max_batch, max_wait_ms)
            cell = dict(snap)
            cell["parity_ok"] = bool(parity)
            report["arrivals"][arrival][placement] = cell
            lat = snap["latency_ms"]
            emit(f"serving/{dataset}/{codec}/{arrival}_{placement}",
                 (lat.get("p50", 0.0)) * 1e3,
                 f"p50={lat.get('p50', 0):.2f}ms,p99={lat.get('p99', 0):.2f}ms,"
                 f"p999={lat.get('p999', 0):.2f}ms,"
                 f"goodput={snap['goodput_qps']:.1f}qps,"
                 f"shed={snap['shed_rate']:.3f},"
                 f"mean_batch={snap['mean_batch']:.1f}")
            if not parity:
                raise AssertionError(
                    f"served results diverged from the host oracle "
                    f"({arrival}/{placement})")
            if smoke and arrival == "poisson" and snap["shed_rate"] != 0.0:
                raise AssertionError(
                    f"Poisson smoke load shed {snap['shed_rate']:.3f} of "
                    f"requests on {placement} (must be 0)")

    path = os.environ.get("BENCH_SERVING_JSON", "BENCH_serving.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"# wrote {path}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-requests", type=int, default=192)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="mean open-loop arrival rate (qps)")
    ap.add_argument("--deadline-ms", type=float, default=2500.0,
                    help="per-request SLO budget; the generous default "
                         "absorbs first-seen jit-bucket compile stalls on "
                         "the CPU-interpret backend")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload + arrival seed (fixed default keeps runs "
                         "deterministic)")
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + assert shed-rate-0 / parity guarantees")
    args = ap.parse_args()
    run(n_requests=64 if args.smoke and args.n_requests == 192
        else args.n_requests,
        seed=args.seed, rate_qps=args.rate, deadline_ms=args.deadline_ms,
        smoke=args.smoke)
