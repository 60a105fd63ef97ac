"""Smoke run of the index-serving path on a TPU, through the user entry points.

  python chip_smoke.py             # one chip: a 6.3M-doc GOV2 shard
  python chip_smoke.py --chips 4   # doc-range sharded serving over 4 chips

One chip holds one doc-range shard of a GOV2 deployment (25.2M docs over 4
chips): a seeded corpus of the ``gov2`` shape from ``repro.data.synth``
(top-200 Zipf posting lists, doc length 778) at 6.3M docs goes through
``InvertedIndex.build`` -> ``QueryEngine.to_device(fused=True)`` ->
``IndexServer`` (``serve_stream``).  The server answers ``and``, ``or`` and
``and_scored`` (k=10) requests with the placement pinned first to ``device``
and then to ``fused``; every batch it formed is replayed through the host
numpy oracle (``plan(..., placement="host")``) and must match bitwise, and
no batch may have been planned anywhere but the pinned placement.

``--chips 4`` runs only the sharded phase: the same corpus shape split into 4
doc-range shards, one per chip (``to_device(shards=4,
mesh=serving_mesh(4))``), each shard's arenas checked to sit on its own
device, ``and`` and ``or`` batches planned and executed on the device
placement (``or`` merges through the one all-gather collective) and
compared bitwise with the unsharded host oracle.

Times printed here are from a smoke run, not a benchmark.  Any failure exits
non-zero; on success the last line of stdout is one JSON object naming the
device.  JAX's persistent compilation cache is on
(``repro.launch.compile_cache``), so a second run in the same checkout reads
what the first one compiled.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

GOV2_DOCS = 25_200_000          # TREC GOV2 collection
SHARD_DOCS = GOV2_DOCS // 4     # one chip's doc-range share
# the 4-chip phase splits one shard-sized corpus four ways: 4 x 6.3M docs
# would take four times the host build time, on four chips at once
SHARDED_DOCS = SHARD_DOCS
MODES = ("and", "or", "and_scored")
K = 10
PER_MODE = 32                   # requests per mode and placement
# every shard compiles its own programs (its widths differ), so the sharded
# phase runs a few batches through plan/execute, on the device placement
SHARDED_BATCHES = 1
MAX_BATCH = 16
RATE_QPS = 200.0
DEADLINE_MS = 600_000.0         # nothing may be shed: every result is audited


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU; JAX found {devs[0].platform!r}")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, found {len(devs)}")
    return devs


def make_requests(postings: dict, modes, seed: int,
                  per_mode: int = PER_MODE) -> list:
    """``per_mode`` requests per mode, interleaved: 2-3 of the 120 head
    terms each (the ``launch.serve --index`` workload)."""
    import numpy as np
    from repro.index.serve import Request
    rng = np.random.default_rng(3 + seed)
    head = sorted(postings)[:120]
    return [Request(rng.choice(head, size=rng.integers(2, 4),
                               replace=False).tolist(),
                    mode=mode, k=K, deadline_ms=DEADLINE_MS)
            for _ in range(per_mode) for mode in modes]


def build(n_docs: int, seed: int):
    from repro.data import synth
    from repro.index.invindex import InvertedIndex
    t = time.perf_counter()
    doclen, postings = synth.make_corpus("gov2", seed, n_docs=n_docs)
    idx = InvertedIndex.build(doclen, postings)
    n_post = sum(len(ids) for ids, _ in postings.values())
    log(f"corpus: gov2 shape, {n_docs} docs, {len(postings)} terms, "
        f"{n_post} postings; build {time.perf_counter() - t:.1f} s "
        f"(smoke run, not a benchmark)")
    return idx, postings


def serve_and_audit(engine, reqs: list, placement: str, seed: int,
                    modes) -> dict:
    """Serve ``reqs`` with the placement pinned, then replay every formed
    batch through the host oracle.  Returns served counts per mode; raises
    on a shed request, a batch planned elsewhere, or a parity mismatch."""
    from benchmarks.bench_serving import audit_parity
    from repro.index.serve import (Rejected, ServeConfig, poisson_offsets,
                                   serve_stream)
    cfg = ServeConfig(max_batch=MAX_BATCH, max_wait_ms=4.0,
                      queue_cap=4 * len(reqs),
                      default_deadline_ms=DEADLINE_MS, placement=placement,
                      warm_terms=32, warm_modes=tuple(modes),
                      warm_queries=[r.terms for r in reqs[:MAX_BATCH]])
    t = time.perf_counter()
    results, stats = serve_stream(engine, reqs,
                                  poisson_offsets(len(reqs), RATE_QPS,
                                                  seed=41 + seed), cfg)
    wall = time.perf_counter() - t
    shed = [r for r in results if isinstance(r, Rejected)]
    if shed:
        raise AssertionError(f"{placement}: {len(shed)} requests shed: "
                             f"{shed[:3]}")
    off = {b.placement for b in stats.batches} - {placement}
    if off:
        raise AssertionError(f"{placement}: batches planned on {off}")
    t = time.perf_counter()
    bad = audit_parity(engine, stats, results)
    audit_s = time.perf_counter() - t
    served = {m: sum(len(b.rids) for b in stats.batches if b.mode == m)
              for m in modes}
    snap = stats.snapshot()
    log(f"{placement}: warm-up/compile {stats.warmup_s:.1f} s, stream "
        f"{wall - stats.warmup_s:.1f} s, {snap['n_batches']} batches, "
        f"mean batch {snap['mean_batch']:.1f}; host-oracle replay "
        f"{audit_s:.1f} s (smoke run, not a benchmark)")
    for m in modes:
        wrong = [b for b in bad if b[0] == m]
        log(f"{placement}/{m}: served {served[m]}, parity with host oracle "
            f"{'bitwise equal' if not wrong else f'{len(wrong)} MISMATCHED'}")
    if bad:
        raise AssertionError(f"{placement}: {len(bad)} results differ from "
                             f"the host oracle, first {bad[:3]}")
    return served


def one_chip(seed: int, n_docs: int = SHARD_DOCS) -> None:
    from repro.index.engine import QueryEngine
    idx, postings = build(n_docs, seed)
    t = time.perf_counter()
    engine = QueryEngine(idx).to_device(fused=True)
    engine.arena.ensure_scores()
    log(f"upload: device + fused + score arenas {time.perf_counter() - t:.1f} s")
    reqs = make_requests(postings, MODES, seed)
    for placement in ("device", "fused"):
        serve_and_audit(engine, reqs, placement, seed, MODES)


def arena_devices(arena) -> set:
    """Every device holding one of the arena's buffers."""
    arrays = [a for g in arena._groups.values() for a in g.arenas]
    arrays += [pk["tiles"] for pk in (arena._pk or {}).values()]
    if arena.dense_words is not None:
        arrays.append(arena.dense_words)
    if arena.scores is not None:
        arrays.append(arena.scores.tiles)
    return {d for a in arrays for d in a.devices()}


def four_chips(seed: int, n_docs: int = SHARDED_DOCS) -> None:
    from benchmarks.bench_serving import bitwise_equal
    from repro.index.engine import QueryBatch, QueryEngine
    from repro.launch.mesh import serving_mesh
    idx, postings = build(n_docs, seed)
    mesh = serving_mesh(4)
    if mesh is None:
        raise AssertionError("serving_mesh(4) found fewer than 4 devices")
    t = time.perf_counter()
    engine = QueryEngine(idx).to_device(shards=4, mesh=mesh)
    log(f"upload: 4 shards {time.perf_counter() - t:.1f} s")
    spec, engs, _ = engine._shard_engines(engine._cur())
    placed = []
    for s, eng in enumerate(engs):
        devs = arena_devices(eng.arena)
        if devs != {eng._shard_device}:
            raise AssertionError(f"shard {s} arenas on {devs}, expected "
                                 f"{eng._shard_device}")
        placed.append(eng._shard_device)
        log(f"shard {s}: docs [{spec.bounds[s]}, {spec.bounds[s + 1]}) "
            f"on {eng._shard_device}")
    if len(set(placed)) != 4:
        raise AssertionError(f"shards share devices: {placed}")
    queries = [r.terms for r in make_requests(postings, ("and",), seed,
                                              SHARDED_BATCHES * MAX_BATCH)]
    for mode in ("and", "or"):
        bad, run_s = 0, 0.0
        for i in range(0, len(queries), MAX_BATCH):
            batch = QueryBatch(queries[i:i + MAX_BATCH], mode=mode, k=K)
            t = time.perf_counter()
            plan = engine.plan(batch, placement="device")
            got = engine.execute(plan)
            run_s += time.perf_counter() - t
            if "sharded x4" not in plan.note or "mesh-placed" not in plan.note:
                raise AssertionError(f"batch not mesh-sharded: {plan.note}")
            want = engine.execute(engine.plan(batch, placement="host"))
            bad += sum(not bitwise_equal(a, b) for a, b in zip(want, got))
        log(f"sharded x4/{mode}: {len(queries)} queries in "
            f"{len(queries) // MAX_BATCH} batch(es), {run_s:.1f} s incl. "
            f"compile (smoke run, not a benchmark); parity with the "
            f"unsharded host oracle "
            f"{'bitwise equal' if not bad else f'{bad} MISMATCHED'}")
        if bad:
            raise AssertionError(f"sharded {mode}: {bad} results differ")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the doc-range sharded phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="corpus, query and arrival seed")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    hits = {"hits": 0, "misses": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            hits["misses"] += 1

    jax.monitoring.register_event_listener(count)
    log(f"device: {devs[0].device_kind} x {len(devs)} "
        f"({devs[0].platform}); compile cache {cache_dir}")
    t = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    log(f"compile cache: {hits['hits']} hits, {hits['misses']} misses; "
        f"total {time.perf_counter() - t:.1f} s (smoke run, not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
