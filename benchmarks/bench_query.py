"""Table X analogue: query processing rate (queries/second) per codec over
the compressed inverted index (AND + OR BM25 top-10, warm cache), plus the
batched-engine mode: queries/sec at batch sizes {1, 16, 256} for the host
numpy path AND the device-arena path (``QueryEngine.to_device()``), against
the seed per-query ``np.isin`` loop (``and_query_ref``).

The batched run also records the device work-list discipline — raw (term,
block) references per batch vs deduped decodes actually issued — plus the
ranked modes (``or`` / ``and_scored`` through the quantized score arenas and
block-max top-k: qps per placement, ``blocks_pruned`` / ``blocks_scored``,
and per-round host syncs, which must be zero on the resident ranked path) —
and writes the whole thing to ``BENCH_query.json`` (override the path with
the ``BENCH_QUERY_JSON`` env var) so CI can track the perf trajectory as an
artifact.  Two more report sections feed the serving stack: ``mode_qps``
(host-vs-device qps per batch size, per query MODE, with the placement
pinned — ``CrossoverTable.from_bench`` derives one demotion cell per mode
from these, so ranked modes demote independently of plain AND) and
``sharded`` (doc-range sharded serving scaling curves over ``--shards``
counts: qps per mode, plus the collective accounting — merge syncs and
collective bytes per ranked batch, and the cross-shard round syncs, which
must be ZERO: doc-wise partitioning keeps every round shard-local).  On the CPU/interpret CI backend the device path's wall-clock is
not the headline (jitted gathers vs raw numpy); the tracked guarantee there
is ``decodes_per_hot_block == 1.0``: no (term, block) repeats inside one
round's decode call, read off the calls themselves, in O(rounds) device
calls instead of O(blocks) Python iterations.  A block that a later round
of the same batch needs again is decoded again (no cache spans rounds);
``cross_round_redecodes`` counts those.

``--mutate`` (also run as part of the default suite) exercises the streaming
mutable index: qps on the device placement at 0% / 1% / 10% tombstone
density, the compaction pause (one ``compact()`` merge re-encoding the live
corpus into the next generation), and the delta-segment scan overhead (qps
with freshly inserted docs pending in the mutable segment vs the compacted
clean index).  Results go to ``BENCH_mutation.json`` (override with
``BENCH_MUTATION_JSON``); the tracked CI guarantees are that tombstone
gating stays resident — ``cand_syncs == 0`` at every density — and that
block-max pruning stays ARMED under the tombstone-only epoch
(``ranked_tomb_1pct.blocks_pruned > 0``: deletes only raise idf, so the
idf-ratio-deflated threshold keeps the upper-bound test sound; see the
re-arm note in ``repro/index/scores.py``).

Every input is derived from fixed RNG seeds (corpus via
``synth.make_corpus(dataset, seed)``, query sets via seeded generators), so
two runs at the same sizes measure the identical workload — the committed
``BENCH_query.json`` baseline at the repo root is reproducible bit-for-bit
on the inputs (timings vary, the workload does not).
"""

from __future__ import annotations

import json
import os
import subprocess
import time

import numpy as np
import jax

from repro.data import synth
from repro.index.invindex import InvertedIndex
from repro.index.engine import QueryBatch, QueryEngine
from repro.index import query as Q
from .util import emit, timeit

CODECS = ["group_simple", "group_scheme_8-IU", "group_pfd", "bp128",
          "group_afor", "varbyte", "stream_vbyte", "simple9", "pfordelta",
          "afor", "gvb"]

BATCH_SIZES = (1, 16, 256)


def git_sha() -> str:
    """Current commit, so the qps trajectory is comparable across PRs."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=10,
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def make_queries(postings: dict, n_queries: int, seed: int = 3) -> list:
    rng = np.random.default_rng(seed)
    terms = sorted(postings)
    return [rng.choice(terms[:120], size=rng.integers(2, 4), replace=False).tolist()
            for _ in range(n_queries)]


def make_ranked_queries(postings: dict, n_queries: int, seed: int = 7) -> list:
    """Ranked workload: one tail term (high idf -> strong impacts) plus 1-2
    head terms per query — the rare+common shape where block-max pruning
    earns its keep (head-term blocks outside the tail term's docid
    neighbourhood can't reach the top-k threshold)."""
    rng = np.random.default_rng(seed)
    terms = sorted(postings)
    return [[int(rng.choice(terms[120:]))]
            + rng.choice(terms[:120], size=rng.integers(1, 3),
                         replace=False).tolist()
            for _ in range(n_queries)]


def run(n_queries: int = 100, dataset: str = "gov2", seed: int = 0,
        shard_counts: tuple = (1, 2, 4)) -> None:
    doclen, postings = synth.make_corpus(dataset, seed)
    queries = make_queries(postings, n_queries, seed=3 + seed)
    for name in CODECS:
        idx = InvertedIndex.build(doclen, postings, codec=name)

        def run_and():
            for q in queries:
                Q.and_query_scored(idx, q, k=10)

        def run_or():
            for q in queries[: n_queries // 4]:
                Q.or_query(idx, q, k=10)

        t = timeit(run_and, repeats=3, warmup=1)
        emit(f"query/{dataset}/{name}/and", t * 1e6, f"{n_queries / t:.1f}qps")
        t = timeit(run_or, repeats=3, warmup=1)
        emit(f"query/{dataset}/{name}/or", t * 1e6, f"{(n_queries // 4) / t:.1f}qps")
    # batched mode needs enough queries sharing terms to expose cache reuse —
    # keep the canonical 256 except under CI smoke sizing (n_queries <= 20)
    run_batched(dataset=dataset, n_queries=n_queries if n_queries <= 20 else 256,
                seed=seed, shard_counts=shard_counts)
    run_mutation(dataset=dataset, n_queries=n_queries if n_queries <= 20 else 128,
                 seed=seed)


def run_batched(dataset: str = "gov2", codec: str = "group_simple",
                n_queries: int = 256, seed: int = 0,
                shard_counts: tuple = (1, 2, 4)) -> None:
    """Batched engine (host + device paths) vs the seed scalar loop."""
    doclen, postings = synth.make_corpus(dataset, seed)
    queries = make_queries(postings, n_queries, seed=3 + seed)
    idx = InvertedIndex.build(doclen, postings, codec=codec)
    # provenance stamp: codec, jax backend, and commit make the trajectory
    # comparable across PRs and across CI/TPU runners
    report = {"dataset": dataset, "codec": codec, "n_queries": n_queries,
              "backend": jax.default_backend(), "git_sha": git_sha(),
              "host_qps": {}, "device_qps": {}}

    def seed_loop():
        for q in queries:
            Q.and_query_ref(idx, q)

    t_ref = timeit(seed_loop, repeats=3, warmup=1)
    emit(f"query/{dataset}/{codec}/and_seed_loop", t_ref * 1e6,
         f"{n_queries / t_ref:.1f}qps")
    report["seed_loop_qps"] = n_queries / t_ref

    # build arenas once, outside the timers (no fused tiles: the timed
    # device path is the batched work-list decode, not the fused kernel)
    idx.to_device(build_fused=False)
    for bs in BATCH_SIZES:
        batches = [queries[i:i + bs] for i in range(0, len(queries), bs)]

        def run_engine(device: bool):
            # fresh engine per repeat: cold cache, so the measurement includes
            # every decode the batch actually pays for
            eng = QueryEngine(idx)
            if device:
                eng.to_device()
            for b in batches:
                eng.execute(eng.plan(QueryBatch(b, mode="and")))

        t = timeit(lambda: run_engine(False), repeats=3, warmup=1)
        emit(f"query/{dataset}/{codec}/and_batched_{bs}", t * 1e6,
             f"{n_queries / t:.1f}qps,{t_ref / t:.1f}x")
        report["host_qps"][bs] = n_queries / t
        t = timeit(lambda: run_engine(True), repeats=3, warmup=1)
        emit(f"query/{dataset}/{codec}/and_device_{bs}", t * 1e6,
             f"{n_queries / t:.1f}qps,{t_ref / t:.1f}x")
        report["device_qps"][bs] = n_queries / t

    # work-list discipline at the largest batch size, read off the slots
    # each decode call is handed: a slot repeated inside one call is a
    # round's dedup regressing (a ratio > 1); a slot an earlier call of the
    # batch already decoded is a cross-round redecode, reported on its own
    eng = QueryEngine(idx).to_device()
    calls = []
    for name, g in eng.arena._groups.items():
        def counted(slots, _name=name, _decode=g.decode_rows):
            calls.append([(_name, int(s)) for s in slots])
            return _decode(slots)
        g.decode_rows = counted
    eng.execute(eng.plan(QueryBatch(queries, mode="and")))
    refs = eng.dev_stats["worklist_refs"]
    decodes = sum(len(c) for c in calls)
    hot = sum(len(set(c)) for c in calls)
    redecodes = hot - len({s for c in calls for s in c})
    report["cross_round_redecodes"] = redecodes
    report["worklist_refs"] = refs
    report["worklist_decodes"] = decodes
    report["hot_blocks"] = hot
    report["decodes_per_hot_block"] = decodes / max(hot, 1)
    emit(f"query/{dataset}/{codec}/device_worklist", 0.0,
         f"{refs}refs,{decodes}decodes,{hot}hot,"
         f"{decodes / max(hot, 1):.2f}per_hot_block,"
         f"{redecodes}cross_round_redecodes")

    # candidate residency per placement: rounds executed with candidates
    # device-resident, and candidate downloads per query (the resident
    # placements must show zero syncs between rounds — their only download
    # is the one final result copy per batch, reported separately)
    report["placements"] = {}
    for placement in ("host", "device", "fused"):
        eng = QueryEngine(idx)
        if placement != "host":
            eng.to_device(fused=placement == "fused")
        eng.execute(eng.plan(QueryBatch(queries, mode="and")))
        stats = {
            "rounds_on_device": eng.dev_stats["resident_rounds"],
            "host_syncs_per_query": eng.dev_stats["cand_syncs"] / n_queries,
            "final_syncs": eng.dev_stats["final_syncs"],
        }
        report["placements"][placement] = stats
        emit(f"query/{dataset}/{codec}/residency_{placement}", 0.0,
             f"{stats['rounds_on_device']}rounds_on_device,"
             f"{stats['host_syncs_per_query']:.3f}syncs_per_query")

    # ranked modes (or / and_scored): quantized score arenas + block-max
    # top-k.  Arenas, fused tiles, and the score column are built once
    # outside the timers; the tracked CI guarantees are blocks_pruned > 0
    # (the upper-bound test actually drops work) and zero per-round host
    # syncs (only the final candidate bitmap is downloaded, once per batch).
    ranked_queries = make_ranked_queries(postings, n_queries, seed=7 + seed)
    idx.to_device(build_fused=True).ensure_scores()
    report["ranked"] = {}
    for mode in ("or", "and_scored"):
        entry = {"k": 10, "qps": {}}
        for placement in ("host", "device", "fused"):

            def run_ranked():
                eng = QueryEngine(idx)
                if placement != "host":
                    eng.to_device(fused=placement == "fused")
                for i in range(0, len(ranked_queries), 64):
                    eng.execute(eng.plan(QueryBatch(
                        ranked_queries[i:i + 64], mode=mode, k=10)))

            t = timeit(run_ranked, repeats=3, warmup=1)
            entry["qps"][placement] = n_queries / t
            emit(f"query/{dataset}/{codec}/{mode}_{placement}", t * 1e6,
                 f"{n_queries / t:.1f}qps")
        eng = QueryEngine(idx).to_device()
        eng.execute(eng.plan(QueryBatch(ranked_queries, mode=mode, k=10)))
        entry["blocks_pruned"] = eng.dev_stats["blocks_pruned"]
        entry["blocks_scored"] = eng.dev_stats["blocks_scored"]
        entry["blocks_dense"] = eng.dev_stats["blocks_dense"]
        entry["score_rounds"] = eng.dev_stats["score_rounds"]
        entry["host_syncs_per_query"] = eng.dev_stats["score_syncs"] / n_queries
        entry["final_syncs"] = eng.dev_stats["final_syncs"]
        report["ranked"][mode] = entry
        emit(f"query/{dataset}/{codec}/{mode}_blockmax", 0.0,
             f"{entry['blocks_pruned']}pruned,{entry['blocks_scored']}scored,"
             f"{entry['host_syncs_per_query']:.3f}syncs_per_query")

    # per-mode placement crossover curves, placement PINNED (the auto-placed
    # curves above fold the planner's own demotion into the measurement):
    # CrossoverTable.from_bench derives one demotion cell per mode from
    # "mode_qps", so ranked modes — which amortize score uploads and the
    # final-merge sync over the batch — demote independently of plain AND
    report["mode_qps"] = {"and": {"host": dict(report["host_qps"]),
                                  "device": dict(report["device_qps"])}}
    for mode in ("or", "and_scored"):
        curves = {"host": {}, "device": {}}
        for bs in BATCH_SIZES:
            rbatches = [ranked_queries[i:i + bs]
                        for i in range(0, len(ranked_queries), bs)]

            def run_mode(device: bool):
                eng = QueryEngine(idx)
                if device:
                    eng.to_device()
                for b in rbatches:
                    eng.execute(eng.plan(
                        QueryBatch(b, mode=mode, k=10),
                        placement="device" if device else "host"))

            t = timeit(lambda: run_mode(False), repeats=3, warmup=1)
            curves["host"][bs] = n_queries / t
            t = timeit(lambda: run_mode(True), repeats=3, warmup=1)
            curves["device"][bs] = n_queries / t
            emit(f"query/{dataset}/{codec}/{mode}_crossover_{bs}", 0.0,
                 f"host={curves['host'][bs]:.1f}qps,"
                 f"device={curves['device'][bs]:.1f}qps")
        report["mode_qps"][mode] = curves

    # doc-range sharded serving: scaling curves over shard counts.  The
    # per-generation shard cache means the slice-and-re-encode build cost is
    # paid once per count (in the warmup), so the timers measure serving.
    # Tracked contracts: ONE top-k merge collective per ranked batch, and
    # ZERO cross-shard round syncs (candidates and score accumulators never
    # leave their shard — doc-wise partitioning, not term-wise).
    report["sharded"] = {}
    for s in shard_counts:
        entry = {"qps": {}}
        for mode in ("and", "or", "and_scored"):
            qs = queries if mode == "and" else ranked_queries

            def run_shard_engine():
                eng = QueryEngine(idx).to_device(shards=s)
                for i in range(0, len(qs), 64):
                    eng.execute(eng.plan(
                        QueryBatch(qs[i:i + 64], mode=mode, k=10),
                        placement="device"))
                return eng

            t = timeit(run_shard_engine, repeats=3, warmup=1)
            entry["qps"][mode] = n_queries / t
            emit(f"query/{dataset}/{codec}/sharded{s}_{mode}", t * 1e6,
                 f"{n_queries / t:.1f}qps")
        eng = QueryEngine(idx).to_device(shards=s)
        n_batches = -(-len(ranked_queries) // 64)
        for i in range(0, len(ranked_queries), 64):
            eng.execute(eng.plan(
                QueryBatch(ranked_queries[i:i + 64], mode="or", k=10),
                placement="device"))
        spec, engs, _ = eng._shard_engines(eng._ctx_now())
        entry["bounds"] = list(spec.bounds)
        entry["merge_syncs_per_batch"] = \
            eng.dev_stats["merge_syncs"] / n_batches
        entry["collective_bytes_per_batch"] = \
            eng.dev_stats["collective_bytes"] / n_batches
        entry["cross_shard_round_syncs"] = sum(
            e.dev_stats["cand_syncs"] + e.dev_stats["score_syncs"]
            for e in engs if e is not None)
        report["sharded"][s] = entry
        emit(f"query/{dataset}/{codec}/sharded{s}_collectives", 0.0,
             f"{entry['merge_syncs_per_batch']:.1f}merges_per_batch,"
             f"{entry['collective_bytes_per_batch']:.0f}B,"
             f"{entry['cross_shard_round_syncs']}cross_shard_syncs")

    path = os.environ.get("BENCH_QUERY_JSON", "BENCH_query.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"# wrote {path}")


def run_mutation(dataset: str = "gov2", codec: str = "group_pfd",
                 n_queries: int = 128, seed: int = 0) -> None:
    """Streaming-mutation serving cost: tombstone-gated qps, compaction
    pause, and delta-segment scan overhead (see the module docstring)."""
    doclen, postings = synth.make_corpus(dataset, seed)
    queries = make_queries(postings, n_queries, seed=3 + seed)
    ranked_queries = make_ranked_queries(postings, n_queries, seed=7 + seed)
    n_docs = len(doclen)
    rng = np.random.default_rng(11 + seed)
    report = {"dataset": dataset, "codec": codec, "n_queries": n_queries,
              "n_docs": n_docs, "backend": jax.default_backend(),
              "git_sha": git_sha(), "tombstone_qps": {}}

    def measure(idx, tag: str) -> dict:
        """Device-placement and-mode qps over the whole query set (fresh
        engine per repeat: the per-epoch live-bitmap upload is part of the
        serving cost being measured)."""
        def go():
            eng = QueryEngine(idx)
            eng.to_device()
            for i in range(0, len(queries), 64):
                eng.execute(eng.plan(QueryBatch(queries[i:i + 64], mode="and")))
            return eng
        t = timeit(go, repeats=3, warmup=1)
        eng = go()   # one extra run for the residency counters
        stats = {"qps": n_queries / t,
                 "cand_syncs": eng.dev_stats["cand_syncs"],
                 "tomb_gates": eng.dev_stats["tomb_gates"]}
        emit(f"query/{dataset}/{codec}/mutate_{tag}", t * 1e6,
             f"{n_queries / t:.1f}qps,{stats['cand_syncs']}cand_syncs")
        return stats

    idx = InvertedIndex.build(doclen, postings, codec=codec)
    idx.to_device(build_fused=False)
    report["tombstone_qps"]["0%"] = clean = measure(idx, "tomb_0pct")

    # tombstone density sweep: each step deletes up to the target fraction of
    # the base doc space; the live bitmap is re-packed once per epoch and the
    # gate must add zero candidate downloads
    victims = rng.permutation(n_docs)
    n_deleted = 0
    for frac, tag in ((0.01, "1%"), (0.10, "10%")):
        target = int(n_docs * frac)
        for d in victims[n_deleted:target]:
            idx.delete(int(d))
        n_deleted = target
        report["tombstone_qps"][tag] = measure(idx, f"tomb_{tag.rstrip('%')}pct")
        if tag == "1%":
            # re-armed block-max pruning under the tombstone-only epoch:
            # deletes only raise idf, so the idf-ratio-deflated threshold
            # keeps the upper-bound test sound and pruning must still fire
            # (blocks_pruned > 0 is the tracked CI guarantee for the re-arm)
            idx.to_device(build_fused=False).ensure_scores()

            def go_ranked():
                eng = QueryEngine(idx).to_device()
                for i in range(0, len(ranked_queries), 64):
                    eng.execute(eng.plan(QueryBatch(
                        ranked_queries[i:i + 64], mode="or", k=10)))
                return eng
            t = timeit(go_ranked, repeats=3, warmup=1)
            eng = go_ranked()
            report["ranked_tomb_1pct"] = {
                "qps": n_queries / t,
                "blocks_pruned": eng.dev_stats["blocks_pruned"],
                "blocks_scored": eng.dev_stats["blocks_scored"],
                "score_syncs": eng.dev_stats["score_syncs"],
            }
            emit(f"query/{dataset}/{codec}/mutate_ranked_tomb_1pct", t * 1e6,
                 f"{n_queries / t:.1f}qps,"
                 f"{eng.dev_stats['blocks_pruned']}pruned,"
                 f"{eng.dev_stats['blocks_scored']}scored")

    # compaction pause: one merge of generation-minus-tombstones through the
    # codec registry into the next generation (10% of the corpus dead)
    t0 = time.perf_counter()
    idx.compact()
    pause = time.perf_counter() - t0
    report["compaction_pause_s"] = pause
    report["compacted_gid"] = idx.gen.gid
    emit(f"query/{dataset}/{codec}/mutate_compact_pause", pause * 1e6,
         f"{n_docs - n_deleted}live_docs,gid{idx.gen.gid}")

    # delta-segment scan overhead: fresh docs pending in the mutable segment
    # are brute-force scanned and merged into every query's result
    idx.to_device(build_fused=False)
    report["post_compact_qps"] = measure(idx, "post_compact")
    terms = sorted(postings)
    base = idx.doc_space
    n_delta = max(16, n_docs // 100)
    for j in range(n_delta):
        picked = rng.choice(terms[:120], size=8, replace=False)
        idx.insert(base + j, {int(t): int(rng.integers(1, 5)) for t in picked},
                   doclen=int(doclen.mean()))
    delta = measure(idx, "delta_1pct")
    report["delta_qps"] = delta
    report["n_delta_docs"] = n_delta
    report["delta_scan_overhead_x"] = clean["qps"] / max(delta["qps"], 1e-9)
    emit(f"query/{dataset}/{codec}/mutate_delta_overhead", 0.0,
         f"{n_delta}delta_docs,{report['delta_scan_overhead_x']:.2f}x")

    path = os.environ.get("BENCH_MUTATION_JSON", "BENCH_mutation.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"# wrote {path}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mutate", action="store_true",
                    help="only the streaming-mutation suite (BENCH_mutation.json)")
    ap.add_argument("--n-queries", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="workload seed (corpus + query sets); fixed default "
                         "keeps runs deterministic")
    ap.add_argument("--shards", type=str, default="1,2,4",
                    help="comma-separated shard counts for the sharded "
                         "serving scaling curves (BENCH_query.json)")
    args = ap.parse_args()
    shard_counts = tuple(int(x) for x in args.shards.split(",") if x)
    if args.mutate:
        run_mutation(n_queries=args.n_queries or 128, seed=args.seed)
    else:
        run(n_queries=args.n_queries or 100, seed=args.seed,
            shard_counts=shard_counts)
