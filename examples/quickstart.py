"""Quickstart: the paper's compression approach in five minutes.

  1. encode/decode posting-list d-gaps with every Group codec,
  2. compare scalar vs vectorized decode (the paper's central axis),
  3. run the TPU-layout Pallas kernels (interpret mode on CPU),
  4. build + query a compressed inverted index,
  5. serve a query batch through the fused decode-and-intersect engine
     (plan, then execute: engine.execute(engine.plan(batch))),
  6. move the index into device-resident arenas (engine.to_device()) and
     serve the same batch with round-batched lane-parallel block decodes —
     arena coverage comes from each codec's declared ArenaLayout capability.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import time

import numpy as np
import jax.numpy as jnp

from repro.core import codec as codec_lib
from repro.core.dgap import dgap_encode_np
from repro.data import synth
from repro.index.invindex import InvertedIndex
from repro.index.engine import QueryBatch, QueryEngine
from repro.kernels import ops


def main() -> None:
    lists = synth.make_dataset("gov2", seed=0)
    gaps = synth.concat_gaps(lists)
    print(f"GOV2-like stream: {len(gaps)} d-gaps, "
          f"{100*float(np.mean(gaps < 256)):.1f}% fit in one byte\n")

    print(f"{'codec':22}{'bits/int':>9}{'scalar(ms)':>12}{'vec(ms)':>9}")
    for name in ("group_simple", "group_scheme_1-CU", "group_scheme_8-IU",
                 "group_afor", "group_pfd", "bp128"):
        spec = codec_lib.get(name)
        enc = spec.encode(gaps)
        args = spec.jax_args(enc)
        out = np.asarray(spec.decode_jax_vec(**args))
        assert np.array_equal(out, gaps)
        for f in (spec.decode_jax_scalar, spec.decode_jax_vec):
            f(**args).block_until_ready()
        t0 = time.perf_counter(); spec.decode_jax_scalar(**args).block_until_ready()
        ts = time.perf_counter() - t0
        t0 = time.perf_counter(); spec.decode_jax_vec(**args).block_until_ready()
        tv = time.perf_counter() - t0
        print(f"{name:22}{enc.bits_per_int:9.2f}{ts*1e3:12.2f}{tv*1e3:9.2f}")

    # Pallas kernels (TPU target, interpret on CPU): pack -> fused unpack+delta
    docids = np.sort(np.random.default_rng(0).choice(1 << 20, 20000, replace=False)).astype(np.uint32)
    g = dgap_encode_np(docids)
    bw = int(np.ceil(np.log2(g.max() + 1)))
    packed = ops.pack_stream(jnp.asarray(g), bw)
    recon = np.asarray(ops.unpack_delta_stream(packed, bw, len(g)))
    assert np.array_equal(recon, docids)
    print(f"\nPallas fused unpack+prefix-sum: {len(g)} gaps at bw={bw} -> docids OK "
          f"({packed.size * 4 / len(g):.2f} B/int vs 4.00 raw)")

    # compressed inverted index + queries
    doclen, postings = synth.make_corpus("gov2")
    idx = InvertedIndex.build(doclen, postings, codec="group_simple")
    one = QueryEngine(idx)
    hits = one.execute(one.plan(QueryBatch([[1, 5]], mode="and_scored", k=5)))[0]
    print(f"\nindex: {idx.size_bytes()/1e6:.2f} MB (group_simple); "
          f"AND(1,5) top hit doc={hits[0][0]} bm25={hits[0][1]:.2f}")

    # batched serving: many queries per call, shared decoded-block LRU
    rng = np.random.default_rng(0)
    terms = sorted(postings)
    queries = [rng.choice(terms[:100], size=3, replace=False).tolist()
               for _ in range(256)]
    engine = QueryEngine(idx, cache_blocks=4096)
    plan = engine.plan(QueryBatch(queries, mode="and"))
    t0 = time.perf_counter()
    results = engine.execute(plan)
    dt = time.perf_counter() - t0
    st = engine.cache.stats()
    print(f"batched engine: {len(queries)} AND queries in {dt*1e3:.1f} ms "
          f"({len(queries)/dt:.0f} qps); block cache {st['hits']} hits / "
          f"{st['misses']} misses; first result has {len(results[0])} docs")

    # device-resident serving: compressed blocks flattened into device arenas,
    # each AND round issues ONE lane-parallel decode for the whole batch's
    # deduped (term, block) work-list instead of O(blocks) Python iterations
    dev = QueryEngine(idx, cache_blocks=4096).to_device()
    dev_plan = dev.plan(QueryBatch(queries, mode="and"))
    dev.execute(dev_plan)                               # warm up the jits
    dev = QueryEngine(idx, cache_blocks=4096).to_device()
    calls0 = dev.arena.stats["device_calls"]   # arena (and stats) are shared
    t0 = time.perf_counter()
    dev_results = dev.execute(dev_plan)
    dt = time.perf_counter() - t0
    assert all(np.array_equal(a, b) for a, b in zip(results, dev_results))
    ds = dev.dev_stats
    print(f"device engine:  {len(queries)} AND queries in {dt*1e3:.1f} ms "
          f"({len(queries)/dt:.0f} qps, exact parity); work-list "
          f"{ds['worklist_refs']} block refs -> {ds['worklist_decodes']} decodes "
          f"in {dev.arena.stats['device_calls'] - calls0} device calls")

    # ranked top-k through the quantized score arenas: BM25 impacts ride as
    # u8 score columns next to the docid streams, OR work-lists are block-max
    # pruned, and only the final candidate bitmap returns to the host — the
    # float rescore makes the results exactly the host oracle's (docid ties)
    topk_plan = dev.plan(QueryBatch(queries[:64], mode="or", k=5))
    top = dev.execute(topk_plan)
    host_top = engine.execute(engine.plan(QueryBatch(queries[:64], mode="or", k=5)))
    assert top == host_top
    ds = dev.dev_stats
    print(f"ranked top-k:   64 OR queries, k=5 -> top hit doc={top[0][0][0]} "
          f"bm25={top[0][0][1]:.2f}; {ds['blocks_pruned']} blocks pruned / "
          f"{ds['blocks_scored']} scored, {ds['score_syncs']} per-round syncs "
          f"(exact parity with the host float oracle)")


if __name__ == "__main__":
    main()
