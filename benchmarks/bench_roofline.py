"""§Roofline: three-term roofline per (arch x shape x mesh) from the dry-run
JSONs (see launch/dryrun.py + launch/hlo_census.py).  Prints one row per cell;
the full table + analysis lives in EXPERIMENTS.md.

``run_kernels`` is the serving-kernel counterpart: each hot ranked/AND kernel
(``kernels/topk.py`` / ``kernels/intersect_rounds.py``) is lowered and
compiled at a canonical gov2-scale serving shape on the CURRENT backend, the
post-fusion HLO is fed through ``launch/hlo_census.py``, and the per-kernel
flop / memory / wire census plus roofline terms (v5e constants) land in
``BENCH_kernel_roofline.json`` (override with ``BENCH_KERNEL_ROOFLINE_JSON``)
— the CI artifact that makes kernel-lowering regressions (a scatter sneaking
back in, a fusion breaking apart) visible per PR as a census diff."""

from __future__ import annotations

import glob
import json
import os

from .util import emit

PEAK_FLOPS = 197e12          # v5e bf16 / chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link (1 link assumed per transfer)


def load_records(out_dir: str = "experiments/dryrun"):
    recs = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def terms(rec: dict):
    c = rec.get("census") or {}
    t_comp = c.get("flops_per_chip", 0) / PEAK_FLOPS
    t_mem = c.get("mem_bytes_per_chip", 0) / HBM_BW
    t_coll = c.get("wire_bytes_per_chip", 0) / ICI_BW
    dom = max((("compute", t_comp), ("memory", t_mem), ("collective", t_coll)),
              key=lambda kv: kv[1])[0]
    return t_comp, t_mem, t_coll, dom


def run(out_dir: str = "experiments/dryrun") -> None:
    for rec in load_records(out_dir):
        name = f"roofline/{rec['arch']}/{rec['shape']}/{'x'.join(map(str, rec['mesh']))}"
        if rec.get("status") == "skipped":
            emit(name, 0.0, "skipped:" + rec["skip_reason"][:40])
            continue
        if rec.get("status") != "ok":
            emit(name, 0.0, "FAILED")
            continue
        t_comp, t_mem, t_coll, dom = terms(rec)
        emit(name, max(t_comp, t_mem, t_coll) * 1e6,
             f"comp={t_comp*1e3:.2f}ms|mem={t_mem*1e3:.2f}ms|coll={t_coll*1e3:.2f}ms|dom={dom}")


def _kernel_cases():
    """The serving hot loop at a canonical gov2-scale shape: 64 queries,
    128 work-list entries, 512-posting blocks, 25k-doc bitmap geometry.
    Sparse rounds read a fused part's rows or gather them by row index from
    a decoded matrix; dense rounds gather their windows by row index from
    arena-sized matrices, as they serve."""
    import jax.numpy as jnp
    from repro.kernels import topk
    from repro.kernels import intersect_rounds as ir

    words, _ = ir.bitmap_geometry(25_000)
    q, p, ow = 64, 128, 512
    acc = jnp.zeros((q, words * 32), jnp.uint32)
    bm = jnp.zeros((q, words), jnp.uint32)
    ids = jnp.zeros((p, ow), jnp.uint32)
    qslot = jnp.zeros((p,), jnp.int32)
    codes = jnp.zeros((p, ow), jnp.uint32)
    ns = jnp.zeros((p,), jnp.int32)
    ub = jnp.zeros((p,), jnp.int32)
    theta = jnp.zeros((q,), jnp.uint32)
    iq = jnp.full((q,), 1 << 16, jnp.uint32)
    margin = jnp.zeros((q,), jnp.int32)
    rows = jnp.zeros((p,), jnp.int32)
    slots = 4 * p                       # dense windows in the arenas
    dense_words = jnp.zeros((slots, 128), jnp.uint32)
    dense_tiles = jnp.zeros((slots, 1024), jnp.uint32)
    w0 = jnp.zeros((p,), jnp.int32)
    act = jnp.zeros((p,), bool)
    active = jnp.zeros((q,), bool)
    return [
        ("score_round", topk.score_round,
         (acc, bm, ids, qslot, codes, ns, bm, ub, theta, iq),
         {"gated": False}),
        ("score_round_gated", topk.score_round,
         (acc, bm, ids, qslot, codes, ns, bm, ub, theta, iq),
         {"gated": True}),
        ("score_round_gathered", topk.score_round,
         (acc, bm, ids, qslot, codes, ns, bm, ub, theta, iq, rows),
         {"gated": False}),
        ("dense_score_round", topk.dense_score_round,
         (acc, bm, dense_tiles, dense_words, qslot, w0, ub, theta, iq, bm,
          rows, rows), {"gated": True}),
        ("topk_threshold", topk._topk_threshold_jit, (acc,), {"k": 10}),
        ("pooled_threshold", topk.pooled_threshold, (acc,), {"k": 10}),
        ("candidate_bitmap", topk.candidate_bitmap,
         (acc, bm, theta, margin, iq), {}),
        ("round_accumulate", ir.round_accumulate,
         (bm, ids, qslot, ns, bm), {}),
        ("round_accumulate_gathered", ir.round_accumulate,
         (bm, ids, qslot, ns, bm, rows), {}),
        ("dense_round_accumulate", ir.dense_round_accumulate,
         (bm, dense_words, qslot, w0, act, bm, rows), {}),
        ("round_commit", ir.round_commit, (bm, bm, active), {}),
    ]


def run_kernels() -> None:
    """Per-kernel flop/memory census of the compiled serving kernels."""
    import jax
    from repro.launch.hlo_census import census

    report = {"backend": jax.default_backend(), "kernels": {}}
    for name, fn, args, kw in _kernel_cases():
        hlo = fn.lower(*args, **kw).compile().as_text()
        c = census(hlo)
        t_comp = c.get("flops_per_chip", 0) / PEAK_FLOPS
        t_mem = c.get("mem_bytes_per_chip", 0) / HBM_BW
        report["kernels"][name] = {
            "flops": c.get("flops_per_chip", 0),
            "mem_bytes": c.get("mem_bytes_per_chip", 0),
            "wire_bytes": c.get("wire_bytes_per_chip", 0),
            "n_computations": c.get("n_computations", 0),
            "t_comp_us": t_comp * 1e6,
            "t_mem_us": t_mem * 1e6,
        }
        emit(f"roofline/kernel/{name}", max(t_comp, t_mem) * 1e6,
             f"flops={c.get('flops_per_chip', 0):.3g}|"
             f"mem={c.get('mem_bytes_per_chip', 0):.3g}B|"
             f"dom={'compute' if t_comp >= t_mem else 'memory'}")
    path = os.environ.get("BENCH_KERNEL_ROOFLINE_JSON",
                          "BENCH_kernel_roofline.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"# wrote {path}")


if __name__ == "__main__":
    import sys
    if "--kernels" in sys.argv:
        run_kernels()
    else:
        run()
