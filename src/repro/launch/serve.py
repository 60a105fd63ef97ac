"""Serving launcher: prefill + batched decode (LM), batched scoring /
retrieval (recsys) under the serving sharding plan, or the latency-governed
index serving loop (``--index``: async admission + dynamic batching over the
``QueryEngine``, see ``repro.index.serve``).

  python -m repro.launch.serve --arch smollm-135m --smoke --tokens 8
  python -m repro.launch.serve --arch din --shape serve_p99 --smoke
  python -m repro.launch.serve --index --smoke
  python -m repro.launch.serve --index --rate 300 --requests 512 --placement device
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import STEP_FNS
from repro.distributed import sharding as shlib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh


def serve_index(args) -> None:
    """Index retrieval serving: build a seeded corpus, start the
    :class:`~repro.index.serve.IndexServer`, drive an open-loop Poisson
    stream through it, and print the SLO snapshot.  ``--smoke`` shrinks the
    stream to CI size and asserts nothing was shed."""
    import json

    from repro.data import synth
    from repro.index.invindex import InvertedIndex
    from repro.index.engine import QueryEngine
    from repro.index.serve import (Rejected, Request, ServeConfig,
                                   poisson_offsets, serve_stream)
    from repro.obs import (enable_tracing, get_tracer, to_chrome_trace,
                           trace_coverage)

    n = 32 if args.smoke else args.requests
    if args.trace_out:
        # deep engine/kernel spans ride the process-global tracer; the
        # server's lifecycle spans are always on (server-owned tracer)
        enable_tracing(True, fenced=args.fenced)
    doclen, postings = synth.make_corpus(args.dataset, args.seed)
    idx = InvertedIndex.build(doclen, postings)
    idx.to_device(build_fused=True)
    engine = QueryEngine(idx).to_device(fused=True)
    # head-term conjunctions, same shape as benchmarks.bench_query's workload
    rng = np.random.default_rng(3 + args.seed)
    terms = sorted(postings)
    queries = [rng.choice(terms[:120], size=rng.integers(2, 4),
                          replace=False).tolist() for _ in range(n)]
    reqs = [Request(list(q), mode="and", k=10, deadline_ms=args.deadline_ms)
            for q in queries]
    offsets = poisson_offsets(n, args.rate, seed=41 + args.seed)
    cfg = ServeConfig(max_batch=16, max_wait_ms=4.0, slack_ms=2.0,
                      queue_cap=max(256, 4 * n),
                      default_deadline_ms=args.deadline_ms,
                      placement=args.placement, warm_terms=32,
                      # prime the jit buckets with the (seeded, known)
                      # workload so the stream measures serving, not
                      # first-seen compile stalls
                      warm_queries=queries)
    results, stats = serve_stream(engine, reqs, offsets, cfg)
    snap = stats.snapshot()
    lat = snap["latency_ms"]
    print(f"served {snap['served']}/{snap['submitted']} "
          f"(shed_rate={snap['shed_rate']:.3f}) at {args.rate:.0f} qps "
          f"poisson on placement={args.placement or 'auto'}")
    print(f"latency ms: p50={lat.get('p50', 0):.2f} p99={lat.get('p99', 0):.2f} "
          f"p999={lat.get('p999', 0):.2f}  goodput={snap['goodput_qps']:.1f} qps  "
          f"mean_batch={snap['mean_batch']:.1f}  warmup={snap['warmup_s']:.2f}s")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(stats.to_prometheus())
        print(f"wrote prometheus metrics to {args.metrics_out}")
    if args.trace_out:
        trace = to_chrome_trace(stats.tracer, get_tracer())
        with open(args.trace_out, "w") as f:
            json.dump(trace, f)
        cov = trace_coverage(stats.tracer.spans())
        print(f"wrote {len(trace['traceEvents'])} trace events to "
              f"{args.trace_out} (batch coverage {cov:.3f}) — load at "
              f"https://ui.perfetto.dev")
        if args.smoke:
            # the exported trace must round-trip as JSON and the
            # plan/execute/deliver children must account for >= 90% of
            # measured batch wall-clock
            with open(args.trace_out) as f:
                assert json.load(f)["traceEvents"], "empty trace export"
            assert cov >= 0.9, f"trace covers {cov:.3f} < 0.9 of batch time"
        enable_tracing(False)
    if args.smoke:
        shed = [r for r in results if isinstance(r, Rejected)]
        assert not shed, f"smoke stream shed {len(shed)} requests: {shed[:3]}"
        print("index serve smoke ok")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=sorted(configs.ARCHS))
    ap.add_argument("--index", action="store_true",
                    help="serve the inverted index (async admission + "
                         "dynamic batching) instead of a model arch")
    ap.add_argument("--shape", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dataset", default="gov2")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="index mode: mean Poisson arrival rate (qps)")
    ap.add_argument("--deadline-ms", type=float, default=2500.0,
                    help="index mode: per-request SLO budget (generous "
                         "default absorbs jit compile stalls on CPU)")
    ap.add_argument("--placement", default=None,
                    choices=["host", "device", "fused"],
                    help="index mode: pin every batch's placement "
                         "(default: engine auto-placement)")
    ap.add_argument("--trace-out", default=None,
                    help="index mode: write a Perfetto-loadable Chrome "
                         "trace-event JSON of the run (also enables the "
                         "deep engine/kernel spans)")
    ap.add_argument("--metrics-out", default=None,
                    help="index mode: write the server's Prometheus text "
                         "exposition to this file after the stream")
    ap.add_argument("--fenced", action="store_true",
                    help="with --trace-out: block_until_ready inside round "
                         "spans so durations attribute device wall-clock "
                         "to the producing kernel")
    args = ap.parse_args()
    enable_compile_cache()

    if args.index:
        serve_index(args)
        return
    if args.arch is None:
        ap.error("either --arch or --index is required")

    spec = configs.get(args.arch)
    serve_cells = [c for c in spec.shapes.values()
                   if c.kind in ("prefill", "decode", "serve", "retrieval")]
    cell = spec.shapes[args.shape] if args.shape else serve_cells[0]
    cfg = spec.config_for_cell(
        spec.make_smoke_config() if args.smoke else spec.make_config(), cell)
    mesh = (make_host_mesh((len(jax.devices()), 1), ("data", "model"))
            if args.smoke or len(jax.devices()) < 256
            else make_production_mesh(multi_pod=args.multi_pod))
    plan = spec.plan_for(cfg, cell)

    from repro.models import recsys, transformer
    with shlib.activate(mesh, plan):
        if spec.family == "lm":
            params = transformer.init(cfg, jax.random.PRNGKey(0))
            b, s = 2, 32
            prompts = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (b, s)), jnp.int32)
            logits, cache = jax.jit(lambda p, t: transformer.prefill(p, t, cfg))(params, prompts)
            if not cfg.window:
                cache = {k: jnp.concatenate([v, jnp.zeros(v.shape[:2] + (args.tokens,) + v.shape[3:], v.dtype)], axis=2)
                         for k, v in cache.items()}
            decode = jax.jit(lambda p, c, t, pos: transformer.decode_step(p, c, t, pos, cfg))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            t0 = time.perf_counter()
            for i in range(args.tokens):
                logits, cache = decode(params, cache, tok, jnp.int32(s + i))
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
            print(f"decoded {args.tokens} steps x batch {b} in {(time.perf_counter()-t0)*1e3:.1f} ms")
        else:
            params = recsys.init(cfg, jax.random.PRNGKey(0))
            step_fn, _ = STEP_FNS["recsys"](cfg, cell, None)
            from tests.test_arch_smoke import _smoke_batch
            batch = _smoke_batch(spec, cfg, cell)
            if cell.kind == "retrieval":
                batch = {k: (v[:1] if not k.startswith("cand_") else v) for k, v in batch.items()}
            out = jax.jit(step_fn)(params, batch)
            out0 = out[0] if isinstance(out, tuple) else out
            print(f"{cell.name}: output {np.asarray(out0).shape} ok")


if __name__ == "__main__":
    main()
