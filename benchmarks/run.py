"""Benchmark driver — one suite per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--full`` uses paper-scale
stream lengths (slower); default sizes finish on a laptop-class CPU.

``--smoke`` is DETERMINISTIC on its inputs: every suite draws its corpus /
stream / query workload from fixed RNG seeds (``--seed``, default 0) at
pinned sizes (streams 2**14, 20 queries, 64 serving requests, the
``synth.DATASETS`` corpus shapes), so two smoke runs measure the identical
workload and the JSON artifacts (``BENCH_query.json`` / ``BENCH_mutation.json``
/ ``BENCH_serving.json`` — baselines of the first and last are committed at
the repo root) differ only in timings.  The serving smoke additionally
asserts its CI guarantees: zero shed under the Poisson load and bitwise
parity with the offline plan/execute oracle.
"""

from __future__ import annotations

import argparse
import os
import sys
import traceback

# allow `python benchmarks/run.py` (script mode) as well as `-m benchmarks.run`
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized quick pass (tiny streams, fast suites only)")
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset: speed ratio gsc query index opt pipeline "
                         "roofline kernels serving")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload seed for the query suite (fixed default "
                         "keeps --smoke deterministic)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    n = 1 << 21 if args.full else (1 << 14 if args.smoke else 1 << 18)
    suites = {
        "ratio": lambda: __import__("benchmarks.bench_ratio", fromlist=["run"]).run(),
        "gsc": lambda: __import__("benchmarks.bench_group_scheme", fromlist=["run"]).run(n=max(n >> 1, 1 << 16)),
        "speed": lambda: __import__("benchmarks.bench_speed", fromlist=["run"]).run(n=n),
        "opt": lambda: __import__("benchmarks.bench_optimizations", fromlist=["run"]).run(n=n),
        "query": lambda: __import__("benchmarks.bench_query", fromlist=["run"]).run(
            n_queries=200 if args.full else (20 if args.smoke else 60),
            seed=args.seed),
        "index": lambda: __import__("benchmarks.bench_index_size", fromlist=["run"]).run(),
        "pipeline": lambda: __import__("benchmarks.bench_pipeline", fromlist=["run"]).run(
            n_tokens=max(n >> 1, 1 << 16)),
        "roofline": lambda: __import__("benchmarks.bench_roofline", fromlist=["run"]).run(),
        "kernels": lambda: __import__("benchmarks.bench_roofline", fromlist=["run_kernels"]).run_kernels(),
        "serving": lambda: __import__("benchmarks.bench_serving", fromlist=["run"]).run(
            n_requests=512 if args.full else (64 if args.smoke else 192),
            seed=args.seed, smoke=args.smoke),
    }
    todo = args.only or (["speed", "query", "index", "kernels", "serving"]
                         if args.smoke else list(suites))
    print("name,us_per_call,derived")
    failed = []
    for key in todo:
        try:
            suites[key]()
        except Exception:
            failed.append(key)
            traceback.print_exc()
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
