"""The benchmark's one command: serve one cell on the chip and report it.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

From the root of a checkout.  The cell, its configuration, its traffic mix
and its metrics are found by name from ``BENCHMARK.json`` (see
``chipbench/spec.py``).  In order, a run

1. fails unless JAX finds TPU chips, as many as the cell asks for;
2. turns on the persistent compile cache (``repro.launch.compile_cache``:
   ``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``);
3. makes the configuration's corpus for ``--seed`` (``chipbench/corpus``:
   its lists under the seed's term ids) and builds the index, or loads
   the one an earlier run of this configuration and seed built from the
   same program sources
   (``chipbench/built/``), then uploads it (``to_device(fused=True)`` and
   the score arena);
4. starts an ``IndexServer`` with its own defaults (only the deadline comes
   from the configuration), and warms it with the cell's own mix from a
   warm-up stream (``warm_requests`` of a closed loop, ``warm_seconds``
   of an open one), so the window's queries are new to it;
5. turns the persistent compile cache off and measures for ``--seconds``:
   the open- or closed-loop driver of ``chipbench/drive`` submits the
   window's requests, and every answer due in the window is awaited (up to
   a minute past the close).  A program first met inside the window is
   compiled there in every run, whatever earlier runs of the checkout
   cached, so a seed run twice measures the same work, and every seed
   the same work in another order (``chipbench/traffic``);
6. reads device memory, frees the program's state, compares the answers
   with ``chipbench/reference`` (``chipbench/check``), and prints the
   compared numbers beside their limits as the last lines of stderr and
   the result as the last line of stdout.

``--trace 1`` runs the window under the JAX profiler with the engine's span
tracer on, and reports the cell's per-layer metrics plus a ``breakdown``
instead of its end-to-end metrics.

Two modes outside the benchmark's runs, for whoever adds a cell:
``--sweep r1,r2,...`` serves the open-loop mix at each offered rate for
``--seconds`` after one set-up and prints what each sustained (how a cell's
knee is found), and ``--control 1`` also prints the control's readings
(``chipbench/reference.Control`` put in the program's place).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import sys  # noqa: E402

from . import check, corpus, spec, traffic as traffic_mod  # noqa: E402
from .stats import latencies_ms, nearest_rank  # noqa: E402

DRAIN_S = 60.0            # how long past the close an answer is awaited
TRACE_S = 30.0            # profiled stretch at the start of a traced window
SYNC_MARK = "chipbench/sync"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class RunData:
    """What a metric reader (``metrics/<name>.py``) reads: the window's
    records, the server's records and spans of the window, engine counters,
    compile events, and the reduced device trace of a traced run."""
    seconds: float
    t0: float                       # window start (first due request)
    records: list                   # chipbench.drive.Record, due in window
    traces: list                    # repro TraceRecord of window requests
    batches: list                   # repro BatchRecord of window batches
    spans: list                     # server + engine spans of the window
    counters: dict                  # engine counter deltas over the window
    compiles: int                   # programs compiled inside the window
    setup_s: float
    postings: int
    index_bytes: int                # bytes_in_use after - before the upload
    serving_bytes: int              # ... once the window has drained
    trace: object = None            # trace_reduce.Summary (traced runs)
    trace_t0: float = 0.0           # the profiled stretch, cut to the
    trace_t1: float = 0.0           # whole batches inside it

    def traced_batches(self) -> list:
        """The window's batches that ran wholly inside the profiled
        stretch."""
        return [b for b in self.batches
                if b.t_close >= self.trace_t0 and b.t_done <= self.trace_t1]


# --------------------------------------------------------------------------- #
# set-up
# --------------------------------------------------------------------------- #

def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU; JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise SystemExit(f"chipbench: the cell needs {n} chips, JAX found "
                         f"{len(devs)}")
    return devs


def source_hash(root: str, cfg: dict) -> str:
    """Key of a built index: the program's index sources, the corpus
    generator and the configuration."""
    h = hashlib.sha256(json.dumps(cfg, sort_keys=True).encode())
    files = [os.path.join(spec.HERE, "corpus.py")]
    for sub in ("core", "index", "data"):
        files += sorted(glob.glob(os.path.join(root, "src", "repro", sub,
                                               "**", "*.py"), recursive=True))
    for p in files:
        h.update(os.path.relpath(p, root).encode())
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def load_or_build(cfg: dict, seed: int, root: str, built: str):
    """The built index for (configuration, seed): loaded from ``built``
    when this program already built it, else built from the seeded corpus
    and kept there.  Returns ``(index, postings, how)``."""
    from repro.index.invindex import InvertedIndex
    doclen, postings = corpus.make_corpus(cfg, seed)
    n_post = corpus.n_postings(postings)
    path = os.path.join(built, f"{cfg['name']}-{int(seed)}-"
                               f"{source_hash(root, cfg)}.pkl")
    if os.path.exists(path):
        with open(path, "rb") as f:
            return pickle.load(f), n_post, "loaded"
    idx = InvertedIndex.build(doclen, postings, codec=cfg["codec"])
    os.makedirs(built, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        pickle.dump(idx, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)
    return idx, n_post, "built"


class CompileCounter:
    """Counts compile requests to the persistent cache, and the programs
    compiled or loaded with the seconds they took, from
    ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.requests = 0
        self.compile_s = 0.0
        self.names: list = []           # programs compiled, in order
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._dur)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def _dur(self, event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.names.append(fun_name)


@contextlib.contextmanager
def persistent_cache_off():
    """Neither read nor write the persistent compile cache inside the
    block; programs compiled before it stay in memory."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def bytes_in_use(devs) -> int:
    gc.collect()
    return sum(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devs)


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


# --------------------------------------------------------------------------- #
# serving
# --------------------------------------------------------------------------- #

def server_config(cfg: dict):
    """The server's defaults but the configuration's deadline, and none of
    its own warm-up: the harness warms with the cell's own mix instead.
    The server's warm-up decodes the 16 highest-df lists and scores them
    (``warm_terms``) and primes every batch size with a fixed sample
    (``warm_modes``), which no cell's traffic asks for in that form; it
    took 51 s of a seen seed's set-up on one TPU v5e and 144-156 s of
    a new seed's."""
    from repro.index.serve import ServeConfig
    return ServeConfig(default_deadline_ms=float(cfg["deadline_ms"]),
                       warm_terms=0, warm_modes=())


async def warm_traffic(server, traffic: dict, terms, seed: int,
                       seconds: float) -> int:
    """Serve the cell's mix from its warm-up stream before the window
    (a fixed amount of it: ``warm_seconds`` of an open loop,
    ``warm_requests`` of a closed one); returns the requests served."""
    from . import drive as drv
    if traffic["loop"] == "open":
        warm_s = float(traffic["warm_seconds"])
        queries, offsets = traffic_mod.window_requests(
            traffic, terms, warm_s, seed, traffic_mod.WARM)
        recs, _ = await drive(server, traffic, queries, offsets, drv.now(),
                              warm_s)
        return len(recs)
    stream, _ = traffic_mod.window_requests(traffic, terms, seconds, seed,
                                            traffic_mod.WARM)
    recs, _ = await drv.closed_loop(server, stream, int(traffic["clients"]),
                                    drv.now(), math.inf,
                                    int(traffic["warm_requests"]))
    return len(recs)


async def drive(server, traffic: dict, queries: list, offsets, t0: float,
                seconds: float):
    """One window of the mix; returns ``(records, never)`` once every
    answer due in it came back or ``DRAIN_S`` passed after the close."""
    from . import drive as drv
    if traffic["loop"] == "open":
        recs, futs = await drv.open_loop(server, queries, offsets, t0)
    else:
        recs, futs = await drv.closed_loop(server, queries,
                                           int(traffic["clients"]), t0,
                                           seconds)
    close = t0 + seconds
    if drv.now() < close:
        await asyncio.sleep(close - drv.now())
    never = await drv.settle(futs, max(0.0, close + DRAIN_S - drv.now()))
    return recs, never


def window_spans(server, t0: float, t1: float) -> list:
    from repro.obs.trace import get_tracer
    return [s for tr in (server.tracer, get_tracer()) for s in tr.spans()
            if s.t1 is not None and s.t0 >= t0 and s.t0 <= t1]


def counters(engine) -> dict:
    return dict(engine.dev_stats.items())


async def serve_window(engine, cfg, traffic, seed, seconds, trace_dir,
                       devs, ccount, marks):
    """Warm up, then measure one window.  Returns the pieces of
    :class:`RunData` that serving produces."""
    from repro.index.serve import IndexServer
    from repro.obs.trace import enable_tracing, get_tracer
    from . import drive as drv
    terms = corpus.term_ids(cfg, seed)
    server = IndexServer(engine, server_config(cfg))
    t = drv.now()
    await server.start()
    marks["server_warmup_s"] = drv.now() - t
    t = drv.now()
    marks["warm_requests"] = await warm_traffic(server, traffic, terms,
                                                seed, seconds)
    marks["traffic_warmup_s"] = drv.now() - t
    queries, offsets = traffic_mod.window_requests(traffic, terms, seconds,
                                                   seed)
    n_tr, n_b = len(server.stats.traces), len(server.stats.batches)
    c0, k0, n0 = counters(engine), ccount.requests, len(ccount.names)
    marks["compiles_setup"] = k0
    compile_s0 = ccount.compile_s
    server.tracer.clear()
    get_tracer().clear()
    profiling = None
    if trace_dir is not None:
        # started while the server is idle, before the window opens
        enable_tracing(True)
        start_profiler(trace_dir, marks)
    t0 = drv.now() + 0.01
    marks["setup_s"] = t0 - T_START
    if trace_dir is not None:
        profiling = asyncio.create_task(
            stop_profiler(t0 + min(TRACE_S, seconds), marks))
    with persistent_cache_off():
        recs, _ = await drive(server, traffic, queries, offsets, t0, seconds)
    if profiling is not None:
        await profiling
        enable_tracing(False)
    t_end = drv.now()
    compiles = len(ccount.names) - n0
    marks["compiled_in_window"] = ccount.names[n0:]
    marks["compile_s_in_window"] = ccount.compile_s - compile_s0
    c1 = counters(engine)
    marks["memory_peak_bytes"] = peak_bytes(devs)
    spans = window_spans(server, t0, t_end)
    await server.stop()
    marks["serving_bytes"] = (bytes_in_use(devs)
                              - marks["bytes_before_upload"])
    return dict(t0=t0, records=recs,
                traces=server.stats.traces[n_tr:],
                batches=server.stats.batches[n_b:], spans=spans,
                counters={k: c1[k] - c0.get(k, 0) for k in c1},
                compiles=compiles)


def start_profiler(trace_dir: str, marks: dict) -> None:
    """Start the JAX profiler and stamp the clock marker the reduction
    aligns host spans by."""
    import jax
    from . import drive as drv
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(SYNC_MARK):
        marks["sync_mono"] = drv.now()


async def stop_profiler(at: float, marks: dict) -> None:
    """Stop the profiler at ``at`` (host clock); the profiled stretch is
    the window's start to then."""
    import jax
    from . import drive as drv
    await asyncio.sleep(max(0.0, at - drv.now()))
    marks["trace_t1"] = drv.now()
    jax.profiler.stop_trace()


def pct(recs: list, q: float):
    return nearest_rank(latencies_ms(recs), q) if recs else None


async def sweep(engine, cfg, traffic, seed, seconds, rates):
    """One set-up, then an open-loop window at each offered rate."""
    from repro.index.serve import IndexServer
    from . import drive as drv
    if traffic["loop"] != "open":
        raise SystemExit("chipbench: --sweep needs an open-loop traffic mix")
    terms = corpus.term_ids(cfg, seed)
    server = IndexServer(engine, server_config(cfg))
    await server.start()
    await warm_traffic(server, traffic, terms, seed, seconds)
    server.stats.batches.clear()
    rows = []
    for i, rate in enumerate(rates):
        queries, offsets = traffic_mod.window_requests(
            traffic, terms, seconds, seed + i, rate_qps=rate)
        t0 = drv.now() + 0.01
        recs, never = await drive(server, traffic, queries, offsets, t0,
                                  seconds)
        done = [r for r in recs if r.served]
        in_win = sum(r.t_done <= t0 + seconds for r in done)
        half = len(recs) // 2
        row = {"offered_qps": rate, "served_qps": in_win / seconds,
               "requests": len(recs), "failed": len(recs) - len(done),
               "never": never,
               "p50_ms": pct(recs, 50), "p95_ms": pct(recs, 95),
               # a backlog that grows over the window shows as a later half
               # slower than the first
               "p50_first_half_ms": pct(recs[:half], 50),
               "p50_second_half_ms": pct(recs[half:], 50),
               "mean_batch": (sum(len(b.rids) for b in server.stats.batches)
                              / max(1, len(server.stats.batches)))}
        server.stats.batches.clear()
        rows.append(row)
        log(f"sweep {json.dumps(row)}")
        if row["failed"] or row["served_qps"] < 0.5 * rate:
            break                   # far past the knee: stop offering more
    await server.stop()
    return rows


def keep_trace(out: str, trace_dir: str, sync_mono: float,
               run: RunData) -> None:
    """The trace plus what it was reduced with, for a test of the
    reduction (``tests/chipbench``)."""
    import shutil
    from . import trace_reduce
    os.makedirs(out, exist_ok=True)
    shutil.copy(trace_reduce.latest_xplane(trace_dir),
                os.path.join(out, "trace.xplane.pb"))
    side = {"sync_mono": sync_mono, "t0": run.trace_t0, "t1": run.trace_t1,
            "spans": [[s.name, s.t0, s.t1] for s in run.spans],
            "summary": dataclasses.asdict(run.trace)}
    with open(os.path.join(out, "trace.json"), "w") as f:
        json.dump(side, f)


# --------------------------------------------------------------------------- #
# main
# --------------------------------------------------------------------------- #

def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated offered rates (qps): find a knee")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also print the control's readings")
    ap.add_argument("--trace-out", default="",
                    help="with --trace 1: copy the trace and the host spans "
                         "it was reduced with into this directory")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench = spec.Bench()
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    devs = require_chips(int(cell["chips"]))[:int(cell["chips"])]
    src = os.path.join(spec.ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"chipbench: {cell['name']} on {devs[0].device_kind} x {len(devs)}; "
        f"compile cache {enable_compile_cache()}")
    result = run_cell(bench, cell, cfg, traffic, args, devs)
    if result is not None:
        print(json.dumps(result), flush=True)
    return 0


def run_cell(bench, cell, cfg, traffic, args, devs):
    """Everything after the chip check and the compile cache; returns the
    result object (None in sweep mode).  Tests call this with CPU devices
    at a tiny size."""
    ccount = CompileCounter()
    try:
        return _run_cell(bench, cell, cfg, traffic, args, devs, ccount)
    finally:
        ccount.close()


def _run_cell(bench, cell, cfg, traffic, args, devs, ccount):
    from repro.index.engine import QueryEngine
    from . import trace_reduce
    marks: dict = {}
    t = time.monotonic()
    idx, n_post, how = load_or_build(cfg, args.seed, bench.root,
                                     os.path.join(bench.here, "built"))
    marks["index_s"] = time.monotonic() - t
    marks["bytes_before_upload"] = bytes_in_use(devs)
    t = time.monotonic()
    engine = QueryEngine(idx).to_device(fused=True)
    engine.arena.ensure_scores()
    marks["upload_s"] = time.monotonic() - t
    # the index as uploaded: codec arenas, fused tiles, dense words, scores
    marks["index_bytes"] = bytes_in_use(devs) - marks["bytes_before_upload"]
    log(f"setup: index {how} {marks['index_s']:.3f} s ({n_post} postings), "
        f"upload {marks['upload_s']:.3f} s")
    if args.sweep:
        rates = [float(r) for r in args.sweep.split(",")]
        rows = asyncio.run(sweep(engine, cfg, traffic, args.seed,
                                 args.seconds, rates))
        print(json.dumps({"sweep": rows}), flush=True)
        return None
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(bench.here, "traces", cell["name"])
        if os.path.isdir(trace_dir):
            import shutil
            shutil.rmtree(trace_dir)
    got = asyncio.run(serve_window(engine, cfg, traffic, args.seed,
                                   args.seconds, trace_dir, devs, ccount,
                                   marks))
    log(f"setup: server warm-up {marks['server_warmup_s']:.3f} s, traffic "
        f"warm-up {marks['traffic_warmup_s']:.3f} s "
        f"({marks['warm_requests']} requests), compile requests "
        f"{marks['compiles_setup']} before the window; setup_s "
        f"{marks['setup_s']:.3f}")
    log(f"window: {got['compiles']} programs compiled inside it in "
        f"{marks['compile_s_in_window']:.3f} s "
        f"{marks['compiled_in_window']}; device bytes of the index "
        f"{marks['index_bytes']} after the upload, "
        f"{marks['serving_bytes']} after the window")
    del engine, idx
    gc.collect()
    run = RunData(seconds=args.seconds,
                  t0=got["t0"], records=got["records"], traces=got["traces"],
                  batches=got["batches"], spans=got["spans"],
                  counters=got["counters"], compiles=got["compiles"],
                  setup_s=marks["setup_s"], postings=n_post,
                  index_bytes=marks["index_bytes"],
                  serving_bytes=marks["serving_bytes"])
    breakdown = None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes":
              marks["memory_peak_bytes"]}
    if trace_dir is not None:
        t = time.monotonic()
        run.trace_t0, run.trace_t1 = run.t0, marks["trace_t1"]
        whole = run.traced_batches()
        if whole:
            run.trace_t0 = min(b.t_close for b in whole)
            run.trace_t1 = max(b.t_done for b in whole)
        log(f"trace: {len(whole)} whole batches in the profiled "
            f"{marks['trace_t1'] - run.t0:.3f} s, read over "
            f"{run.trace_t1 - run.trace_t0:.3f} s")
        run.trace = trace_reduce.reduce_dir(
            trace_dir, marks["sync_mono"], run.trace_t0, run.trace_t1,
            run.spans)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
        log(f"trace: reduced in {time.monotonic() - t:.3f} s")
        if getattr(args, "trace_out", ""):
            keep_trace(args.trace_out, trace_dir, marks["sync_mono"], run)
    metrics = {}
    for m in bench.metrics(cell, bool(args.trace)):
        v = bench.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    t = time.monotonic()
    from .reference import Control, Reference
    doclen, postings = corpus.make_corpus(cfg, args.seed)
    ref = Reference(doclen, postings)
    n_ranked = int(traffic["check_ranked"])
    read = check.readings(ref, run.records, args.seed, n_ranked)
    log(f"reference: {time.monotonic() - t:.3f} s")
    if args.control:
        ctl = check.control_readings(ref, Control(doclen, postings),
                                     run.records, args.seed, n_ranked)
        log(f"control readings: {json.dumps(ctl)}")
    correct, lines = check.verdict(read)
    result = {"correct": correct, "attempted": len(run.records),
              "failed": read["missing"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": read[k], "limit": check.LIMITS[k]}
                        for k in check.LIMITS}
    for line in lines:
        log(line)
    return result


if __name__ == "__main__":
    sys.exit(main())
