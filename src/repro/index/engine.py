"""Batched query engine: fused decode-and-intersect over the compressed index.

The seed path (`repro.index.query`) decoded every term's full posting list per
query and intersected with ``np.isin``.  This engine makes the serving path
hardware-speed along three axes:

  1. **Vectorized intersection** — per-block candidates are intersected with
     the kernels in ``repro.kernels.intersect`` (galloping ``searchsorted``
     probes or packed-bitmap AND, picked by density) instead of a scalar
     ``np.isin`` over the whole list.
  2. **Fused decode-and-intersect** — AND queries walk the rarest term first;
     for every other term the skip table (first docid per 512-posting block)
     is consulted *before* decompression, so blocks containing no candidate
     docids are never decoded.  Short candidate lists therefore touch only a
     handful of blocks of even the longest posting lists.
  3. **Batched execution with a decoded-block LRU** — ``QueryBatch`` groups
     queries by term signature so queries sharing terms run adjacently; each
     hot (term, block) is decompressed once into an LRU cache
     (``BlockCache``) and reused across the whole batch.  BM25 per-term score
     vectors are cached the same way for OR queries.
  4. **Device-resident execution** (``to_device()``) — the compressed blocks
     live in ``repro.index.device.DeviceArena`` arenas; per AND round the
     engine dedupes the *whole batch's* (term, block) work-list and issues
     ONE jitted lane-parallel decode instead of O(blocks) Python iterations.
     The per-query candidate sets live in a **device-resident segmented
     bitmap** across rounds (``kernels/intersect_rounds``): every round
     probes the old bitmap and scatters the survivors on device, block
     selection uses only static skip metadata (block first/last docids), and
     the only candidate download is the final result — zero host candidate
     syncs between rounds.  Under the ``fused`` placement the rounds decode
     with the Pallas kernel instead: unpack + d-gap prefix sum of packed gap
     tiles in VMEM, with the next work-list tile's DMA double-buffered; the
     probe and scatter are the same XLA round as the ``device`` placement.
     Results are bit-identical to the host path.
  5. **Device-resident ranked top-k** — ``or`` / ``and_scored`` batches
     accumulate u8-quantized BM25 impact codes (``repro.index.scores``: one
     packed score column per posting block, next to the docid streams) into
     a segmented device score buffer across rounds (``kernels/topk``), with
     OR work-lists block-max pruned against a static per-query threshold
     before any decode and ``and_scored`` gated by the AND-result bitmap
     that never left the device.  The single download per batch is the
     compacted candidate bitmap (k-th quantized sum minus the quantization
     margin — a provable superset of the float top-k), rescored exactly by
     the block-lazy float oracle: results are bitwise identical to the host
     BM25 path, ties broken by ascending docid.
  6. **Streaming mutation** (``repro.index.segments``) — the engine serves an
     :class:`~repro.index.invindex.InvertedIndex` handle that may carry
     tombstones and a delta segment on top of its immutable compressed
     generation.  Every query resolves a frozen :class:`_ExecCtx` (generation
     + delta snapshot + tombstone set + live corpus stats); plans pin their
     ctx, so a ``compact()`` under an in-flight plan cannot change its
     results.  Device paths gate probes with the epoch's packed live bitmap
     (one upload per epoch, zero downloads) and the host merges in a brute
     -force scan of the small delta segment; all block/score caches are keyed
     by generation / epoch so no stale state can serve across a compaction.
     Results stay bitwise identical to rebuilding the index from scratch.

Execution is planned, then run: ``engine.plan(batch)`` resolves *once* where
the batch runs (placement: host / device / fused) and what every referenced
term's codec is capable of (:class:`TermCaps`, read from the codec registry's
declared capabilities), and ``engine.execute(plan)`` just follows the plan —
the engine contains no per-codec special cases.

Typical use::

    engine = QueryEngine(idx, cache_blocks=4096)
    plan = engine.plan(QueryBatch(queries=[[1, 5], [2, 5, 9]], mode="and"))
    results = engine.execute(plan)
    engine.to_device()                       # device arenas from here on
    results = engine.execute(engine.plan(QueryBatch([[1, 5]], mode="and")))

Deprecated shims (see the migration note in ``repro/index/__init__.py``):
``execute(QueryBatch)`` plans implicitly; ``QueryEngine(idx, device=True,
fused=True)`` maps to ``to_device(fused=True)``; the one-shot helpers in
``repro.index.query`` delegate to plans.
"""

from __future__ import annotations

import contextlib
import dataclasses
import difflib
import itertools
import json
import os
import warnings
from collections import OrderedDict
from typing import Mapping, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import codec as codec_lib
from repro.kernels import intersect, intersect_rounds, topk
from repro.obs.metrics import DevStatsView, MetricsRegistry
from repro.obs.trace import get_tracer
from .device import ARENA_COUNTERS, _bucket   # one jit-bucket policy
from .invindex import InvertedIndex
from .scores import B, K1, bm25_scores, topk_select  # noqa: F401  (B/K1 re-export)

# plan-time auto-placement, static fallback: below this batch size the host
# numpy path beats the device round machinery on every backend measured so
# far, so tiny batches are planned onto the host even when arenas exist.
# When a committed BENCH_query.json baseline is present, ``plan()`` instead
# derives a :class:`CrossoverTable` from its measured host/device qps curves
# and only falls back to this constant when the curves show no true
# host->device crossing (see ``CrossoverTable.from_bench``).
HOST_BATCH_MAX = 1


@dataclasses.dataclass(frozen=True)
class CrossoverTable:
    """Host-vs-device placement crossover derived from a measured
    ``BENCH_query.json`` baseline.

    ``host_batch_max`` is the demotion threshold ``plan()`` uses: batches of
    at most this many queries are auto-placed on the host.  It is derived
    conservatively — the largest measured batch size where the host wins
    (host_qps >= device_qps) AND the device wins at *every* larger measured
    size.  That second clause matters: a backend where the host wins at the
    largest measured size (true of CPU-emulated device backends) has no
    real crossing, and extrapolating one would demote production-sized
    batches off the arenas.  In that case ``host_batch_max`` is None and
    ``plan()`` falls back to the static ``HOST_BATCH_MAX`` rule.  A backend
    where the device wins everywhere yields 0 (never demote).

    ``mode_cuts`` refines the single cell per query mode: a baseline whose
    report carries per-mode qps curves (``mode_qps``: mode -> {"host"/
    "device": {batch: qps}}) yields one cell per measured mode, derived with
    the same conservative rule.  Ranked modes amortize quantized-score
    uploads and the final-merge sync over the batch, so they typically cross
    to the device EARLIER than plain AND — one blended cell would demote
    ranked batches the device already wins.  ``cut_for(mode)`` resolves the
    cell ``plan()`` applies: the mode's own cell when measured (even a
    no-crossing None — then the static rule decides), else the blended
    ``host_batch_max``."""
    host_batch_max: Optional[int]
    sizes: tuple = ()
    source: str = "BENCH_query.json"
    mode_cuts: tuple = ()       # ((mode, cut_or_None), ...) measured cells

    def cut_for(self, mode: str) -> Optional[int]:
        """The demotion threshold for one query mode (see class docstring)."""
        for m, c in self.mode_cuts:
            if m == mode:
                return c
        return self.host_batch_max

    @staticmethod
    def _derive(host: Mapping, dev: Mapping):
        """The conservative crossover rule over one pair of qps curves:
        (cut, common sizes) — cut None when there is no true crossing."""
        sizes = sorted(set(host) & set(dev))
        if not sizes:
            return None, ()
        if all(dev[b] > host[b] for b in sizes):
            return 0, tuple(sizes)
        cut = None
        for b in sizes:
            larger = [s for s in sizes if s > b]
            if (host[b] >= dev[b] and larger
                    and all(dev[s] > host[s] for s in larger)):
                cut = b
        return cut, tuple(sizes)

    @classmethod
    def from_bench(cls, report: Mapping, source: str = "BENCH_query.json"
                   ) -> "CrossoverTable":
        host = {int(b): float(q)
                for b, q in (report.get("host_qps") or {}).items()}
        dev = {int(b): float(q)
               for b, q in (report.get("device_qps") or {}).items()}
        cut, sizes = cls._derive(host, dev)
        mode_cuts = []
        for m in sorted(report.get("mode_qps") or {}):
            curves = report["mode_qps"][m] or {}
            mh = {int(b): float(q)
                  for b, q in (curves.get("host") or {}).items()}
            md = {int(b): float(q)
                  for b, q in (curves.get("device") or {}).items()}
            mc, msz = cls._derive(mh, md)
            if msz:
                mode_cuts.append((m, mc))
        return cls(cut, sizes, source, tuple(mode_cuts))


def _repo_root() -> str:
    here = os.path.abspath(__file__)            # src/repro/index/engine.py
    for _ in range(4):
        here = os.path.dirname(here)
    return here


def _load_crossover() -> Optional[CrossoverTable]:
    """The crossover table from the committed benchmark baseline
    (``BENCH_QUERY_JSON`` env override, else ``BENCH_query.json`` at the
    repo root), or None when the file is absent/unreadable or was measured
    on another backend than the one running (a CPU curve says nothing about
    where a TPU batch should go) — ``plan()`` then applies the static
    ``HOST_BATCH_MAX`` rule."""
    path = (os.environ.get("BENCH_QUERY_JSON")
            or os.path.join(_repo_root(), "BENCH_query.json"))
    try:
        with open(path) as f:
            report = json.load(f)
        if report.get("backend") != jax.default_backend():
            return None
        return CrossoverTable.from_bench(report, source=os.path.basename(path))
    except (OSError, ValueError, TypeError, AttributeError):
        return None


_CROSSOVER_UNSET = object()
_crossover = _CROSSOVER_UNSET


def get_crossover() -> Optional[CrossoverTable]:
    """The cached placement crossover table (loaded once per process)."""
    global _crossover
    if _crossover is _CROSSOVER_UNSET:
        _crossover = _load_crossover()
    return _crossover


def set_crossover(table=_CROSSOVER_UNSET) -> None:
    """Override the cached crossover table.  Pass a :class:`CrossoverTable`
    to force one, ``None`` to simulate an absent baseline (static-rule
    fallback), or no argument to drop the override and reload from disk on
    next use.  Test hook — production code never calls this."""
    global _crossover
    _crossover = table

_EMPTY_U32 = np.zeros(0, np.uint32)
_EMPTY_U32.setflags(write=False)
_EMPTY_I64 = np.zeros(0, np.int64)
_EMPTY_I64.setflags(write=False)

# a ranked margin so large the candidate compact keeps EVERY member doc:
# under a delta-bearing mutation epoch the quantized accumulator uses
# generation-time impact codes (stale df/avdl), so the theta-margin cut is
# disarmed and the exact float rescore (live stats) does all the ranking.
# Tombstone-ONLY epochs stay armed through the idf-ratio deflation instead
# (see the re-arm note in ``repro/index/scores.py``).
_KEEP_ALL_MARGIN = 1 << 30

# per-entry quantized upper bound so large the adaptive-theta work-list
# masking never drops the entry (``and_scored`` rounds, whose membership
# must cover the whole intersection, always scatter)
_UB_ALWAYS = 1 << 30

# round memo entries kept per engine (each holds a round's decoded device
# matrices and index vectors; hot repeated batches skip the decode)
_ROUND_CACHE = 32


def _merge_disjoint(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Union of two sorted uint32 docid arrays known to be disjoint (the
    generation half and the delta half of a result share no docids by the
    shadowing invariant of ``repro.index.segments``)."""
    if len(b) == 0:
        return a if a.flags.writeable else a.copy()
    if len(a) == 0:
        return b if b.flags.writeable else b.copy()
    out = np.concatenate([a, b])
    out.sort()
    return out


def _dead_hits(dead: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Bool mask over ``ids`` marking tombstoned docids (``dead`` sorted
    int64, non-empty; ``ids`` sorted uint32)."""
    pos = np.minimum(np.searchsorted(dead, ids), len(dead) - 1)
    return dead[pos] == ids


class BlockCache:
    """Cost-weighted LRU cache keyed by (term, block) for decoded postings.

    ``capacity`` is in cost units; a single decoded 512-posting block costs 1
    and callers caching larger objects (whole-term concatenations) pass their
    block count as ``cost``, so one giant entry cannot masquerade as one
    block.  An entry costing more than the whole capacity is simply never
    retained.  Capacity 0 disables caching entirely (every lookup misses),
    which is what the stateless one-shot query helpers use.
    """

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._d: OrderedDict = OrderedDict()
        self._cost: dict = {}
        self.cost_used = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key):
        v = self._d.get(key)
        if v is None:
            self.misses += 1
            return None
        self._d.move_to_end(key)
        self.hits += 1
        return v

    def contains(self, key) -> bool:
        """Membership probe that touches neither the LRU order nor the stats
        (used by the device prefetch planner)."""
        return key in self._d

    def keys(self):
        return list(self._d.keys())

    def put(self, key, value, cost: int = 1) -> None:
        if self.capacity <= 0:
            return
        if key in self._d:
            self.cost_used -= self._cost[key]
            del self._d[key]
        self._d[key] = value
        self._cost[key] = cost
        self.cost_used += cost
        while self.cost_used > self.capacity and self._d:
            k, _ = self._d.popitem(last=False)
            self.cost_used -= self._cost.pop(k)
            self.evictions += 1

    def __len__(self) -> int:
        return len(self._d)

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "entries": len(self._d),
                "cost_used": self.cost_used}


@dataclasses.dataclass
class QueryBatch:
    """A batch of term queries executed together for cache locality.

    mode: "and" (docid arrays), "or" (BM25 top-k), or "and_scored"
    (AND semantics + BM25 top-k over the matches).
    """
    queries: list
    mode: str = "and"
    k: int = 10


MODES = ("and", "or", "and_scored")
PLACEMENTS = ("host", "device", "fused")


def _check_mode(mode) -> None:
    """Reject unknown batch modes with the registry's nearest-name
    convention (``codec.get``): list what exists, suggest what was meant."""
    if mode in MODES:
        return
    near = difflib.get_close_matches(str(mode), MODES, n=1)
    hint = f" (did you mean {near[0]!r}?)" if near else ""
    raise ValueError(
        f"unknown query mode {mode!r}{hint}; modes: {', '.join(MODES)}")


@dataclasses.dataclass(frozen=True)
class TermCaps:
    """One term's execution capabilities, resolved once at plan time from the
    codec registry's declarations (no codec-name dispatch at run time).

    codec: the codec of the term's posting blocks (None for terms that only
        exist in the mutable delta segment — they have no compressed blocks).
    arena: the codec declares an ``ArenaLayout`` — its blocks decode natively
        in the batched device work-list (otherwise they fall back to the
        per-block numpy oracle inside the arena).
    fused: the arena's fused decode tiles cover every block of the term.
    """
    codec: Optional[str]
    arena: bool
    fused: bool


class _ExecCtx:
    """One mutation epoch's frozen serving view: everything a query (or a
    pinned plan) needs to execute bit-identically regardless of writes or
    compactions that land afterwards.

    gen: the immutable :class:`~repro.index.invindex.Generation`.
    delta: frozen delta-segment snapshot (None when the epoch is unmutated).
    dead: sorted int64 tombstoned base docids (all < ``gen.n_docs``).
    doclen / n_docs / avdl: live corpus stats over the full append-only doc
        space — exactly what a from-scratch rebuild would compute, so BM25
        floats match the rebuild bitwise.
    mutated: whether serving must consult delta/tombstone state at all.
    skey: the epoch key (gid, tombstone version, delta version) that score
        -cache entries carry.
    """
    __slots__ = ("gen", "delta", "dead", "doclen", "n_docs", "avdl",
                 "mutated", "skey", "_df", "_live_dev", "_live_host")

    def __init__(self, idx):
        gen = getattr(idx, "gen", idx)
        self.gen = gen
        self.mutated = bool(getattr(idx, "mutated", False))
        self._df: dict = {}        # term -> live df memo
        self._live_dev = None      # uploaded packed live bitmap (per epoch)
        self._live_host = None     # pre-packed host words (shard ctxs only)
        if self.mutated:
            self.delta = idx.delta.snapshot()
            self.dead = idx.tomb.sorted_ids(below=gen.n_docs)
            self.doclen = idx.doclen_now()
            self.n_docs = int(idx.doc_space)
            # the same expression Generation.build's avdl uses, on the same
            # array a rebuild would be given -> bitwise-equal BM25 floats
            self.avdl = (float(np.asarray(self.doclen).mean())
                         if self.n_docs else 1.0)
            self.skey = idx.epoch
        else:
            self.delta = None
            self.dead = _EMPTY_I64
            self.doclen = gen.doclen
            self.n_docs = gen.n_docs
            self.avdl = gen.avdl
            self.skey = (gen.gid, 0, 0)

    def live_dev(self, words: int):
        """The epoch's packed live bitmap as ONE device row, uploaded on
        first use and reused for every round of every batch in the epoch
        (the gate never downloads anything).  Shard ctxs pre-pack their
        boundary-sliced words (``pack_live_words_range``), so a tombstone
        epoch uploads only each shard's span of the live bitmap."""
        if self._live_dev is None:
            packed = (self._live_host if self._live_host is not None
                      else intersect_rounds.pack_live_words(
                          self.dead, self.gen.n_docs, words))
            self._live_dev = jnp.asarray(packed)
        return self._live_dev


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """A typed, resolved execution of one ``QueryBatch``.

    placement: where the batch runs — "host" (numpy per query, grouped by
        term signature), "device" (round-batched arena work-list decode with
        device-resident candidates), or "fused" (device, with covered terms'
        blocks decoded by the fused Pallas tile kernel).  Tiny batches (<=
        ``HOST_BATCH_MAX`` queries) are auto-placed on the host even when
        arenas exist; ``note`` records that decision in the plan's repr.
    terms: per distinct referenced term, its :class:`TermCaps`.  Unknown
        terms (absent from the index) are omitted — execution ignores them.
    ctx: the pinned :class:`_ExecCtx` — the mutation epoch (generation +
        delta snapshot + tombstones) this plan executes against.  Mutations
        or ``compact()`` calls after planning do not affect this plan's
        results; re-plan to serve the new epoch.

    A plan snapshots engine state (placement follows ``to_device``); build
    plans after the engine is in its serving configuration.
    """
    mode: str
    k: int
    placement: str
    queries: tuple
    terms: Mapping[int, TermCaps]
    note: str = ""
    ctx: object = dataclasses.field(default=None, repr=False, compare=False)


# per-engine counter taxonomy (registered on every QueryEngine's registry;
# the long-form semantics are documented inline in ``__init__`` below)
_DEV_COUNTERS = (
    ("worklist_refs", "raw (term, block) work-list references, pre-dedup"),
    ("worklist_decodes", "deduped batched arena decodes actually issued"),
    ("fallback_decodes", "per-block arena decodes outside the work-list"),
    ("resident_rounds", "AND rounds run with candidates device-resident"),
    ("cand_syncs", "per-round candidate downloads (0 on resident paths)"),
    ("final_syncs", "end-of-batch result downloads (one per batch)"),
    ("extract_words_nonzero", "nonzero bitmap words the final extraction "
                              "expanded"),
    ("extract_ids", "docids the final extraction returned"),
    ("score_rounds", "ranked accumulate rounds run device-resident"),
    ("score_syncs", "per-round score downloads (always 0 when resident)"),
    ("blocks_pruned", "ranked work-list entries dropped by block-max"),
    ("blocks_scored", "ranked work-list entries actually scored"),
    ("blocks_dense", "entries served from the dense-bitmap representation"),
    ("tomb_gates", "device live-bitmap gates applied (uploads, not syncs)"),
    ("merge_syncs", "sharded ranked top-k merge collectives (one/batch)"),
    ("collective_bytes", "wire bytes moved by the top-k merge collectives"),
    ("shard_final_syncs", "per-shard end-of-batch result downloads"),
) + ARENA_COUNTERS
_ENGINE_SEQ = itertools.count()


class QueryEngine:
    def __init__(self, idx: InvertedIndex, cache_blocks: int = 4096,
                 cache_score_terms: int = 512, device: bool = False,
                 fused: bool = False):
        self.idx = idx
        self.cache = BlockCache(cache_blocks)
        self.score_cache = BlockCache(cache_score_terms)
        self.arena = None
        self._fused = fused
        self._ctx = None           # pinned ctx while executing a plan
        self._ctx_cache = None     # (epoch, _ExecCtx) for the live handle
        # resident_rounds: AND rounds executed with candidates device-resident
        # cand_syncs: per-round candidate downloads (legacy device loop only;
        #   the resident path never syncs between rounds)
        # final_syncs: end-of-batch result downloads (one per resident batch)
        # extract_words_nonzero / extract_ids: the nonzero words the final
        #   bitmap -> docid extraction expanded, and the ids it returned
        #   (every final and per-shard download counts into both)
        # score_rounds / score_syncs: ranked accumulate rounds executed
        #   device-resident / per-round score downloads (always 0 on the
        #   resident ranked path — only the final candidate bitmap syncs)
        # blocks_pruned / blocks_scored: ranked (term, block) work-list
        #   entries dropped by the block-max upper-bound test vs. scattered
        # blocks_dense: work-list entries served word-parallel from the
        #   density-adaptive bitmap representation (no unpack / prefix sum)
        # tomb_gates: live-bitmap gates applied on device (uploads, not
        #   downloads — the resident paths stay download-free under deletes)
        # merge_syncs / collective_bytes: sharded ranked batches' final
        #   top-k merges (the ONE collective per batch) and their wire bytes
        # shard_final_syncs: per-shard end-of-batch result downloads under
        #   sharded execution (each shard contributes one, like final_syncs)
        # ARENA_COUNTERS (device_calls ... rows_padded): counted by the
        #   arena this engine serves from, which counts into this registry
        #   (``_arena_ctx`` binds it)
        #
        # The counters live in a typed MetricsRegistry (repro.obs.metrics);
        # ``dev_stats`` is a read-only live view over it, so every existing
        # read keeps working while Prometheus exposition and ``scoped()``
        # delta sampling come from the registry.  Counts are per engine
        # (sub-engines own their own registries), starting at zero — the
        # same semantics as the old per-engine dict.
        self.metrics = MetricsRegistry(
            namespace="repro_index",
            const_labels={"engine": f"q{next(_ENGINE_SEQ)}", "shard": ""})
        for mname, mhelp in _DEV_COUNTERS:
            self.metrics.counter(mname, mhelp)
        self.dev_stats = DevStatsView(self.metrics,
                                      tuple(n for n, _ in _DEV_COUNTERS))
        self.tracer = get_tracer()   # process-global; disabled by default
        self.trace_lane = "engine"   # sub-engines relabel to "shard<i>"
        self._shard_cfg = None     # doc-range sharded serving config
        self._sctx_cache: dict = {}  # (skey, lo, hi) -> shard _ExecCtx
        self._last_shard_cands = None  # debug: last ranked per-shard cands
        # (gid, kind, work-list) -> the round's device arrays (decoded
        # matrices and index vectors / score rows / dense windows),
        # immutable per generation; see _round_memo
        self._round_cache: OrderedDict = OrderedDict()
        if device or fused:
            # deprecated: construct with defaults and call to_device() instead
            warnings.warn(
                "QueryEngine(device=..., fused=...) is deprecated; use "
                "QueryEngine(idx).to_device(fused=...) and execute plans "
                "(engine.execute(engine.plan(batch)))",
                DeprecationWarning, stacklevel=2)
        if device:
            self.to_device(fused=fused)

    # ---- mutation-epoch resolution ------------------------------------------ #

    def _ctx_now(self) -> _ExecCtx:
        """The live handle's current epoch ctx (rebuilt when the epoch
        changes, shared otherwise so per-ctx memos and uploads amortize)."""
        e = getattr(self.idx, "epoch", None)
        c = self._ctx_cache
        if c is None or c[0] != e:
            self._ctx_cache = c = (e, _ExecCtx(self.idx))
        return c[1]

    def _cur(self) -> _ExecCtx:
        """The ctx this call executes under: the plan-pinned ctx inside
        ``execute``, else the live epoch — walking ``self.arena`` forward to
        the current generation after a compaction swap."""
        if self._ctx is not None:
            return self._ctx
        ctx = self._ctx_now()
        if (self.arena is not None
                and getattr(self.arena.idx, "gen", self.arena.idx)
                is not ctx.gen):
            self.arena = ctx.gen.to_device(build_fused=self._fused)
        return ctx

    def _arena_ctx(self, ctx: _ExecCtx):
        """The device arena serving ``ctx``'s generation: the engine's own
        arena when it matches, else the generation's cached arena (how a
        plan pinned to a pre-compaction generation keeps its blocks).  It
        counts into this engine's registry."""
        a = self.arena
        if a is None or getattr(a.idx, "gen", a.idx) is not ctx.gen:
            a = ctx.gen.to_device(build_fused=self._fused)
        a.bind_metrics(self.metrics)
        return a

    def to_device(self, fused=None, shards=None, mesh=None,
                  bounds=None) -> "QueryEngine":
        """Switch the engine onto the device-resident arenas: all subsequent
        decodes go through batched lane-parallel device calls (with numpy
        fallback per block for codecs the arena doesn't cover).  ``fused``
        additionally decodes eligible rounds' blocks through the fused
        Pallas tile kernel (``kernels/decode_fused``); its tile arenas are
        only built (or upgraded onto a cached arena) when actually
        requested.

        Doc-range sharded serving: any of ``shards`` (a count — boundaries
        derived from build metadata, :meth:`repro.index.shards.ShardSpec
        .derive`), ``bounds`` (explicit boundary tuple ``(0, ..., n_docs)``,
        uneven and empty ranges legal), or ``mesh`` (a 1-D jax mesh, one
        device per shard — absent or undersized, the shards run logically on
        the default device with identical results) splits every generation
        into self-contained per-shard engines (``_shard_engines``).  All
        resident rounds then run shard-local; ranked modes merge with ONE
        collective per batch (``_execute_sharded``)."""
        if fused is not None:
            self._fused = fused
        if shards is not None or bounds is not None or mesh is not None:
            b = tuple(int(x) for x in bounds) if bounds is not None else None
            n = (int(shards) if shards is not None
                 else len(b) - 1 if b is not None
                 else int(mesh.devices.size))
            if n < 1:
                raise ValueError(f"need at least one shard, got {n}")
            if b is not None and len(b) - 1 != n:
                raise ValueError(
                    f"bounds {b} define {len(b) - 1} shard(s), not {n}")
            self._shard_cfg = {"n": n, "bounds": b, "mesh": mesh}
            self.arena = None           # shards own the arenas
            self._shard_engines(self._ctx_now())    # build eagerly
            return self
        arena = self.idx.to_device(build_fused=self._fused)
        if (self.arena is None
                or getattr(self.arena.idx, "gen", self.arena.idx)
                is not getattr(self.idx, "gen", self.idx)):
            self.arena = arena
        self.arena.bind_metrics(self.metrics)
        return self

    # ---- decode through the cache ------------------------------------------ #
    # Block entries are keyed (term, block, field, gid) with field 0 = docids
    # and field 1 = TFs, so AND queries (which never touch TFs) only pay for
    # the docid stream.  Whole-term concatenations are cached as
    # (term, -1, field, gid) at cost = block count: a hot term used both as
    # the rarest term (concat) and as a skip target (blocks) is deliberately
    # held twice — that trades bounded memory, correctly charged against
    # capacity, for not re-decoding or re-concatenating on either path.  The
    # trailing gid keys every entry to its immutable generation: a compaction
    # swap simply stops referencing the old gid's entries (they age out of
    # the LRU) and can never serve them to the new generation's queries.
    # Every cached array is frozen read-only before insertion: accessors hand
    # out the cache's backing arrays, and a caller mutating one would
    # otherwise silently corrupt later query results.

    @staticmethod
    def _freeze(a: np.ndarray) -> np.ndarray:
        a.setflags(write=False)
        return a

    def _decode_block_field(self, t: int, bi: int, field: int) -> np.ndarray:
        ctx = self._cur()
        key = (t, bi, field, ctx.gen.gid)
        v = self.cache.get(key)
        if v is None:
            if self.arena is not None:
                # cache-eviction stragglers outside the batched work-list
                self.metrics.inc("fallback_decodes")
                v = self._arena_ctx(ctx).decode_blocks([(t, bi, field)])[0]
            elif field == 0:
                v = ctx.gen.decode_block_ids(t, bi)
            else:
                v = ctx.gen.decode_block_tfs(t, bi)
            v = self._freeze(v)
            self.cache.put(key, v)
        return v

    def decode_block_ids(self, t: int, bi: int) -> np.ndarray:
        return self._decode_block_field(t, bi, 0)

    def decode_block_tfs(self, t: int, bi: int) -> np.ndarray:
        return self._decode_block_field(t, bi, 1)

    def decode_block(self, t: int, bi: int):
        return self.decode_block_ids(t, bi), self.decode_block_tfs(t, bi)

    def _term_concat(self, t: int, field: int, decode_one) -> np.ndarray:
        ctx = self._cur()
        key = (t, -1, field, ctx.gen.gid)
        v = self.cache.get(key)
        if v is None:
            nb = ctx.gen.n_blocks(t)
            if nb == 0:
                # frozen like every other accessor result (zero-length, so one
                # shared read-only singleton is contract-equivalent to caching)
                return _EMPTY_U32
            if self.arena is not None:
                self._prefetch_blocks([(t, bi, field) for bi in range(nb)])
            parts = [decode_one(t, bi) for bi in range(nb)]
            v = self._freeze(parts[0] if nb == 1 else np.concatenate(parts))
            self.cache.put(key, v, cost=nb)
        return v

    # ---- device prefetch planner ------------------------------------------- #

    def _prefetch_blocks(self, entries: list) -> None:
        """Dedupe a (term, block, field) work-list against the cache and
        decode the misses in one batched arena call."""
        ctx = self._cur()
        gid = ctx.gen.gid
        missing, seen = [], set()
        for e in entries:
            if e in seen or self.cache.contains(e + (gid,)):
                continue
            seen.add(e)
            missing.append(e)
        self.metrics.inc("worklist_decodes", len(missing))
        if not missing:
            return
        arena = self._arena_ctx(ctx)
        for e, a in zip(missing, arena.decode_blocks(missing)):
            self.cache.put(e + (gid,), self._freeze(a))

    def _prefetch_terms(self, terms, fields=(0, 1)) -> None:
        ctx = self._cur()
        entries = []
        for t in terms:
            if t not in ctx.gen.terms:
                continue
            nb = ctx.gen.n_blocks(t)
            for f in fields:
                if not self.cache.contains((t, -1, f, ctx.gen.gid)):
                    entries.extend((t, bi, f) for bi in range(nb))
        self._prefetch_blocks(entries)

    def term_ids(self, t: int) -> np.ndarray:
        return self._term_concat(t, 0, self.decode_block_ids)

    def term_tfs(self, t: int) -> np.ndarray:
        return self._term_concat(t, 1, self.decode_block_tfs)

    def term_postings(self, t: int):
        return self.term_ids(t), self.term_tfs(t)

    # ---- live (mutation-aware) posting views -------------------------------- #

    def _df_live(self, t: int, ctx: _ExecCtx) -> int:
        """Live document frequency of term t under ``ctx``: generation df
        minus tombstoned postings plus delta postings (memoized per ctx).
        ``known`` under mutation means df_live > 0 — exactly the terms a
        from-scratch rebuild would still contain."""
        if not ctx.mutated:
            tp = ctx.gen.terms.get(t)
            return tp.df if tp is not None else 0
        v = ctx._df.get(t)
        if v is None:
            tp = ctx.gen.terms.get(t)
            base = tp.df if tp is not None else 0
            if base and len(ctx.dead):
                base -= int(_dead_hits(ctx.dead, self.term_ids(t)).sum())
            ctx._df[t] = v = base + ctx.delta.df(t)
        return v

    def _live_postings(self, t: int, ctx: _ExecCtx):
        """Term t's live postings under ``ctx``: generation postings minus
        tombstones, merge-sorted with the delta postings (disjoint by the
        shadowing invariant) — identical arrays to a from-scratch rebuild's
        ``term_ids``/``term_tfs``."""
        if t in ctx.gen.terms:
            ids, tfs = self.term_ids(t), self.term_tfs(t)
            if len(ctx.dead) and len(ids):
                keep = ~_dead_hits(ctx.dead, ids)
                ids, tfs = ids[keep], tfs[keep]
        else:
            ids, tfs = _EMPTY_U32, _EMPTY_U32
        dids, dtfs = ctx.delta.postings(t)
        if len(dids):
            if len(ids) == 0:
                return dids.copy(), dtfs.copy()
            ids = np.concatenate([ids, dids])
            tfs = np.concatenate([tfs, dtfs])
            order = np.argsort(ids, kind="stable")
            ids, tfs = ids[order], tfs[order]
        return ids, tfs

    # ---- fused decode-and-intersect ---------------------------------------- #

    def _block_plan(self, t: int, cand: np.ndarray):
        """Skip-table pruning: candidate cut points per block of term t and
        the indices of blocks whose docid range contains a candidate."""
        gen = self._cur().gen
        firsts = gen.block_firsts(t).astype(cand.dtype)  # avoid a cast copy
        cut = np.empty(len(firsts) + 1, np.int64)
        cut[:-1] = np.searchsorted(cand, firsts)
        cut[-1] = len(cand)
        return cut, np.flatnonzero(cut[1:] > cut[:-1])

    def _term_fused(self, t: int, sel) -> bool:
        """Fallback capability probe for un-planned calls (``and_query`` and
        friends); plans resolve this once per term instead."""
        return (self._fused and self.arena is not None
                and self.arena.has_fused(t, sel))

    def _intersect_plan(self, t: int, cut: np.ndarray, sel: np.ndarray,
                        cand: np.ndarray, fused: bool | None = None) -> np.ndarray:
        if len(sel) == 0:
            return np.zeros(0, np.uint32)
        if self._term_fused(t, sel) if fused is None else fused:
            return self.arena.fused_and(t, sel, cand)
        out = [intersect.intersect_sorted(self.decode_block_ids(t, int(bi)),
                                          cand[cut[bi]:cut[bi + 1]])
               for bi in sel]
        return np.concatenate(out)

    def _intersect_term(self, t: int, cand: np.ndarray) -> np.ndarray:
        """Intersect sorted candidates with term t, decoding only the blocks
        whose docid range [first_i, first_{i+1}) contains a candidate."""
        cut, sel = self._block_plan(t, cand)
        return self._intersect_plan(t, cut, sel, cand)

    def and_many(self, queries: list,
                 terms: Mapping[int, TermCaps] | None = None) -> list:
        """AND all queries together, round-batched for the device arenas —
        the legacy loop that syncs every query's candidates to the host
        between rounds (planned execution now runs the device-resident
        ``_and_many_resident`` instead; this stays for direct callers and as
        the host-candidate reference).  Serves the current generation only —
        planned execution layers tombstones and the delta on top.

        Round r intersects every still-active query with its (r+1)-th rarest
        term; the round's (term, block) needs across the WHOLE batch are
        deduped and decoded in one arena call, so each hot block decodes at
        most once per batch and the Python-loop count drops from O(total
        selected blocks) to O(rounds).  Results are bit-identical to
        ``and_query`` per query.

        ``terms`` is the plan's resolved per-term capability map; when absent
        (direct calls) capabilities are probed on the fly.
        """
        def term_fused(t, sel):
            return (terms[t].fused if terms is not None
                    else self._term_fused(t, sel))

        gen = self._cur().gen
        qterms = [sorted((t for t in q if t in gen.terms),
                         key=lambda t: gen.terms[t].df) for q in queries]
        for ts in qterms:               # raw seed-term block references,
            if ts:                      # pre-dedup (work-list metric)
                self.metrics.inc("worklist_refs", gen.n_blocks(ts[0]))
        if self.arena is not None:
            self._prefetch_terms({ts[0] for ts in qterms if ts}, fields=(0,))
        cands = [self.term_ids(ts[0]) if ts else _EMPTY_U32 for ts in qterms]
        owned = [False] * len(queries)
        r = 1
        while True:
            active = [i for i, ts in enumerate(qterms)
                      if len(ts) > r and len(cands[i])]
            if not active:
                break
            plans, worklist = {}, []
            for i in active:
                t = qterms[i][r]
                cut, sel = self._block_plan(t, cands[i])
                fused = term_fused(t, sel)
                plans[i] = (t, cut, sel, fused)
                self.metrics.inc("worklist_refs", len(sel))
                if self.arena is not None and not fused:
                    worklist.extend((t, int(bi), 0) for bi in sel)
            if self.arena is not None:
                self._prefetch_blocks(worklist)
            for i in active:
                t, cut, sel, fused = plans[i]
                cands[i] = self._intersect_plan(t, cut, sel, cands[i], fused)
                owned[i] = True
            if self.arena is not None:
                # every active query's surviving candidates just landed on
                # the host for the next round's block plan
                self.metrics.inc("cand_syncs", len(active))
            r += 1
        return [c if o else c.copy() for c, o in zip(cands, owned)]

    # ---- device-resident AND rounds ---------------------------------------- #

    def _select_blocks_static(self, t: int, cov_f: np.ndarray,
                              cov_l: np.ndarray) -> np.ndarray:
        """Blocks of term t whose [first, last] docid range overlaps any of
        the seed coverage intervals — computed purely from build-time skip
        metadata, so no candidate state is needed on the host.  The selection
        is a superset of the blocks holding candidates, which is all the
        probe-and-scatter round needs for exactness."""
        gen = self._cur().gen
        f = gen.block_firsts(t)
        l = gen.block_lasts(t)
        j = np.searchsorted(cov_l, f)            # first interval ending >= f
        hit = j < len(cov_l)
        jc = np.minimum(j, max(len(cov_f) - 1, 0))
        return np.flatnonzero(hit & (cov_f[jc] <= l))

    def _round_memo(self, key, build):
        """Bounded memo for a round's device arrays: identical work-lists
        (the benchmark loop, hot repeated batches) reuse the decoded
        matrices and index vectors instead of decoding again.  Keys carry
        the gid, so entries are immutable for their lifetime."""
        v = self._round_cache.get(key)
        if v is None:
            v = build()
            self._round_cache[key] = v
            while len(self._round_cache) > _ROUND_CACHE:
                self._round_cache.popitem(last=False)
        else:
            self._round_cache.move_to_end(key)
        return v

    def _stack_worklist(self, entries: list):
        """Shared round discipline for the resident AND and ranked paths:
        decode the unique (term, block) rows of a round's (qslot, term,
        block) entries once per source (``DeviceArena.decode_round``: one
        matrix per codec present, one for the numpy fallback), and index
        each matrix with a bucketed row vector, never slicing or stacking a
        row.  A ``round/rows`` span covers the decode, a ``round/stack``
        span the index and query-slot vectors.  Returns [(mat, rows,
        qslots, ns, pos), ...], one per source: ``rows`` / ``qslots`` /
        ``ns`` are (P,) device vectors for the P-row ``mat`` (P a bucket;
        padding carries n=0, which scatters nothing) and ``pos`` the
        work-list positions the source serves.  Memoized per (gid,
        work-list)."""
        key = (self._cur().gen.gid, "ids", tuple(entries))
        return self._round_memo(key,
                                lambda: self._stack_worklist_build(entries))

    def _stack_worklist_build(self, entries: list):
        ar = self._arena_ctx(self._cur())
        with self.tracer.span("round/rows", lane=self.trace_lane,
                              entries=len(entries)) as sp:
            sources, decoded = ar.decode_round(
                [(t, bi) for _, t, bi in entries])
            if sp is not None:
                sp.args["decoded"] = decoded
        self.metrics.inc("worklist_decodes", decoded)
        with self.tracer.span("round/stack", lane=self.trace_lane,
                              rows=len(entries),
                              bucket=sum(len(r) for _, _, r, _ in sources)):
            qslot = np.asarray([q for q, _, _ in entries], np.int32)
            out = []
            for mat, pos, rows, ns in sources:
                qs = np.zeros(len(rows), np.int32)
                qs[:len(pos)] = qslot[pos]
                out.append((mat, jnp.asarray(rows), jnp.asarray(qs),
                            jnp.asarray(ns), pos))
        return out

    def _stack_dense(self, entries: list, ubs=None, with_codes: bool = False):
        """Index a round's dense-bitmap work-list (``repro.core
        .dense_bitmap`` blocks, selected per block through the arena's
        ``dense_slot`` capability table): each entry's row of the arena's
        128-word window matrix ``dense_words`` — and, ``with_codes``, of the
        score arena's window-aligned ``dense_tiles`` — as bucketed row
        vectors that the dense kernels gather on the device.  Returns
        (rows, tile_rows, qslots, w0, act, ub); padding carries act=False
        and ub=0, which every dense kernel treats as inert.  The index
        vectors are memoized per (gid, block-list)."""
        ctx = self._cur()
        ar = self._arena_ctx(ctx)
        n = len(entries)
        p = _bucket(n)
        blocks = tuple((t, bi) for _, t, bi in entries)

        def build():
            with self.tracer.span("round/stack", lane=self.trace_lane,
                                  rows=n, bucket=p):
                sel = np.zeros(p, np.int32)
                sel[:n] = [ar.dense_slot[b] for b in blocks]
                tile_rows = None
                if with_codes:
                    sa = ar.ensure_scores().scores
                    srows = np.zeros(p, np.int32)
                    srows[:n] = [sa.dense_slot[b] for b in blocks]
                    tile_rows = jnp.asarray(srows)
                w0 = np.zeros(p, np.int32)
                w0[:n] = ar.dense_w0[sel[:n]]
                return jnp.asarray(sel), tile_rows, jnp.asarray(w0)

        key = (ctx.gen.gid, "dense", with_codes, blocks)
        rows, tile_rows, w0 = self._round_memo(key, build)
        qs = np.zeros(p, np.int32)
        qs[:n] = [q for q, _, _ in entries]
        act = np.zeros(p, bool)
        act[:n] = True
        ub = np.zeros(p, np.int32)
        ub[:n] = ubs if ubs is not None else _UB_ALWAYS
        return (rows, tile_rows, jnp.asarray(qs), w0, jnp.asarray(act),
                jnp.asarray(ub))

    def _score_rows(self, sa, pairs: list, p: int):
        """Memoized ``ScoreArena.rows`` for a round's (term, block) work
        -list, padded to the jit bucket by repeating entry 0 (padded lanes
        scatter with n=0, so the values are inert)."""
        def build():
            with self.tracer.span("round/stack", lane=self.trace_lane,
                                  rows=len(pairs), bucket=p):
                return sa.rows(pairs + [pairs[0]] * (p - len(pairs)))

        key = (self._cur().gen.gid, "codes", p, tuple(pairs))
        return self._round_memo(key, build)

    def _and_qterms(self, queries: list, ctx: _ExecCtx) -> list:
        """Per-query known terms sorted rarest-first (df ascending) with the
        resident AND path's mutation-epoch semantics: a query whose live
        terms include a delta-only term has no generation matches at all and
        collapses to the ``[]`` sentinel (seeds empty; the caller unions in
        the delta-segment scan).  Factored out so sharded execution can
        resolve the batch ONCE on the parent and hand each shard its
        restriction (``_shard_qterms``)."""
        idx = ctx.gen
        if not ctx.mutated:
            return [sorted((t for t in q if t in idx.terms),
                           key=lambda t: idx.terms[t].df) for q in queries]
        qterms = []
        for q in queries:
            known = [t for t in q if self._df_live(t, ctx) > 0]
            if any(t not in idx.terms for t in known):
                qterms.append([])       # delta-only live term: no base match
            else:
                qterms.append(sorted(known, key=lambda t: idx.terms[t].df))
        return qterms

    def _and_many_resident(self, queries: list,
                           terms: Mapping[int, TermCaps] | None = None,
                           use_fused: bool = False,
                           qterms: list | None = None) -> list:
        """AND the batch device-resident; the single host copy turns the
        final bitmaps into sorted docid arrays (``_and_bitmap_resident``
        keeps everything before that copy on device — the ``and_scored``
        path consumes the bitmap directly and never downloads it)."""
        bm, _, _ = self._and_bitmap_resident(queries, terms, use_fused,
                                             qterms=qterms)
        self.metrics.inc("final_syncs")
        return intersect_rounds.extract_ids(np.asarray(bm)[:len(queries)],
                                            self._cur().gen.n_docs,
                                            self.metrics)

    def _and_bitmap_resident(self, queries: list,
                             terms: Mapping[int, TermCaps] | None = None,
                             use_fused: bool = False,
                             qterms: list | None = None):
        """AND the batch with candidates device-resident across rounds.

        Round 0 scatters every query's rarest term into its row of a
        segmented candidate bitmap (one device array for the whole batch);
        round r >= 1 decodes the round's deduped (term, block) work-list,
        probes each decoded docid against its query's bitmap segment and
        scatters the survivors — all on device
        (``kernels/intersect_rounds``).  Block selection is conservative and
        static (seed-term coverage intervals from the skip tables), so no
        candidate ever returns to the host until the single final copy.
        Under ``use_fused`` the rounds decode the packed gap tiles with the
        Pallas kernel instead of the codec arenas.

        Under a mutation epoch the seed bitmap is ANDed with the epoch's
        packed live row right after round 0 (one upload, zero downloads):
        tombstoned docs fail every later probe, so the final bitmaps hold
        exactly the generation's LIVE intersections.  A query whose live
        terms include a delta-only term has no generation matches at all and
        seeds empty; the caller unions in the delta-segment scan.

        Returns (bitmap, qterms, cov) — the (nqp, words) device bitmap, the
        per-query known terms sorted rarest-first, and the per-query seed
        coverage intervals (for further static block selection).  Results
        are bit-identical to ``and_query`` per query.  An injected
        ``qterms`` (sharded execution) replaces the per-query resolution —
        the caller already computed it against the GLOBAL epoch and
        restricted it to this engine's doc range.
        """
        ctx = self._cur()
        idx = ctx.gen
        ar = self._arena_ctx(ctx)
        nq = len(queries)
        words, _ = intersect_rounds.bitmap_geometry(idx.n_docs)
        if nq == 0:
            return jnp.zeros((0, words), jnp.uint32), [], {}
        if qterms is None:
            qterms = self._and_qterms(queries, ctx)
        nqp = _bucket(nq)
        bm = jnp.zeros((nqp, words), jnp.uint32)

        def run_round(bm, plain, fused_pairs, dense, active_idx, probe):
            """One committed AND round: every representation split (sparse
            arena decode, one call per source; fused Pallas decode, one call
            per bit-width bucket; dense bitmap windows) probes the same OLD
            bitmap and ORs survivors into ONE shared new bitmap — exact
            because a block is served by exactly one call, so the calls'
            docid sets are disjoint — then a single commit
            folds active rows forward (empty splits leave active rows
            empty: with no survivors their intersections are empty).  The
            splits' rows are built first, then one ``round/launch`` span
            covers the kernel calls."""
            active = np.zeros(nqp, bool)
            active[active_idx] = True
            sources = self._stack_worklist(plain) if plain else []
            parts = ar.fused_round(fused_pairs) if fused_pairs else []
            if dense:
                drows, _, dqs, dw0, dact, _ = self._stack_dense(dense)
            with self.tracer.span("round/launch", lane=self.trace_lane):
                new = jnp.zeros_like(bm)
                for mat, rows, qs, ns, _ in sources:
                    new = intersect_rounds.round_accumulate(
                        new, mat, qs, ns, bm, rows, probe=probe)
                for ids, qs, ns in parts:
                    new = intersect_rounds.round_accumulate(
                        new, ids, jnp.asarray(qs), jnp.asarray(ns), bm,
                        probe=probe)
                if dense:
                    new = intersect_rounds.dense_round_accumulate(
                        new, ar.dense_words, dqs, dw0, dact, bm, drows,
                        probe=probe)
                return intersect_rounds.round_commit(bm, new,
                                                     jnp.asarray(active))

        def split_dense(pairs):
            """Route (qslot, t, bi) entries to their serving representation
            (per-block capability: the arena's dense window table)."""
            sparse, dense = [], []
            for e in pairs:
                (dense if (e[1], e[2]) in ar.dense_slot else sparse).append(e)
            self.metrics.inc("blocks_dense", len(dense))
            return sparse, dense

        # round 0: seed every query's bitmap row with its rarest term
        seeds = [i for i, ts in enumerate(qterms)
                 if ts and idx.terms[ts[0]].df]
        for ts in qterms:               # raw seed-term block references,
            if ts:                      # pre-dedup (work-list metric)
                self.metrics.inc("worklist_refs", idx.n_blocks(ts[0]))
        pairs0 = [(i, qterms[i][0], bi) for i in seeds
                  for bi in range(idx.n_blocks(qterms[i][0]))]
        plain0, dense0 = split_dense(pairs0)
        with self.tracer.span("and/seed", lane=self.trace_lane, nq=nq,
                              plain=len(plain0), dense=len(dense0)):
            bm = run_round(bm, plain0, [], dense0, seeds, probe=False)
            self.tracer.fence(bm)
        if ctx.mutated and len(ctx.dead):
            # gate the seed with the epoch's live row: every later round
            # only keeps survivors, so one AND suffices for the whole batch
            with self.tracer.span("and/tomb_gate", lane=self.trace_lane,
                                  dead=len(ctx.dead)):
                bm = bm & ctx.live_dev(words)[None, :]
                self.tracer.fence(bm)
            self.metrics.inc("tomb_gates")
        cov = {i: (idx.block_firsts(qterms[i][0]),
                   idx.block_lasts(qterms[i][0])) for i in seeds}

        live = set(seeds)
        r = 1
        while True:
            active = [i for i in live if len(qterms[i]) > r]
            if not active:
                break
            self.metrics.inc("resident_rounds")
            plain, fused_pairs, dense = [], [], []
            for i in active:
                t = qterms[i][r]
                sel = self._select_blocks_static(t, *cov[i])
                self.metrics.inc("worklist_refs", len(sel))
                f = use_fused and (terms[t].fused if terms is not None
                                   else ar.has_fused(t, sel))
                for bi in sel:
                    e = (i, t, int(bi))
                    if (t, int(bi)) in ar.dense_slot:
                        dense.append(e)
                    elif f:
                        fused_pairs.append(e)
                    else:
                        plain.append(e)
            self.metrics.inc("blocks_dense", len(dense))
            with self.tracer.span("and/round", lane=self.trace_lane, r=r,
                                  plain=len(plain), fused=len(fused_pairs),
                                  dense=len(dense)):
                bm = run_round(bm, plain, fused_pairs, dense, active,
                               probe=True)
                self.tracer.fence(bm)
            r += 1

        return bm, qterms, cov

    def and_query(self, terms: list) -> np.ndarray:
        ctx = self._cur()
        if ctx.mutated:
            return self._and_query_mut(list(terms), ctx)
        return self._and_gen([t for t in terms if t in ctx.gen.terms], ctx)

    def _and_gen(self, terms: list, ctx: _ExecCtx) -> np.ndarray:
        """AND over generation postings only (terms already known)."""
        terms = sorted(terms, key=lambda t: ctx.gen.terms[t].df)
        if not terms:
            return np.zeros(0, np.uint32)
        cand = self.term_ids(terms[0])
        owned = False                           # does the caller own `cand`?
        for t in terms[1:]:
            if len(cand) == 0:
                break
            cand = self._intersect_term(t, cand)
            owned = True
        # single-term (or empty-first-term) queries would otherwise hand back
        # the cache's frozen backing array
        return cand if owned else cand.copy()

    def _and_query_mut(self, terms: list, ctx: _ExecCtx) -> np.ndarray:
        """Live AND under a mutation epoch: the generation intersection
        (tombstone-filtered) unioned with the delta-segment scan — bitwise
        what ``and_query`` on a from-scratch rebuild returns.

        ``known`` keeps terms with live postings (df_live > 0), matching the
        rebuild's unknown-term semantics: a term whose postings are all
        tombstoned vanishes from the rebuilt index and is ignored, while a
        live term still ANDs.  If any live term exists only in the delta, no
        generation doc can match it (delta docids shadow their base copies),
        so the generation half is empty.
        """
        known = [t for t in terms if self._df_live(t, ctx) > 0]
        if not known:
            return np.zeros(0, np.uint32)
        if all(t in ctx.gen.terms for t in known):
            base = self._and_gen(known, ctx)
            if len(ctx.dead) and len(base):
                base = base[~_dead_hits(ctx.dead, base)]
        else:
            base = _EMPTY_U32
        return _merge_disjoint(base, ctx.delta.scan_and(known))

    # ---- BM25 -------------------------------------------------------------- #

    def term_scores(self, t: int):
        ctx = self._cur()
        key = (t,) + ctx.skey
        v = self.score_cache.get(key)
        if v is None:
            if ctx.mutated:
                ids, tfs = self._live_postings(t, ctx)
                ids = self._freeze(ids)
                df = len(ids)
            else:
                ids, tfs = self.term_ids(t), self.term_tfs(t)
                df = ctx.gen.terms[t].df
            sc = bm25_scores(tfs, ctx.doclen[ids], df, ctx.n_docs, ctx.avdl)
            v = (ids, self._freeze(sc))
            self.score_cache.put(key, v)
        return v

    def or_query(self, terms: list, k: int = 10):
        ctx = self._cur()
        if ctx.mutated:
            use = [t for t in terms if self._df_live(t, ctx) > 0]
        else:
            use = [t for t in terms if t in ctx.gen.terms]
        parts = [self.term_scores(t) for t in use]
        if not parts:
            return []
        ids = np.concatenate([p[0] for p in parts])
        sc = np.concatenate([p[1] for p in parts])
        docs, inv = np.unique(ids, return_inverse=True)
        if len(docs) == 0:
            return []
        tot = np.zeros(len(docs))
        np.add.at(tot, inv, sc)
        return topk_select(docs, tot, k)

    def _score_docs(self, terms: list, docs: np.ndarray, k: int) -> list:
        """The host float top-k oracle: exact BM25 over ``docs`` (term-level
        score vectors through the score cache), selected with the shared
        argpartition + docid-tiebreak rule (:func:`repro.index.scores
        .topk_select`).  Under a mutation epoch the score vectors are the
        LIVE ones (``_live_postings``), accumulated in the same query-term
        order as the unmutated path."""
        if len(docs) == 0:
            return []
        ctx = self._cur()
        scores = np.zeros(len(docs))
        for t in terms:
            if ctx.mutated:
                if self._df_live(t, ctx) <= 0:
                    continue        # unknown (or fully tombstoned) scores 0
            elif t not in ctx.gen.terms or not ctx.gen.terms[t].blocks:
                continue            # unknown or zero-posting term scores 0
            ids, sc = self.term_scores(t)
            pos = np.searchsorted(ids, docs)
            pos = np.clip(pos, 0, len(ids) - 1)
            hit = ids[pos] == docs
            scores += np.where(hit, sc[pos], 0.0)
        return topk_select(docs, scores, k)

    def _score_docs_blockwise(self, terms: list, docs: np.ndarray,
                              k: int) -> list:
        """Exact float rescore touching only the blocks that hold ``docs``
        (the ranked device path's final stage: candidates are few, so whole
        -term decodes would waste the pruning win).  Bitwise identical to
        :meth:`_score_docs` — same float formula (``bm25_scores``), same
        per-doc term accumulation order, same tie rule.  Generation-only
        (the mutated ranked path rescores with :meth:`_score_docs`, whose
        score vectors carry the live stats)."""
        if len(docs) == 0:
            return []
        ctx = self._cur()
        idx = ctx.gen
        scores = np.zeros(len(docs))
        plans = []
        prefetch = []
        for t in terms:
            if t not in idx.terms or not idx.terms[t].blocks:
                continue            # unknown or zero-posting term scores 0
            firsts = idx.block_firsts(t)
            bi = np.searchsorted(firsts, docs, side="right") - 1
            bi = np.where(idx.block_lasts(t)[np.maximum(bi, 0)] >=
                          docs.astype(np.int64), bi, -1)
            plans.append((t, bi))
            if self.arena is not None:
                prefetch.extend((t, int(b), f)
                                for b in np.unique(bi[bi >= 0]) for f in (0, 1))
        if prefetch:
            self._prefetch_blocks(prefetch)
        for t, bi in plans:
            df = idx.terms[t].df
            for b in np.unique(bi[bi >= 0]):
                sel = np.flatnonzero(bi == b)
                ids, tfs = self.decode_block(t, int(b))
                pos = np.searchsorted(ids, docs[sel])
                pos = np.clip(pos, 0, len(ids) - 1)
                hit = ids[pos] == docs[sel]
                sub = sel[hit]
                sc = bm25_scores(tfs[pos[hit]], ctx.doclen[docs[sub]], df,
                                 ctx.n_docs, ctx.avdl)
                scores[sub] += sc
        return topk_select(docs, scores, k)

    def _rescore_batch_blockwise(self, queries: list, cand: list,
                                 k: int) -> list:
        """Batch form of :meth:`_score_docs_blockwise`: the per-(term, block)
        decode + score work is amortized over the WHOLE batch — each term
        scores the union of its queries' candidates once, then every query
        accumulates its own docs in query-term order from the shared
        per-term vectors.  Bitwise identical to mapping
        :meth:`_score_docs_blockwise` over the batch: same elementwise
        ``bm25_scores`` values, same per-doc term accumulation order, and a
        candidate a term doesn't hold adds +0.0 exactly as the host oracle's
        ``np.where`` does (contributions are strictly positive, so no -0.0
        can ever sit in an accumulator).  Generation-only, like the
        per-query form."""
        union = {}
        for q, c in zip(queries, cand):
            if len(c) == 0:
                continue
            for t in dict.fromkeys(q):
                union.setdefault(t, []).append(c)
        ctx = self._cur()
        idx = ctx.gen
        plans, prefetch = [], []
        for t, parts in union.items():
            if t not in idx.terms or not idx.terms[t].blocks:
                continue            # unknown or zero-posting term scores 0
            docs = (parts[0] if len(parts) == 1
                    else np.unique(np.concatenate(parts)))
            firsts = idx.block_firsts(t)
            bi = np.searchsorted(firsts, docs, side="right") - 1
            bi = np.where(idx.block_lasts(t)[np.maximum(bi, 0)] >=
                          docs.astype(np.int64), bi, -1)
            plans.append((t, docs, bi))
            if self.arena is not None:
                prefetch.extend((t, int(b), f)
                                for b in np.unique(bi[bi >= 0])
                                for f in (0, 1))
        if prefetch:
            self._prefetch_blocks(prefetch)
        shared = {}
        for t, docs, bi in plans:
            df = idx.terms[t].df
            vals = np.zeros(len(docs))
            for b in np.unique(bi[bi >= 0]):
                sel = np.flatnonzero(bi == b)
                ids, tfs = self.decode_block(t, int(b))
                pos = np.searchsorted(ids, docs[sel])
                pos = np.clip(pos, 0, len(ids) - 1)
                hit = ids[pos] == docs[sel]
                sub = sel[hit]
                vals[sub] = bm25_scores(tfs[pos[hit]], ctx.doclen[docs[sub]],
                                        df, ctx.n_docs, ctx.avdl)
            shared[t] = (docs, vals)
        out = []
        for q, c in zip(queries, cand):
            if len(c) == 0:
                out.append([])
                continue
            scores = np.zeros(len(c))
            for t in q:             # query-term order, duplicates kept
                e = shared.get(t)
                if e is not None:
                    docs, vals = e
                    scores += vals[np.searchsorted(docs, c)]
            out.append(topk_select(c, scores, k))
        return out

    def and_query_scored(self, terms: list, k: int = 10):
        return self._score_docs(terms, self.and_query(terms), k)

    # ---- device-resident ranked top-k (OR / and_scored) --------------------- #

    def _prune_ranked_blocks(self, sa, occs: list, r: int, theta0: int,
                             iq: int = 1 << 16) -> tuple:
        """Block-max prune for occurrence ``r`` of an OR query's term list:
        drop blocks whose upper bound — own block-max plus every other
        occurrence's max code over the block's docid range (BMW-style
        aligned bounds, 0 when the other term has no posting there) plus the
        quantization margin — cannot beat ``theta0``.  Dropped blocks only
        lose contributions of docs provably outside the true top-k (see
        ``repro/index/scores.py``).

        Returns (keep, n_pruned, ub[keep]): the kept blocks' bounds ride to
        the device, where every later round re-tests them against the
        adaptively promoted theta (``kernels/topk``) and self-compacts the
        work-list with zero host syncs.  ``iq`` deflates the static
        threshold under tombstone-only epochs (Q16.16, 65536 = identity)."""
        t = occs[r]
        gen = self._cur().gen
        nb = gen.n_blocks(t)
        if nb == 0:
            return np.arange(0), 0, _EMPTY_I64
        firsts = gen.block_firsts(t)
        lasts = gen.block_lasts(t)
        base = sa.slot[(t, 0)]          # a term's slots are contiguous
        ub = sa.block_max[base:base + nb].astype(np.int64) + len(occs)
        for t2 in occs[:r] + occs[r + 1:]:
            ub += sa.range_max_many(t2, firsts, lasts)
        if theta0 <= 0:
            return np.arange(nb), 0, ub
        keep = np.flatnonzero(ub > (theta0 * iq) >> 16)
        return keep, nb - len(keep), ub[keep]

    def _iq_tomb(self, ts: list, ctx: _ExecCtx) -> int:
        """Per-query Q16.16 threshold deflation ``floor(2**16 / Rmax)`` for
        a tombstone-only epoch (the re-arm note in ``repro/index/scores.py``):
        ``Rmax`` is the worst live/generation idf ratio over the query's
        terms — deletes only shrink df, so every ratio is >= 1 — and the
        integer floor is nudged down until ``iq * Rmax <= 2**16``, so float
        rounding can never push a scaled threshold above theta / Rmax."""
        n = ctx.n_docs
        rmax = 1.0
        for t in ts:
            tp = ctx.gen.terms.get(t)
            if tp is None:
                continue
            dfg = tp.df
            dfl = self._df_live(t, ctx)
            if dfl <= 0 or dfl >= dfg:
                continue
            ig = float(np.log(1.0 + (n - dfg + 0.5) / (dfg + 0.5)))
            il = float(np.log(1.0 + (n - dfl + 0.5) / (dfl + 0.5)))
            if ig > 0.0 and il > ig:
                rmax = max(rmax, il / ig)
        iq = int((1 << 16) / rmax)
        while iq * rmax > (1 << 16):
            iq -= 1
        return max(iq, 1)

    def _ranked_resident(self, queries: list, k: int, mode: str,
                         terms: Mapping[int, TermCaps] | None = None,
                         use_fused: bool = False) -> list:
        """Ranked top-k with scores device-resident across rounds.

        Round r scatters every query's r-th strongest term occurrence
        (quantized impact codes next to the decoded docid rows) into a
        segmented score accumulator (``kernels/topk``) — for ``and_scored``
        gated by the AND-result bitmap, which itself never left the device
        (``_and_bitmap_resident``).  OR work-lists are block-max pruned
        against the static per-query threshold theta0 before any decode.
        The single host copy per batch is the compacted candidate bitmap
        (k-th quantized sum minus the quantization margin — a provable
        superset of the float top-k), which the block-lazy float oracle
        rescores exactly: results are bitwise identical to the host path,
        ties broken by ascending docid.

        After every round the per-query theta is PROMOTED on device: the
        pooled k-th statistic of the accumulated state (``kernels/topk
        .pooled_threshold``) is a sound, monotone lower bound on the final
        k-th sum, and each work-list entry carries its quantized upper bound
        to the device, so later rounds drop entries that can no longer beat
        the promoted theta — the work-list compacts itself against promoted
        bounds with zero per-round host syncs.

        Under a delta-bearing mutation epoch the quantized tables carry
        generation-time stats, so the theta cut is disarmed (theta0 = 0,
        margin so large the compact keeps every member — the candidate set
        degrades to the full live membership bitmap, still an exact
        superset) and OR rounds gate with the epoch's live row
        (``gated=True``: tombstoned docs never enter the accumulator or the
        membership bitmap — no new downloads).  TOMBSTONE-ONLY epochs stay
        armed instead: deletes only raise idf, so a per-query Q16.16
        deflation ``iq = floor(2**16 / Rmax)`` keeps every threshold
        comparison sound against the generation-time tables (the re-arm
        note in ``repro/index/scores.py``), with theta0 re-derived from the
        tombstone-filtered top-code tables (``ScoreArena.theta0_live``).
        The final rescore unions the delta-segment scan per query and runs
        the live-stat float oracle; a fresh compaction re-arms fully.
        """
        ctx = self._cur()
        idx = ctx.gen
        nq = len(queries)
        if nq == 0:
            return []
        known, base_ts, tomb_only, armed, margins_l, iqs_l = \
            self._ranked_params(queries, k, ctx)
        if known is None:
            return [[] for _ in queries]
        acc, member, margins, iq_dev, width, _ = self._ranked_accumulate(
            queries, k, mode, terms, use_fused, base_ts=base_ts, armed=armed,
            tomb_only=tomb_only, margins_l=margins_l, iqs_l=iqs_l)
        theta = topk.topk_threshold(acc, min(k, width))
        cand_bm = topk.candidate_bitmap(acc, member, theta,
                                        jnp.asarray(margins), iq_dev)
        # the single host copy: candidate bitmaps -> exact float rescore
        self.metrics.inc("final_syncs")
        cand = intersect_rounds.extract_ids(np.asarray(cand_bm)[:nq],
                                            idx.n_docs, self.metrics)
        return self._ranked_rescore(queries, cand, k, mode, known, ctx)

    def _ranked_params(self, queries: list, k: int, ctx: _ExecCtx):
        """The batch's epoch-derived ranked parameters, resolved once
        against the GLOBAL ctx (sharded execution computes them on the
        parent and injects them into every shard — a shard's own view would
        mis-derive them: shard-local dfs deflate iq unsoundly, and a shard
        never sees the delta, so it would wrongly re-arm a delta-bearing
        epoch).  Returns (known, base_ts, tomb_only, armed, margins_l,
        iqs_l), with known None when the batch trivially yields empties."""
        idx = ctx.gen
        if ctx.mutated:
            known = [[t for t in q if self._df_live(t, ctx) > 0]
                     for q in queries]
            base_ts = [[t for t in ts if t in idx.terms] for ts in known]
        else:
            known = [[t for t in q if t in idx.terms] for q in queries]
            base_ts = known
        if k <= 0 or not any(known):
            return None, None, False, False, None, None
        # tombstone-only epoch: no delta docs and corpus stats untouched
        # (deletes never shrink the doc space or rewrite doclens — the
        # array check guards the doclen-override corner), so pruning stays
        # armed through the idf-ratio deflation
        tomb_only = (ctx.mutated and len(ctx.delta) == 0
                     and ctx.n_docs == idx.n_docs
                     and np.array_equal(ctx.doclen, idx.doclen))
        armed = not ctx.mutated or tomb_only
        margins_l = [len(ts) if armed else _KEEP_ALL_MARGIN for ts in known]
        iqs_l = ([self._iq_tomb(ts, ctx) if ts else 1 << 16 for ts in known]
                 if tomb_only else [1 << 16] * len(queries))
        return known, base_ts, tomb_only, armed, margins_l, iqs_l

    def _ranked_accumulate(self, queries: list, k: int, mode: str,
                           terms: Mapping[int, TermCaps] | None,
                           use_fused: bool, *, base_ts: list, armed: bool,
                           tomb_only: bool, margins_l: list, iqs_l: list,
                           qterms: list | None = None,
                           theta0_l: list | None = None):
        """The round-loop core of :meth:`_ranked_resident`: accumulate the
        batch's quantized impact codes device-resident and return the final
        device state ``(acc, member, margins, iq_dev, width, words)`` — no
        threshold, no download.  Epoch-derived inputs (``base_ts`` ...
        ``iqs_l``) are INJECTED (:meth:`_ranked_params`): under sharded
        execution this engine serves one doc-range shard and they must come
        from the parent's global epoch.  ``theta0_l`` optionally overrides
        the static OR thresholds — the sharded path pools per-shard theta0
        host-side (max over shards is sound: some shard provably holds k
        docs reaching it) and seeds every shard with the pooled value; the
        per-round adaptive promotion stays shard-local, so rounds still run
        with zero cross-shard syncs."""
        ctx = self._cur()
        idx = ctx.gen
        nq = len(queries)
        self.arena.ensure_scores()
        sa = self.arena.scores
        words, _ = intersect_rounds.bitmap_geometry(idx.n_docs)
        nqp = _bucket(nq)
        width = topk.accum_width(idx.n_docs)
        acc = jnp.zeros((nqp, width), jnp.uint32)
        member = jnp.zeros((nqp, words), jnp.uint32)
        gate = cov = None
        if mode == "and_scored":
            gate, _, cov = self._and_bitmap_resident(queries, terms,
                                                     use_fused, qterms=qterms)
        eff_gate = gate
        if gate is None and ctx.mutated and len(ctx.dead):
            # OR mode under deletes: the epoch's live row gates every lane
            with self.tracer.span("ranked/tomb_gate", lane=self.trace_lane,
                                  dead=len(ctx.dead)):
                eff_gate = jnp.broadcast_to(ctx.live_dev(words),
                                            (nqp, words))
            self.metrics.inc("tomb_gates")
        ar = self.arena
        order = [sorted(ts, key=lambda t: -sa.term_max[t]) for ts in base_ts]
        margins = np.zeros(nqp, np.int32)
        margins[:nq] = margins_l
        iqs = np.full(nqp, 1 << 16, np.int64)
        iqs[:nq] = iqs_l
        if mode == "or" and armed:
            theta0 = (list(theta0_l) if theta0_l is not None else
                      [(sa.theta0_live(ts, k, ctx.dead) if tomb_only
                        else sa.theta0(ts, k)) for ts in base_ts])
        else:
            theta0 = [0] * nq
        th0 = np.zeros(nqp, np.uint32)
        th0[:nq] = theta0
        theta_dev = jnp.asarray(th0)
        iq_dev = jnp.asarray(iqs.astype(np.uint32))
        nrounds = max((len(ts) for ts in order), default=0)
        for r in range(nrounds):
            with self.tracer.span("ranked/round", lane=self.trace_lane, r=r,
                                  mode=mode) as rsp:
                plain, fused_pairs, dense = [], [], []
                plain_ub, fused_ub, dense_ub = [], [], []
                for i in range(nq):
                    ts = order[i]
                    if len(ts) <= r or (cov is not None and i not in cov):
                        continue    # done, or AND seed empty -> nothing scores
                    t = ts[r]
                    if mode == "or":
                        sel, pruned, ubs_i = self._prune_ranked_blocks(
                            sa, ts, r, theta0[i], int(iqs[i]))
                    else:
                        sel, pruned, ubs_i = (
                            self._select_blocks_static(t, *cov[i]), 0, None)
                    self.metrics.inc("blocks_pruned", pruned)
                    self.metrics.inc("blocks_scored", len(sel))
                    f = use_fused and (terms[t].fused if terms is not None
                                       else ar.has_fused(t, sel))
                    for j, bi in enumerate(sel):
                        e = (i, t, int(bi))
                        u = (int(ubs_i[j]) if ubs_i is not None
                             else _UB_ALWAYS)
                        if ((t, int(bi)) in ar.dense_slot
                                and (t, int(bi)) in sa.dense_slot):
                            dense.append(e)
                            dense_ub.append(u)
                        elif f:
                            fused_pairs.append(e)
                            fused_ub.append(u)
                        else:
                            plain.append(e)
                            plain_ub.append(u)
                self.metrics.inc("blocks_dense", len(dense))
                self.metrics.inc("score_rounds")
                if rsp is not None:
                    rsp.args.update(plain=len(plain), fused=len(fused_pairs),
                                    dense=len(dense))
                # one score call per sparse source and per fused bucket:
                # integer adds sum and bit adds OR, whichever call order
                calls = []
                if plain:
                    pairs = [(t, bi) for _, t, bi in plain]
                    ubs = np.asarray(plain_ub, np.int32)
                    for mat, rows, qs, ns, pos in self._stack_worklist(plain):
                        p = len(rows)
                        ubp = np.zeros(p, np.int32)
                        ubp[:len(pos)] = ubs[pos]
                        codes = self._score_rows(
                            sa, [pairs[j] for j in pos], p)
                        calls.append((mat, qs, codes, ns, ubp, rows))
                if fused_pairs:
                    calls += [(ids, jnp.asarray(fqs), fcodes,
                               jnp.asarray(fns), ubf, None)
                              for ids, fcodes, fqs, fns, ubf
                              in ar.fused_round_scored(fused_pairs, fused_ub)]
                if dense:
                    drows, dtrows, dqs, dw0, _, dub = self._stack_dense(
                        dense, dense_ub, with_codes=True)
                gated = eff_gate is not None
                with self.tracer.span("round/launch", lane=self.trace_lane):
                    for ids, qs, codes, ns, ub, rows in calls:
                        acc, member = topk.score_round(
                            acc, member, ids, qs, codes, ns,
                            eff_gate if gated else member, jnp.asarray(ub),
                            theta_dev, iq_dev, rows, gated=gated)
                    if dense:
                        acc, member = topk.dense_score_round(
                            acc, member, sa.dense_tiles, ar.dense_words, dqs,
                            dw0, dub, theta_dev, iq_dev,
                            eff_gate if gated else member, drows, dtrows,
                            gated=gated)
                    if (mode == "or" and armed and k <= width // 32
                            and r + 1 < nrounds):
                        # adaptive promotion: the pooled k-th is a sound,
                        # monotone lower bound on the final k-th sum (sound
                        # only with the full k — fewer pooled groups than k
                        # would over-promote)
                        theta_dev = jnp.maximum(
                            theta_dev, topk.pooled_threshold(acc, k))
                self.tracer.fence(acc)
        return acc, member, margins, iq_dev, width, words

    def _ranked_rescore(self, queries: list, cand: list, k: int, mode: str,
                        known: list, ctx: _ExecCtx) -> list:
        """The exact float tail shared by the unsharded and sharded ranked
        paths: block-lazy batch rescore on an unmutated epoch, else the
        per-query delta-segment union + live-stat oracle.  ``cand`` holds
        GLOBAL sorted docids (sharded execution translates each shard's
        extraction by its range base before concatenating), so the tail is
        bitwise identical either way.  Span ``ranked/rescore``."""
        with self.tracer.span("ranked/rescore", lane=self.trace_lane,
                              nq=len(queries), mode=mode,
                              cands=sum(len(c) for c in cand)):
            if not ctx.mutated:
                return self._rescore_batch_blockwise(queries, cand, k)
            out = []
            for i, (q, c) in enumerate(zip(queries, cand)):
                if mode == "or":
                    d = ctx.delta.scan_any(known[i])
                else:
                    d = (ctx.delta.scan_and(known[i]) if known[i]
                         else _EMPTY_U32)
                out.append(self._score_docs(q, _merge_disjoint(c, d), k))
            return out

    # ---- doc-range sharded execution ---------------------------------------- #

    def _shard_engines(self, ctx: _ExecCtx):
        """The per-shard serving set for ``ctx``'s generation: a
        :class:`repro.index.shards.ShardSpec` plus one sub-engine per
        NON-EMPTY shard (empty ranges hold ``None``), each over a
        self-contained stats-fixed shard generation
        (:func:`repro.index.shards.shard_generation`).  The whole set is
        built eagerly and cached ON the generation keyed by (bounds, fused),
        so a ``compact()`` swaps every shard atomically: a pinned plan keeps
        the old generation's set addressable through its ctx, and the new
        epoch's first query builds the new generation's set — mixed
        -generation serving is impossible by construction.  With a mesh of
        one device per shard, each shard's arenas (and its rounds, via
        ``_pinned``) are placed on its own device; otherwise the shards run
        logically on the default device with identical results."""
        from . import shards as shards_lib
        cfg = self._shard_cfg
        gen = ctx.gen
        bounds = cfg["bounds"]
        if bounds is not None and bounds[-1] == gen.n_docs:
            spec = shards_lib.ShardSpec(bounds)
        else:
            # derived boundaries — also the fallback when explicit bounds
            # went stale across a compaction (the doc space changed)
            spec = shards_lib.ShardSpec.derive(gen, cfg["n"])
        mesh = cfg["mesh"]
        key = (spec.bounds, self._fused)
        cache = getattr(gen, "_shard_serving", None)
        if cache is None:
            cache = gen._shard_serving = {}
        got = cache.get(key)
        if got is None:
            devs = (list(mesh.devices.flat)
                    if mesh is not None and mesh.devices.size == spec.n_shards
                    else None)
            engs = []
            for s, (lo, hi) in enumerate(spec.ranges()):
                if hi <= lo:
                    engs.append(None)
                    continue
                dev = devs[s] if devs is not None else None
                with (jax.default_device(dev) if dev is not None
                      else contextlib.nullcontext()):
                    sgen = shards_lib.shard_generation(gen, lo, hi)
                    eng = QueryEngine(sgen).to_device(fused=self._fused)
                    eng.arena.ensure_scores()
                eng._shard_device = dev
                eng.trace_lane = f"shard{s}"    # own Perfetto lane
                eng.metrics.relabel(shard=f"s{s}")
                engs.append(eng)
            cache[key] = got = (spec, engs)
        return got[0], got[1], mesh

    def _shard_ctx(self, ctx: _ExecCtx, lo: int, hi: int, sgen) -> _ExecCtx:
        """A shard's frozen view of the parent epoch: tombstones translated
        into the shard's local docid space, an EMPTY delta snapshot (delta
        docids all sit above the generation's doc space, so no shard serves
        them — the parent unions the delta scan into final results), and
        the parent's live stats where they matter.  The packed live bitmap
        is PRE-SLICED at the shard boundary (``pack_live_words_range``), so
        a tombstone epoch uploads only each shard's words, not the whole
        corpus's, on every shard."""
        key = (ctx.skey, lo, hi)
        got = self._sctx_cache.get(key)
        if got is not None:
            return got
        sctx = _ExecCtx.__new__(_ExecCtx)
        sctx.gen = sgen
        sctx.mutated = ctx.mutated
        sctx._df = {}
        sctx._live_dev = None
        sctx._live_host = None
        if ctx.mutated:
            from .segments import DeltaSegment
            sctx.delta = DeltaSegment.empty_snapshot()
        else:
            sctx.delta = None
        dead = ctx.dead
        sctx.dead = ((dead[(dead >= lo) & (dead < hi)] - lo)
                     if len(dead) else _EMPTY_I64)
        sctx.doclen = np.asarray(ctx.doclen)[lo:hi]
        sctx.n_docs = hi - lo
        sctx.avdl = ctx.avdl
        sctx.skey = tuple(ctx.skey) + (lo, hi)
        if len(sctx.dead):
            words, _ = intersect_rounds.bitmap_geometry(sgen.n_docs)
            sctx._live_host = intersect_rounds.pack_live_words_range(
                ctx.dead, lo, hi, words)
        self._sctx_cache[key] = sctx
        return sctx

    @staticmethod
    def _shard_qterms(ts: list, sgen) -> list:
        """One query's global rarest-first AND term list restricted to a
        shard.  A known term with no postings in the shard's doc range means
        NO doc in the range can match the conjunction — the ``[]`` sentinel
        (same convention as the delta-only case).  Otherwise the parent's
        order is kept verbatim: shard dfs are fixed up to the global ones,
        so re-sorting shard-side would reproduce it anyway."""
        if not ts or any(t not in sgen.terms for t in ts):
            return []
        return list(ts)

    @staticmethod
    @contextlib.contextmanager
    def _pinned(eng: "QueryEngine", sctx: _ExecCtx):
        """Run a sub-engine call under its shard ctx (and its mesh device,
        when placed): the shard's rounds then resolve ``_cur()`` to the
        shard's frozen epoch view, never the parent's."""
        prev = eng._ctx
        eng._ctx = sctx
        dev = getattr(eng, "_shard_device", None)
        try:
            if dev is not None:
                with jax.default_device(dev):
                    yield
            else:
                yield
        finally:
            eng._ctx = prev

    def _execute_sharded(self, plan: ExecutionPlan, ctx: _ExecCtx) -> list:
        """Planned execution over the doc-range shard set: every resident
        round runs shard-local (doc-wise partitioning means AND candidates
        and score accumulators never cross shards — zero cross-shard
        candidate syncs), ranked modes merge with ONE collective of
        per-shard (k-th sum, candidate count) statistics, and the exact
        float tail runs on the parent against global docids.  Results are
        bitwise identical to the unsharded paths."""
        queries = [list(q) for q in plan.queries]
        fused = plan.placement == "fused"
        spec, engs, mesh = self._shard_engines(ctx)
        parts = [(lo, hi, eng, self._shard_ctx(ctx, lo, hi, eng.idx))
                 for (lo, hi), eng in zip(spec.ranges(), engs)
                 if eng is not None]
        if plan.mode == "and":
            return self._sharded_and(queries, fused, parts, ctx)
        return self._sharded_ranked(queries, plan.k, plan.mode, fused,
                                    parts, mesh, ctx)

    def _sharded_and(self, queries: list, fused: bool, parts: list,
                     ctx: _ExecCtx) -> list:
        """AND across shards: the parent resolves the batch's known terms
        once, each shard intersects its restriction device-resident, and the
        per-shard extractions concatenate in range order (already globally
        sorted — ranges are disjoint and ascending)."""
        qterms = self._and_qterms(queries, ctx)
        per_q = [[] for _ in queries]
        for lo, hi, eng, sctx in parts:
            sub_q = [self._shard_qterms(ts, eng.idx) for ts in qterms]
            with self._pinned(eng, sctx):
                ids = eng._and_many_resident(queries, None, fused,
                                             qterms=sub_q)
            self.metrics.inc("shard_final_syncs")
            for i, a in enumerate(ids):
                if len(a):
                    per_q[i].append(a + np.uint32(lo))
        base = [(ps[0] if len(ps) == 1 else np.concatenate(ps)) if ps
                else _EMPTY_U32.copy() for ps in per_q]
        if not ctx.mutated:
            return base
        out = []
        for q, b in zip(queries, base):
            known = [t for t in q if self._df_live(t, ctx) > 0]
            d = ctx.delta.scan_and(known) if known else _EMPTY_U32
            out.append(_merge_disjoint(b, d))
        return out

    def _sharded_ranked(self, queries: list, k: int, mode: str, fused: bool,
                        parts: list, mesh, ctx: _ExecCtx) -> list:
        """Ranked top-k across shards, margin-preserving merge:

        1. the parent derives the epoch parameters ONCE
           (:meth:`_ranked_params`) and, for armed OR batches, pools the
           per-shard static thresholds host-side (max over shards — sound:
           the argmax shard provably holds k docs reaching its theta0);
        2. every shard runs the full round loop shard-local
           (:meth:`_ranked_accumulate` under ``_pinned``) — zero cross
           -shard candidate syncs, the adaptive promotion stays per-shard;
        3. the ONE collective: per-shard (k-th quantized sum, candidate
           count) statistics all-gather + max (``collectives
           .merge_topk_stats`` — under ``shard_map`` when a mesh places the
           shards, host-stacked otherwise, same wire bytes either way).
           theta_merged = max_s theta_s <= the global k-th sum, so cutting
           every shard at theta_merged - margin keeps every global top-k
           doc: the union of per-shard candidate bitmaps stays a guaranteed
           superset of the float top-k under the SAME quantization-margin
           contract as the unsharded path (parent margins >= shard margins,
           global iq deflation injected);
        4. per-shard candidate extraction, translated to global docids and
           concatenated in range order, feeds the parent's exact float tail
           (:meth:`_ranked_rescore`) — bitwise identical to unsharded."""
        nq = len(queries)
        known, base_ts, tomb_only, armed, margins_l, iqs_l = \
            self._ranked_params(queries, k, ctx)
        if known is None or not parts:
            return [[] for _ in queries]
        theta0_l = None
        if mode == "or" and armed:
            pooled = [0] * nq
            for lo, hi, eng, sctx in parts:
                sa = eng.arena.ensure_scores().scores
                for i, ts in enumerate(base_ts):
                    sts = [t for t in ts if t in eng.idx.terms]
                    if not sts:
                        continue
                    th = (sa.theta0_live(sts, k, sctx.dead) if tomb_only
                          else sa.theta0(sts, k))
                    if th > pooled[i]:
                        pooled[i] = int(th)
            theta0_l = pooled
        and_q = (self._and_qterms(queries, ctx) if mode == "and_scored"
                 else None)
        per_shard, th_parts, cnt_parts = [], [], []
        for lo, hi, eng, sctx in parts:
            sts = [[t for t in ts if t in eng.idx.terms] for ts in base_ts]
            qt = ([self._shard_qterms(ts, eng.idx) for ts in and_q]
                  if and_q is not None else None)
            with self._pinned(eng, sctx):
                acc, member, margins, iq_dev, _, _ = eng._ranked_accumulate(
                    queries, k, mode, None, fused, base_ts=sts, armed=armed,
                    tomb_only=tomb_only, margins_l=margins_l, iqs_l=iqs_l,
                    qterms=qt, theta0_l=theta0_l)
                # raw k on purpose: a shard holding fewer than k scored docs
                # reports theta 0 (the sound degenerate answer) — min(k,
                # width) would report its width-th sum, which can EXCEED the
                # global k-th and break the superset contract
                th, cnt = topk.topk_stats(acc, k)
            per_shard.append((lo, hi, eng, sctx, acc, member, margins,
                              iq_dev))
            th_parts.append(th)
            cnt_parts.append(cnt)
        from repro.distributed import collectives
        with self.tracer.span("sharded/merge", lane=self.trace_lane,
                              shards=len(parts), nq=nq):
            theta_m, _, wire = collectives.merge_topk_stats(th_parts,
                                                            cnt_parts,
                                                            mesh=mesh)
        self.metrics.inc("merge_syncs")
        self.metrics.inc("collective_bytes", int(wire))
        theta_dev = jnp.asarray(theta_m.astype(np.uint32))
        cand_parts = [[] for _ in queries]
        shard_cands = []
        for lo, hi, eng, sctx, acc, member, margins, iq_dev in per_shard:
            with self._pinned(eng, sctx):
                bm = topk.candidate_bitmap(acc, member, theta_dev,
                                           jnp.asarray(margins), iq_dev)
                self.metrics.inc("shard_final_syncs")
                ids = intersect_rounds.extract_ids(np.asarray(bm)[:nq],
                                                   hi - lo, self.metrics)
            shard_cands.append(ids)
            for i, a in enumerate(ids):
                if len(a):
                    cand_parts[i].append(a + np.uint32(lo))
        self._last_shard_cands = shard_cands
        cand = [(ps[0] if len(ps) == 1 else np.concatenate(ps)) if ps
                else _EMPTY_U32 for ps in cand_parts]
        return self._ranked_rescore(queries, cand, k, mode, known, ctx)

    # ---- planned execution -------------------------------------------------- #

    def plan(self, batch: QueryBatch,
             placement: Optional[str] = None) -> ExecutionPlan:
        """Resolve a batch into a typed :class:`ExecutionPlan` (span
        ``engine/plan``); see :meth:`_plan_impl` for the full contract."""
        with self.tracer.span("engine/plan", lane=self.trace_lane,
                              mode=batch.mode, nq=len(batch.queries)):
            return self._plan_impl(batch, placement)

    def _plan_impl(self, batch: QueryBatch,
                   placement: Optional[str] = None) -> ExecutionPlan:
        """Resolve a batch into a typed :class:`ExecutionPlan`: placement
        (host / device / fused, following the engine's current arena state)
        plus every referenced term's codec capabilities, read once from the
        codec registry's declarations.  ``execute(plan)`` then runs with no
        per-codec or per-flag branching.

        Auto-placement (``placement=None``) demotes small batches to the
        host using the measured :class:`CrossoverTable` from the committed
        ``BENCH_query.json`` when one exists, else the static
        ``HOST_BATCH_MAX`` rule; ``plan.note`` records which source decided.
        An explicit ``placement`` skips the demotion entirely (the serving
        path and benchmarks use this to pin a placement per run) and is
        validated against the engine's arena state up front.

        The plan also pins the current mutation epoch (:class:`_ExecCtx`):
        its generation, a frozen delta snapshot, and the tombstone set.
        Executing the plan after later inserts/deletes/compactions returns
        the SAME results it would have returned at plan time."""
        _check_mode(batch.mode)
        ctx = self._cur()
        note = ""
        resident = self.arena is not None or self._shard_cfg is not None
        if placement is not None:
            if placement not in PLACEMENTS:
                raise ValueError(f"unknown placement {placement!r}; "
                                 f"placements: {PLACEMENTS}")
            if placement != "host" and not resident:
                raise ValueError(
                    f"explicit placement {placement!r} needs device arenas; "
                    "call to_device() on this engine first")
            if placement == "fused" and not self._fused:
                raise ValueError(
                    "explicit placement 'fused' needs fused tile arenas; "
                    "call to_device(fused=True) on this engine first")
            note = f"placement {placement!r} pinned by caller"
        else:
            placement = ("fused" if resident and self._fused
                         else "device" if resident else "host")
            if placement != "host":
                n = len(batch.queries)
                xo = get_crossover()
                cut = xo.cut_for(batch.mode) if xo is not None else None
                if cut is not None:
                    if n <= cut:
                        note = (f"auto-placed host: batch={n} <= "
                                f"host_batch_max={cut} for "
                                f"mode={batch.mode!r} "
                                f"(measured crossover, {xo.source}, "
                                f"sizes={list(xo.sizes)})")
                        placement = "host"
                elif n <= HOST_BATCH_MAX:
                    reason = ("no BENCH_query.json baseline" if xo is None
                              else f"{xo.source}: no host->device crossover "
                                   f"measured for mode={batch.mode!r}")
                    note = (f"auto-placed host: batch={n} <= "
                            f"HOST_BATCH_MAX={HOST_BATCH_MAX} "
                            f"(static rule; {reason})")
                    placement = "host"
        if self._shard_cfg is not None and placement != "host":
            spec, _, mesh = self._shard_engines(ctx)
            snote = (f"sharded x{spec.n_shards} bounds={list(spec.bounds)} "
                     f"({'mesh-placed' if mesh is not None else 'logical'})")
            note = f"{note}; {snote}" if note else snote
        if ctx.mutated:
            mnote = (f"pinned epoch {ctx.skey}: {len(ctx.dead)} tombstone(s), "
                     f"{len(ctx.delta)} delta doc(s)")
            note = f"{note}; {mnote}" if note else mnote
        terms: dict[int, TermCaps] = {}
        for q in batch.queries:
            for t in q:
                if t in terms:
                    continue
                if t in ctx.gen.terms:
                    blocks = ctx.gen.terms[t].blocks
                    name = blocks[0][1].codec if blocks else None
                    spec = codec_lib.get(name) if name is not None else None
                    # sharded plans record the nominal capability only —
                    # each shard re-probes its OWN arena's fused coverage
                    # at execution (its block geometry differs)
                    terms[t] = TermCaps(
                        codec=name,
                        arena=bool(spec is not None and spec.arena is not None),
                        fused=(placement == "fused"
                               and (self._shard_cfg is not None
                                    or self.arena.has_fused(
                                        t, range(len(blocks))))))
                elif ctx.delta is not None and ctx.delta.has_term(t):
                    # delta-only term: no compressed blocks, host scan only
                    terms[t] = TermCaps(codec=None, arena=False, fused=False)
        return ExecutionPlan(mode=batch.mode, k=batch.k, placement=placement,
                             queries=tuple(tuple(q) for q in batch.queries),
                             terms=terms, note=note, ctx=ctx)

    def execute(self, work) -> list:
        """Run an :class:`ExecutionPlan` (span ``engine/execute``); see
        :meth:`_execute_impl` for the full contract."""
        if isinstance(work, QueryBatch):
            work = self.plan(work)
        with self.tracer.span("engine/execute", lane=self.trace_lane,
                              mode=work.mode, placement=work.placement,
                              nq=len(work.queries)):
            return self._execute_impl(work)

    def _execute_impl(self, work) -> list:
        """Run an :class:`ExecutionPlan`; results align with the planned
        queries.  Passing a ``QueryBatch`` is a deprecated shim that plans
        implicitly (bit-identical results).

        Execution happens under the plan's pinned ctx: the generation, delta
        snapshot, and tombstone set resolved at plan time — so a
        ``compact()`` racing an in-flight plan never changes its results
        (the pinned generation's arena and caches stay addressable by gid).

        On the host placement queries are processed grouped by sorted term
        signature so queries sharing terms hit the decoded-block/score caches
        back to back.  On the device/fused placements AND semantics run
        round-batched through ``_and_many_resident`` — one deduped arena
        decode per round across the whole batch — and OR/scored modes run the
        resident ranked accumulator.
        """
        if isinstance(work, QueryBatch):
            work = self.plan(work)
        plan: ExecutionPlan = work
        _check_mode(plan.mode)
        ctx: _ExecCtx = plan.ctx if plan.ctx is not None else self._cur()
        if plan.placement != "host":
            if self._shard_cfg is not None:
                # sharded serving: the shard set (not self.arena) holds the
                # arenas; sub-engines pin their shard ctxs per call
                prev_ctx, self._ctx = self._ctx, ctx
                try:
                    return self._execute_sharded(plan, ctx)
                finally:
                    self._ctx = prev_ctx
            if self.arena is None:
                raise ValueError(
                    f"plan placement {plan.placement!r} needs device arenas; "
                    "call to_device() on this engine (or re-plan on it) first")
            arena = self._arena_ctx(ctx)
            if plan.placement == "fused" and arena._pk is None:
                raise ValueError(
                    "plan placement 'fused' needs fused tile arenas; call "
                    "to_device(fused=True) on this engine (or re-plan on it) "
                    "first")
            prev_ctx, self._ctx = self._ctx, ctx
            prev_arena, self.arena = self.arena, arena
            try:
                return self._execute_device(plan, ctx)
            finally:
                self._ctx, self.arena = prev_ctx, prev_arena
        fn = {"and": self.and_query,
              "or": lambda q: self.or_query(q, plan.k),
              "and_scored": lambda q: self.and_query_scored(q, plan.k)}[plan.mode]
        order = sorted(range(len(plan.queries)),
                       key=lambda i: tuple(sorted(plan.queries[i])))
        results = [None] * len(plan.queries)
        # a host plan stays pinned to host intersection AND host block
        # decodes even on an engine that has arenas — placement is the
        # plan's contract, not a hint (and per-block arena calls would be
        # strictly slower than the numpy oracle for the tiny batches the
        # auto-placement sends here); the bits are identical either way.
        prev_ctx, self._ctx = self._ctx, ctx
        prev_fused, self._fused = self._fused, False
        prev_arena, self.arena = self.arena, None
        try:
            for i in order:
                results[i] = fn(list(plan.queries[i]))
        finally:
            self._ctx = prev_ctx
            self._fused, self.arena = prev_fused, prev_arena
        return results

    def _execute_device(self, plan: ExecutionPlan, ctx: _ExecCtx) -> list:
        queries = [list(q) for q in plan.queries]
        fused = plan.placement == "fused"
        if plan.mode == "and":
            base = self._and_many_resident(queries, plan.terms, fused)
            if not ctx.mutated:
                return base
            out = []
            for q, b in zip(queries, base):
                known = [t for t in q if self._df_live(t, ctx) > 0]
                d = ctx.delta.scan_and(known) if known else _EMPTY_U32
                out.append(_merge_disjoint(b, d))
            return out
        return self._ranked_resident(queries, plan.k, plan.mode,
                                     plan.terms, fused)
