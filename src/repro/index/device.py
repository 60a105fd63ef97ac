"""Device-resident posting arenas: the compressed index as contiguous device
arrays, decodable in bulk without host round-trips.

The host engine (``repro.index.engine``) stores every (term, block) as its own
little ``Encoded`` and decodes through Python one block at a time, so the
paper's SIMD-decode win (Table VII) never reaches the serving path: per AND
round the engine pays O(selected blocks) interpreter iterations.  A
``DeviceArena`` flattens the whole index once at build time — and it does so
*generically*: any codec whose registry entry declares an
:class:`repro.core.codec.ArenaLayout` capability participates, with zero
codec-name (or column-count) dispatch in this module.  Per declared layout
the arena holds:

  * **one arena per declared column** — every block's words for that column
    (ctrl / data / exceptions / …, per the codec's own
    :class:`repro.core.codec.ArenaColumn` declarations), concatenated into
    one device array of the column's dtype.  Exception-bearing codecs (the
    Group-PFD family) are therefore first-class: their patch streams live in
    a third column and are applied inside the fixed-shape ``decode_block``.
  * **tables** — per-entry per-column offset/length plus posting count and
    first-docid (skip-table) columns, so any (term, block, field) is
    addressable on device by a handful of integers.

On top sit two batched execution paths:

  * ``decode_blocks`` — ONE jitted call per codec present in the work-list
    decodes all of that codec's entries lane-parallel: each work-list lane
    gathers its padded control/data slice from the arenas (``dynamic_slice``
    under ``vmap``) and runs the layout's fixed-shape ``decode_block``, fused
    with the d-gap prefix sum and first-docid add.  Work-lists are padded to
    power-of-two buckets so jit variants stay bounded.  Blocks whose codec
    declares no arena capability (and empty blocks) fall back to the numpy
    decoder per block, preserving exact results for every registered codec.
  * ``fused_and`` / ``fused_round`` — the ``kernels/decode_fused`` Pallas
    path: block gaps re-packed into fixed (rows, 128) tiles at the block's
    own bit width rounded up to ``decode_fused.BW_BUCKETS``, decoded in
    VMEM with the next work-list block's DMA double-buffered via
    scalar-prefetched work-list indices; the candidate probe runs in XLA on
    the decoded rows.

``stats`` is a read-only view of the arena's counters (``ARENA_COUNTERS``:
device calls and blocks decoded per path, postings decoded on the device,
and how a round's rows reach the kernels: by row index into a decoded
matrix, one at a time, or as bucket padding).  They live in a
``MetricsRegistry``: the arena's own until ``QueryEngine.to_device()`` binds
the engine's (``bind_metrics``), so an engine's ``dev_stats`` carries them.
A resident round's work-list decode (``decode_round``) decodes each distinct
(term, block) once per round.

Generations (the streaming mutable index): an arena is built from — and
belongs to — exactly one immutable ``Generation`` (``repro.index.segments``
holds the mutable side).  ``Generation.to_device`` caches the arena on the
generation object, so an ``ExecutionPlan`` pinned to an old generation keeps
resolving the old arena after a ``compact()`` swap, while new plans build (or
reuse) the next generation's arena; nothing in this module is mutated in
place.  Tombstone gating happens above, in the engine, as one packed
live-bitmap AND per epoch (``intersect_rounds.pack_live_words``) — the arena
tables themselves never change under deletes.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import codec as codec_lib
from repro.core.bits import ebw_np
from repro.obs.metrics import DevStatsView, MetricsRegistry
from repro.obs.trace import get_tracer
from repro.kernels import decode_fused, intersect_rounds, topk
from repro.kernels.bitpack import LANES
from repro.kernels.intersect import bitmap_build_np

_MIN_WORKLIST = 8             # smallest jit bucket
_DECODE_CHUNK = 256           # lanes per step of a longer work-list decode

# the arena's counters; every engine registry declares them too
ARENA_COUNTERS = (
    ("device_calls", "jitted arena work-list decode calls"),
    ("blocks_device", "blocks decoded by the arena work-list decode"),
    ("blocks_host", "blocks decoded by the numpy fallback"),
    ("fused_calls", "fused tile kernel calls (one per bit-width bucket)"),
    ("fused_blocks", "blocks decoded by the fused tile kernel"),
    ("decode_postings", "real postings decoded on the device (no padding)"),
    ("rows_sliced", "device rows cut one at a time out of a decoded or "
                    "uploaded matrix"),
    ("rows_stacked", "rows put one at a time into a round's row matrix "
                     "(numpy-fallback blocks)"),
    ("rows_gathered", "work-list entries served by row index from a "
                      "round's decoded device matrix"),
    ("rows_padded", "bucket lanes of a round's row-index vectors and fused "
                    "parts that carry n = 0"),
)


def _bucket(k: int) -> int:
    w = _MIN_WORKLIST
    while w < k:
        w *= 2
    return w


def _pad_rows(cols: list[np.ndarray], w: int) -> list[jnp.ndarray]:
    """Pad every per-entry column to the jit bucket by repeating entry 0."""
    k = len(cols[0])
    out = []
    for c in cols:
        c = np.asarray(c)
        if k < w:
            c = np.concatenate([c, np.repeat(c[:1], w - k)])
        out.append(jnp.asarray(c))
    return out


@functools.partial(jax.jit, static_argnames=("decode", "widths"))
def _decode_worklist(arenas, offs, lens, n, first, is_delta, *, decode, widths):
    """Work-list decode over one codec's column arenas, one lane per block.

    ``arenas`` / ``offs`` / ``lens`` are tuples with one element per declared
    column; each lane gathers one padded fixed-width slice per column and
    calls ``decode(*slices, *lens, n_valid)``.  ``decode`` is the codec's
    declared ``ArenaLayout.decode_block`` — a stable registry object, so the
    jit cache stays bounded by the number of registered arena layouts times
    the work-list buckets.
    """

    def one(off, ln, nn, fi, dl):
        cols = tuple(jax.lax.dynamic_slice(a, (o,), (w,))
                     for a, o, w in zip(arenas, off, widths))
        vals = decode(*cols, *ln, nn)
        ids = jnp.cumsum(vals, dtype=jnp.uint32) + fi
        i = jnp.arange(vals.shape[0], dtype=jnp.int32)
        return jnp.where(dl, jnp.where(i < nn, ids, 0), vals)

    cols = (offs, lens, n, first, is_delta)
    p = n.shape[0]
    if p <= _DECODE_CHUNK:
        return jax.vmap(one)(*cols)
    # one vmapped decode compiles in time linear in its lanes (23 s at
    # 8,192 for a v5e); a loop over fixed chunks compiles, at any bucket,
    # in about the time of one chunk.  Buckets are powers of two, so the
    # chunks divide p.
    chunks = jax.tree.map(lambda c: c.reshape(p // _DECODE_CHUNK, -1), cols)
    out = jax.lax.map(lambda c: jax.vmap(one)(*c), chunks)
    return out.reshape(p, out.shape[-1])


class _ArenaGroup:
    """Per-codec contiguous column arenas + per-entry tables, built from the
    codec's declared :class:`repro.core.codec.ArenaColumn` tuple — two
    columns or five, the group never branches on the count."""

    def __init__(self, name: str, layout):
        self.name = name
        self.layout = layout
        k = len(layout.columns)
        self._parts: list = [[] for _ in range(k)]
        self._off = [0] * k
        self.offs: list = [[] for _ in range(k)]
        self.lens: list = [[] for _ in range(k)]
        self.tab: dict = {"n": [], "first": []}

    def add(self, enc, first: int) -> int:
        lay = self.layout
        assert enc.n <= lay.max_n, (self.name, enc.n)
        slot = len(self.tab["n"])
        for c, col in enumerate(lay.columns):
            w = np.asarray(col.extract(enc), col.dtype).reshape(-1)
            assert w.size <= col.width, (self.name, col.name, w.size, col.width)
            self._parts[c].append(w)
            self.offs[c].append(self._off[c])
            self.lens[c].append(w.size)
            self._off[c] += w.size
        self.tab["n"].append(enc.n)
        self.tab["first"].append(first)
        return slot

    def finalize(self) -> "_ArenaGroup":
        # trailing slack so the fixed-size dynamic_slice gathers never clamp
        self.arenas = tuple(
            jnp.asarray(np.concatenate(parts + [np.zeros(col.width, col.dtype)]))
            for parts, col in zip(self._parts, self.layout.columns))
        self.offs = [np.asarray(o, np.int32) for o in self.offs]
        self.lens = [np.asarray(v, np.int32) for v in self.lens]
        self.tab = {k: np.asarray(v, np.uint32 if k == "first" else np.int32)
                    for k, v in self.tab.items()}
        self._parts = None
        return self

    def _run(self, slots: np.ndarray, delta: np.ndarray):
        """One jitted lane-parallel decode of ``slots``; returns the padded
        (bucket, out_width) device array (rows with delta get the d-gap
        prefix sum + first docid fused in, zero past their n)."""
        w = _bucket(len(slots))
        ns = self.tab["n"][slots]
        offs = _pad_rows([o[slots] for o in self.offs], w)
        lens = _pad_rows([v[slots] for v in self.lens], w)
        rest = _pad_rows([ns, self.tab["first"][slots], delta], w)
        return _decode_worklist(
            self.arenas, tuple(offs), tuple(lens), *rest,
            decode=self.layout.decode_block,
            widths=tuple(col.width for col in self.layout.columns)), ns

    def decode(self, items: list, out: list) -> None:
        """Decode [(out_index, slot, (t, bi, field)), ...] in one jitted call;
        field 0 entries get the d-gap prefix sum + first docid fused in."""
        slots = np.asarray([slot for _, slot, _ in items], np.int64)
        delta = np.asarray([e[2] == 0 for _, _, e in items])
        res, ns = self._run(slots, delta)
        res = np.asarray(res)
        for row, ((j, _, _), n) in enumerate(zip(items, ns)):
            out[j] = res[row, :n].copy()

    def decode_rows(self, slots: np.ndarray):
        """Device-resident decode: padded (bucket, out_width) docid rows
        (prefix sum + first fused, zero past n) kept on device, plus per-slot
        posting counts.  The round-resident engine consumes the matrix
        without any host copy."""
        return self._run(np.asarray(slots, np.int64),
                         np.ones(len(slots), bool))


class DeviceArena:
    """Flattened device-resident copy of an ``InvertedIndex``.

    Build once via ``DeviceArena.from_index(idx)`` (or ``idx.to_device()`` /
    ``QueryEngine.to_device()``); decode any work-list of (term, block, field)
    entries with ``decode_blocks`` (field 0 = docids, 1 = TFs), or intersect a
    term's skip-selected blocks against a candidate set on device with
    ``fused_and``.  Coverage is capability-driven: every codec declaring an
    ``ArenaLayout`` in the registry decodes natively; the rest fall back to
    the numpy oracle per block.
    """

    # kept as a class attribute for callers that sized things off the arena;
    # the buckets themselves are owned by the fused kernel
    FUSED_BW_BUCKETS = decode_fused.BW_BUCKETS

    def __init__(self, idx, build_fused: bool = True):
        self.idx = idx
        self.n_docs = idx.n_docs
        # doc-range shard generations (repro.index.shards) declare the global
        # docid window they serve; unsharded indexes cover [0, n_docs)
        self.doc_lo = int(getattr(idx, "doc_lo", 0))
        self.doc_hi = int(getattr(idx, "doc_hi", idx.n_docs))
        self.metrics = None
        own = MetricsRegistry(namespace="repro_arena")
        for name, doc in ARENA_COUNTERS:
            own.counter(name, doc)
        self.bind_metrics(own)
        self._loc: dict = {}
        self._groups: dict = {}
        self._build_compressed_arenas(idx)
        self._pk = None
        self.scores = None
        if build_fused:
            self.ensure_fused()

    # ---- build ------------------------------------------------------------- #

    def _build_compressed_arenas(self, idx) -> None:
        staging: dict = {}
        dense_rows, dense_w0 = [], []
        self.dense_slot: dict = {}
        words_total = intersect_rounds.bitmap_geometry(idx.n_docs)[0]
        for t, tp in idx.terms.items():
            for bi, (first, encg, enct) in enumerate(tp.blocks):
                for field, enc, fi in ((0, encg, first), (1, enct, 0)):
                    key = (t, bi, field)
                    spec = codec_lib.get(enc.codec) if enc.n else None
                    lay = spec.arena if spec is not None else None
                    if lay is None or not lay.supports(enc):
                        self._loc[key] = (None, -1)
                        continue
                    g = staging.get(enc.codec)
                    if g is None:
                        g = staging[enc.codec] = _ArenaGroup(enc.codec, lay)
                    self._loc[key] = (enc.codec, g.add(enc, fi))
                    if (field == 0 and lay.bitmap_words
                            and lay.is_bitmap is not None
                            and lay.is_bitmap(enc)):
                        # word-parallel-servable block: stage its raw bitmap
                        # window realigned to the serving bitmap geometry
                        # (first window word rounded down to a 4-word phase,
                        # so the window's column offset is lane-tile aligned;
                        # clamped so the window stays inside the geometry).
                        ids = first + np.cumsum(spec.decode_np(enc),
                                                dtype=np.uint64)
                        w0 = min((int(ids[0]) >> 5) & ~3,
                                 words_total - lay.bitmap_words)
                        bits = np.zeros(lay.bitmap_words * 32, np.uint8)
                        bits[(ids - np.uint64(w0 * 32)).astype(np.int64)] = 1
                        self.dense_slot[(t, bi)] = len(dense_rows)
                        dense_rows.append(np.packbits(
                            bits, bitorder="little").view(np.uint32))
                        dense_w0.append(w0)
        self._groups = {name: g.finalize() for name, g in staging.items()}
        self.dense_w0 = np.asarray(dense_w0, np.int32)
        self.dense_words = (jnp.asarray(np.stack(dense_rows)) if dense_rows
                            else None)

    def ensure_fused(self) -> "DeviceArena":
        """Build the fused-kernel tile arenas if absent: every block's d-gaps
        re-packed into the fixed (rows, 128) tiles ``kernels/decode_fused``
        consumes, grouped into per-bit-width buckets, one (S, rows, 128)
        arena each."""
        if self._pk is not None:
            return self
        idx = self.idx
        self._pk = {}
        self._pk_slot = {}
        staged: dict = {bw: [] for bw in decode_fused.BW_BUCKETS}
        for t, tp in idx.terms.items():
            for bi in range(len(tp.blocks)):
                ids = idx.decode_block_ids(t, bi)
                g = np.zeros(len(ids), np.uint32)
                g[1:] = ids[1:] - ids[:-1]
                ebw = max(1, int(ebw_np(g.max(initial=0))))
                bw = next(b for b in decode_fused.BW_BUCKETS if b >= ebw)
                staged[bw].append(((t, bi), tp.blocks[bi][0], g))
        for bw, items in staged.items():
            if not items:
                continue
            rpb = decode_fused.rows_per_block(bw)
            tiles = np.zeros((len(items), rpb, LANES), np.uint32)
            firsts, ns = [], []
            for s, (key, first, g) in enumerate(items):
                self._pk_slot[key] = (bw, s)
                firsts.append(first)
                ns.append(len(g))
                tiles[s] = decode_fused.pack_gaps(g, bw)
            self._pk[bw] = {"tiles": jnp.asarray(tiles),
                            "first": np.asarray(firsts, np.uint32),
                            "n": np.asarray(ns, np.int32)}
        return self

    def ensure_scores(self) -> "DeviceArena":
        """Build the quantized impact score arena if absent: per posting
        block one packed 128-word score column (``repro.index.scores``) plus
        the block-max / term-max WAND tables, all device-resident."""
        if self.scores is None:
            from .scores import ScoreArena
            self.scores = ScoreArena.from_index(self.idx)
        return self

    @classmethod
    def from_index(cls, idx, build_fused: bool = True) -> "DeviceArena":
        return cls(idx, build_fused=build_fused)

    def bind_metrics(self, registry: MetricsRegistry) -> None:
        """Count into ``registry``, which declares ``ARENA_COUNTERS``: the
        engine serving from this arena hands it its own registry."""
        if registry is not self.metrics:
            self.metrics = registry
            self.stats = DevStatsView(registry,
                                      tuple(n for n, _ in ARENA_COUNTERS))

    # ---- capability probes -------------------------------------------------- #

    def covers(self, key) -> bool:
        """True if (term, block, field) decodes natively on device."""
        return self._loc[key][0] is not None

    # ---- batched work-list decode ------------------------------------------ #

    def decode_blocks(self, entries: list) -> list:
        """Decode a work-list of (term, block, field) entries; field 0 decodes
        docids (d-gap prefix sum + first docid fused in), field 1 raw TFs.

        One jitted device call per codec represented in the work-list;
        entries without an arena capability decode through the numpy oracle.
        Returns arrays aligned with ``entries``.
        """
        out: list = [None] * len(entries)
        by_codec: dict = {}
        host: list = []
        for j, e in enumerate(entries):
            name, slot = self._loc[e]
            if name is None:
                host.append((j, e))
            else:
                by_codec.setdefault(name, []).append((j, slot, e))
        m = self.metrics
        for name, items in by_codec.items():
            g = self._groups[name]
            post = int(g.tab["n"][[s for _, s, _ in items]].sum())
            with get_tracer().span(f"decode/{name}", lane="device",
                                   blocks=len(items), postings=post):
                g.decode(items, out)
            m.inc("device_calls")
            m.inc("blocks_device", len(items))
            m.inc("decode_postings", post)
        for j, (t, bi, field) in host:
            out[j] = (self.idx.decode_block_ids(t, bi) if field == 0
                      else self.idx.decode_block_tfs(t, bi))
        m.inc("blocks_host", len(host))
        return out

    def decode_round(self, pairs: list):
        """Decode a round's (term, block) docid work-list on the device as
        row-indexed matrices, never cut into rows.

        Each source — one per codec present, then the blocks without an
        arena capability (decoded by the numpy oracle and uploaded in one
        batch) — decodes its distinct blocks once into ONE (bucket, 512)
        device matrix of absolute docids (d-gap prefix sum + first fused,
        zero past each block's n) whose rows are ``_bucket`` of the distinct
        blocks, so only bucket shapes reach the compiler.

        Returns (sources, decoded): ``sources`` is [(mat, pos, rows, ns),
        ...] where ``pos`` lists the work-list positions the source serves
        and ``rows`` / ``ns`` (int32, padded with 0 to ``_bucket`` of the
        entries) give each of them its matrix row and posting count;
        ``decoded`` counts the distinct blocks.  Postings may flow host ->
        device for fallback blocks, but nothing flows back.
        """
        by_src: dict = {}           # codec name (None: fallback) -> lists
        for j, (t, bi) in enumerate(pairs):
            name, slot = self._loc[(t, bi, 0)]
            uniq, pos, rows = by_src.setdefault(name, ({}, [], []))
            pos.append(j)
            rows.append(uniq.setdefault((t, bi, slot), len(uniq)))
        m, tr = self.metrics, get_tracer()
        sources, decoded = [], 0
        for name, (uniq, pos, rows) in by_src.items():
            if name is None:
                batch = np.zeros((_bucket(len(uniq)), codec_lib.ARENA_BLOCK),
                                 np.uint32)
                ns_u = np.zeros(len(uniq), np.int32)
                for k, (t, bi, _) in enumerate(uniq):
                    ids = self.idx.decode_block_ids(t, bi)
                    batch[k, :len(ids)] = ids
                    ns_u[k] = len(ids)
                mat = jnp.asarray(batch)
                m.inc("blocks_host", len(uniq))
                m.inc("rows_stacked", len(uniq))
            else:
                g = self._groups[name]
                slots = np.asarray([s for _, _, s in uniq], np.int64)
                ns_u = g.tab["n"][slots]
                post = int(ns_u.sum())
                with tr.span(f"decode/{name}", lane="device",
                             blocks=len(uniq), postings=post, resident=True):
                    mat, _ = g.decode_rows(slots)
                if mat.shape[1] != codec_lib.ARENA_BLOCK:   # defensive: all
                    mat = mat[:, :codec_lib.ARENA_BLOCK]    # layouts use 512
                m.inc("device_calls")
                m.inc("blocks_device", len(uniq))
                m.inc("decode_postings", post)
            w = _bucket(len(pos))
            r = np.zeros(w, np.int32)
            r[:len(pos)] = rows
            ns = np.zeros(w, np.int32)
            ns[:len(pos)] = ns_u[r[:len(pos)]]
            sources.append((mat, np.asarray(pos, np.int64), r, ns))
            decoded += len(uniq)
            m.inc("rows_gathered", len(pos))
            m.inc("rows_padded", w - len(pos))
        return sources, decoded

    # ---- fused decode + AND ------------------------------------------------ #

    def has_fused(self, t, blocks) -> bool:
        return (self._pk is not None
                and all((t, int(bi)) in self._pk_slot for bi in blocks))

    def fused_and(self, t, blocks, cand: np.ndarray) -> np.ndarray:
        """Intersect sorted candidates with term t's skip-selected blocks
        through the fused tile decode + probe (one call per bit-width bucket
        present in the work-list); exact ``intersect_sorted`` parity."""
        k = len(blocks)
        if k == 0 or len(cand) == 0:
            return np.zeros(0, np.uint32)
        groups: dict = {}
        for j, bi in enumerate(blocks):
            bw, row = self._pk_slot[(t, int(bi))]
            groups.setdefault(bw, []).append((j, row))
        words = intersect_rounds.bitmap_geometry(self.n_docs)[0]
        cand_words = jnp.asarray(bitmap_build_np(cand, 0, words * 32))
        parts: list = [None] * k
        for bw, items in groups.items():
            pk = self._pk[bw]
            rows = np.asarray([r for _, r in items], np.int64)
            slots = rows.astype(np.int32)
            firsts = pk["first"][rows]
            ns = pk["n"][rows]
            w = _bucket(len(items))
            if len(items) < w:   # pad: repeated entries with n=0 hit nothing
                slots = np.concatenate([slots, np.repeat(slots[:1], w - len(items))])
                firsts = np.concatenate([firsts, np.repeat(firsts[:1], w - len(items))])
                ns = np.concatenate([ns, np.zeros(w - len(items), np.int32)])
            ids, hits = decode_fused.fused_decode_and(
                pk["tiles"], jnp.asarray(slots), jnp.asarray(firsts),
                jnp.asarray(ns), cand_words, bw=bw)
            ids = np.asarray(ids)
            hits = np.asarray(hits).astype(bool)
            for g, (j, _) in enumerate(items):
                parts[j] = ids[g][hits[g]]
            self.metrics.inc("fused_calls")
            self.metrics.inc("fused_blocks", len(items))
        return np.concatenate(parts)

    def _fused_rounds(self, pairs: list, with_scores: bool, ubs=None):
        """One ``decode_fused.decode_tiles`` call per bit-width bucket
        present in the work-list (plus, with scores, one
        ``topk.unpack_codes`` call for the bucket's packed score column):
        the shared body of the AND and ranked fused rounds — grouping, n=0
        bucket padding, and stats live here exactly once.  ``ubs``
        (optional, aligned with ``pairs``) are per-entry quantized upper
        bounds the ranked caller threads through to the adaptive-theta
        masking; they ride the same grouping/padding (padded rows have n=0
        and scatter nothing, so their ub value is irrelevant).  Each
        bucket's decode is a ``decode/fused`` span.

        Returns one part per bucket, [(ids, codes, qslots, ns, ubs), ...],
        each padded to its own ``_bucket`` and never joined: the caller
        accumulates each part with its own call (the parts' docid sets are
        disjoint), so only bucket shapes reach the compiler."""
        sa = self.ensure_scores().scores if with_scores else None
        tr, m = get_tracer(), self.metrics
        if ubs is None:
            ubs = [0] * len(pairs)
        groups: dict = {}
        for (qs, t, bi), ub in zip(pairs, ubs):
            bw, row = self._pk_slot[(t, int(bi))]
            groups.setdefault(bw, []).append(
                (qs, row, sa.slot[(t, int(bi))] if with_scores else 0, ub))
        parts = []
        for bw, items in groups.items():
            pk = self._pk[bw]
            rows = np.asarray([r for _, r, _, _ in items], np.int64)
            cols = [rows.astype(np.int32),
                    np.asarray([q for q, _, _, _ in items], np.int32),
                    np.asarray([s for _, _, s, _ in items], np.int32),
                    pk["first"][rows], pk["n"][rows],
                    np.asarray([u for _, _, _, u in items], np.int32)]
            w = _bucket(len(items))
            if len(items) < w:   # pad: repeated entries with n=0 hit nothing
                pad = w - len(items)
                cols = [np.concatenate([c, np.repeat(c[:1], pad)]) for c in cols]
                cols[4][-pad:] = 0
            slots, qs, sslots, firsts, ns, ub = cols
            post = int(ns.sum())            # padding rows carry n=0
            with tr.span("decode/fused", lane="device", bw=bw,
                         blocks=len(items), postings=post):
                ids = decode_fused.decode_tiles(
                    pk["tiles"], jnp.asarray(slots), jnp.asarray(firsts),
                    bw=bw)
                codes = (topk.unpack_codes(sa.tiles, jnp.asarray(sslots))
                         if with_scores else None)
            parts.append((ids, codes, qs, ns, ub))
            m.inc("fused_calls")
            m.inc("fused_blocks", len(items))
            m.inc("decode_postings", post)
            m.inc("rows_padded", w - len(items))
        return parts

    def fused_round(self, pairs: list):
        """Fused Pallas decode for one device-resident AND round.

        pairs: [(qslot, t, bi), ...] — this round's work-list.

        Returns one part per bit-width bucket, [(ids, qslots, ns), ...]:
        (P, 512) device docid rows plus the aligned owning-query and
        posting-count columns, P a bucket, each ready for its own
        probe-and-scatter call of ``intersect_rounds.round_accumulate``.
        The decoded ids never touch the host.
        """
        return [(ids, qs, ns)
                for ids, _, qs, ns, _ in self._fused_rounds(pairs, False)]

    def fused_round_scored(self, pairs: list, ubs=None):
        """Fused Pallas decode + score-unpack for one ranked round: like
        :meth:`fused_round` but each work-list entry also runs its block's
        packed score words through the ``kernels/topk`` Pallas unpack tile,
        so the engine can scatter the codes straight into the segmented
        accumulator with ``topk.score_round``.  Returns one part per
        bit-width bucket, [(ids, codes, qslots, ns, ubs), ...]; the decoded
        ids and codes never touch the host.
        """
        return self._fused_rounds(pairs, True, ubs)
