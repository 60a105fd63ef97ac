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

``stats`` counts device calls and blocks decoded per path; the engine's
work-list dedup guarantees <= 1 decode per hot (term, block) per batch, which
``benchmarks/bench_query.py`` records alongside the qps numbers.

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
from repro.obs.trace import get_tracer
from repro.kernels import decode_fused, intersect_rounds, topk
from repro.kernels.bitpack import LANES
from repro.kernels.intersect import bitmap_build_np

_MIN_WORKLIST = 8             # smallest jit bucket


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

    return jax.vmap(one)(offs, lens, n, first, is_delta)


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
        posting counts.  The round-resident engine consumes the rows without
        any host copy."""
        res, ns = self._run(np.asarray(slots, np.int64),
                            np.ones(len(slots), bool))
        return res, ns


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
        self.stats = {"device_calls": 0, "blocks_device": 0, "blocks_host": 0,
                      "fused_calls": 0, "fused_blocks": 0}
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
        for name, items in by_codec.items():
            with get_tracer().span(f"decode/{name}", lane="device",
                                   blocks=len(items)):
                self._groups[name].decode(items, out)
            self.stats["device_calls"] += 1
            self.stats["blocks_device"] += len(items)
        for j, (t, bi, field) in host:
            out[j] = (self.idx.decode_block_ids(t, bi) if field == 0
                      else self.idx.decode_block_tfs(t, bi))
            self.stats["blocks_host"] += 1
        return out

    def decode_blocks_device(self, entries: list):
        """Decode a work-list of (term, block) docid entries WITHOUT copying
        the results to the host: returns (rows, ns) where ``rows[j]`` is a
        padded (ARENA_BLOCK,) device array of absolute docids (d-gap prefix
        sum + first fused, zero past ``ns[j]``).  One jitted call per codec
        present; blocks without an arena capability decode through the numpy
        oracle and are *uploaded* in one batch — postings may flow host ->
        device here, but candidates never flow back.
        """
        rows: list = [None] * len(entries)
        ns: list = [0] * len(entries)
        by_codec: dict = {}
        host: list = []
        for j, (t, bi) in enumerate(entries):
            name, slot = self._loc[(t, bi, 0)]
            if name is None:
                host.append((j, t, bi))
            else:
                by_codec.setdefault(name, []).append((j, slot))
        for name, items in by_codec.items():
            g = self._groups[name]
            with get_tracer().span(f"decode/{name}", lane="device",
                                   blocks=len(items), resident=True):
                res, n_arr = g.decode_rows(np.asarray([s for _, s in items]))
            if res.shape[1] != codec_lib.ARENA_BLOCK:       # defensive: all
                res = res[:, :codec_lib.ARENA_BLOCK]        # layouts use 512
            for r, ((j, _), n) in enumerate(zip(items, n_arr)):
                rows[j] = res[r]
                ns[j] = int(n)
            self.stats["device_calls"] += 1
            self.stats["blocks_device"] += len(items)
        if host:
            batch = np.zeros((len(host), codec_lib.ARENA_BLOCK), np.uint32)
            for k, (j, t, bi) in enumerate(host):
                ids = self.idx.decode_block_ids(t, bi)
                batch[k, :len(ids)] = ids
                ns[j] = len(ids)
            up = jnp.asarray(batch)
            for k, (j, _, _) in enumerate(host):
                rows[j] = up[k]
            self.stats["blocks_host"] += len(host)
        return rows, ns

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
            self.stats["fused_calls"] += 1
            self.stats["fused_blocks"] += len(items)
        return np.concatenate(parts)

    def _fused_rounds(self, pairs: list, with_scores: bool, ubs=None):
        """One ``decode_fused.decode_tiles`` call per bit-width bucket
        present in the work-list (plus, with scores, one
        ``topk.unpack_codes`` call for the bucket's packed score column):
        the shared body of the AND and ranked fused rounds — grouping, n=0
        bucket padding, and stats live here exactly once.  ``ubs``
        (optional, aligned with ``pairs``) are per-entry quantized upper
        bounds the ranked caller threads through to the adaptive-theta
        masking; they ride the same grouping/padding so the returned array
        aligns with the output rows (padded rows have n=0 and scatter
        nothing, so their ub value is irrelevant)."""
        sa = self.ensure_scores().scores if with_scores else None
        if ubs is None:
            ubs = [0] * len(pairs)
        groups: dict = {}
        for (qs, t, bi), ub in zip(pairs, ubs):
            bw, row = self._pk_slot[(t, int(bi))]
            groups.setdefault(bw, []).append(
                (qs, row, sa.slot[(t, int(bi))] if with_scores else 0, ub))
        parts: list = [[] for _ in range(5)]   # ids, codes, qs, ns, ubs
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
            parts[0].append(decode_fused.decode_tiles(
                pk["tiles"], jnp.asarray(slots), jnp.asarray(firsts), bw=bw))
            if with_scores:
                parts[1].append(topk.unpack_codes(sa.tiles,
                                                  jnp.asarray(sslots)))
            parts[2].append(qs)
            parts[3].append(ns)
            parts[4].append(ub)
            self.stats["fused_calls"] += 1
            self.stats["fused_blocks"] += len(items)
        cat = (lambda xs: xs[0] if len(xs) == 1 else jnp.concatenate(xs))
        ncat = (lambda xs: xs[0] if len(xs) == 1 else np.concatenate(xs))
        return (cat(parts[0]), cat(parts[1]) if with_scores else None,
                ncat(parts[2]), ncat(parts[3]), ncat(parts[4]))

    def fused_round(self, pairs: list):
        """Fused Pallas decode for one device-resident AND round.

        pairs: [(qslot, t, bi), ...] — this round's work-list.

        Returns (ids, qslots, ns): (P, 512) device docid rows plus the
        aligned owning-query and posting-count columns, ready for the
        probe-and-scatter of ``intersect_rounds.round_accumulate``.  The
        decoded ids never touch the host.
        """
        ids, _, qs, ns, _ = self._fused_rounds(pairs, False)
        return ids, qs, ns

    def fused_round_scored(self, pairs: list, ubs=None):
        """Fused Pallas decode + score-unpack for one ranked round: like
        :meth:`fused_round` but each work-list entry also runs its block's
        packed score words through the ``kernels/topk`` Pallas unpack tile,
        so the engine can scatter the codes straight into the segmented
        accumulator with ``topk.score_round``.  Returns (ids, codes, qslots,
        ns, ubs); the decoded ids and codes never touch the host.
        """
        return self._fused_rounds(pairs, True, ubs)
