"""Quantized impact score arenas: BM25 impacts as a device-resident column.

The ranked modes (``or`` / ``and_scored``) were the engine's last scalar
holdout: BM25 was recomputed per term on cache miss, merged on host, and
full-sorted with ``np.argsort``.  This module gives the ranked path the same
treatment the docid streams got — per-(term, doc) impacts quantized to u8 and
packed as an additional named arena column per posting block, plus the
block-max metadata a WAND/BMW-style top-k needs:

  * **global-max scalar quantization** — one scale for the whole index:
    ``delta = global_max_impact / 255`` and ``code = floor(impact / delta)``
    (clipped to 255).  Floor is *monotone*, so equal float impacts always map
    to equal codes and ``max(codes of a block) == floor(block_max / delta)``
    — the stored block-max tables are exactly the maxima of the stored codes
    (the registry lint cross-checks this).
  * **score column** — each block's <= 512 codes packed four-per-word into a
    fixed 128-word uint32 stream (:data:`SCORE_COLUMN`, the same padded
    ``ArenaColumn`` contract the codec arenas declare: value ``i`` lives in
    word ``i % 128``, bits ``8 * (i // 128)`` — the bw=8 case of
    ``decode_fused.pack_gaps``), stacked into one ``(S, 1, 128)`` device
    arena aligned with the block slots.
  * **block-max / term-max / top-impact tables** — per (term, block) the max
    code, per term the max code and its top-:data:`TOP_TABLE` codes sorted
    descending.  ``InvertedIndex.build`` precomputes the float form of the
    block/term maxima from the raw postings (before compression); hand-built
    indexes reconstruct them here from a decode pass.

Quantization-rank parity contract
---------------------------------
Quantized ranks need not equal float ranks; exactness is restored by a
*candidate margin*.  For a query with ``m`` (known) term occurrences and a
doc matching with quantized sum ``C``, the true score ``S`` satisfies

    C * delta <= S < (C + m) * delta                      (floor, per term)

so (1) the k-th largest quantized sum ``theta`` lower-bounds the k-th best
true score by ``theta * delta``, and (2) any doc of the true top-k must have
``C > theta - m``.  The device path therefore syncs the candidate set
``{C >= theta - m}`` (as a bitmap, one copy per batch) and rescores it with
the exact float oracle — top-k sets and scores match the host float-BM25
path bitwise, with ties broken by ascending docid (:func:`topk_select`).
The same bound makes block-max pruning sound: a (term, block) work-list
entry whose upper bound ``block_max + sum(other term maxima) + m`` cannot
reach a static threshold (``theta0``, the k-th top impact of the query's
strongest term — k docs provably score at least that) only loses
contributions of docs that are provably outside the true top-k.

Mutation epochs: every table above is computed from one generation's corpus
stats (df, doclen, avdl) and rebuilt per generation at ``compact()`` time —
never patched in place.  Between compactions, epochs that carry a delta
segment (or changed doclens) are served with pruning *disarmed* (``theta0 =
0`` and a keep-all margin): the generation-time codes then act only as
membership markers, the candidate set degenerates to the full live
membership superset, and the exact float rescore — which recomputes
:func:`bm25_scores` from the epoch's *live* df / doclen / avdl — restores
bitwise parity with a from-scratch rebuild.

**Tombstone-only epochs keep pruning armed.**  When the only mutation is
deletes (no delta docs, doclen/avdl unchanged), the live score of doc d is
``S' = sum_t R_t * s_t(d)`` where ``R_t = idf_live(t) / idf_gen(t) >= 1``
(deletes can only shrink df, which only raises idf; the tf/doclen factor is
untouched).  With ``Rmax = max_t R_t`` over the query's terms and the live
-gated accumulator (tombstoned docs never enter, so every quantized sum C is
a live doc's):

    C * delta <= S' < Rmax * delta * (C + m)

so the k-th largest live quantized sum ``theta`` still bounds the k-th best
live score by ``theta * delta``, and every true-top-k doc has
``C > theta / Rmax - m``.  The engine carries ``iq = floor(2**16 / Rmax)``
as a per-query Q16.16 deflation: thresholds compare against
``(theta * iq) >> 16 <= theta / Rmax``, which keeps both the block-max prune
and the candidate compact sound with the *generation-time* tables — blocks
whose upper bound cannot beat the deflated theta only lose docs provably
outside the live top-k.  The static seed ``theta0`` comes from
:meth:`ScoreArena.theta0_live`: the per-term top-code tables carry their
docids (``term_top_ids``) so tombstoned entries are filtered before taking
the k-th survivor.  Delta epochs still disarm as above; compaction re-arms
with fresh tables either way.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.codec import ARENA_BLOCK, ArenaColumn, get as codec_get
from repro.kernels.decode_fused import pack_gaps
from repro.kernels.intersect_rounds import bitmap_geometry

K1, B = 1.2, 0.75

CODE_MAX = 255                    # u8 quantization ceiling
TOP_TABLE = 32                    # per-term top-impact codes kept for theta0
SCORE_WORDS = ARENA_BLOCK // 4    # 512 codes packed four-per-word
STRIPE_TARGET = 512               # docid stripes per index for range bounds
STRIPE_MIN = 32                   # smallest stripe width (docids)

# the score stream as the same named-padded-column contract the codec arenas
# declare (repro.core.codec.ArenaColumn): fixed width, uint32 words, values
# masked past the block's dynamic posting count
SCORE_COLUMN = ArenaColumn("scores", SCORE_WORDS, dtype=np.uint32)


# --------------------------------------------------------------------------- #
# shared float BM25 (the exact oracle — one formula for every path)
# --------------------------------------------------------------------------- #


def bm25_scores(tfs: np.ndarray, dls: np.ndarray, df: int, n_docs: int,
                avdl: float) -> np.ndarray:
    """Element-wise float64 BM25 impacts; the host oracle, the quantizer, and
    the candidate rescore all call exactly this, so their floats are bitwise
    identical regardless of which slice of a term they score."""
    idf = np.log(1.0 + (n_docs - df + 0.5) / (df + 0.5))
    tf = tfs.astype(np.float64)
    return idf * tf * (K1 + 1) / (tf + K1 * (1 - B + B * dls / avdl))


def topk_select(docs: np.ndarray, scores: np.ndarray, k: int) -> list:
    """Top-k (docid, score) pairs by descending score, ties broken by
    ascending docid — the one selection rule of every ranked path.

    ``np.argpartition`` pre-selects the k-th score so the full
    (-score, docid) lexsort only touches the k best plus their boundary ties
    (the seed path full-sorted everything with ``np.argsort``).
    """
    k = min(k, len(docs))
    if k <= 0:
        return []
    if len(docs) > 2 * k:
        kth = scores[np.argpartition(-scores, k - 1)[:k]].min()
        cand = np.flatnonzero(scores >= kth)
    else:
        cand = np.arange(len(docs))
    order = cand[np.lexsort((docs[cand], -scores[cand]))][:k]
    return [(int(docs[i]), float(scores[i])) for i in order]


# --------------------------------------------------------------------------- #
# the quantized score arena
# --------------------------------------------------------------------------- #


@jax.jit
def _unpack_rows(tiles: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    """Gather + unpack packed score words: (P,) slots -> (P, 512) uint32
    codes (value i of a block at word i % 128, bits 8 * (i // 128))."""
    w = tiles[slots, 0]                                 # (P, 128)
    parts = [(w >> jnp.uint32(8 * r)) & jnp.uint32(0xFF) for r in range(4)]
    return jnp.stack(parts, axis=1).reshape(slots.shape[0], -1)


def unpack_words_np(words: np.ndarray, n: int) -> np.ndarray:
    """Host-side unpack of one block's packed score words (lint/tests)."""
    w = np.asarray(words, np.uint32)
    out = np.stack([(w >> np.uint32(8 * r)) & np.uint32(0xFF)
                    for r in range(4)]).reshape(-1)
    return out[:n]


class ScoreArena:
    """Device-resident quantized impact scores for one ``InvertedIndex``.

    tiles:     (S, 1, 128) uint32 device arena — slot s holds block s's
               packed codes (:data:`SCORE_COLUMN` layout) as one whole
               trailing (1, 128) block, the shape ``topk.unpack_codes``
               DMAs per work-list entry.
    block_max: (S,) int32 — max code per slot (== max of the stored codes).
    slot:      {(term, block) -> s}.
    term_max:  {term -> int} max code over the term.
    term_tops: {term -> int32[<=TOP_TABLE]} top codes sorted descending.
    term_top_ids: {term -> uint32[<=TOP_TABLE]} the docids carrying those
               codes (same order; code ties broken by ascending docid), so a
               tombstone-only epoch can filter dead entries and re-derive a
               sound theta0 (:meth:`theta0_live`).
    dense_slot / dense_w0 / dense_tiles: blocks whose docid stream is
               word-parallel servable (the posting codec declares
               ``ArenaLayout.bitmap_words`` and the block is in bitmap
               format) additionally get a *window-aligned* code tile: (D,
               1024) uint32, window position p (docid ``w0 * 32 + p``) at
               word ``p >> 2``, bits ``8 * (p & 3)`` — the layout
               ``kernels/topk.dense_score_round`` adds as one contiguous
               4096-column window, no unpack/scatter.  ``w0`` follows the
               device arena's 4-word-aligned clamp, so both views of a dense
               block agree on the window.
    stripes:   {term -> int32[n_stripes]} max code per fixed docid stripe of
               ``stripe_width`` docids — the range bound for block-max
               pruning.  Posting blocks of a sparse term span the whole
               docid space, so block granularity cannot localize it; the
               stripe table is keyed by *docid*, so a range where the term
               has no posting bounds to 0.
    delta:     the quantization scale (global max impact / 255).
    """

    def __init__(self, idx):
        self.idx = idx
        n_docs = idx.n_docs
        doclen = np.asarray(idx.doclen)
        avdl = idx.avdl
        # doc-range shard generations (repro.index.shards) carry the PARENT
        # index's corpus statistics: df is already global in their fixed-up
        # TermPostings, and stat_n_docs / stat_avdl / stat_gmax pin n_docs,
        # avdl, and the quantizer scale to the parent's values so a shard's
        # code for (term, doc) is bitwise the unsharded arena's code.  Only
        # the *geometry* (stripe width, bitmap words, dense windows) stays
        # local to the shard's doc range.
        stat_n = int(getattr(idx, "stat_n_docs", n_docs))
        stat_avdl = float(getattr(idx, "stat_avdl", avdl))
        # pass 1: float impacts per block (build-time tables give the global
        # max without decoding; hand-assembled indexes reconstruct lazily)
        gmax = 0.0
        for t in idx.terms:
            gmax = max(gmax, float(idx.impact_block_max(t).max(initial=0.0)))
        gmax = float(getattr(idx, "stat_gmax", gmax))
        self.gmax = gmax
        self.delta = (gmax / CODE_MAX) if gmax > 0 else 1.0
        # docid stripes sized for ~STRIPE_TARGET range-bound cells per index
        self.stripe_width = max(STRIPE_MIN, -(-n_docs // STRIPE_TARGET))
        n_stripes = max(1, -(-n_docs // self.stripe_width))
        words_total = bitmap_geometry(n_docs)[0]
        # pass 2: quantize per-posting impacts into the packed column
        tiles, bmax = [], []
        dense_tiles, dense_w0 = [], []
        self.slot: dict = {}
        self.dense_slot: dict = {}
        self.term_max: dict = {}
        self.term_tops: dict = {}
        self.term_top_ids: dict = {}
        self.stripes: dict = {}
        for t, tp in idx.terms.items():
            codes_all, ids_all = [], []
            stripe = np.zeros(n_stripes, np.int32)
            for bi in range(len(tp.blocks)):
                ids, tfs = idx.decode_block(t, bi)
                sc = bm25_scores(tfs, doclen[ids], tp.df, stat_n, stat_avdl)
                codes = np.minimum(np.floor(sc / self.delta),
                                   CODE_MAX).astype(np.uint32)
                self.slot[(t, bi)] = len(tiles)
                tiles.append(pack_gaps(codes, 8)[0])
                bmax.append(int(codes.max(initial=0)))
                codes_all.append(codes)
                ids_all.append(ids)
                np.maximum.at(stripe, ids // self.stripe_width,
                              codes.astype(np.int32))
                encg = tp.blocks[bi][1]
                lay = codec_get(encg.codec).arena
                if (lay is not None and lay.bitmap_words
                        and lay.is_bitmap is not None and lay.is_bitmap(encg)):
                    # window-aligned code tile for word-parallel serving:
                    # same w0 formula as the device arena's dense windows
                    bw = lay.bitmap_words
                    w0 = min((int(ids[0]) >> 5) & ~3, words_total - bw)
                    pos = ids.astype(np.int64) - w0 * 32
                    tile = np.zeros(bw * 8, np.uint32)     # bw*32 / 4 words
                    np.bitwise_or.at(tile, pos >> 2,
                                     codes << ((pos & 3) * 8).astype(np.uint32))
                    self.dense_slot[(t, bi)] = len(dense_tiles)
                    dense_tiles.append(tile)
                    dense_w0.append(w0)
            cat = (np.concatenate(codes_all) if codes_all
                   else np.zeros(0, np.uint32))
            ids_cat = (np.concatenate(ids_all) if ids_all
                       else np.zeros(0, np.uint32))
            self.term_max[t] = int(cat.max(initial=0))
            order = np.lexsort((ids_cat, -cat.astype(np.int64)))[:TOP_TABLE]
            self.term_tops[t] = cat[order].astype(np.int32)
            self.term_top_ids[t] = ids_cat[order].astype(np.uint32)
            self.stripes[t] = stripe
        self.block_max = np.asarray(bmax, np.int32)
        self.tiles = (jnp.asarray(np.stack(tiles)[:, None, :]) if tiles
                      else jnp.zeros((1, 1, SCORE_WORDS), jnp.uint32))
        self.dense_w0 = np.asarray(dense_w0, np.int32)
        self.dense_tiles = (jnp.asarray(np.stack(dense_tiles)) if dense_tiles
                            else None)

    @classmethod
    def from_index(cls, idx) -> "ScoreArena":
        return cls(idx)

    # ---- device decode ------------------------------------------------------ #

    def rows(self, pairs: list) -> jnp.ndarray:
        """Decode a work-list of (term, block) score entries WITHOUT a host
        copy: (len(pairs), 512) uint32 code rows, zero past each block's
        posting count (the packing zero-pads)."""
        slots = np.asarray([self.slot[p] for p in pairs], np.int64)
        return _unpack_rows(self.tiles, jnp.asarray(slots))

    # ---- WAND metadata ------------------------------------------------------ #

    def theta0(self, terms: list, k: int) -> int:
        """Static per-query threshold: the k-th top impact code of the
        query's strongest term — k docs of that term provably reach it, so it
        lower-bounds the k-th best total (sound for OR; see the module
        docstring).  0 when no term has k postings or k > TOP_TABLE."""
        best = 0
        for t in terms:
            tops = self.term_tops.get(t)
            if tops is not None and k <= len(tops):
                best = max(best, int(tops[k - 1]))
        return best

    def theta0_live(self, terms: list, k: int, dead: np.ndarray) -> int:
        """:meth:`theta0` for a tombstone-only epoch: tombstoned entries are
        filtered out of the per-term top-code table (``term_top_ids``)
        before taking the k-th survivor, so the k docs backing the bound are
        all live.  Sound but weaker than a rebuild's table when more than
        ``TOP_TABLE - k`` of a term's top codes are dead (the k-th survivor
        may fall off the table — then that term contributes 0)."""
        if len(dead) == 0:
            return self.theta0(terms, k)
        best = 0
        for t in terms:
            tops = self.term_tops.get(t)
            if tops is None or not len(tops):
                continue
            alive = tops[~np.isin(self.term_top_ids[t].astype(np.int64),
                                  dead)]
            if k <= len(alive):
                best = max(best, int(alive[k - 1]))
        return best

    def range_max(self, t: int, lo: int, hi: int) -> int:
        """Max code of term t over the docid range [lo, hi] — the BMW-style
        aligned bound, from the stripe table: 0 when the term has no posting
        in any stripe the range touches."""
        stripe = self.stripes[t]
        j0 = lo // self.stripe_width
        j1 = hi // self.stripe_width + 1
        return int(stripe[j0:j1].max(initial=0))

    def range_max_many(self, t: int, los: np.ndarray,
                       his: np.ndarray) -> np.ndarray:
        """Vectorized :meth:`range_max` over per-block [lo, hi] ranges (the
        prune pass calls this once per other term per round, not per block):
        segment maxima via ``np.maximum.reduceat`` over the stripe table."""
        if len(los) == 0:
            return np.zeros(0, np.int64)
        j0 = np.asarray(los) // self.stripe_width
        j1 = np.asarray(his) // self.stripe_width + 1
        # sentinel keeps every reduceat index in range (j1 can equal the
        # stripe count); a [j0, j1) segment never reaches it since j1 > j0
        ext = np.append(self.stripes[t], np.int32(0))
        idx = np.empty(2 * len(j0), np.int64)
        idx[0::2] = j0
        idx[1::2] = j1
        return np.maximum.reduceat(ext, idx)[0::2].astype(np.int64)
