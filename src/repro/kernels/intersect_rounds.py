"""Device-resident AND rounds: segmented candidate bitmaps + per-round
intersection that never copies candidates back to the host.

The PR-2 device AND loop kept the *decode* on device but synced every query's
candidate set to the host between rounds: round r downloaded the surviving
docids, ran ``searchsorted`` pruning + per-block intersection in numpy, and
re-uploaded the shrunken set for round r+1.  Lemire & Boytsov's intersection
work (PAPERS.md) makes the case for keeping the whole multi-round pipeline
vectorized; this module is that pipeline's state + kernels:

  * **segmented candidate bitmap** — the whole batch's candidate sets as ONE
    device array of shape (n_queries, words): query q owns row q, a packed
    LSB-first bitmap over [0, n_docs) (``intersect.bitmap_build_np`` order,
    padded to whole 128-word rows).
  * ``round_accumulate`` / ``round_commit`` — one AND round over the whole
    batch: every work-list lane probes its query's segment of the *old*
    bitmap (decode results feed in directly as padded (out_width,) docid
    rows) and survivors are scattered into the *new* bitmap.  Distinct
    docids per (query, term) guarantee the scatter-add is an exact bitwise
    OR.  When one round mixes representations (sparse arena decode, fused
    Pallas decode, dense bitmap windows), every split probes the *old*
    bitmap and ORs survivors into one shared *new* bitmap — sound because a
    block is served by exactly one representation, so the calls' docid sets
    are disjoint — followed by a single ``round_commit`` in which inactive
    queries carry their segment forward untouched.
  * ``dense_round_accumulate`` — the density-adaptive representation's round
    (``repro.core.dense_bitmap``): a dense block arrives as its raw 128-word
    window, is ANDed word-parallel against the query's old-bitmap window and
    committed back — no unpack, no prefix-sum, no per-posting lanes at all.
  * ``extract_ids`` — the single final host copy: bitmap rows back to sorted
    uint32 docid arrays, once per batch, after the last round.  It expands
    only the batch's nonzero words, in one vectorised numpy pass, so a
    sparse answer costs its words and not the bits of ``n_docs``; a row
    mostly of nonzero words unpacks whole, bound by its output either way.

Correctness does not depend on block selection: decoding a superset of the
blocks that could hold candidates is sound, because ids outside the current
candidate set fail the probe and scatter nothing.

Tombstone gating (the streaming mutable index, ``repro.index.segments``) rides
the same geometry: :func:`pack_live_words` packs the live-doc mask of a
mutation epoch into one ``(words,)`` row, and the engine ANDs it into the seed
bitmap (and the ranked membership gate) right after round 0 — deleted docs
fail every subsequent probe exactly like non-candidates, so the gate costs one
host->device upload per epoch and zero downloads.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from . import accumulate
from .bitpack import LANES


def bitmap_geometry(n_docs: int) -> tuple[int, int]:
    """(words, rows) of one query's candidate bitmap segment: enough uint32
    words to cover [0, n_docs), padded to whole (rows, 128) lane tiles."""
    cw = max(1, -(-n_docs // 32))
    rows = -(-cw // LANES)
    return rows * LANES, rows


def pack_live_words(dead: np.ndarray, n_docs: int, words: int) -> np.ndarray:
    """Pack one mutation epoch's live-doc mask into a ``(words,)`` uint32
    bitmap row in this module's segmented-bitmap order (LSB-first: bit d of
    word d // 32 is 1 iff doc d is live).

    ``dead`` is the sorted tombstoned docid array (all < ``n_docs``); bits in
    [n_docs, words * 32) are 0, so ANDing this row into a candidate bitmap
    never admits out-of-range docs.  The result is host-side — the caller
    uploads it once per epoch and reuses the device copy across rounds."""
    bits = np.zeros(words * 32, np.uint8)
    bits[:n_docs] = 1
    if len(dead):
        bits[dead] = 0
    return np.packbits(bits, bitorder="little").view(np.uint32)


def pack_live_words_range(dead: np.ndarray, lo: int, hi: int,
                          words: int) -> np.ndarray:
    """Per-shard form of :func:`pack_live_words`: the live row of the doc
    range [lo, hi) in the range's *local* docid space (bit d is doc lo + d).

    Doc-range sharded serving slices one mutation epoch's live mask at the
    shard boundaries, so each shard uploads only its own ``words`` (sized by
    ``bitmap_geometry(hi - lo)``) instead of the full doc-space bitmap.
    ``dead`` is the epoch's sorted global tombstone array; entries outside
    [lo, hi) are dropped before packing."""
    dead = np.asarray(dead, np.int64)
    sub = dead[(dead >= lo) & (dead < hi)] - lo
    return pack_live_words(sub, hi - lo, words)


# --------------------------------------------------------------------------- #
# probe + scatter round (both device placements)
# --------------------------------------------------------------------------- #


def _scatter_survivors(bm, ids, qslot, surv):
    """OR survivor docids into a fresh bitmap: scatter-add is exact because
    every (query, term) contributes each docid at most once per round."""
    return accumulate.scatter_bits(bm, ids, qslot, surv)


@functools.partial(jax.jit, static_argnames=("probe",))
def round_accumulate(new, ids, qslot, ns, bm_old, rows=None, *,
                     probe: bool = True):
    """Probe ``bm_old``, OR survivors into the shared ``new`` bitmap.

    One AND round may split across several accumulate calls (sparse arena
    decode per source, fused Pallas decode per bit-width bucket, dense
    windows) — every call probes the same *old* state and adds into the
    same *new* state, and the calls' docid sets are disjoint, so the adds
    compose into an exact OR regardless of call order.  ``round_commit``
    folds the result back per query.

    ``rows`` (optional, (P,) int32) picks each work-list entry's row of
    ``ids`` on the device: a round's distinct decoded blocks arrive once,
    as one matrix, however many entries share them.
    """
    if rows is not None:
        ids = ids[rows]
    lane = jnp.arange(ids.shape[1], dtype=jnp.int32)
    surv = lane[None, :] < ns[:, None]
    if probe:
        word = (ids >> 5).astype(jnp.int32)
        bit = (ids & 31).astype(jnp.uint32)
        hit = (bm_old[qslot[:, None], word] >> bit) & jnp.uint32(1)
        surv = surv & (hit == 1)
    return new | _scatter_survivors(new, ids, qslot, surv)


@functools.partial(jax.jit, static_argnames=("probe",))
def dense_round_accumulate(new, words, qslot, w0, act, bm_old, rows, *,
                           probe: bool = True):
    """Dense-bitmap blocks' AND round: pure word-parallel bitmap algebra.

    words: (S, 128) uint32 — the arena's posting windows
           (``repro.core.dense_bitmap`` words at the arena's 4-word phase).
    w0:    (P,) int32 — the window's first word in the bitmap geometry.
    act:   (P,) bool — live entries (False for jit padding).
    rows:  (P,) int32 — each entry's row of ``words``, gathered on the
           device.

    The probe is 128 word ANDs against the query's old-bitmap window — no
    unpack, no prefix-sum, no per-posting lanes.
    """
    surv = words[rows]
    if probe:
        surv = surv & accumulate.dense_window_gather(bm_old, qslot, w0)
    return accumulate.dense_window_add(new, surv, qslot, w0, act)


@jax.jit
def round_commit(bm_old, new, active):
    """Fold a round's accumulated ``new`` bitmap back into the batch state:
    active queries take their new segment, inactive rows keep the old one."""
    return jnp.where(active[:, None], new, bm_old)


# --------------------------------------------------------------------------- #
# final extraction (the one host copy per batch)
# --------------------------------------------------------------------------- #


def extract_ids(bm_np: np.ndarray, n_docs: int, metrics=None) -> list:
    """Bitmap rows -> sorted uint32 docid arrays (fresh, caller-owned).

    The work is proportional to the batch's nonzero words: only those are
    unpacked, in one vectorised pass over the whole ``(rows, words)``
    matrix, and each set bit k of word w maps back to ``w << 5 | k``.  A
    row whose nonzero words are more than half its words is output-bound
    either way and unpacks whole, which is cheaper there than the gather.
    Bits at ``n_docs`` and past it (row padding, or a shard's narrower
    ``hi - lo``) are dropped.  The arrays are disjoint and writable; an
    empty row gives an empty array.

    ``metrics`` (an engine's registry) counts ``extract_words_nonzero``
    (nonzero words of the rows below ``n_docs``) and ``extract_ids`` (ids
    returned); the ``kernel/extract_ids`` span carries both as args.
    """
    from repro.obs.trace import get_tracer
    with get_tracer().span("kernel/extract_ids", lane="device",
                           rows=int(bm_np.shape[0]), n_docs=n_docs) as sp:
        nw = -(-n_docs // 32)
        bm = np.asarray(bm_np, np.uint32)[:, :nw]
        rows = bm.shape[0]
        r, w = np.divmod(np.flatnonzero(bm != 0), nw)
        per_row = np.bincount(r, minlength=rows)
        dense = per_row * 2 > nw
        if dense.any():
            keep = ~dense[r]
            r, w = r[keep], w[keep]
        vals = bm[r, w]
        if n_docs % 32:
            vals[w == nw - 1] &= np.uint32((1 << (n_docs % 32)) - 1)
        pc = np.bitwise_count(vals)
        bits = np.unpackbits(vals.view(np.uint8), bitorder="little")
        pos = np.flatnonzero(bits.view(np.bool_))
        # pos indexes the bits of the expanded words; shift each word's
        # bits from its slot j to its place w in the row
        pos += np.repeat((w - np.arange(len(w))) << 5, pc)
        ids = pos.astype(np.uint32)
        counts = np.bincount(r, weights=pc, minlength=rows).astype(np.int64)
        out = np.split(ids, np.cumsum(counts)[:-1]) if rows else []
        for i in np.flatnonzero(dense):
            row = np.ascontiguousarray(bm[i]).view(np.uint8)
            bits = np.unpackbits(row, bitorder="little")[:n_docs]
            out[i] = np.flatnonzero(bits.view(np.bool_)).astype(np.uint32)
        nz = int(per_row.sum())
        n_ids = sum(map(len, out))
        if sp is not None:
            sp.args.update(words_nonzero=nz, ids=n_ids)
        if metrics is not None:
            metrics.inc("extract_words_nonzero", nz)
            metrics.inc("extract_ids", n_ids)
        return out
