"""Segmented device-resident top-k: quantized score accumulation + threshold
-and-compact candidate selection for the ranked (OR / and_scored) modes.

The state mirrors ``intersect_rounds``'s segmented candidate bitmaps, with a
score accumulator next to them:

  * **segmented score accumulator** — ONE (n_queries, n_docs_padded) uint32
    device array; query q owns row q and accumulates the quantized impact
    codes (``repro.index.scores``) of its terms, one term occurrence per
    round, via an exact integer scatter-add.
  * **membership bitmap** — the same (n_queries, words) packed geometry as
    the AND candidate bitmaps: a bit per doc that contributed anything
    (needed because a code can floor to 0 while the float impact is > 0).
  * ``score_round`` — one jitted call per round: every work-list lane
    scatters its decoded block's codes into its query's accumulator row.
    For ``and_scored`` the lanes first probe the AND-result bitmap (``gate``)
    so only intersection docs accumulate.  Both device placements share it;
    they differ only in how the docids and codes were decoded.
  * ``topk_threshold`` + ``candidate_bitmap`` — the bounded "heap" as
    iterative threshold-and-compact: the per-query k-th largest accumulated
    code sum is the threshold theta; the compact keeps every member doc with
    ``acc >= theta - margin`` (the quantization margin of
    ``repro.index.scores`` — a provable superset of the true float top-k)
    packed as a bitmap, which is the batch's single host sync.  The k-th
    statistic is found by a per-bit binary descend over rank counts instead
    of ``lax.top_k`` — a sort-free fixed 16-step reduce that is the single
    biggest ranked-path cost on the XLA lowering, and exact for every
    quantized sum below 2**16 (above, it saturates low, which only widens
    the candidate superset).
  * ``pooled_threshold`` — the cheap per-round form of the same statistic
    for **adaptive theta promotion**: the k-th largest *32-group pooled
    maximum*.  The top-k pooled values are maxima of k distinct groups,
    hence k distinct accumulator entries, so the pooled k-th is a sound
    lower bound on the true k-th — and the accumulator only grows across
    rounds, so ``theta = max(theta, pooled_threshold(acc, k))`` after every
    round is monotone and never exceeds the final k-th sum.  Rounds mask
    work-list entries whose precomputed upper bound cannot beat the promoted
    theta (``ub <= (theta * iq) >> 16``) entirely on device: the work-list
    compacts itself against promoted bounds with zero per-round host syncs.
  * ``unpack_codes`` — the Pallas tile for the score side of the fused
    placement: each grid step DMAs one block's packed (1, 128) score words
    from the (S, 1, 128) score arena (slot selected by a scalar-prefetched
    work-list array, double-buffered like the gap tiles) and shifts/masks
    them into (4, 128) code tiles — the bw=8 instantiation of the paper's
    static shift/mask unroll.

Correctness does not depend on work-list selection: scattering a superset of
blocks is exact (codes of docs outside the gate fail the probe), and pruned
blocks only drop docs provably outside the top-k (see the parity-contract
note in ``repro/index/scores.py``).

Tombstone gating (the streaming mutable index) needs no new kernel: under a
mutation epoch the engine passes the epoch's packed live bitmap
(``intersect_rounds.pack_live_words``, broadcast per query row) as ``gate``
with ``gated=True`` for OR rounds — deleted docs fail the probe and never
enter ``acc``/``member``, so ``topk_threshold``/``candidate_bitmap`` only
ever see live docs and the gate adds zero host syncs.  ``and_scored`` rounds
are already gated by the AND bitmap, which the engine live-gates at seed
time.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import accumulate
from .bitpack import LANES, auto_interpret
from .decode_fused import BLOCK_ROWS

THRESH_BITS = 16        # binary-descend range: exact for sums < 2**16


def accum_width(n_docs: int) -> int:
    """Accumulator row width: [0, n_docs) padded to the bitmap geometry of
    ``intersect_rounds`` (whole 32-bit words, whole 128-lane tiles) so the
    compacted candidate bitmap packs without a remainder."""
    from .intersect_rounds import bitmap_geometry
    return bitmap_geometry(n_docs)[0] * 32


def _scale_q16(theta, iq):
    """floor(theta * iq / 2**16) per query, exact in 32-bit arithmetic.

    ``iq`` is a Q16.16 scale in [1, 2**16] (65536 = identity; smaller values
    deflate theta to stay a sound bound when tombstones raise live idf — see
    ``repro/index/scores.py``).  Split theta into hi/lo 16-bit halves so no
    intermediate exceeds uint32: hi * iq is already an integer multiple of
    the floor, and (lo * iq) >> 16 supplies the exact remainder floor.
    """
    t = theta.astype(jnp.uint32)
    s = iq.astype(jnp.uint32)
    return ((t >> 16) * s + (((t & jnp.uint32(0xFFFF)) * s) >> 16)).astype(
        jnp.int32)


def _scatter(acc, member, ids, qslot, codes, surv):
    """Exact scatter: per round a (query, term occurrence) contributes every
    docid at most once, so the integer add is a plain sum and the bit add is
    an exact OR."""
    contrib = jnp.where(surv, codes, jnp.uint32(0))
    acc = accumulate.scatter_add(acc, ids, qslot, contrib)
    mem = accumulate.scatter_bits(member, ids, qslot, surv)
    return acc, member | mem


@functools.partial(jax.jit, static_argnames=("gated",))
def score_round(acc, member, ids, qslot, codes, ns, gate, ub, theta, iq,
                rows=None, *, gated: bool):
    """One ranked round over the whole batch.

    acc:    (Q, width) uint32 — segmented score accumulator (old state).
    member: (Q, words) uint32 — packed membership bitmap (old state).
    ids:    (P, out_width) uint32 — decoded docid rows per work-list entry.
    qslot:  (P,) int32 — owning query row per entry.
    codes:  (P, out_width) uint32 — quantized impact codes aligned with ids.
    ns:     (P,) int32 — valid posting count per entry (0 for jit padding).
    gate:   (Q, words) uint32 — AND-result bitmap; probed when ``gated``
            (the ``and_scored`` path) so only intersection docs accumulate.
    ub:     (P,) int32 — quantized upper bound of the entry's block against
            its query (block max + margin + other terms' range maxes); the
            entry is skipped when it cannot beat the promoted theta.
            Entries that must always run carry a huge ub.
    theta:  (Q,) uint32 — promoted per-query threshold (0 before promotion).
    iq:     (Q,) uint32 — Q16.16 idf-ratio deflation (65536 = identity).
    rows:   optional (P,) int32 — each entry's row of ``ids``, gathered on
            the device (a round's distinct decoded blocks, indexed);
            ``codes`` stays one row per entry.

    Returns (acc, member), both still on device.  Dropping an entry with
    ``ub <= scaled theta`` is sound: every doc in it ends below
    theta_final - margin, outside the candidate superset.
    """
    if rows is not None:
        ids = ids[rows]
    ns = jnp.where(ub > _scale_q16(theta, iq)[qslot], ns, 0)
    lane = jnp.arange(ids.shape[1], dtype=jnp.int32)
    surv = lane[None, :] < ns[:, None]
    if gated:
        word = (ids >> 5).astype(jnp.int32)
        hit = (gate[qslot[:, None], word] >> (ids & 31)) & jnp.uint32(1)
        surv = surv & (hit == 1)
    return _scatter(acc, member, ids, qslot, codes, surv)


def _kth_descend(vals, k: int):
    """Largest t with |{v : v >= t}| >= k, by THRESH_BITS halving steps.

    That t *is* the k-th largest value when it fits the bit range; when
    fewer than k values are >= 1 the descend stays at 0 (keep-everything),
    which is the right degenerate answer for k > candidate count."""
    a = vals.astype(jnp.int32)
    lo = jnp.zeros(vals.shape[0], jnp.int32)
    for b in range(THRESH_BITS - 1, -1, -1):
        mid = lo + (1 << b)
        cnt = jnp.sum(a >= mid[:, None], axis=1, dtype=jnp.int32)
        lo = jnp.where(cnt >= k, mid, lo)
    return lo.astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_threshold_jit(acc, k: int):
    return _kth_descend(acc, k)


def topk_threshold(acc, k: int):
    """Per-query threshold theta: the k-th largest accumulated code sum."""
    from repro.obs.trace import get_tracer
    with get_tracer().span("kernel/topk", lane="device", k=k,
                           nq=int(acc.shape[0])):
        return _topk_threshold_jit(acc, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_stats_jit(acc, k: int):
    """Per-query (theta, count) merge statistics for doc-range sharded top-k.

    theta is the shard-local k-th largest accumulated sum — with the RAW k,
    not ``min(k, width)``: a shard holding fewer than k scored docs must
    report 0 (``_kth_descend`` stays at 0 when fewer than k entries are
    >= 1), because its local "k-th" over fewer candidates would not be a
    sound lower bound on the global k-th.  count is the shard's candidate
    population at its own threshold (reporting / collective accounting).

    Soundness of the merge (the shard-local margin argument): shard s has at
    least k docs with sum >= theta_s, so globally at least k docs reach
    theta_s and the global k-th sum is >= max_s theta_s.  Compacting every
    shard at ``max_s theta_s`` therefore keeps a superset of the unsharded
    candidate set — the one all-gather of these (theta, count) pairs is the
    only cross-shard traffic in a ranked batch.
    """
    theta = _kth_descend(acc, k)
    count = jnp.sum(acc >= jnp.maximum(theta, 1)[:, None], axis=1,
                    dtype=jnp.int32)
    return theta, count


def topk_stats(acc, k: int):
    """Traced wrapper over :func:`_topk_stats_jit` (same contract)."""
    from repro.obs.trace import get_tracer
    with get_tracer().span("kernel/topk", lane="device", k=k,
                           nq=int(acc.shape[0]), stats=True):
        return _topk_stats_jit(acc, k)


@functools.partial(jax.jit, static_argnames=("k",))
def pooled_threshold(acc, k: int):
    """Sound per-round lower bound on the k-th largest sum, over the 32-group
    max pool (32x fewer rank-count columns than :func:`topk_threshold`)."""
    q, width = acc.shape
    pooled = acc.reshape(q, width // 32, 32).max(axis=-1)
    return _kth_descend(pooled, k)


@jax.jit
def candidate_bitmap(acc, member, theta, margin, iq):
    """Compact the accumulator against (theta * iq / 2**16 - margin) into a
    packed candidate bitmap — every member doc whose quantized sum could
    still reach the true top-k (the provable superset of
    ``repro/index/scores.py``; ``iq`` deflates theta under tombstone epochs,
    65536 = identity)."""
    # int32 is exact here: sums of u8 codes stay far below 2**31
    thr = _scale_q16(theta, iq) - margin.astype(jnp.int32)
    keep = acc.astype(jnp.int32) >= thr[:, None]
    q, width = acc.shape
    bits = keep.reshape(q, width // 32, 32).astype(jnp.uint32)
    words = (bits << jnp.arange(32, dtype=jnp.uint32)).sum(
        axis=-1, dtype=jnp.uint32)
    return words & member


# --------------------------------------------------------------------------- #
# dense-bitmap score round (density-adaptive posting blocks)
# --------------------------------------------------------------------------- #


@functools.partial(jax.jit, static_argnames=("gated",))
def dense_score_round(acc, member, tiles, words, qslot, w0, ub, theta, iq,
                      gate, rows, tile_rows, *, gated: bool):
    """One ranked round over the batch's dense-bitmap work-list entries.

    tiles: (S', 1024) uint32 — the score arena's packed code windows, four
           u8 codes per word in window-position order (position p lives in
           word p >> 2, byte p & 3); positions with no posting carry code 0.
    words: (S, 128) uint32 — the arena's posting bitmap windows
           (``dense_bitmap`` words, realigned to the arena's 4-word phase).
    w0:    (P,) int32 — first word of the entry's window in the bitmap
           geometry; 4-word aligned, so column w0 * 32 is lane-tile aligned.
    rows / tile_rows: (P,) int32 — each entry's row of ``words`` /
           ``tiles``, gathered on the device.

    No unpack/prefix-sum: codes add as one contiguous 4096-column window
    (:func:`repro.kernels.accumulate.dense_add`) and membership/gating stay
    word-parallel on the packed windows.  Composes exactly with the sparse
    :func:`score_round` of the same round — integer adds sum and the bit
    adds OR, whichever call order.
    """
    words, tiles = words[rows], tiles[tile_rows]
    act = ub > _scale_q16(theta, iq)[qslot]
    p = tiles.shape[0]
    codes = ((tiles[:, :, None] >> (jnp.uint32(8) *
                                    jnp.arange(4, dtype=jnp.uint32)))
             & jnp.uint32(0xFF)).reshape(p, -1)
    win = words
    if gated:
        win = win & accumulate.dense_window_gather(gate, qslot, w0)
        bits = ((win[:, :, None] >> jnp.arange(32, dtype=jnp.uint32))
                & jnp.uint32(1)).reshape(p, -1)
        codes = codes * bits
    acc = accumulate.dense_add(acc, codes, qslot,
                               (w0 * 32).astype(jnp.int32), act)
    mem = accumulate.dense_window_add(jnp.zeros_like(member), win, qslot,
                                      w0, act)
    return acc, member | mem


# --------------------------------------------------------------------------- #
# Pallas score-unpack tile (the fused placement's score side)
# --------------------------------------------------------------------------- #


def _unpack_kernel(slot_ref, tile_ref, out_ref):
    del slot_ref                        # consumed by the tile's index map
    w = tile_ref[...]
    for r in range(BLOCK_ROWS):
        out_ref[pl.ds(r, 1), :] = (w >> jnp.uint32(8 * r)) & jnp.uint32(0xFF)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack_codes(tiles, slots, interpret=None) -> jnp.ndarray:
    """Unpack a work-list of packed score tiles in one call.

    tiles: (S, 1, 128) uint32 — the score arena (four codes per word).
    slots: (W,) int32 — arena row per work-list entry; drives the
           scalar-prefetched DMA index map exactly like the gap tiles.

    Returns (W, 512) uint32 codes in the linear order of the docid rows
    they accompany.
    """
    w = slots.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(w,),
        in_specs=[pl.BlockSpec((None, 1, LANES), lambda i, s: (s[i], 0, 0))],
        out_specs=pl.BlockSpec((None, BLOCK_ROWS, LANES),
                               lambda i, s: (i, 0, 0)),
    )
    codes = pl.pallas_call(
        _unpack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((w, BLOCK_ROWS, LANES), jnp.uint32),
        interpret=auto_interpret(interpret),
    )(slots, tiles)
    return codes.reshape(w, BLOCK_ROWS * LANES)
