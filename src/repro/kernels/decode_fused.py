"""Pallas kernel: fixed-width block decode for the fused serving placement.

The arena codecs (``repro.index.device``) decode each block through its
codec's own ``decode_block``.  The ``fused`` placement instead re-packs every
block's d-gaps once, at build time, into a fixed (rows, 128) tile at the
block's own bit width rounded up to :data:`BW_BUCKETS`, and decodes a whole
work-list of such tiles in one Pallas call: one grid step per work-list
entry

  1. DMAs the entry's packed gap tile into VMEM — the tile is selected by a
     *scalar-prefetched* work-list array, so Pallas's pipelined grid issues the
     DMA for the next entry's block while the current one computes,
  2. unpacks the fixed-width gaps (static shift/mask unroll, the §3.2/§4.4
     idiom of ``bitpack``), and
  3. prefix-sums them along the lanes (a log-step ``pltpu.roll`` scan: the
     TPU compiler has no in-kernel ``cumsum``) and adds the block's first
     docid (skip-table entry), so docids are reconstructed without writing
     gaps anywhere.

The candidate probe is NOT in the kernel: a per-lane lookup into a bitmap of
``crows * 128`` words is a general gather, which the TPU kernel compiler
refuses ("Only 2D gather is supported").  It runs in XLA on the decoded
rows — :func:`fused_decode_and` here, ``intersect_rounds.round_accumulate``
and ``topk.score_round`` on the resident rounds.

Layout: a block of up to 512 postings is one (rows_per_block, 128) uint32
tile.  Value ``i`` of the block lives at row ``i // 128``, lane ``i % 128``
(the linear order of ``ops.pad_to_frames``), packed LSB-first at the tile's
uniform bit width: lane ``l`` squeezes its 4 values into ``ceil(4*bw/32)``
words.  The arena is 3-D, (S, rows_per_block, 128), so each block spec
covers whole trailing dimensions — the TPU compiler refuses a 2-D block of
fewer than 8 rows.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitpack import LANES, _mask, auto_interpret

BLOCK_ROWS = 4                       # 512 postings = 4 rows x 128 lanes
BLOCK = BLOCK_ROWS * LANES

# per-block bit widths round up to one of these buckets, so a single outlier
# gap widens only its own bucket instead of the whole arena (and the kernel
# compiles at most this many bw variants)
BW_BUCKETS = (4, 8, 12, 16, 24, 32)


def rows_per_block(bw: int) -> int:
    """Packed tile rows for one 512-posting block at bit width ``bw``."""
    return -(-BLOCK_ROWS * bw // 32)


def pack_gaps(gaps: np.ndarray, bw: int) -> np.ndarray:
    """Pack one block's d-gaps (<= 512 values, each < 2**bw) into the
    (rows_per_block(bw), 128) uint32 tile ``_decode_kernel`` consumes: value
    ``i`` at row ``i // 128``, lane ``i % 128``, LSB-first at width ``bw``."""
    vals = np.zeros(BLOCK, np.uint32)
    vals[: len(gaps)] = gaps
    vals = vals.reshape(BLOCK_ROWS, LANES).astype(np.uint64)
    tile = np.zeros((rows_per_block(bw), LANES), np.uint32)
    for r in range(BLOCK_ROWS):
        start = r * bw
        w, off = start // 32, start % 32
        tile[w] |= ((vals[r] << off) & 0xFFFFFFFF).astype(np.uint32)
        if off + bw > 32:
            tile[w + 1] |= (vals[r] >> (32 - off)).astype(np.uint32)
    return tile


def _lane_sums(v):
    """(inclusive prefix sum, total broadcast to every lane) of a (1, 128)
    row, by log-step lane rotations — the scan masks lanes that would wrap
    around, the total keeps them (after 7 doublings every lane has summed
    all 128)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    scan, total = v, v
    s = 1
    while s < LANES:
        scan = scan + jnp.where(lane >= s, pltpu.roll(scan, s, 1),
                                jnp.uint32(0))
        total = total + pltpu.roll(total, s, 1)
        s *= 2
    return scan, total


def _decode_kernel(slot_ref, first_ref, tile_ref, ids_ref, *, bw: int):
    del slot_ref                        # consumed by the tile's index map
    m = _mask(bw)
    base = jnp.full((1, LANES), first_ref[pl.program_id(0)], jnp.uint32)
    for r in range(BLOCK_ROWS):
        # unpack row r: 128 gaps at static bit offset r*bw within each lane
        w, off = divmod(r * bw, 32)
        v = tile_ref[pl.ds(w, 1), :] >> jnp.uint32(off)
        if off + bw > 32:
            v = v | (tile_ref[pl.ds(w + 1, 1), :] << jnp.uint32(32 - off))
        scan, total = _lane_sums(v & m)
        ids_ref[pl.ds(r, 1), :] = scan + base
        base = base + total


@functools.partial(jax.jit, static_argnames=("bw", "interpret"))
def decode_tiles(tiles: jnp.ndarray, slots: jnp.ndarray, firsts: jnp.ndarray,
                 bw: int, interpret=None) -> jnp.ndarray:
    """Decode a work-list of packed gap tiles in one call.

    tiles:  (S, rows_per_block(bw), 128) uint32 — one bit-width bucket's arena.
    slots:  (W,) int32 — arena tile per work-list entry (drives the
            prefetched DMA index map).
    firsts: (W,) uint32 — first docid per entry (skip-table value).

    Returns (W, 512) uint32 docids in linear order; lanes past an entry's
    posting count repeat its last docid (zero gaps), so callers mask them.
    """
    w = slots.shape[0]
    rpb = tiles.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(w,),
        in_specs=[pl.BlockSpec((None, rpb, LANES),
                               lambda i, s, f: (s[i], 0, 0))],
        out_specs=pl.BlockSpec((None, BLOCK_ROWS, LANES),
                               lambda i, s, f: (i, 0, 0)),
    )
    ids = pl.pallas_call(
        functools.partial(_decode_kernel, bw=bw),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((w, BLOCK_ROWS, LANES), jnp.uint32),
        interpret=auto_interpret(interpret),
    )(slots, firsts, tiles)
    return ids.reshape(w, BLOCK)


@functools.partial(jax.jit, static_argnames=("bw", "interpret"))
def fused_decode_and(tiles: jnp.ndarray, slots: jnp.ndarray,
                     firsts: jnp.ndarray, ns: jnp.ndarray,
                     cand: jnp.ndarray, bw: int,
                     interpret=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Decode + intersect a work-list of packed block tiles in one jitted
    call: :func:`decode_tiles`, then every docid probes the packed
    candidate bitmap ``cand`` ((words,) uint32 over [0, words * 32)).

    ns: (W,) int32 posting count per entry.  Returns (docids, hits), each
    (W, 512) uint32; entry j's intersection is ``docids[j][hits[j] == 1]``
    in linear order.
    """
    ids = decode_tiles(tiles, slots, firsts, bw=bw, interpret=interpret)
    word = cand[jnp.minimum(ids >> 5, cand.shape[0] - 1).astype(jnp.int32)]
    hit = (word >> (ids & 31)) & jnp.uint32(1)
    valid = jnp.arange(BLOCK, dtype=jnp.int32)[None, :] < ns[:, None]
    return ids, jnp.where(valid, hit, jnp.uint32(0))
