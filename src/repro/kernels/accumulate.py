"""Segmented accumulate kernels: the scatter half of every serving round.

Every device-resident round ends the same way: per work-list entry, combine a
(lane,) contribution vector into the owning query's row of a batch-segmented
state array — quantized impact codes into the (Q, width) score accumulator,
survivor bits into the (Q, words) candidate/membership bitmaps.  This module
is the single home for that step, in three shapes:

* :func:`scatter_add` / :func:`scatter_bits` — the *sparse* form: per-lane
  docids address arbitrary columns, one XLA scatter-add per call.
* :func:`dense_add` — the *dense window* form for bitmap blocks
  (``repro.core.dense_bitmap``): each entry adds a contiguous 4096-column
  window at a 128-aligned offset, a sequential ``fori_loop`` of
  ``dynamic_update_slice`` adds with no gather at all.
* :func:`dense_window_gather` / :func:`dense_window_add` — 128-word window
  probe/commit for the dense AND rounds.

Exactness contract (shared with the callers' docstrings): within one round a
(query, term occurrence) contributes to each docid at most once, so integer adds are
plain sums and bit adds are exact ORs; across calls that accumulate into the
same state the contributing docid sets are disjoint, so add still equals OR.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

DENSE_WINDOW = 4096          # dense score window: 128 words * 32 bits
WINDOW_WORDS = 128


def scatter_add(acc, ids, qslot, contrib):
    """acc[qslot[j], ids[j, l]] += contrib[j, l] — exact (docids distinct per
    entry; masked lanes carry contrib == 0)."""
    return acc.at[qslot[:, None], ids].add(contrib)


def scatter_bits(bm, ids, qslot, surv):
    """OR survivor docids into a zeroed copy of ``bm``'s geometry: the
    sparse accumulate instantiated for packed bitmap words."""
    word = (ids >> 5).astype(jnp.int32)
    contrib = jnp.where(surv, jnp.uint32(1) << (ids & 31), jnp.uint32(0))
    return jnp.zeros_like(bm).at[qslot[:, None], word].add(contrib)


# --------------------------------------------------------------------------- #
# dense 4096-column window accumulate (score side of bitmap blocks)
# --------------------------------------------------------------------------- #


@jax.jit
def dense_add(acc, codes, qslot, col0, act):
    """acc[qslot[j], col0[j] : col0[j] + 4096] += codes[j] where act[j].

    ``col0`` is 128-aligned (the arena aligns dense windows at build time so
    the lane-dimension dynamic slice is tile-aligned on TPU).
    """
    def body(i, a):
        row = jax.lax.dynamic_slice(a, (qslot[i], col0[i]), (1, DENSE_WINDOW))
        add = jnp.where(act[i], codes[i], jnp.uint32(0))[None, :]
        return jax.lax.dynamic_update_slice(a, row + add, (qslot[i], col0[i]))
    return jax.lax.fori_loop(0, codes.shape[0], body, acc)


# --------------------------------------------------------------------------- #
# 128-word window probe / commit (bitmap AND rounds, membership bitmaps)
# --------------------------------------------------------------------------- #


@jax.jit
def dense_window_gather(bm, qslot, w0):
    """(P, 128) uint32: each entry's word window of its query's bitmap row."""
    return jax.vmap(
        lambda q, s: jax.lax.dynamic_slice(bm[q], (s,), (WINDOW_WORDS,))
    )(qslot, w0)


@jax.jit
def dense_window_add(dst, vals, qslot, w0, act):
    """dst[qslot[j], w0[j] : w0[j] + 128] += vals[j] where act[j] — exact OR
    under the disjoint-bits contract.  Windows are 128 contiguous words, so
    the XLA scatter stays cheap (one word-aligned segment per entry)."""
    contrib = jnp.where(act[:, None], vals, jnp.uint32(0))
    cols = w0[:, None] + jnp.arange(WINDOW_WORDS, dtype=jnp.int32)[None, :]
    return dst.at[qslot[:, None], cols].add(contrib)
