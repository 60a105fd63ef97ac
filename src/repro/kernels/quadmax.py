"""Pallas kernel: per-frame pseudo-max via OR reduction (paper §4.4).

The paper replaces the 4-way compare-max with a logical OR — same effective
bit width, no comparisons.  On TPU the group is a frame tile: OR-reduce a
(32, 128) block over its sublane (row) axis -> (1, 128); the final cross-lane
OR (128 -> 1) is a cheap host-side epilogue on F*128 values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bitpack import FRAME_ROWS, LANES, auto_interpret


def _frame_or_kernel(x_ref, o_ref, *, frames: int):
    for f in range(frames):
        acc = x_ref[f * FRAME_ROWS, :]
        for r in range(1, FRAME_ROWS):
            acc = acc | x_ref[f * FRAME_ROWS + r, :]
        o_ref[f, :] = acc


@functools.partial(jax.jit, static_argnames=("interpret", "frames_per_block"))
def frame_or(x: jnp.ndarray, interpret=None, frames_per_block: int = 8) -> jnp.ndarray:
    """(F*32, 128) -> (F, 128) per-frame, per-lane OR."""
    f = x.shape[0] // FRAME_ROWS
    fpb = min(frames_per_block, f)
    while f % fpb:
        fpb -= 1
    return pl.pallas_call(
        functools.partial(_frame_or_kernel, frames=fpb),
        grid=(f // fpb,),
        in_specs=[pl.BlockSpec((fpb * FRAME_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((fpb, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((f, LANES), jnp.uint32),
        interpret=auto_interpret(interpret),
    )(x)
