"""Pallas TPU kernels: fixed-bit-width pack/unpack over the wide vertical layout.

This is the paper's hot loop (vectorized shift+mask, §3.2/§4.4) adapted to the
TPU: a frame of 4096 integers lives in a (32, 128) VMEM tile — 128 lanes play
the role of the four SSE components, 32 slots per lane.  Packing at bit width
``bw`` emits exactly (bw, 128) words per frame: each lane squeezes its 32
values (32*bw bits) into bw words, LSB-first.  All shift amounts are static
(the bit width is closed over at trace time — the TPU analogue of the paper's
per-selector SWITCH-CASE specialization, §4.4), so the unrolled body is pure
VPU shift/AND/OR work with no data-dependent control flow.

Grid: one step per frame (or several frames per step via the ``frames_per_block``
knob — fewer grid steps, bigger VMEM tiles).  BlockSpecs tile HBM -> VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

FRAME_ROWS = 32
LANES = 128
FRAME_INTS = FRAME_ROWS * LANES


def auto_interpret(interpret) -> bool:
    """Resolve an ``interpret`` kwarg: None means "compile only on TPU".

    TPU runs compile the real Mosaic kernels by default; every other backend
    (this container's CPU, but also GPU, whose Triton lowering has no
    ``pltpu`` grid-spec/scratch dialect) keeps the interpreter path.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _mask(bw: int) -> jnp.ndarray:
    return jnp.uint32(0xFFFFFFFF if bw >= 32 else (1 << bw) - 1)


def _pack_kernel(x_ref, o_ref, *, bw: int, frames: int):
    m = _mask(bw)
    for f in range(frames):
        acc = jnp.zeros((LANES,), jnp.uint32)
        off = 0
        w = 0
        for r in range(FRAME_ROWS):
            v = x_ref[f * FRAME_ROWS + r, :] & m
            acc = acc | (v << jnp.uint32(off)) if off else (acc | v)
            if off + bw >= 32:
                o_ref[f * bw + w, :] = acc
                w += 1
                rem = off + bw - 32
                acc = (v >> jnp.uint32(bw - rem)) if rem else jnp.zeros((LANES,), jnp.uint32)
                off = rem
            else:
                off += bw
        assert w == bw and off == 0  # 32*bw bits == bw words, always exact


def _unpack_kernel(p_ref, o_ref, *, bw: int, frames: int):
    m = _mask(bw)
    for f in range(frames):
        for r in range(FRAME_ROWS):
            start = r * bw
            w, off = start // 32, start % 32
            v = p_ref[f * bw + w, :] >> jnp.uint32(off)
            if off + bw > 32:
                v = v | (p_ref[f * bw + w + 1, :] << jnp.uint32(32 - off))
            o_ref[f * FRAME_ROWS + r, :] = v & m


@functools.partial(jax.jit, static_argnames=("bw", "interpret", "frames_per_block"))
def pack_frames(x: jnp.ndarray, bw: int, interpret=None, frames_per_block: int = 4) -> jnp.ndarray:
    """(F*32, 128) uint32 -> (F*bw, 128) uint32; F must be a multiple of frames_per_block."""
    f = x.shape[0] // FRAME_ROWS
    fpb = min(frames_per_block, f)
    while f % fpb:
        fpb -= 1
    grid = (f // fpb,)
    return pl.pallas_call(
        functools.partial(_pack_kernel, bw=bw, frames=fpb),
        grid=grid,
        in_specs=[pl.BlockSpec((fpb * FRAME_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((fpb * bw, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((f * bw, LANES), jnp.uint32),
        interpret=auto_interpret(interpret),
    )(x)


@functools.partial(jax.jit, static_argnames=("bw", "interpret", "frames_per_block"))
def unpack_frames(packed: jnp.ndarray, bw: int, interpret=None, frames_per_block: int = 4) -> jnp.ndarray:
    """(F*bw, 128) uint32 -> (F*32, 128) uint32."""
    f = packed.shape[0] // bw
    fpb = min(frames_per_block, f)
    while f % fpb:
        fpb -= 1
    grid = (f // fpb,)
    return pl.pallas_call(
        functools.partial(_unpack_kernel, bw=bw, frames=fpb),
        grid=grid,
        in_specs=[pl.BlockSpec((fpb * bw, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((fpb * FRAME_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((f * FRAME_ROWS, LANES), jnp.uint32),
        interpret=auto_interpret(interpret),
    )(packed)
