"""Pallas kernel: blocked inclusive prefix sum (d-gap decode, paper §2.1.1).

Reconstructing docids from d-gaps is a prefix sum.  The TPU grid executes
sequentially on a core, so the running carry lives in SMEM scratch and flows
across grid steps — each step scans one (R, 128) VMEM block in linear
(row-major) stream order: lane-axis cumsum + exclusive row-total prefix +
carry.  uint32 wraparound is intentional (docids < 2**32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitpack import auto_interpret

LANES = 128


def _scan_kernel(x_ref, o_ref, carry_ref):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        carry_ref[0, 0] = jnp.uint32(0)

    x = x_ref[...]
    c = jnp.cumsum(x, axis=1, dtype=jnp.uint32)                 # within-row (lane) scan
    row_tot = c[:, -1]
    row_pref = (jnp.cumsum(row_tot, dtype=jnp.uint32) - row_tot)  # exclusive row prefix
    o_ref[...] = c + row_pref[:, None] + carry_ref[0, 0]
    carry_ref[0, 0] = carry_ref[0, 0] + jnp.sum(row_tot, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("rows_per_block", "interpret"))
def prefix_sum_blocks(x: jnp.ndarray, rows_per_block: int = 256, interpret=None) -> jnp.ndarray:
    """(R, 128) uint32 -> inclusive prefix sum in linear row-major order."""
    rows = x.shape[0]
    rpb = min(rows_per_block, rows)
    while rows % rpb:
        rpb -= 1
    return pl.pallas_call(
        _scan_kernel,
        grid=(rows // rpb,),
        in_specs=[pl.BlockSpec((rpb, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rpb, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.uint32),
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.uint32)],
        interpret=auto_interpret(interpret),
    )(x)
