"""Sharded embedding tables + EmbeddingBag.

JAX has no native ``nn.EmbeddingBag`` and no CSR sparse — lookups are built
from ``jnp.take`` + masking + segment-style reductions (kernel_taxonomy
§RecSys: "this IS part of the system").  Two paths per op:

  * plain path (no mesh / replicated table): jnp.take.
  * EP path (table rows sharded over "model"): shard_map mask-gather-psum —
    each shard gathers only the rows it owns, zeros the rest, psums.  Wire
    bytes per lookup: batch*dim psum instead of all-gathering the table.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.distributed import sharding as shlib


def _ep_ctx(table_rows: int):
    """Returns (mesh, row_axis, batch_axes) when the EP path applies."""
    ctx = shlib._active()
    if ctx is None:
        return None
    mesh, plan = ctx
    axes = tuple(a for a in (plan.axes_of("table_rows") or ()) if a in mesh.shape)
    if not axes or table_rows % shlib._mesh_size(mesh, axes) != 0:
        return None
    batch_axes = tuple(a for a in (plan.axes_of("batch") or ()) if a in mesh.shape)
    return mesh, axes[0], batch_axes


def _local_gather(tbl, loc, ok):
    """tbl (..., r, D); loc int (B, ...) same leading rank as ids; per-table."""
    if tbl.ndim == 2:
        v = jnp.take(tbl, loc, axis=0)
    else:  # stacked (T, r, D); loc (..., T) -> gather per table
        v = jax.vmap(lambda t, i: jnp.take(t, i, axis=0), in_axes=(0, -1), out_axes=-2)(tbl, loc)
        # out (..., T, D)
    return jnp.where(ok[..., None], v, 0)


def lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Single table (R, D), ids (...,) -> (..., D)."""
    ep = _ep_ctx(table.shape[0])
    if ep is None:
        return jnp.take(table, ids, axis=0)
    mesh, raxis, baxes = ep

    def local(tbl, ids_l):
        me = jax.lax.axis_index(raxis)
        r = tbl.shape[0]
        loc = ids_l - me * r
        ok = (loc >= 0) & (loc < r)
        return jax.lax.psum(_local_gather(tbl, jnp.clip(loc, 0, r - 1), ok), raxis)

    ids_spec = PS(baxes if baxes else None, *([None] * (ids.ndim - 1)))
    out_spec = PS(baxes if baxes else None, *([None] * ids.ndim))
    return jax.shard_map(local, mesh=mesh, in_specs=(PS(raxis, None), ids_spec),
                     out_specs=out_spec, check_vma=False)(table, ids)


def lookup_stacked(tables: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Stacked tables (T, R, D), ids (..., T) -> (..., T, D): out[..., t, :] =
    tables[t, ids[..., t], :]."""
    ep = _ep_ctx(tables.shape[1])
    if ep is None:
        return jax.vmap(lambda t, i: jnp.take(t, i, axis=0), in_axes=(0, -1), out_axes=-2)(tables, ids)
    mesh, raxis, baxes = ep

    def local(tbl, ids_l):
        me = jax.lax.axis_index(raxis)
        r = tbl.shape[1]
        loc = ids_l - me * r
        ok = (loc >= 0) & (loc < r)
        return jax.lax.psum(_local_gather(tbl, jnp.clip(loc, 0, r - 1), ok), raxis)

    ids_spec = PS(baxes if baxes else None, *([None] * (ids.ndim - 1)))
    out_spec = PS(baxes if baxes else None, *([None] * ids.ndim))
    return jax.shard_map(local, mesh=mesh, in_specs=(PS(None, raxis, None), ids_spec),
                     out_specs=out_spec, check_vma=False)(tables, ids)


def bag_sum(table: jnp.ndarray, ids: jnp.ndarray, valid=None) -> jnp.ndarray:
    """EmbeddingBag(sum): ids (..., L) -> (..., D); valid (..., L) bool."""
    v = lookup(table, ids)
    if valid is not None:
        v = v * valid[..., None].astype(v.dtype)
    return v.sum(axis=-2)


def bag_mean(table: jnp.ndarray, ids: jnp.ndarray, valid=None) -> jnp.ndarray:
    v = lookup(table, ids)
    if valid is None:
        return v.mean(axis=-2)
    m = valid[..., None].astype(v.dtype)
    return (v * m).sum(axis=-2) / jnp.maximum(m.sum(axis=-2), 1.0)
