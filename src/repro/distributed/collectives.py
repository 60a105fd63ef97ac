"""Compressed gradient collectives — the paper's bit packing applied to the
collective roofline term (DESIGN.md §3).

``compressed_psum_mean`` replaces a fp32 all-reduce with:

    quantize(int8/int4, per-block scale) -> all_to_all (reduce-scatter phase)
    -> local dequant+sum -> requantize -> all_gather -> dequant

Wire bytes: 2 * N * bits/8 vs ~8 * N for a ring fp32 all-reduce — 8x (int4)
or 4x (int8) off the collective term.  int4 payloads are bit-packed with the
same LSB-first shift+mask scheme as kernels/bitpack (the §3.2 vectorized pack;
on TPU the VPU executes it in-register before the ICI transfer).

Error feedback (1-bit-Adam style): callers keep a residual tree; quantization
error is re-injected next step, so the compression bias vanishes in
expectation.  Must be called INSIDE shard_map (manual axes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------------- #
# int4 pack/unpack (pure jnp: shift+mask, 8 nibbles per uint32)
# --------------------------------------------------------------------------- #


def pack4(x: jnp.ndarray) -> jnp.ndarray:
    """int8 values in [-8, 7], length % 8 == 0 -> uint32 (n/8,)."""
    u = (x.astype(jnp.int32) & 0xF).astype(jnp.uint32).reshape(-1, 8)
    out = jnp.zeros(u.shape[0], jnp.uint32)
    for i in range(8):
        out = out | (u[:, i] << jnp.uint32(4 * i))
    return out


def unpack4(w: jnp.ndarray, n: int) -> jnp.ndarray:
    vals = []
    for i in range(8):
        nib = (w >> jnp.uint32(4 * i)) & jnp.uint32(0xF)
        vals.append(nib.astype(jnp.int32))
    v = jnp.stack(vals, axis=1).reshape(-1)[:n]
    return jnp.where(v >= 8, v - 16, v).astype(jnp.int8)


# --------------------------------------------------------------------------- #
# quantization with per-block scales
# --------------------------------------------------------------------------- #

BLOCK = 1024


def _quantize(x: jnp.ndarray, bits: int):
    """x fp32 (n,) n % BLOCK == 0 -> (q int8 (n,), scales fp32 (n/BLOCK,))."""
    qmax = (1 << (bits - 1)) - 1
    xb = x.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(xb), axis=1) / qmax
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(xb / scale[:, None]), -qmax - 1, qmax).astype(jnp.int8)
    return q.reshape(-1), scale


def _dequantize(q: jnp.ndarray, scale: jnp.ndarray):
    return (q.astype(jnp.float32).reshape(-1, BLOCK) * scale[:, None]).reshape(-1)


# --------------------------------------------------------------------------- #
# compressed all-reduce (call inside shard_map over `axis_names`)
# --------------------------------------------------------------------------- #


def _pad_to(x: jnp.ndarray, m: int) -> jnp.ndarray:
    r = (-x.shape[0]) % m
    return jnp.concatenate([x, jnp.zeros(r, x.dtype)]) if r else x


def compressed_allreduce_flat(x: jnp.ndarray, axis_names, bits: int = 8):
    """Mean all-reduce of flat fp32 x over manual mesh axes, 2 quant rounds.

    Returns (reduced (n,), local_residual (n,)): residual = what THIS device's
    transmitted payload lost to quantization (phase-1 error everywhere, plus
    the phase-2 requantization error on the chunk this device owns) — the
    error-feedback term, computed with local knowledge only.
    """
    n = x.shape[0]
    r = jax.lax.psum(1, axis_names)                              # ring size
    me = jax.lax.axis_index(axis_names)
    xp = _pad_to(x.astype(jnp.float32), r * BLOCK)
    chunk = xp.shape[0] // r
    # phase 1: quantize, all_to_all rows (reduce-scatter)
    q, s = _quantize(xp, bits)
    resid = xp - _dequantize(q, s)                               # local phase-1 error
    qr = q.reshape(r, chunk)
    sr = s.reshape(r, chunk // BLOCK)
    if bits == 4:
        payload = jax.vmap(pack4)(qr)
        payload = jax.lax.all_to_all(payload, axis_names, 0, 0, tiled=False)
        got = jax.vmap(lambda w: unpack4(w, chunk))(payload)
    else:
        got = jax.lax.all_to_all(qr, axis_names, 0, 0, tiled=False)
    got_s = jax.lax.all_to_all(sr, axis_names, 0, 0, tiled=False)
    # local sum of everyone's contribution to my chunk
    part = jax.vmap(_dequantize)(got, got_s).sum(axis=0) / r     # mean
    # phase 2: requantize reduced chunk, all_gather
    q2, s2 = _quantize(part, bits)
    resid2 = part - _dequantize(q2, s2)                          # owner-chunk error
    resid = jax.lax.dynamic_update_slice(
        resid, jax.lax.dynamic_slice(resid, (me * chunk,), (chunk,)) + resid2 * r,
        (me * chunk,))
    if bits == 4:
        p2 = pack4(q2)
        allp = jax.lax.all_gather(p2, axis_names, axis=0, tiled=False)
        allq = jax.vmap(lambda w: unpack4(w, chunk))(allp)
    else:
        allq = jax.lax.all_gather(q2, axis_names, axis=0, tiled=False)
    alls = jax.lax.all_gather(s2, axis_names, axis=0, tiled=False)
    out = jax.vmap(_dequantize)(allq, alls).reshape(-1)
    return out[:n], resid[:n]


# --------------------------------------------------------------------------- #
# sharded-serving top-k merge (the serving path's ONE collective per batch)
# --------------------------------------------------------------------------- #


@functools.lru_cache(maxsize=None)
def topk_merge_fn(mesh, axis_name: str = "shards"):
    """The merge collective itself, jitted once per (mesh, axis): per-shard
    (theta, count) rows sharded over ``axis_name`` in, one ``all_gather``
    each, theta maxed over shards; both outputs replicated."""
    from jax.sharding import PartitionSpec as P

    def gather_max(ts, cs):
        g = jax.lax.all_gather(ts, axis_name, tiled=True)
        gc = jax.lax.all_gather(cs, axis_name, tiled=True)
        return g.max(axis=0), gc

    return jax.jit(jax.shard_map(
        gather_max, mesh=mesh, in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(), P()), check_vma=False))


def merge_topk_stats(theta_parts, count_parts, mesh=None,
                     axis_name: str = "shards"):
    """Merge per-shard (k-th sum, candidate-count) statistics into the global
    ranked threshold — doc-range sharded serving's single collective.

    theta_parts / count_parts: per-shard device arrays, each (nqp,).  Returns
    ``(theta_merged (nqp,) int64 np, counts (S, nqp) np, wire_bytes)`` where
    theta_merged[q] = max over shards (a sound lower bound on the global
    k-th sum; see ``kernels/topk.topk_stats``).

    When ``mesh`` spans exactly one device per shard the merge runs as one
    ``all_gather`` + max under ``shard_map`` over ``axis_name``; otherwise
    (logical shards on one device — the CPU CI case) the per-shard vectors
    are stacked host-side, which moves the same ``wire_bytes``.
    """
    import numpy as np
    s = len(theta_parts)
    nqp = int(theta_parts[0].shape[0])
    wire_bytes = s * nqp * 4 * 2                 # u32 theta + i32 count
    if mesh is not None and mesh.devices.size == s and s > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        t = jax.device_put(jnp.stack([jnp.asarray(p) for p in theta_parts]),
                           NamedSharding(mesh, P(axis_name)))
        c = jax.device_put(jnp.stack([jnp.asarray(p) for p in count_parts]),
                           NamedSharding(mesh, P(axis_name)))
        theta, counts = topk_merge_fn(mesh, axis_name)(t, c)
        return (np.asarray(theta).astype(np.int64),
                np.asarray(counts), wire_bytes)
    thetas = np.stack([np.asarray(p) for p in theta_parts])
    counts = np.stack([np.asarray(p) for p in count_parts])
    return thetas.max(axis=0).astype(np.int64), counts, wire_bytes


def compressed_psum_mean(tree, axis_names, bits: int = 8, error_feedback=None):
    """Mean-all-reduce a pytree with compression + error feedback.

    error_feedback: residual tree (same structure) or None.  Returns
    (reduced_tree, new_error_feedback).
    """
    leaves, tdef = jax.tree.flatten(tree)
    sizes = [int(l.size) for l in leaves]
    flat = jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in leaves])
    if error_feedback is not None:
        ef = jax.tree.leaves(error_feedback)
        flat = flat + jnp.concatenate([e.astype(jnp.float32).reshape(-1) for e in ef])
    red, new_ef_flat = compressed_allreduce_flat(flat, axis_names, bits)
    outs, efs, off = [], [], 0
    for l, sz in zip(leaves, sizes):
        outs.append(red[off:off + sz].reshape(l.shape).astype(l.dtype))
        efs.append(new_ef_flat[off:off + sz].reshape(l.shape))
        off += sz
    return jax.tree.unflatten(tdef, outs), jax.tree.unflatten(tdef, efs)
