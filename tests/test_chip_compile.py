"""Compile the serving path's kernels for a described TPU v5e, with no chip.

The TPU compiler ships with JAX and compiles for a topology that is only
described, so the tiling and lowering refusals that interpret mode cannot
see fail here instead of on the chip.  Widths are the one-chip deployment of
``chip_smoke.py``: a 6.3M-doc GOV2 shard, a 16-query batch, every fused
bit-width bucket.  Nothing runs, so nothing here checks a result — the
interpret-mode parity tests in ``test_kernels.py`` do that.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library at a time, and every
test worker imports every test file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels import decode_fused, intersect_rounds, topk

SHARD_DOCS = 6_300_000          # one chip's share of GOV2 (25.2M / 4)
NQ = 16                         # the server's max_batch
WORKLIST = 1024                 # work-list entries per round (jit bucket)
ARENA_SLOTS = 40_000            # blocks per arena (~35k at SHARD_DOCS)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).compile().as_text()


@pytest.mark.parametrize("bw", decode_fused.BW_BUCKETS)
def test_decode_tiles_compiles(one_chip, bw):
    rpb = decode_fused.rows_per_block(bw)
    hlo = _compiled_text(
        decode_fused.decode_tiles,
        _sds(one_chip, (ARENA_SLOTS, rpb, 128), jnp.uint32),
        _sds(one_chip, (WORKLIST,), jnp.int32),
        _sds(one_chip, (WORKLIST,), jnp.uint32), bw=bw, interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("lanes", [64, 8 * WORKLIST])
def test_decode_worklist_compiles(one_chip, lanes):
    """The arena work-list decode, in one vmap and, past one chunk of
    lanes, as a loop over chunks."""
    import numpy as np
    from repro.core import codec
    from repro.index import device
    lay = codec.get("group_simple").arena
    widths = tuple(col.width for col in lay.columns)
    per_e = _sds(one_chip, (lanes,), jnp.int32)
    _compiled_text(
        device._decode_worklist,
        tuple(_sds(one_chip, (ARENA_SLOTS * 512,), np.dtype(col.dtype))
              for col in lay.columns),
        (per_e,) * len(widths), (per_e,) * len(widths), per_e,
        _sds(one_chip, (lanes,), jnp.uint32),
        _sds(one_chip, (lanes,), jnp.bool_),
        decode=lay.decode_block, widths=widths)


def test_unpack_codes_compiles(one_chip):
    hlo = _compiled_text(
        topk.unpack_codes,
        _sds(one_chip, (ARENA_SLOTS, 1, 128), jnp.uint32),
        _sds(one_chip, (WORKLIST,), jnp.int32), interpret=False)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("gathered", [False, True])
@pytest.mark.parametrize("probe", [False, True])
def test_round_accumulate_compiles(one_chip, probe, gathered):
    """The AND round both device placements share: XLA probe + scatter,
    over a fused part's rows or gathered by row index from a decoded
    matrix."""
    words, _ = intersect_rounds.bitmap_geometry(SHARD_DOCS)
    bm = _sds(one_chip, (NQ, words), jnp.uint32)
    per_e = _sds(one_chip, (WORKLIST,), jnp.int32)
    hlo = _compiled_text(
        intersect_rounds.round_accumulate, bm,
        _sds(one_chip, (WORKLIST, decode_fused.BLOCK), jnp.uint32),
        per_e, per_e, bm, per_e if gathered else None, probe=probe)
    assert "tpu_custom_call" not in hlo     # no Pallas scatter on this path


@pytest.mark.parametrize("gathered", [False, True])
@pytest.mark.parametrize("gated", [False, True])
def test_score_round_compiles(one_chip, gated, gathered):
    """The ranked round both device placements share, on the 6.3M-wide
    accumulator."""
    words, _ = intersect_rounds.bitmap_geometry(SHARD_DOCS)
    width = topk.accum_width(SHARD_DOCS)
    rows = _sds(one_chip, (WORKLIST, decode_fused.BLOCK), jnp.uint32)
    bm = _sds(one_chip, (NQ, words), jnp.uint32)
    per_q = _sds(one_chip, (NQ,), jnp.uint32)
    per_e = _sds(one_chip, (WORKLIST,), jnp.int32)
    hlo = _compiled_text(
        topk.score_round, _sds(one_chip, (NQ, width), jnp.uint32), bm,
        rows, per_e, rows, per_e, bm, per_e, per_q, per_q,
        per_e if gathered else None, gated=gated)
    assert "tpu_custom_call" not in hlo


@pytest.mark.parametrize("probe", [False, True])
def test_dense_round_accumulate_compiles(one_chip, probe):
    """The dense-window AND round, gathering its windows by row index from
    the arena's whole window matrix."""
    words, _ = intersect_rounds.bitmap_geometry(SHARD_DOCS)
    bm = _sds(one_chip, (NQ, words), jnp.uint32)
    per_e = _sds(one_chip, (WORKLIST,), jnp.int32)
    hlo = _compiled_text(
        intersect_rounds.dense_round_accumulate, bm,
        _sds(one_chip, (ARENA_SLOTS, 128), jnp.uint32), per_e, per_e,
        _sds(one_chip, (WORKLIST,), jnp.bool_), bm, per_e, probe=probe)
    assert "tpu_custom_call" not in hlo


@pytest.mark.parametrize("gated", [False, True])
def test_dense_score_round_compiles(one_chip, gated):
    """The dense-window ranked round, gathering windows and score tiles by
    row index from the arenas' whole matrices."""
    words, _ = intersect_rounds.bitmap_geometry(SHARD_DOCS)
    width = topk.accum_width(SHARD_DOCS)
    bm = _sds(one_chip, (NQ, words), jnp.uint32)
    per_q = _sds(one_chip, (NQ,), jnp.uint32)
    per_e = _sds(one_chip, (WORKLIST,), jnp.int32)
    hlo = _compiled_text(
        topk.dense_score_round, _sds(one_chip, (NQ, width), jnp.uint32), bm,
        _sds(one_chip, (ARENA_SLOTS, 1024), jnp.uint32),
        _sds(one_chip, (ARENA_SLOTS, 128), jnp.uint32), per_e, per_e, per_e,
        per_q, per_q, bm, per_e, per_e, gated=gated)
    assert "tpu_custom_call" not in hlo


def test_topk_stats_compiles(one_chip):
    width = topk.accum_width(SHARD_DOCS)
    _compiled_text(topk._topk_stats_jit,
                   _sds(one_chip, (NQ, width), jnp.uint32), k=10)


def test_topk_merge_compiles_on_four_chips(topo, one_chip):
    """Doc-range sharded serving's one collective, over a 4-chip mesh."""
    import numpy as np
    from repro.distributed import collectives
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("shards",))
    rows = NamedSharding(mesh, PartitionSpec("shards"))
    hlo = _compiled_text(
        collectives.topk_merge_fn(mesh, "shards"),
        jax.ShapeDtypeStruct((4, NQ), jnp.uint32, sharding=rows),
        jax.ShapeDtypeStruct((4, NQ), jnp.int32, sharding=rows))
    assert "all-gather" in hlo
