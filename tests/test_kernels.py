"""Per-kernel shape/bit-width sweeps: Pallas (interpret=True) vs ref.py oracle."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import bitpack, ops, quadmax, ref, scan_add, unpack_delta

RNG = np.random.default_rng(7)
BWS = [1, 2, 3, 4, 5, 6, 7, 8, 9, 11, 12, 13, 16, 17, 20, 24, 27, 31, 32]


def _tiles(n_frames: int, bw: int) -> jnp.ndarray:
    x = RNG.integers(0, 2**bw, n_frames * bitpack.FRAME_INTS, dtype=np.uint64).astype(np.uint32)
    return jnp.asarray(x.reshape(n_frames * bitpack.FRAME_ROWS, bitpack.LANES))


@pytest.mark.parametrize("bw", BWS)
def test_pack_matches_ref(bw):
    t = _tiles(2, bw)
    got = bitpack.pack_frames(t, bw, interpret=True, frames_per_block=1)
    want = ref.pack_frames_ref(t, bw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bw", BWS)
def test_unpack_roundtrip(bw):
    t = _tiles(3, bw)
    packed = ref.pack_frames_ref(t, bw)
    got = bitpack.unpack_frames(packed, bw, interpret=True, frames_per_block=3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(t))


@pytest.mark.parametrize("frames", [1, 2, 5, 8])
def test_frame_or_matches_ref(frames):
    t = _tiles(frames, 32)
    got = quadmax.frame_or(t, interpret=True, frames_per_block=2)
    want = ref.frame_or_ref(t)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("rows,rpb", [(8, 8), (64, 16), (96, 32), (256, 256)])
def test_prefix_sum_matches_ref(rows, rpb):
    x = jnp.asarray(RNG.integers(0, 2**20, rows * 128, dtype=np.uint64)
                    .astype(np.uint32).reshape(rows, 128))
    got = scan_add.prefix_sum_blocks(x, rows_per_block=rpb, interpret=True)
    want = ref.prefix_sum_ref(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prefix_sum_uint32_wraparound():
    x = jnp.full((8, 128), 2**31, jnp.uint32)
    got = scan_add.prefix_sum_blocks(x, rows_per_block=8, interpret=True)
    want = ref.prefix_sum_ref(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bw", [1, 5, 8, 13, 17, 32])
def test_fused_unpack_delta_matches_ref(bw):
    t = _tiles(2, bw)
    packed = ref.pack_frames_ref(t, bw)
    got = unpack_delta.unpack_delta_frames(packed, bw, interpret=True, frames_per_block=2)
    want = ref.unpack_delta_ref(packed, bw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 32), st.integers(1, 3), st.integers(0, 4095))
def test_property_stream_roundtrip(bw, frames, tail):
    n = (frames - 1) * 4096 + tail + 1
    x = RNG.integers(0, 2**bw, n, dtype=np.uint64).astype(np.uint32)
    xj = jnp.asarray(x)
    packed = ops.pack_stream(xj, bw)
    out = ops.unpack_stream(packed, bw, n)
    np.testing.assert_array_equal(np.asarray(out), x)


def test_select_bw_matches_effective_width():
    # each frame gets values of a known max width
    widths = [3, 11, 26]
    xs = [RNG.integers(2**(w - 1), 2**w, 4096, dtype=np.uint64).astype(np.uint32) for w in widths]
    x = jnp.asarray(np.concatenate(xs))
    got = np.asarray(ops.select_bw(x))
    np.testing.assert_array_equal(got, widths)


# --------------------------------------------------------------------------- #
# serving kernels: fused tile decode + accumulate, against numpy
# --------------------------------------------------------------------------- #

from repro.kernels import accumulate, decode_fused, intersect_rounds  # noqa: E402


def _gap_blocks(bw: int, sizes=(512, 300, 129, 1)) -> list:
    """d-gap blocks as the arena stores them: gap 0 first, the rest < 2**bw."""
    out = []
    for n in sizes:
        g = RNG.integers(0, 2**bw, n, dtype=np.uint64).astype(np.uint32)
        g[0] = 0
        out.append(g)
    return out


@pytest.mark.parametrize("bw", decode_fused.BW_BUCKETS)
def test_decode_tiles_matches_numpy(bw):
    blocks = _gap_blocks(bw)
    tiles = jnp.asarray(np.stack([decode_fused.pack_gaps(g, bw)
                                  for g in blocks]))
    slots = np.asarray([2, 0, 3, 1, 0], np.int32)      # permuted + repeated
    firsts = RNG.integers(0, 2**31, len(slots), dtype=np.uint64).astype(
        np.uint32)
    got = np.asarray(decode_fused.decode_tiles(
        tiles, jnp.asarray(slots), jnp.asarray(firsts), bw=bw,
        interpret=True))
    assert got.shape == (len(slots), decode_fused.BLOCK)
    for j, s in enumerate(slots):
        g = blocks[s]
        want = (firsts[j] + np.cumsum(g, dtype=np.uint64)) & 0xFFFFFFFF
        np.testing.assert_array_equal(got[j, :len(g)], want.astype(np.uint32))
        # lanes past the block repeat its last docid (zero gaps)
        np.testing.assert_array_equal(got[j, len(g):], want[-1])


def _disjoint_entries(n_docs: int, q: int, p: int):
    """p work-list entries over q queries whose docids never repeat (the
    exactness contract of the accumulate step)."""
    ids = RNG.permutation(n_docs)[: p * decode_fused.BLOCK].astype(np.uint32)
    ids = ids.reshape(p, decode_fused.BLOCK)
    qslot = (np.arange(p) % q).astype(np.int32)
    return ids, qslot


@pytest.mark.parametrize("n_docs", [25_000, 77_824])   # 896 / 2432 words
def test_scatter_add_matches_numpy(n_docs):
    q, p = 3, 8
    width = intersect_rounds.bitmap_geometry(n_docs)[0] * 32
    ids, qslot = _disjoint_entries(n_docs, q, p)
    contrib = RNG.integers(0, 256, ids.shape).astype(np.uint32)
    got = accumulate.scatter_add(jnp.zeros((q, width), jnp.uint32),
                                 jnp.asarray(ids), jnp.asarray(qslot),
                                 jnp.asarray(contrib))
    want = np.zeros((q, width), np.uint32)
    np.add.at(want, (qslot[:, None], ids), contrib)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("n_docs", [25_000, 77_824])   # 896 / 2432 words
def test_scatter_bits_matches_numpy(n_docs):
    q, p = 3, 8
    words = intersect_rounds.bitmap_geometry(n_docs)[0]
    ids, qslot = _disjoint_entries(n_docs, q, p)
    surv = RNG.random(ids.shape) < 0.5
    got = accumulate.scatter_bits(jnp.zeros((q, words), jnp.uint32),
                                  jnp.asarray(ids), jnp.asarray(qslot),
                                  jnp.asarray(surv))
    bits = np.zeros((q, words * 32), np.uint8)
    bits[np.broadcast_to(qslot[:, None], ids.shape)[surv], ids[surv]] = 1
    want = np.packbits(bits, axis=1, bitorder="little").view(np.uint32)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_dense_add_matches_numpy():
    q, p, width = 3, 6, 896 * 32
    codes = RNG.integers(0, 256, (p, accumulate.DENSE_WINDOW)).astype(
        np.uint32)
    qslot = RNG.integers(0, q, p).astype(np.int32)
    col0 = (RNG.integers(0, (width - accumulate.DENSE_WINDOW) // 128, p)
            * 128).astype(np.int32)
    act = np.asarray([True, True, False, True, True, True])
    got = accumulate.dense_add(jnp.zeros((q, width), jnp.uint32),
                               jnp.asarray(codes), jnp.asarray(qslot),
                               jnp.asarray(col0), jnp.asarray(act))
    want = np.zeros((q, width), np.uint32)
    for j in np.flatnonzero(act):
        want[qslot[j], col0[j]:col0[j] + accumulate.DENSE_WINDOW] += codes[j]
    np.testing.assert_array_equal(np.asarray(got), want)
