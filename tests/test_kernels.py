"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py)."""
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import (encode_bucket, ssd_scan, swa_attention,
                           xor_parity_decode, xor_parity_encode)
from repro.kernels.ref import (encode_bucket_ref, ssd_scan_ref,
                               swa_attention_ref, xor_reduce_ref)
from repro.kernels.xor_parity import xor_reduce


# ------------------------------------------------------------ xor_parity
@pytest.mark.parametrize("k", [2, 3, 5, 7])
@pytest.mark.parametrize("n", [128, 384, 4096, 65536])
def test_xor_reduce_sweep(k, n):
    rng = np.random.default_rng(k * n)
    blocks = jnp.asarray(
        rng.integers(0, 2 ** 32, size=(k, n), dtype=np.uint64)
        .astype(np.uint32))
    out = xor_reduce(blocks)
    assert bool(jnp.all(out == xor_reduce_ref(blocks)))


@pytest.mark.parametrize("n", [1, 7, 127, 129, 255, 4097])
def test_xor_reduce_odd_sizes_padded_tile(n):
    """An odd lane count is zero-padded to whole (8, 128) tiles, not
    ground down to one-element grid cells (and the interpret default
    follows the input's placement — no explicit flag here)."""
    rng = np.random.default_rng(n)
    blocks = jnp.asarray(
        rng.integers(0, 2 ** 32, size=(3, n), dtype=np.uint64)
        .astype(np.uint32))
    out = xor_reduce(blocks)
    assert out.shape == (n,)
    assert bool(jnp.all(out == xor_reduce_ref(blocks)))


# ----------------------------------------------------- stage encode kernel
def _bucket(k, nbytes, seed):
    """(k, n_lanes) uint32 stripe blocks whose bytes past `nbytes` are the
    zero padding the device gather leaves there."""
    from repro.kernels.stage import LANE_BYTES
    rng = np.random.default_rng(seed)
    npad = -(-nbytes // LANE_BYTES) * LANE_BYTES
    data = np.zeros((k, npad), np.uint8)
    data[:, :nbytes] = rng.integers(0, 256, (k, nbytes), dtype=np.uint8)
    return data.view(np.uint32)


@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("nbytes", [4, 5, 7, 100, 1001, 4096])
def test_encode_bucket_crc_matches_zlib(k, nbytes):
    """Own-data buckets (k=1) and checksummed parity folds (k=3, the
    delta path's digest compare) agree with zlib at every tail length."""
    from repro.kernels.stage import bucket_crc
    blocks = _bucket(k, nbytes, nbytes)
    ref = np.bitwise_xor.reduce(blocks, axis=0)
    out, crc = encode_bucket(jnp.asarray(blocks), nbytes=nbytes)
    assert bucket_crc(crc, nbytes) == zlib.crc32(
        ref.view(np.uint8)[:nbytes].tobytes())
    assert np.array_equal(np.asarray(out), ref)


@pytest.mark.parametrize("k", [2, 3, 5])
def test_encode_bucket_xor_fold_matches_ref(k):
    from repro.kernels.stage import bucket_crc
    rng = np.random.default_rng(k)
    blocks = rng.integers(0, 2 ** 32, (k, 256), dtype=np.uint64) \
        .astype(np.uint32)
    out, crc = encode_bucket(jnp.asarray(blocks), nbytes=1024,
                             want_crc=True)
    ref, ref_crc = encode_bucket_ref(blocks, 1024)
    assert np.array_equal(np.asarray(out), ref)
    assert bucket_crc(crc, 1024) == ref_crc
    # parity callers skip the CRC
    out2, crc2 = encode_bucket(jnp.asarray(blocks), nbytes=1024,
                               want_crc=False)
    assert np.array_equal(np.asarray(out2), ref)
    assert int(crc2[0]) == 0


@pytest.mark.parametrize("nbytes", [(1 << 20) + 13, 4 << 20])
def test_encode_bucket_tiled_large_matches_zlib(nbytes):
    """Buckets past one grid cell run the CRC over a grid of cells that
    carry the 1024 lane registers between them; the chunk digests fold
    into exactly zlib's answer, and the digests do not depend on how the
    word axis is tiled."""
    from repro.kernels.stage import bucket_crc, crc_cells, encode_bucket
    blocks = _bucket(1, nbytes, nbytes)
    data = blocks.view(np.uint8).reshape(-1)
    assert crc_cells(nbytes) > 1                    # really tiled
    out, crc = encode_bucket(jnp.asarray(blocks), nbytes=nbytes)
    assert np.asarray(crc).size == 1024             # one digest per lane
    assert bucket_crc(crc, nbytes) == zlib.crc32(data[:nbytes].tobytes())
    assert np.array_equal(np.asarray(out).view(np.uint8), data)
    # another tiling of the word axis: identical digests
    assert crc_cells(nbytes, cell_words=48) != crc_cells(nbytes)
    out2, crc2 = encode_bucket(jnp.asarray(blocks), nbytes=nbytes,
                               cell_words=48)
    assert np.array_equal(np.asarray(crc2), np.asarray(crc))


def test_encode_bucket_tiled_xor_fold():
    from repro.kernels.stage import bucket_crc, crc_cells
    rng = np.random.default_rng(3)
    k, n = 3, 1 << 19                               # 2 MiB: 2 grid cells
    assert crc_cells(4 * n) > 1
    blocks = rng.integers(0, 2 ** 32, (k, n), dtype=np.uint64) \
        .astype(np.uint32)
    out, crc = encode_bucket(jnp.asarray(blocks), nbytes=4 * n)
    ref = blocks[0] ^ blocks[1] ^ blocks[2]
    assert np.array_equal(np.asarray(out), ref)
    assert bucket_crc(crc, 4 * n) == zlib.crc32(ref.tobytes())


def test_crc32_combine_matches_zlib():
    from repro.core.crcutil import crc32_combine, crc32_concat
    rng = np.random.default_rng(0)
    parts = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
             for n in (0, 1, 3, 100, 4096, 65537)]
    whole = b"".join(parts)
    crc = crc32_concat((zlib.crc32(p), len(p)) for p in parts)
    assert crc == zlib.crc32(whole)
    assert crc32_combine(0, zlib.crc32(b"x"), 1) == zlib.crc32(b"x")


@pytest.mark.parametrize("nbytes", [1, 7, 100, 1000, 4096, 100001])
def test_xor_parity_bytes_roundtrip(nbytes):
    rng = np.random.default_rng(nbytes)
    blocks = rng.integers(0, 256, size=(4, nbytes), dtype=np.uint8)
    parity = np.asarray(xor_parity_encode(jnp.asarray(blocks)))
    np.testing.assert_array_equal(
        parity, blocks[0] ^ blocks[1] ^ blocks[2] ^ blocks[3])
    for missing in range(4):
        surv = np.delete(blocks, missing, axis=0)
        rec = np.asarray(xor_parity_decode(jnp.asarray(surv),
                                           jnp.asarray(parity)))
        np.testing.assert_array_equal(rec, blocks[missing])


# ------------------------------------------------------ device byte gather
def _leaf(shape, dtype, seed):
    """A CPU leaf of random bits (NaN and subnormal patterns included)."""
    rng = np.random.default_rng(seed)
    if dtype == np.bool_:
        return jnp.asarray(rng.integers(0, 2, shape).astype(bool))
    n = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = rng.integers(0, 256, n, dtype=np.uint8)
    return jnp.asarray(raw.view(dtype).reshape(shape))


def _check_place(leaf, src, n, dst, out_bytes, seed=0):
    """`place_bytes` of leaf bytes [src, src+n) into bytes [dst, dst+n) of
    a random bucket changes those bytes, to the leaf's row-major bytes
    (as numpy and the host path's `LeafReader` give them), and no other."""
    from repro.core.pipeline import LeafReader
    from repro.core.treebytes import make_flat_spec
    from repro.kernels.stage import place_bytes
    raw = np.asarray(leaf).tobytes()
    host = np.empty(n, np.uint8)
    LeafReader(make_flat_spec({"x": leaf}), [leaf]).read(src, src + n, host)
    assert host.tobytes() == raw[src:src + n]
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 2 ** 32, out_bytes // 4, dtype=np.uint64) \
        .astype(np.uint32)
    got = np.asarray(place_bytes(jnp.asarray(base), leaf, src, dst, n))
    want = bytearray(base.tobytes())
    want[dst:dst + n] = raw[src:src + n]
    assert got.tobytes() == bytes(want)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, np.float16, np.uint8,
                                   np.bool_])
@pytest.mark.parametrize("shape", [(3, 4096), (5, 768), (7, 896),
                                   (2, 50272), (300, 256), (1001,)])
def test_place_bytes_packs_narrow_leaves(dtype, shape):
    """8- and 16-bit leaves (bool as uint8) go through the pack kernel
    (interpreted here): the whole leaf, a piece from an odd element, and
    one from an odd byte into an odd bucket byte, each with its tail."""
    from repro.kernels.stage import packs
    leaf = _leaf(shape, dtype, seed=len(shape) * shape[-1])
    assert packs(leaf.dtype)
    nb = leaf.nbytes
    out_bytes = 1 << 16
    isz = leaf.dtype.itemsize
    for src, dst in [(0, 0), (3 * isz, 4), (5, 3), (nb // 2 + 1, 7)]:
        n = min(nb - src, out_bytes - dst)
        _check_place(leaf, src, n, dst, out_bytes, seed=src)


@pytest.mark.parametrize("shape", [(600, 896), (700, 768)])
def test_place_bytes_pack_kernel_runs_several_blocks(shape):
    """A 1 MiB bucket: the pack kernel's grid takes two steps, the last
    one past the window's rows (flattened 896-element rows; 768-element
    rows read in place)."""
    from repro.kernels.stage import pack_block_rows
    out_bytes = 1 << 20
    leaf = _leaf(shape, jnp.bfloat16, seed=shape[0])
    # windows of 587 rows (514 rows of 1024 when flat) and of 684 rows
    assert pack_block_rows(514, 1024, 2) == 512
    assert pack_block_rows(684, 768, 2) == 640
    n = min(leaf.nbytes - 2, out_bytes - 1)
    _check_place(leaf, 2, n, 1, out_bytes)


def test_place_bytes_keeps_32_bit_leaves_off_the_kernel():
    """32-bit leaves are bitcast, as before: same bytes, no pack kernel."""
    from repro.kernels.stage import packs
    leaf = _leaf((9, 384), np.float32, seed=9)
    assert not packs(leaf.dtype)
    _check_place(leaf, 6, leaf.nbytes - 6, 1, 1 << 14)


def test_gather_lanes_buckets_across_leaves():
    """`DeviceEncoder.gather_lanes` over a state of mixed widths, in odd
    4093-byte buckets (each crosses leaf boundaries and starts at an odd
    byte; the last is a short tail): the flat stream's bytes, zero
    padding to whole lanes, and counters that split the bytes by width."""
    from repro.core.pipeline import DeviceEncoder, LeafReader
    from repro.core.treebytes import leaf_arrays, make_flat_spec
    from repro.kernels.stage import LANE_BYTES
    state = {"a": _leaf((5, 896), jnp.bfloat16, 1),
             "b": _leaf((33,), np.float32, 2),
             "c": _leaf((1001,), np.uint8, 3),
             "d": _leaf((7, 96), np.bool_, 4),
             "e": _leaf((2, 768), np.float16, 5),
             "f": _leaf((3, 128), np.float32, 6)}
    spec = make_flat_spec(state)
    leaves = leaf_arrays(state)
    enc = DeviceEncoder(spec, leaves)
    reader = LeafReader(spec, leaves)
    total = spec.total_bytes
    for lo in range(0, total, 4093):
        hi = min(lo + 4093, total)
        got = np.asarray(enc.gather_lanes(lo, hi)).view(np.uint8)
        want = np.empty(hi - lo, np.uint8)
        reader.read(lo, hi, want)
        assert got.nbytes % LANE_BYTES == 0
        assert got[:hi - lo].tobytes() == want.tobytes()
        assert not got[hi - lo:].any()
    wide = sum(l.nbytes for l in spec.leaves if l.dtype == "float32")
    assert enc.gather_bytes == total
    assert enc.gather_packed_bytes == total - wide


# ------------------------------------------------------------- ssd_scan
@pytest.mark.parametrize("B,S,H,P,N,Q", [
    (2, 64, 4, 8, 16, 16),
    (1, 256, 2, 64, 128, 128),
    (2, 128, 3, 32, 64, 32),
    (1, 96, 1, 16, 32, 48),       # non-power-of-two chunking
])
def test_ssd_scan_sweep(B, S, H, P, N, Q):
    ks = jax.random.split(jax.random.PRNGKey(B * S + H), 5)
    u = jax.random.normal(ks[0], (B, S, H, P))
    a = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    Bm = jax.random.normal(ks[2], (B, S, N))
    Cm = jax.random.normal(ks[3], (B, S, N))
    h0 = jax.random.normal(ks[4], (B, H, P, N))
    yk, hk = ssd_scan(u, a, Bm, Cm, h0, chunk=Q)
    yr, hr = ssd_scan_ref(u, a, Bm, Cm, h0=h0)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr),
                               atol=5e-4, rtol=1e-3)


def test_ssd_scan_bf16_inputs():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    B, S, H, P, N = 1, 64, 2, 16, 32
    u = jax.random.normal(ks[0], (B, S, H, P), jnp.bfloat16)
    a = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    Bm = jax.random.normal(ks[2], (B, S, N), jnp.bfloat16)
    Cm = jax.random.normal(ks[3], (B, S, N), jnp.bfloat16)
    yk, hk = ssd_scan(u.astype(jnp.float32), a, Bm.astype(jnp.float32),
                      Cm.astype(jnp.float32), chunk=16)
    yr, hr = ssd_scan_ref(u.astype(jnp.float32), a, Bm.astype(jnp.float32),
                          Cm.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=2e-2,
                               rtol=2e-2)


# -------------------------------------------------------- swa_attention
@pytest.mark.parametrize("B,S,KV,G,hd,w,causal", [
    (2, 128, 2, 3, 16, None, True),
    (1, 256, 2, 2, 64, 37, True),
    (2, 128, 1, 4, 32, 64, False),
    (1, 512, 2, 1, 16, 128, True),
    (1, 128, 4, 1, 8, 1, True),       # degenerate window
])
def test_swa_attention_sweep(B, S, KV, G, hd, w, causal):
    ks = jax.random.split(jax.random.PRNGKey(S + hd), 3)
    q = jax.random.normal(ks[0], (B, S, KV, G, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    o = swa_attention(q, k, v, window=w, causal=causal,
                      block_q=64, block_k=32)
    r = swa_attention_ref(q, k, v, window=(w or 1 << 30), causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               atol=2e-5, rtol=1e-4)


def test_swa_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 2, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 2, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 32), jnp.bfloat16)
    o = swa_attention(q, k, v, window=32, block_q=64, block_k=64)
    r = swa_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), window=32)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(r),
                               atol=3e-2, rtol=3e-2)


# ----------------------------------------------------- causal_attention
@pytest.mark.parametrize("S,G", [(256, 1), (512, 2)])
def test_causal_attention_matches_flash(S, G):
    """The splash kernel (interpreted) against the pure-JAX loops it
    replaces on a TPU: output and q, k, v gradients, bf16 in, within a
    few bf16 roundings of each tensor's largest magnitude."""
    from repro.kernels.causal_attention import causal_attention
    from repro.models.flash import flash_attention
    B, KV, hd = 2, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(S + G), 4)
    q = jax.random.normal(ks[0], (B, S, KV, G, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (B, S, KV, G, hd), jnp.bfloat16)

    def kernel(q, k, v):
        return causal_attention(q, k, v, interpret=True)

    def loops(q, k, v):
        return flash_attention(q, k, v, window=jnp.int32(1 << 30),
                               block_q=128, block_k=128)

    def outs(f):
        o, vjp = jax.vjp(f, q, k, v)
        return (o, *vjp(ct))

    for name, a, r in zip(("out", "dq", "dk", "dv"), outs(kernel),
                          outs(loops)):
        assert a.dtype == r.dtype == jnp.bfloat16, name
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        tol = 4 * 2.0 ** -8 * np.abs(r).max()          # 4 bf16 ulps
        assert np.abs(a - r).max() <= tol, (name, np.abs(a - r).max(), tol)


def test_swa_skips_out_of_band_blocks_same_result():
    """Band skipping is an optimization, never a semantic change."""
    from repro.models.flash import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, S, KV, G, hd, w = 1, 256, 1, 2, 16, 32
    q = jax.random.normal(ks[0], (B, S, KV, G, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    full = flash_attention(q, k, v, window=jnp.int32(w), block_q=64,
                           block_k=32)
    band = flash_attention(q, k, v, window=jnp.int32(w), block_q=64,
                           block_k=32, band=w)
    np.testing.assert_allclose(np.asarray(full), np.asarray(band),
                               atol=1e-5, rtol=1e-5)
