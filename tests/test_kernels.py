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
