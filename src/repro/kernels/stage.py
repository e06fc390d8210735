"""Device-side snapshot bucket encode: XOR parity fold + CRC32 (Pallas TPU).

The save hot path's per-bucket host work (gather -> XOR parity -> zlib
CRC) moves onto the accelerator: the L1 pump gathers a bucket's scattered
leaf byte-ranges into one contiguous uint32 lane buffer on device
(`repro.core.pipeline.DeviceEncoder`, `place_bytes`: 32-bit leaves are
bitcast to words, 8- and 16-bit ones packed into words by the lane-dense
`pack_words16` / `pack_words8` kernel), and `encode_bucket` finishes the
encode *before* the d2h copy —

  * XOR-folds the k stacked stripe blocks of a parity bucket with the
    lane-dense `repro.kernels.xor_parity` kernel (k == 1, an own-data
    bucket, is a pass-through: no kernel runs), and
  * checksums the bucket with a table-free CRC32 kernel.

CRC formulation.  A CRC is sequential in its input, so the bucket is cut
into `CHUNKS` = 8 x 128 contiguous chunks of `chunk_words(nbytes)` words,
one chunk per vector lane of an (8, 128) vreg.  The bucket is viewed
step-major (word w of every chunk side by side), and the kernel walks the
words with the bitwise update ``c = (c >> 1) ^ (POLY & -(c & 1))`` x 32,
vectorised across all 1024 lanes: no table gather, no per-element dynamic
index, no scalar store to VMEM.  A grid over the word axis keeps one
`CELL_WORDS` x 4 KiB block in VMEM at a time and carries the 1024 running
registers in the resident (8, 128) output block.  Each lane stops at its
chunk's last live byte, so zero padding never enters a digest.  The 1024
chunk digests are plain zlib CRC32s of consecutive chunks; `bucket_crc`
folds them on the host into the bucket's CRC (`crcutil.crc32_fold_chunks`).

`interpret=None` follows the placement of the input
(`repro.kernels.placement`): compiled for an accelerator-resident bucket,
interpreted for a CPU-resident one.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.crcutil import POLY, crc32_fold_chunks
from repro.kernels.placement import runs_interpreted
from repro.kernels.xor_parity import xor_reduce

LANE_BYTES = 512              # pad buckets to 128 uint32 lanes x 4 bytes
SUBLANES, LANES = 8, 128
CHUNKS = SUBLANES * LANES     # one CRC chunk per vector lane
CELL_WORDS = 256              # chunk words per grid cell: 1 MiB of bucket

_INIT = 0xFFFFFFFF            # plain ints: jnp constants created at module
_BYTE = 0xFF                  # scope would be captured consts in the kernel


def chunk_words(nbytes: int) -> int:
    """Words per CRC chunk for an `nbytes` bucket (a function of the live
    length only, so the host fold never needs the padded lane count)."""
    return max(1, -(-(-(-nbytes // 4)) // CHUNKS))


def _crc_bits(c, nbits: int):
    """Advance 1024 CRC registers by `nbits` input-free bit steps."""
    poly = jnp.uint32(POLY)
    for _ in range(nbits):
        c = (c >> 1) ^ (poly & (jnp.uint32(0) - (c & 1)))
    return c


def _crc_kernel(x_ref, o_ref, *, nbytes: int, wc: int, cw: int,
                ncells: int):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _():
        o_ref[...] = jnp.full((SUBLANES, LANES), _INIT, jnp.uint32)

    nw = nbytes // 4
    chunk = (jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0) * LANES
             + jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1))
    live = jnp.clip(nw - chunk * wc, 0, wc)     # whole live words per lane
    base = g * cw

    def body(w, c):
        x = x_ref[pl.ds(pl.multiple_of(w * SUBLANES, SUBLANES), SUBLANES), :]
        return jnp.where(base + w < live, _crc_bits(c ^ x, 32), c)

    o_ref[...] = jax.lax.fori_loop(0, cw, body, o_ref[...])

    rem = nbytes % 4
    if rem:
        # the 1-3 byte tail word belongs to exactly one lane, at a static
        # (cell, row) position: byte-step it there after its full words
        jstar, wp = divmod(nw, wc)
        gp, lp = divmod(wp, cw)

        @pl.when(g == gp)
        def _():
            x = x_ref[lp * SUBLANES:(lp + 1) * SUBLANES, :]
            c = o_ref[...]
            t = c
            for b in range(rem):
                t = _crc_bits(t ^ ((x >> (8 * b)) & _BYTE), 8)
            o_ref[...] = jnp.where(chunk == jstar, t, c)

    @pl.when(g == ncells - 1)
    def _():
        o_ref[...] = o_ref[...] ^ jnp.uint32(_INIT)


def _crc_chunks(acc, *, nbytes: int, interpret: bool, cell_words: int):
    """(CHUNKS,) zlib CRC32s of the consecutive chunks of `acc`'s first
    `nbytes` bytes (chunks past the live length digest 0 bytes)."""
    wc = chunk_words(nbytes)
    total = CHUNKS * wc
    n = acc.shape[0]
    x = acc[:total] if n >= total else jnp.pad(acc, (0, total - n))
    x = x.reshape(CHUNKS, wc).T              # step-major: one chunk per lane
    cw = min(cell_words, wc)
    ncells = -(-wc // cw)
    if ncells * cw != wc:                    # masked steps past every chunk
        x = jnp.pad(x, ((0, ncells * cw - wc), (0, 0)))
    x = x.reshape(ncells * cw * SUBLANES, LANES)
    kern = functools.partial(_crc_kernel, nbytes=nbytes, wc=wc, cw=cw,
                             ncells=ncells)
    out = pl.pallas_call(
        kern,
        grid=(ncells,),
        in_specs=[pl.BlockSpec((cw * SUBLANES, LANES), lambda g: (g, 0))],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda g: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="crc32_chunks",
    )(x)
    return out.reshape(-1)


def crc_cells(nbytes: int, cell_words: int = CELL_WORDS) -> int:
    """Grid cells the CRC kernel runs for an `nbytes` bucket."""
    wc = chunk_words(nbytes)
    return -(-wc // min(cell_words, wc))


@functools.partial(jax.jit, static_argnames=("nbytes", "want_crc",
                                             "interpret", "cell_words"))
def _encode(blocks, *, nbytes: int, want_crc: bool, interpret: bool,
            cell_words: int):
    k = blocks.shape[0]
    acc = blocks[0] if k == 1 else xor_reduce(blocks, interpret=interpret)
    if not want_crc:
        return acc, jnp.zeros((1,), jnp.uint32)
    return acc, _crc_chunks(acc, nbytes=nbytes, interpret=interpret,
                            cell_words=cell_words)


def encode_bucket(blocks: jax.Array, *, nbytes: int, want_crc: bool = True,
                  interpret: Optional[bool] = None,
                  cell_words: int = CELL_WORDS):
    """Bucket encode.  blocks: (k, n_lanes) uint32 (n_lanes % 128 == 0;
    bytes past `nbytes` are zero padding).  Returns (encoded (n_lanes,)
    uint32, digests): `CHUNKS` chunk CRCs, folded by `bucket_crc`, or a
    (1,) zero when `want_crc` is False.

    k == 1: own-data bucket — pass-through + CRC.
    k  > 1: parity bucket — XOR fold of the stripe blocks (+ CRC if
    asked; parity regions carry no checksum, so callers pass False)."""
    k, n = blocks.shape
    assert blocks.dtype == jnp.uint32 and 0 < nbytes <= 4 * n
    if interpret is None:
        interpret = runs_interpreted(blocks)
    return _encode(blocks, nbytes=nbytes, want_crc=want_crc,
                   interpret=interpret, cell_words=cell_words)


# ------------------------------------------------------------ byte gather
def _window_geometry(shape, itemsize: int, n_out: int):
    """How `_place` windows a leaf for an `n_out`-word bucket: (unit
    bytes, units in the leaf, units per window).  A unit is an element
    of a 0/1-D leaf or a row (last axis) of an N-D one, so the window is
    a dynamic slice of the leaf's own layout — never a flattened copy of
    the whole leaf."""
    if len(shape) <= 1:
        unit, count = itemsize, int(np.prod(shape, dtype=np.int64))
    else:
        unit, count = shape[-1] * itemsize, \
            int(np.prod(shape[:-1], dtype=np.int64))
    return unit, count, min(count, (4 * n_out) // unit + 2)


PACK_ROWS = 128          # rows the pack kernel transposes at a time
PACK_MAX_ROW = 4096      # widest leaf row the kernel reads in place
PACK_WORDS = 512         # words per row of a window it reads flattened
PACK_BLOCK_BYTES = 1 << 20   # input bytes per grid step


def packs(dtype) -> bool:
    """True iff leaves of `dtype` are packed into words by the kernel (8-
    and 16-bit, bool as uint8); 32-bit leaves are bitcast."""
    return np.dtype(dtype).itemsize < 4


def _pack_kernel(x_ref, o_ref, t_ref, *, per: int):
    """(tr, W) 8/16-bit rows -> (tr, W / per) little-endian uint32 words.
    A transpose turns each row's neighbouring lanes into neighbouring
    sublanes, where strided loads take the `per` bytes of a word apart
    without a lane gather; a second transpose restores the rows."""
    bits = 32 // per
    n = t_ref.shape[0] // per

    def body(j, carry):
        r = pl.ds(pl.multiple_of(j * PACK_ROWS, PACK_ROWS), PACK_ROWS)
        t_ref[...] = x_ref[r, :].astype(jnp.uint32).T
        w = t_ref[pl.ds(0, n, stride=per), :]
        for i in range(1, per):
            w = w | (t_ref[pl.ds(i, n, stride=per), :] << (bits * i))
        o_ref[r, :] = w.T
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0] // PACK_ROWS, body, 0)


def pack_block_rows(rows: int, width: int, itemsize: int) -> int:
    """Rows per grid step of the pack kernel over (rows, width) input
    (rows >= `PACK_ROWS`): about `PACK_BLOCK_BYTES` of it, in whole
    `PACK_ROWS` and no more than the rows hold."""
    per_step = PACK_BLOCK_BYTES // (width * itemsize * PACK_ROWS)
    return min(max(1, per_step), rows // PACK_ROWS) * PACK_ROWS


def _pack(u, *, interpret: bool):
    """(R, W) uint8/uint16 (R >= `PACK_ROWS`, W a whole number of 128
    words) -> (R, W / per) uint32 words of the same bytes.  The last grid
    step may run past R: those rows are never written back."""
    rows, width = u.shape
    isz = u.dtype.itemsize
    per = 4 // isz
    tr = pack_block_rows(rows, width, isz)
    return pl.pallas_call(
        functools.partial(_pack_kernel, per=per),
        grid=(pl.cdiv(rows, tr),),
        in_specs=[pl.BlockSpec((tr, width), lambda g: (g, 0))],
        out_specs=pl.BlockSpec((tr, width // per), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, width // per), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((width, PACK_ROWS), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=f"pack_words{8 * isz}",
    )(u)


def _pack_words(win, *, interpret: bool):
    """Leaf window ((rows, last axis) or (elements,)) -> its row-major
    bytes as little-endian uint32 words, zero-padded past its end.
    32-bit leaves are bitcast; 8- and 16-bit ones go through the pack
    kernel, in the window's own rows where they are whole 128-word rows
    of at most `PACK_MAX_ROW` elements, else as a flat view cut into
    `PACK_WORDS`-word rows."""
    if win.dtype == jnp.bool_:
        win = win.astype(jnp.uint8)
    isz = win.dtype.itemsize
    if isz == 4:
        return jax.lax.bitcast_convert_type(win.reshape(-1), jnp.uint32)
    if isz not in (1, 2):
        raise TypeError(f"no uint32 word view for {win.dtype} leaves")
    per = 4 // isz
    u = jax.lax.bitcast_convert_type(
        win, jnp.uint16 if isz == 2 else jnp.uint8)
    if u.ndim != 2 or u.shape[1] % (LANES * per) \
            or u.shape[1] > PACK_MAX_ROW:
        width = PACK_WORDS * per
        u = u.reshape(-1)
        u = jnp.pad(u, (0, -u.shape[0] % width)).reshape(-1, width)
    u = jnp.pad(u, ((0, max(0, PACK_ROWS - u.shape[0])), (0, 0)))
    return _pack(u, interpret=interpret).reshape(-1)


def _byte_mask(k):
    """uint32 with the low `k` bytes set (k in 0..4)."""
    k = jnp.clip(k, 0, 4).astype(jnp.uint32)
    return jnp.where(k >= 4, jnp.uint32(_INIT),
                     (jnp.uint32(1) << (8 * k)) - jnp.uint32(1))


def _take_window(leaf, u0, uw: int):
    """Window of `uw` units of `leaf` starting at unit `u0`: (uw, last
    axis) rows of an N-D leaf, (uw,) elements of a 0/1-D one."""
    if leaf.ndim <= 1:
        return jax.lax.dynamic_slice(leaf.reshape(-1), (u0,), (uw,))
    rows = leaf.reshape(-1, leaf.shape[-1])
    return jax.lax.dynamic_slice(rows, (u0, 0), (uw, leaf.shape[-1]))


_window = jax.jit(_take_window, static_argnames=("uw",))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _place(out, leaf, u0, s, dst, n, *, interpret: bool = False):
    """`out` (n_out,) uint32 with its bytes [dst, dst+n) replaced by the
    leaf bytes that start `s` bytes into the window at unit `u0`."""
    n_out = out.shape[0]
    _, _, uw = _window_geometry(leaf.shape, leaf.dtype.itemsize, n_out)
    p = _pack_words(_take_window(leaf, u0, uw), interpret=interpret)
    # output word q holds window bytes [4q + d, 4q + d + 4): a funnel shift
    # of words q + dw and q + dw + 1 by db bytes
    d = s - dst
    dw, db = d // 4, d % 4
    pp = jnp.pad(p, (n_out + 1, n_out + 2))
    a = jax.lax.dynamic_slice(pp, (dw + n_out + 1,), (n_out + 1,))
    a0, a1 = a[:-1], a[1:]
    sh = (8 * db).astype(jnp.uint32)
    val = jnp.where(db == 0, a0, (a0 >> sh) | (a1 << (32 - sh)))
    q4 = 4 * jnp.arange(n_out, dtype=jnp.int32)
    mask = _byte_mask(dst + n - q4) & ~_byte_mask(dst - q4)
    return (out & ~mask) | (val & mask)


def _devices(x):
    return x.devices() if isinstance(x, jax.Array) else None


def shard_runs(leaf, src: int, n: int, prefer=None):
    """Split leaf bytes [src, src+n) (row-major flat order of the global
    array) into runs that are contiguous in one addressable shard:
    yields (shard data, byte offset in the shard, bytes).  A run follows
    the shard's block along the innermost axis it does not hold whole,
    so it never crosses a shard boundary.  Where several shards hold an
    element (replication), the one on `prefer` is used."""
    gshape = tuple(leaf.shape)
    shape = gshape or (1,)
    isz = np.dtype(leaf.dtype).itemsize
    data = {sh.device: sh.data for sh in leaf.addressable_shards}
    blocks = []
    for dev, index in leaf.sharding.devices_indices_map(gshape).items():
        if dev in data:
            index = index or (slice(None),)
            blocks.append((dev != prefer, dev.id, dev,
                           [sl.indices(d)[0] for sl, d in zip(index, shape)],
                           [sl.indices(d)[1] for sl, d in zip(index, shape)]))
    blocks.sort(key=lambda b: b[:2])
    pos, end = src, src + n
    while pos < end:
        e = pos // isz
        idx = np.unravel_index(e, shape)
        _, _, dev, lo, hi = next(
            b for b in blocks
            if all(a <= i < z for a, i, z in zip(b[3], idx, b[4])))
        inner = len(shape) - 1           # runs span the axes held whole
        while inner > 0 and lo[inner] == 0 and hi[inner] == shape[inner]:
            inner -= 1
        tail = shape[inner + 1:]
        run = (hi[inner] - idx[inner]) * int(np.prod(tail, dtype=np.int64)) \
            - int(np.ravel_multi_index(idx[inner + 1:], tail)
                  if tail else 0)
        stop = min(end, (e + run) * isz)
        local = np.ravel_multi_index([i - a for i, a in zip(idx, lo)],
                                     [z - a for a, z in zip(lo, hi)])
        yield data[dev], int(local) * isz + pos % isz, stop - pos
        pos = stop


def place_bytes(out, leaf, src: int, dst: int, n: int):
    """Copy leaf bytes [src, src+n) (row-major flat order) into bytes
    [dst, dst+n) of the uint32 lane buffer `out`, on `out`'s device.

    Offsets are split on the host into a window start (in units of the
    leaf's rows or elements) and small in-window byte offsets, so every
    call with the same leaf shape and bucket size reuses one compiled
    program and no offset overflows int32.  A leaf sharded over several
    devices is read shard by shard (`shard_runs`); a window that lives on
    another device than `out` is sliced there and only the window moves."""
    home = _devices(out)
    where = _devices(leaf)
    if where is not None and len(where) > 1:
        (prefer,) = home if home is not None and len(home) == 1 else (None,)
        for shard, s, m in shard_runs(leaf, src, n, prefer):
            out = place_bytes(out, shard, s, dst, m)
            dst += m
        return out
    shape = tuple(leaf.shape)
    unit, count, uw = _window_geometry(shape, np.dtype(leaf.dtype).itemsize,
                                       out.shape[0])
    u0 = min(src // unit, count - uw)
    s = src - u0 * unit
    if where is not None and home is not None and where != home:
        (dev,) = home
        win = jax.device_put(_window(leaf, np.int32(u0), uw=uw), dev)
        return place_bytes(out, win, s, dst, n)
    return _place(out, leaf, np.int32(u0), np.int32(s),
                  np.int32(dst), np.int32(n),
                  interpret=runs_interpreted(out))


def bucket_crc(crc, nbytes: int) -> int:
    """`encode_bucket` digests (already on the host) -> the bucket's
    zlib-compatible CRC32."""
    arr = np.asarray(crc, np.uint32).reshape(-1)
    if arr.size == 1:                     # want_crc=False placeholder
        return int(arr[0])
    return crc32_fold_chunks(arr, 4 * chunk_words(nbytes), nbytes)
