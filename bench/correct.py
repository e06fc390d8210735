"""What decides `correct`: the comparisons with the plain reference.

Three layers, each against what the program produced at the timed sizes:

* the train step: the first three steps of the run, as the window's own
  loop drove them, against the reference trained from the same seed on the
  same rows (`train_numbers`, `train_gaps`);
* the saving path: every member's newest published snapshot in shared
  memory, its own data blocks and its RAIM5 parity block, against the
  bytes of the state the trainer held at that step (`snapshot_mismatch`);
* the restore path: each restored state against the state the trainer
  held at the restored step (`tree_mismatch`).

The RAIM5 layout is written here from the paper (section 4.3), not taken
from the program: the state's bytes (leaves in pytree order, each in C
order) are cut into n stripes of n - 1 blocks of bs = ceil(W / (n(n-1)))
bytes; block j of stripe s lives on member (s + 1 + j) mod n; a member
keeps its data blocks in stripe order, then the XOR of its own stripe.
"""
from __future__ import annotations

import os
import struct

import numpy as np

import jax

SHM = "/dev/shm"
NBUF = 3
ST_CLEAN = 2


# ------------------------------------------------------------- train step
def train_gaps(prog: dict, ref: dict, opt: dict) -> dict:
    """The numbers compared for the train step (all relative):

    loss_gap    largest |loss - ref| / |ref| over the three steps;
    grad_gap    worst leaf of | |g| - |g_ref| | / max(|g_ref|, median);
                the program's first clipped gradient is mu / (1 - b1)
                after step 1;
    update_gap  the same over the weights' change after three steps.

    Leaves whose reference gradient is under a thousandth of the median
    leaf's are left out of both leaf numbers (they move by round-off)."""
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    gp = np.asarray(prog["mu_norms"]) / (1.0 - opt["b1"])
    gr = np.asarray(ref["grad_norms"])
    dp, dr = np.asarray(prog["change_norms"]), np.asarray(ref["change_norms"])
    keep = gr >= 1e-3 * np.median(gr)
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr)))}
    leaves = ref.get("leaves") or [str(i) for i in range(len(gr))]
    for name, a, b in (("grad", gp, gr), ("update", dp, dr)):
        gap = np.where(keep, np.abs(a - b) / np.maximum(b, np.median(b)),
                       0.0)
        out[f"{name}_gap"] = float(np.max(gap))
        out[f"worst_{name}_leaf"] = leaves[int(np.argmax(gap))]
    out["leaves_left_out"] = int(np.sum(~keep))
    return out


# ------------------------------------------------------------ state bytes
def state_bytes(tree) -> np.ndarray:
    """The state as one byte stream: leaves in pytree order, C order."""
    leaves = [np.ascontiguousarray(np.asarray(x)) for x in
              jax.device_get(jax.tree.leaves(tree))]
    return np.concatenate([a.reshape(-1).view(np.uint8) for a in leaves])


def tree_mismatch(got, want) -> int:
    """Bytes that differ between two trees of the same layout (a leaf of
    another shape or type counts whole)."""
    ga, wa = jax.tree.leaves(got), jax.tree.leaves(want)
    if len(ga) != len(wa):
        return -1
    bad = 0
    for a, b in zip(ga, jax.device_get(wa)):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype != b.dtype or a.shape != b.shape:
            bad += max(a.nbytes, b.nbytes)
            continue
        bad += int(np.count_nonzero(a.reshape(-1).view(np.uint8)
                                    != b.reshape(-1).view(np.uint8)))
    return bad


def raim5_blocks(stream: np.ndarray, node: int, n: int):
    """Member `node`'s published buffer for the byte stream, block by
    block: its n - 1 data blocks in stripe order, then its stripe's parity
    block.  Yields (offset in the buffer, expected bytes)."""
    W = stream.nbytes
    bs = -(-W // (n * (n - 1)))

    def block(s, j):
        k = s * (n - 1) + j
        out = stream[min(k * bs, W):min((k + 1) * bs, W)]
        if out.nbytes < bs:                     # past W: zero padding
            out = np.concatenate([out, np.zeros(bs - out.nbytes, np.uint8)])
        return out

    slots = [block(s, (node - s - 1) % n) for s in range(n) if s != node]
    for i, blk in enumerate(slots):
        yield i * bs, blk
    parity = np.zeros(bs, np.uint8)
    for j in range(n - 1):
        np.bitwise_xor(parity, block(node, j), out=parity)
    yield (n - 1) * bs, parity


def _ctl(run: str, node: int):
    with open(os.path.join(SHM, f"reft-{run}-n{node}-ctl"), "rb") as f:
        raw = f.read(8 * (2 + 2 * NBUF))
    return struct.unpack(f"<{2 + 2 * NBUF}q", raw)


def published_steps(run: str, node: int) -> dict:
    """{step: buffer index} of member `node`'s clean buffers."""
    ctl = _ctl(run, node)
    return {ctl[2 + 2 * i]: i for i in range(NBUF)
            if ctl[3 + 2 * i] == ST_CLEAN}


def snapshot_mismatch(run: str, node: int, n: int, step: int,
                      stream: np.ndarray) -> int:
    """Bytes of member `node`'s published buffer of `step` that differ
    from what the reference layout puts there (-1: not published)."""
    idx = published_steps(run, node).get(step)
    if idx is None:
        return -1
    bs = -(-stream.nbytes // (n * (n - 1)))
    got = np.memmap(os.path.join(SHM, f"reft-{run}-n{node}-buf{idx}"),
                    np.uint8, "r", shape=(n * bs,))
    bad = 0
    chunk = 64 << 20
    for off, want in raim5_blocks(stream, node, n):
        for a in range(0, bs, chunk):
            b = min(a + chunk, bs)
            bad += int(np.count_nonzero(got[off + a:off + b] != want[a:b]))
    del got
    return bad


# ----------------------------------------------------------------- verdict
def verdict(values: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]): every value at or under its
    limit, and every limit read."""
    rows = [(k, values.get(k), v) for k, v in limits.items()]
    ok = all(val is not None and np.isfinite(val) and val <= lim
             for _, val, lim in rows)
    return bool(ok), rows
