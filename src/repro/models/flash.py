"""Flash-style (online-softmax) attention in pure JAX.

Long-sequence attention (`models.attention.attention`) wherever the TPU
kernel `kernels/causal_attention.py` does not run: on the CPU, for
sliding-window and banded layers, for bidirectional attention and in
unrolled dry runs.  It never materializes the (Sq, Sk) score matrix.
Supports causal masking, sliding windows (traced width), GQA, and an
optional *banded* mode (static window) that skips out-of-window KV blocks
entirely, turning O(S^2) FLOPs into O(S*W) — the §Perf hillclimb for SWA
architectures.

Also the reference semantics for the Pallas kernels: the causal splash
kernel's test oracle, and `swa_attention`'s (whose own oracle is
kernels/ref.py's naive masked softmax).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _pick_block(n, target):
    b = min(target, n)
    while n % b:
        b -= 1
    return b


def flash_attention(q, k, v, *, window, causal=True, q_offset=0,
                    block_q=512, block_k=1024, band=None, unroll=False):
    """q: (B,Sq,KV,G,hd), k/v: (B,Sk,KV,hd); window: traced int32 scalar.

    band: optional *static* int window; KV blocks fully outside the band of
    each query block are skipped (exact banded attention).
    unroll: python loops instead of lax.scan (dry-run FLOP accounting).
    Returns (B,Sq,KV,G,hd) in q.dtype.
    """
    B, Sq, KV, G, hd = q.shape
    Sk = k.shape[1]
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk
    scale = hd ** -0.5

    qb = q.reshape(B, nq, bq, KV, G, hd).swapaxes(0, 1)   # (nq,B,bq,KV,G,hd)
    kb = k.reshape(B, nk, bk, KV, hd).swapaxes(0, 1)
    vb = v.reshape(B, nk, bk, KV, hd).swapaxes(0, 1)
    kpos_all = jnp.arange(Sk, dtype=jnp.int32).reshape(nk, bk)

    def q_block(iq, q_i, kv_idxs):
        """iq: scalar (traced or static); kv_idxs: 1-D block index array."""
        qpos = q_offset + iq * bq + jnp.arange(bq, dtype=jnp.int32)
        m0 = jnp.full((B, KV, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, bq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, bq, hd), jnp.float32)

        def kv_step(carry, ik):
            m, l, acc = carry
            k_i, v_i, kpos = kb[ik], vb[ik], kpos_all[ik]
            s = jnp.einsum("bqkgh,bskh->bkgqs", q_i, k_i,
                           preferred_element_type=jnp.float32) * scale
            ok = jnp.ones((bq, bk), bool)
            if causal:
                ok = ok & (kpos[None, :] <= qpos[:, None])
            ok = ok & (qpos[:, None] - kpos[None, :] < window)
            ok = ok & (kpos[None, :] - qpos[:, None] < window)
            s = jnp.where(ok[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(v_i.dtype), v_i)
            acc = acc * corr[..., None] + pv.astype(jnp.float32)
            return (m_new, l_new, acc), None

        if isinstance(kv_idxs, (range, list, tuple)):
            carry = (m0, l0, a0)
            for ik in kv_idxs:
                carry, _ = kv_step(carry, ik)
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), kv_idxs)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)                        # (B,KV,G,bq,hd)

    if band is None and unroll:
        o = jnp.stack([q_block(iq, qb[iq], range(nk))
                       for iq in range(nq)], axis=0)
    elif band is None:
        def q_step(_, xs):
            iq, q_i = xs
            return None, q_block(iq, q_i, jnp.arange(nk))
        _, o = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    else:
        outs = []
        for iq in range(nq):
            q_lo = iq * bq + q_offset
            q_hi = q_lo + bq - 1
            k_lo_blk = max(0, (q_lo - band + 1) // bk)
            if causal:
                k_hi_blk = min(nk - 1, q_hi // bk)
            else:
                k_hi_blk = min(nk - 1, (q_hi + band - 1) // bk)
            idxs = (range(k_lo_blk, k_hi_blk + 1) if unroll
                    else jnp.arange(k_lo_blk, k_hi_blk + 1))
            outs.append(q_block(iq, qb[iq], idxs))
        o = jnp.stack(outs, axis=0)

    # o: (nq, B, KV, G, bq, hd) -> (B, Sq, KV, G, hd)
    o = jnp.moveaxis(o, 0, 1)                              # (B,nq,KV,G,bq,hd)
    o = jnp.transpose(o, (0, 1, 4, 2, 3, 5))               # (B,nq,bq,KV,G,hd)
    return o.reshape(B, Sq, KV, G, hd)
