"""Pallas TPU flash attention (blocked online softmax).

TPU adaptation: the GPU version streams KV through shared memory per thread
block; here each grid step owns a (bq x hd) query tile resident in VMEM and
loops over (bk x hd) KV tiles with an online-softmax carry held in VMEM
scratch.  Tile sizes are MXU-aligned (128) and sized so the working set
(q tile + 2 kv tiles + acc) stays well under the ~16 MB VMEM budget.
Supports causal and sliding-window masks (gemma3 local layers).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, sk: int,
            causal: bool, window: Optional[int], q_offset: int, scale: float):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale        # [bq, hd]
    n_kv = sk // bk

    def body(kv_i, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(kv_i * bk, bk), :]
        v = v_ref[pl.ds(kv_i * bk, bk), :]
        logits = jnp.dot(q, k.astype(jnp.float32).T,
                         preferred_element_type=jnp.float32)   # [bq, bk]
        q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        k_pos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window is not None:
            mask = mask & (k_pos > q_pos - window)
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1))
        p = jnp.exp(logits - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[:, None] + jnp.dot(
            p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, q_ref.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=None, q_offset=0,
                           bq=128, bk=128, interpret=False):
    """q [B,H,Sq,hd]; k,v [B,H,Sk,hd] (kv heads pre-repeated).  -> [B,H,Sq,hd]"""
    b, h, sq, hd = q.shape
    sk = k.shape[2]
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    scale = hd ** -0.5
    qf = q.reshape(b * h, sq, hd)
    kf = k.reshape(b * h, sk, hd)
    vf = v.reshape(b * h, sk, hd)
    kern = functools.partial(_kernel, bq=bq, bk=bk, sk=sk, causal=causal,
                             window=window, q_offset=q_offset, scale=scale)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        grid=(b * h, sq // bq),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sk, hd), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sk, hd), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, hd)
