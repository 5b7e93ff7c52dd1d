"""Pallas TPU kernel: chunked RWKV6 WKV scan.

Grid = (B*H, T/Q) with the chunk dimension iterated sequentially (TPU grid
order) so the [N,N] state lives in a VMEM scratch across chunk steps.  Each
step does three MXU matmuls (att = q~ k~^T, y = att v + q~ S, S update) on a
[Q,N] tile — VMEM footprint = 4 Q*N input tiles + N*N state + Q*Q att.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CLAMP = 30.0


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref, s_scr,
            *, q: int, n: int, nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[...]

    rb = r_ref[...].astype(jnp.float32)       # [Q,N]
    kb = k_ref[...].astype(jnp.float32)
    vb = v_ref[...].astype(jnp.float32)
    wb = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)        # [1,N]
    s = s_scr[...]

    la = jnp.cumsum(jnp.log(wb), axis=0)
    la_prev = la - jnp.log(wb)                # exclusive cumulative
    q_t = rb * jnp.exp(la_prev)
    k_t = kb * jnp.exp(jnp.minimum(-la, CLAMP))
    att = jnp.dot(q_t, k_t.T, preferred_element_type=jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    att = jnp.where(col < row, att, 0.0)
    y = jnp.dot(att, vb, preferred_element_type=jnp.float32)
    y = y + (rb * u * kb).sum(-1, keepdims=True) * vb
    y = y + jnp.dot(q_t, s, preferred_element_type=jnp.float32)

    la_q = la[-1:, :]                          # [1,N]
    k_dec = kb * jnp.exp(la_q - la)
    s_new = jnp.exp(la_q).T * s + jnp.dot(k_dec.T, vb,
                                          preferred_element_type=jnp.float32)
    s_scr[...] = s_new
    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _final():
        sT_ref[...] = s_new


def rwkv6_pallas(r, k, v, w, u, s0=None, chunk: int = 64, interpret=False):
    """r,k,v,w [B,H,T,N]; u [H,N]; s0 [B,H,N,N] -> (y, sT)."""
    b, h, t, n = r.shape
    q = min(chunk, t)
    assert t % q == 0
    nc = t // q
    if s0 is None:
        s0 = jnp.zeros((b, h, n, n), jnp.float32)
    bh = b * h
    rf, kf, vf, wf = (x.reshape(bh, t, n) for x in (r, k, v, w))
    uf = jnp.broadcast_to(u[None], (b, h, n)).reshape(bh, 1, n)
    s0f = s0.reshape(bh, n, n).astype(jnp.float32)

    kern = functools.partial(_kernel, q=q, n=n, nc=nc)
    y, sT = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((bh, t, n), r.dtype),
                   jax.ShapeDtypeStruct((bh, n, n), jnp.float32)),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((None, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, n, n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((None, q, n), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((None, n, n), lambda i, j: (i, 0, 0))),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0f)
    return y.reshape(b, h, t, n), sT.reshape(b, h, n, n)
