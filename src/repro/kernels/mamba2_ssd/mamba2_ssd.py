"""Pallas TPU kernel: chunked Mamba2 SSD.

Grid = (B*H, T/Q), chunk dim sequential; [P,N] state in VMEM scratch.  The
intra-chunk work is a [Q,Q] decay-masked attention (C B^T ⊙ L) plus two MXU
matmuls — per-step VMEM = Q*(P+2N) inputs + P*N state + Q*Q mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xd_ref, la_ref, b_ref, c_ref, h0_ref, y_ref, hT_ref, h_scr,
            *, q: int, nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[...]

    xb = xd_ref[...].astype(jnp.float32)       # [Q,P] (dt-weighted)
    lb = la_ref[...].astype(jnp.float32)       # [Q,1] log decay per step
    bb = b_ref[...].astype(jnp.float32)        # [Q,N]
    cb = c_ref[...].astype(jnp.float32)        # [Q,N]
    hs = h_scr[...]                            # [P,N]

    la = jnp.cumsum(lb[:, 0], axis=0)          # [Q]
    seg = la[:, None] - la[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(col <= row, jnp.exp(seg), 0.0)
    att = jnp.dot(cb, bb.T, preferred_element_type=jnp.float32) * L
    y = jnp.dot(att, xb, preferred_element_type=jnp.float32)
    y = y + jnp.exp(la)[:, None] * jnp.dot(cb, hs.T,
                                           preferred_element_type=jnp.float32)
    la_q = la[-1]
    x_dec = xb * jnp.exp(la_q - la)[:, None]
    hs_new = jnp.exp(la_q) * hs + jnp.dot(x_dec.T, bb,
                                          preferred_element_type=jnp.float32)
    h_scr[...] = hs_new
    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _final():
        hT_ref[...] = hs_new


def mamba2_pallas(x, dt, a, bm, c, d, h0=None, chunk: int = 128,
                  interpret=False):
    b, h, t, p = x.shape
    n = bm.shape[-1]
    q = min(chunk, t)
    assert t % q == 0
    nc = t // q
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)
    bh = b * h
    f32 = jnp.float32
    xd = (x.astype(f32) * dt[..., None].astype(f32)).reshape(bh, t, p)
    la = (dt.astype(f32) * a[None, :, None]).reshape(bh, t, 1)
    bf = jnp.broadcast_to(bm.astype(f32)[:, None], (b, h, t, n)).reshape(bh, t, n)
    cf = jnp.broadcast_to(c.astype(f32)[:, None], (b, h, t, n)).reshape(bh, t, n)
    h0f = h0.reshape(bh, p, n).astype(f32)

    kern = functools.partial(_kernel, q=q, nc=nc)
    y, hT = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((bh, t, p), x.dtype),
                   jax.ShapeDtypeStruct((bh, p, n), f32)),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((None, q, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, p, n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((None, q, p), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((None, p, n), lambda i, j: (i, 0, 0))),
        scratch_shapes=[pltpu.VMEM((p, n), f32)],
        interpret=interpret,
    )(xd, la, bf, cf, h0f)
    y = y.reshape(b, h, t, p) + d[None, :, None, None].astype(f32) * x.astype(f32)
    return y.astype(x.dtype), hT.reshape(b, h, p, n)
