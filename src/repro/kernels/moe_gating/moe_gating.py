"""Pallas TPU kernel: fused MoE gating (softmax + top-k + load histogram).

This fuses the Reshape metric collection (per-expert routed-token counts, the
workload metric phi of paper §3.2) into the router itself: the histogram is
accumulated in a VMEM-resident [E] output across grid steps, so skew detection
costs zero extra passes (vs the paper's reported 1–2 % metric overhead).
Top-k is K iterations of (max, mask) over the row block — K is small (<=8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(logits_ref, w_ref, e_ref, cnt_ref, *, k: int, bt: int, e: int):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    x = logits_ref[...].astype(jnp.float32)            # [bt, E]
    x = x - x.max(-1, keepdims=True)
    p = jnp.exp(x)
    probs = p / p.sum(-1, keepdims=True)

    iota_e = jax.lax.broadcasted_iota(jnp.int32, (bt, e), 1)
    remaining = probs
    ws, es, hist = [], [], jnp.zeros((e,), jnp.int32)
    for _ in range(k):
        m = remaining.max(-1)
        # lowest index among equal probabilities, as lax.top_k breaks ties:
        # bf16 router logits tie often, and a different pick changes the
        # routing, hence the drops.  argmax's tie order is not pinned on TPU.
        idx = jnp.where(remaining == m[:, None], iota_e, e).min(-1)
        onehot = (iota_e == idx[:, None])
        remaining = jnp.where(onehot, -1.0, remaining)
        ws.append(m)
        es.append(idx)
        hist = hist + onehot.astype(jnp.int32).sum(0)
    w = jnp.stack(ws, -1)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    w_ref[...] = w.astype(w_ref.dtype)
    e_ref[...] = jnp.stack(es, -1)
    cnt_ref[...] += hist


def gating_pallas(logits, k: int, bt: int = 256, interpret=False):
    """logits [T,E] -> (weights [T,k] f32, experts [T,k] i32, counts [E] i32)."""
    t, e = logits.shape
    bt = min(bt, t)
    assert t % bt == 0, (t, bt)
    kern = functools.partial(_kernel, k=k, bt=bt, e=e)
    return pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((t, k), jnp.float32),
                   jax.ShapeDtypeStruct((t, k), jnp.int32),
                   jax.ShapeDtypeStruct((e,), jnp.int32)),
        grid=(t // bt,),
        in_specs=[pl.BlockSpec((bt, e), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((bt, k), lambda i: (i, 0)),
                   pl.BlockSpec((bt, k), lambda i: (i, 0)),
                   pl.BlockSpec((e,), lambda i: (0,))),
        interpret=interpret,
    )(logits)
