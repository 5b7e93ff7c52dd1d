"""The plain reference: the configuration's model in float32 ``jax.numpy``.

It imports nothing of the program and takes nothing the program made: the
weights are the ones the benchmark built from the seed (``model.py``), read
through the benchmark's own layout.  Matrix products run at ``highest``
precision.  It follows the program's architecture where that departs from
the published model (the configuration file lists each departure).

Serving (:func:`serve_gaps`): one causal forward pass over each sampled
prompt with its served tokens, every expert computed for every token and
weighted by the renormalised top-k router probabilities (no capacity, no
drops: one token at a time never overflows an expert).  For each served
token it reads how far that token's logit lies below the reference's best.

The control (``quant="fp8"``) is the same pass with both operands of every
matrix product with a weight rounded to float8 e4m3 (one scale per output
column of the weight, one per row of the activation): the precision step
below the configuration's bfloat16 compute that a later change might be
tempted to take.  It is read at the same prompts and tokens: the gap of the token it
would put first.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def quantize(w, quant):
    """Round a weight [..., in, out] to ``quant`` with one scale per output
    column (per row of the input dimension's reduction); float32 back."""
    w = w.astype(F32)
    if quant is None:
        return w
    if quant == "fp8":
        amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
        scale = jax.lax.stop_gradient(jnp.maximum(amax, 1e-12) / 448.0)
        q = (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
        # the forward pass sees the rounded weight; the gradient reaches the
        # float32 weight as it would through a cast (straight through)
        return w + jax.lax.stop_gradient(q - w)
    raise ValueError(quant)


def act(x, quant):
    """Round an activation [..., in] to ``quant`` with one scale per row,
    straight through for the gradient; float32 back."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jax.lax.stop_gradient(jnp.maximum(amax, 1e-12) / 448.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm(x, w, quant):
    """x @ w with both operands in ``quant`` (None: float32)."""
    return act(x, quant) @ quantize(w, quant)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """Rotate-half rotary embedding; x [B,S,H,hd] at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs         # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(c, lp, h, quant):
    b, s, d = h.shape
    nh, kh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // nh
    q = mm(h, lp["wq"], quant).reshape(b, s, nh, hd)
    k = mm(h, lp["wk"], quant).reshape(b, s, kh, hd)
    v = mm(h, lp["wv"], quant).reshape(b, s, kh, hd)
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    rep = nh // kh
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    return mm(o.reshape(b, s, nh * hd), lp["wo"], quant)


def router(c, lp, h, quant):
    """Top-k experts and their renormalised weights, as a dense [T, E]
    gate (zero off the top k)."""
    logits = mm(h, lp["router"], quant)
    probs = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    w = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros_like(probs)
    rows = jnp.arange(probs.shape[0])[:, None]
    return gate.at[rows, idx].set(w), probs


def experts_dense(c, lp, h, gate, quant):
    """sum_e gate[:, e] * expert_e(h), one expert at a time."""
    e = c["num_experts"]

    def one(acc, xs):
        wg, wu, wd, g = xs
        y = mm(jax.nn.silu(mm(h, wg, quant)) * mm(h, wu, quant), wd, quant)
        return acc + g[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (lp["w_gate"][:e], lp["w_up"][:e], lp["w_down"][:e],
                         gate.T))
    return y


@partial(jax.jit, static_argnames=("cj", "quant"))
def _layer(x, lp, cj, quant):
    c = dict(cj)
    eps = c["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = x + attention(c, lp, rms_norm(x, lp["ln1"].astype(F32), eps),
                          quant)
        b, s, d = x.shape
        h = rms_norm(x, lp["ln2"].astype(F32), eps).reshape(b * s, d)
        gate, _ = router(c, lp, h, quant)
        return x + experts_dense(c, lp, h, gate, quant).reshape(b, s, d)


@partial(jax.jit, static_argnames=("cj", "quant"))
def _head(x, final_ln, head, cj, quant):
    c = dict(cj)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, final_ln.astype(F32), c["rms_norm_eps"])
        return mm(h, head, quant)


def _frozen(c: Dict) -> Tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "hidden_size", "rope_theta", "rms_norm_eps", "num_experts",
            "num_experts_per_tok")
    return tuple((k, c.get(k)) for k in keys)


def hidden(c: Dict, params, tokens: np.ndarray, quant=None):
    """Final hidden states [B, S, D] for padded token rows [B, S]; padding
    must trail each row (causal attention keeps it out of real rows)."""
    cj = _frozen(c)
    x = jnp.asarray(params["embed"])[jnp.asarray(tokens)].astype(F32)
    blk = params["moe"]
    for layer in range(c["num_hidden_layers"]):
        lp = {k: v[layer] for k, v in blk.items()}
        x = _layer(x, lp, cj, quant)
    return x


def _head_w(c, params):
    return params["embed"].T if c["tie_word_embeddings"] \
        else params["lm_head"]


def serve_gaps(c: Dict, params, seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
               pad_to: int, quant=None) -> List[np.ndarray]:
    """Per sequence (prompt, served tokens), per served position: the gap
    between the reference's best logit and the logit of the token served
    (quant None), or of the token the control would put first (quant
    "fp8")."""
    rows = np.zeros((len(seqs), pad_to), np.int32)
    for i, (p, o) in enumerate(seqs):
        full = np.concatenate([p, o[:-1]])
        rows[i, :len(full)] = full
    h_ref = hidden(c, params, rows)
    h_ctl = hidden(c, params, rows, quant) if quant else None
    cj = _frozen(c)
    head = _head_w(c, params)
    out = []
    for i, (p, o) in enumerate(seqs):
        lo, n = len(p) - 1, len(o)
        sl = slice(lo, lo + n)
        ref = np.asarray(_head(h_ref[i, sl], params["final_ln"], head, cj,
                               None))
        if quant:
            ctl = np.asarray(_head(h_ctl[i, sl], params["final_ln"], head,
                                   cj, quant))
            chosen = np.argmax(ctl, -1)
        else:
            chosen = np.asarray(o)
        best = ref.max(-1)
        got = ref[np.arange(n), chosen]
        out.append(best - got)
    return out
