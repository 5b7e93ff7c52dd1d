"""Per-block-type parameter definitions and apply functions.

Block types (see configs.base): attn, local, moe, rwkv, mamba, shared_attn,
enc, dec.  Each type defines:
  defs(cfg)                         parameter declaration (ParamDef tree)
  apply(p, x, ctx)                  full-sequence forward (train / prefill)
  decode(p, x, cache, ctx)          one-token forward + updated cache
  init_cache(cfg, batch, smax)      per-layer cache pytree (ShapeDtypeStruct-able)

Decode takes the cache in one of two ways, by cache type.  A positional
cache (:data:`POSITIONAL_CACHE_TYPES`: K/V rows addressed by position) is
handed over as the type's whole layer stack ``[n, B, S, ...]`` with the
layer's index in ``ctx["layer"]``: the decode writes the new token's row in
place and reads its layer where it lies, and returns the stack.  A
recurrent state (rwkv, mamba) is handed over as the layer's own slice and
returned rewritten whole.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models.layers import apply_mrope, apply_rope, rms_norm, swiglu
from repro.models.params import ParamDef

LORA_DIM = 64


def _attn_defs(cfg: ArchConfig, cross: bool = False) -> Dict[str, ParamDef]:
    d, h, kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pre = "c" if cross else ""
    return {
        pre + "wq": ParamDef((d, h * hd), ("embed", "qkv")),
        pre + "wk": ParamDef((d, kh * hd), ("embed", "qkv")),
        pre + "wv": ParamDef((d, kh * hd), ("embed", "qkv")),
        pre + "wo": ParamDef((h * hd, d), ("qkv", "embed")),
    }


def _mlp_defs(cfg: ArchConfig) -> Dict[str, ParamDef]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": ParamDef((d, f), ("embed", "mlp")),
        "w_up": ParamDef((d, f), ("embed", "mlp")),
        "w_down": ParamDef((f, d), ("mlp", "embed")),
    }


def _rope(cfg: ArchConfig, x, ctx):
    if cfg.rope_theta <= 0:
        return x
    if cfg.mrope:
        return apply_mrope(x, ctx["positions3"], cfg.rope_theta)
    return apply_rope(x, ctx["positions"], cfg.rope_theta)


def _project_qkv(cfg, p, x):
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"].astype(x.dtype)).reshape(
        b, s, cfg.n_heads, cfg.hd)
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"].astype(x.dtype)).reshape(
        b, s, cfg.n_kv_heads, cfg.hd)
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"].astype(x.dtype)).reshape(
        b, s, cfg.n_kv_heads, cfg.hd)
    return q, k, v


# Device scopes: ``attention`` (projections, attention, KV-cache read and
# write) and ``moe`` (router, dispatch, experts, combine) name the ops of
# the two in training and serving alike, so a profile groups device time
# by them (HLO metadata only; the compiled program is unchanged).

def _self_attention(cfg, p, x, ctx, *, causal=True, window=None):
    with jax.named_scope("attention"):
        b, s, d = x.shape
        q, k, v = _project_qkv(cfg, p, x)
        q = _rope(cfg, q, ctx)
        k = _rope(cfg, k, ctx)
        out = attn_lib.chunked_attention(q, k, v, causal=causal,
                                         window=window)
        out = out.reshape(b, s, cfg.n_heads * cfg.hd)
        return jnp.einsum("bsq,qd->bsd", out, p["wo"].astype(x.dtype))


def _attn_block_apply(p, x, ctx, *, window=None, causal=True):
    cfg = ctx["cfg"]
    h = x + _self_attention(cfg, p, rms_norm(x, p["ln1"], cfg.norm_eps), ctx,
                            causal=causal, window=window)
    return h + swiglu(rms_norm(h, p["ln2"], cfg.norm_eps),
                      p["w_gate"], p["w_up"], p["w_down"])


def _attn_cache(cfg: ArchConfig, batch: int, smax: int, kv_dtype=None):
    kh, hd = cfg.n_kv_heads, cfg.hd
    z = jnp.zeros
    dt = kv_dtype or jnp.bfloat16
    return {"k": z((batch, smax, kh, hd), dt),
            "v": z((batch, smax, kh, hd), dt)}


def _decode_self_attention(p, x, cache, ctx, *, window=None,
                           rolling=False):
    """One new token's self-attention against the KV cache: pre-norm,
    projections, the cache write at ``ctx['pos']`` and the read.  x [B,1,D];
    cache {k,v [n,B,Smax,KH,hd]}, the type's layer stack, of which this is
    layer ``ctx['layer']``.  The token's K/V row is written into the stack
    in place and attention reads the layer where it lies, so no layer is
    copied out or written back.  Returns (the residual branch, the
    stack)."""
    with jax.named_scope("attention"):
        cfg, pos, layer = ctx["cfg"], ctx["pos"], ctx["layer"]
        xb = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(cfg, p, xb)
        q = _rope(cfg, q, ctx)
        k = _rope(cfg, k, ctx)
        smax = cache["k"].shape[2]
        widx = pos % smax if rolling else pos

        def write(c, row):
            return jax.lax.dynamic_update_slice(
                c, row[None].astype(c.dtype), (layer, 0, widx, 0, 0))

        def read(c):
            return jax.lax.dynamic_index_in_dim(c, layer, keepdims=False)

        k_cache, v_cache = write(cache["k"], k), write(cache["v"], v)
        out = attn_lib.decode_attention(q, read(k_cache), read(v_cache), pos,
                                        window=window, rolling=rolling)
        out = out.reshape(x.shape[0], 1, cfg.n_heads * cfg.hd)
        return (jnp.einsum("bsq,qd->bsd", out, p["wo"].astype(x.dtype)),
                {"k": k_cache, "v": v_cache})


def _attn_block_decode(p, x, cache, ctx, *, window=None, rolling=False):
    """x [B,1,D]; cache {k,v [n,B,Smax,KH,hd]}; ctx['pos'], ctx['layer']
    scalars."""
    cfg = ctx["cfg"]
    a, new = _decode_self_attention(p, x, cache, ctx, window=window,
                                    rolling=rolling)
    h = x + a
    h = h + swiglu(rms_norm(h, p["ln2"], cfg.norm_eps),
                   p["w_gate"], p["w_up"], p["w_down"])
    return h, new


# ---------------------------------------------------------------- attn/local

def attn_defs(cfg):
    return {"ln1": ParamDef((cfg.d_model,), ("embed",), "ones"),
            "ln2": ParamDef((cfg.d_model,), ("embed",), "ones"),
            **_attn_defs(cfg), **_mlp_defs(cfg)}


def attn_apply(p, x, ctx):
    return _attn_block_apply(p, x, ctx)


def attn_decode(p, x, cache, ctx):
    return _attn_block_decode(p, x, cache, ctx)


def local_apply(p, x, ctx):
    return _attn_block_apply(p, x, ctx, window=ctx["cfg"].window)


def local_decode(p, x, cache, ctx):
    # rolling window cache: smax == window
    return _attn_block_decode(p, x, cache, ctx, window=ctx["cfg"].window,
                              rolling=True)


# ----------------------------------------------------------------------- moe

def moe_defs(cfg):
    m = cfg.moe
    s = moe_lib.num_slots(cfg)
    d, f = cfg.d_model, m.expert_d_ff
    return {"ln1": ParamDef((cfg.d_model,), ("embed",), "ones"),
            "ln2": ParamDef((cfg.d_model,), ("embed",), "ones"),
            **_attn_defs(cfg),
            "router": ParamDef((d, m.num_experts), ("embed", None)),
            "w_gate": ParamDef((s, d, f), ("experts", "embed", None)),
            "w_up": ParamDef((s, d, f), ("experts", "embed", None)),
            "w_down": ParamDef((s, f, d), ("experts", None, "embed"))}


def _moe_branch(p, h, ctx):
    """The MoE FFN's residual branch over every token of h [B,S,D]: pre-norm,
    router, dispatch, experts, combine.  Records the layer's load metrics
    in ``ctx['moe_metrics']``."""
    cfg = ctx["cfg"]
    b, s, d = h.shape
    with jax.named_scope("moe"):
        flat = rms_norm(h, p["ln2"], cfg.norm_eps).reshape(b * s, d)
        y, metrics = moe_lib.moe_ffn(
            p, flat, ctx["plan_slots"], ctx["plan_cum"], cfg,
            token_offset=ctx.get("token_offset", 0), mesh=ctx.get("mesh"),
            tokens_sharded=ctx.get("tokens_sharded", True),
            layout=ctx.get("layout", "tp"))
    ctx["moe_metrics"].append(metrics)
    return y.reshape(b, s, d)


def moe_apply(p, x, ctx):
    cfg = ctx["cfg"]
    h = x + _self_attention(cfg, p, rms_norm(x, p["ln1"], cfg.norm_eps), ctx)
    return h + _moe_branch(p, h, ctx)


def _moe_decode_impl(p, x, cache, ctx):
    a, new = _decode_self_attention(p, x, cache, ctx)
    h = x + a
    return h + _moe_branch(p, h, ctx), new


# ---------------------------------------------------------------------- rwkv

def rwkv_defs(cfg):
    d = cfg.d_model
    h, n = cfg.n_heads, cfg.hd
    f = cfg.d_ff
    return {
        "ln1": ParamDef((d,), ("embed",), "ones"),
        "ln2": ParamDef((d,), ("embed",), "ones"),
        "mu_r": ParamDef((d,), ("embed",), "zeros"),
        "mu_k": ParamDef((d,), ("embed",), "zeros"),
        "mu_v": ParamDef((d,), ("embed",), "zeros"),
        "mu_w": ParamDef((d,), ("embed",), "zeros"),
        "mu_g": ParamDef((d,), ("embed",), "zeros"),
        "wr": ParamDef((d, d), ("embed", "qkv")),
        "wk": ParamDef((d, d), ("embed", "qkv")),
        "wv": ParamDef((d, d), ("embed", "qkv")),
        "wg": ParamDef((d, d), ("embed", "qkv")),
        "w0": ParamDef((d,), ("embed",), "zeros"),
        "w_lora_a": ParamDef((d, LORA_DIM), ("embed", None)),
        "w_lora_b": ParamDef((LORA_DIM, d), (None, "embed")),
        "u": ParamDef((h, n), (None, None)),
        "ln_x": ParamDef((d,), ("embed",), "ones"),
        "wo": ParamDef((d, d), ("qkv", "embed")),
        "mu_ck": ParamDef((d,), ("embed",), "zeros"),
        "wck": ParamDef((d, f), ("embed", "mlp")),
        "wcv": ParamDef((f, d), ("mlp", "embed")),
        "wcr": ParamDef((d, d), ("embed", "qkv")),
    }


def _shift(x, x_prev_token=None):
    """Token shift: prepend previous-token row (zeros / carried state)."""
    pad = jnp.zeros_like(x[:, :1]) if x_prev_token is None else x_prev_token
    return jnp.concatenate([pad, x[:, :-1]], axis=1)


def _rwkv_decay(p, xw):
    lora = jnp.einsum("bsd,dk->bsk", xw, p["w_lora_a"].astype(xw.dtype))
    lora = jnp.einsum("bsk,kd->bsd", jnp.tanh(lora),
                      p["w_lora_b"].astype(xw.dtype))
    return jnp.exp(-jnp.exp((p["w0"].astype(jnp.float32) +
                             lora.astype(jnp.float32)).clip(-8, 1.5)))


def rwkv_time_mix(p, x, ctx, x_prev=None, state=None):
    """x [B,S,D].  Returns (out, last_x, new_state)."""
    cfg = ctx["cfg"]
    b, s, d = x.shape
    h, n = cfg.n_heads, cfg.hd
    xs = _shift(x, x_prev)
    def mix(mu):
        return x + mu.astype(x.dtype) * (xs - x)
    from repro.kernels.rwkv6_scan.ops import rwkv6, rwkv6_decode_step
    r = jnp.einsum("bsd,dq->bsq", mix(p["mu_r"]), p["wr"].astype(x.dtype))
    k = jnp.einsum("bsd,dq->bsq", mix(p["mu_k"]), p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dq->bsq", mix(p["mu_v"]), p["wv"].astype(x.dtype))
    g = jnp.einsum("bsd,dq->bsq", mix(p["mu_g"]), p["wg"].astype(x.dtype))
    w = _rwkv_decay(p, mix(p["mu_w"]))
    to_heads = lambda z: z.reshape(b, s, h, n).transpose(0, 2, 1, 3)
    u = p["u"].astype(jnp.float32)
    if s == 1 and state is not None:
        y, s_new = rwkv6_decode_step(
            to_heads(r)[:, :, 0], to_heads(k)[:, :, 0], to_heads(v)[:, :, 0],
            to_heads(w.astype(x.dtype))[:, :, 0], u, state)
        y = y[:, :, None]                     # [B,H,1,N]
    else:
        y, s_new = rwkv6(to_heads(r), to_heads(k), to_heads(v),
                         to_heads(w.astype(x.dtype)), u, s0=state,
                         chunk=cfg.ssm.chunk, impl=ctx.get("impl", "jnp"))
    y = y.transpose(0, 2, 1, 3).reshape(b, s, d)
    y = rms_norm(y, p["ln_x"], cfg.norm_eps) * jax.nn.silu(g)
    out = jnp.einsum("bsq,qd->bsd", y, p["wo"].astype(x.dtype))
    return out, x[:, -1:], s_new


def rwkv_channel_mix(p, x, x_prev=None):
    xs = _shift(x, x_prev)
    xk = x + p["mu_ck"].astype(x.dtype) * (xs - x)
    r = jax.nn.sigmoid(jnp.einsum("bsd,dq->bsq", xk, p["wcr"].astype(x.dtype)))
    k = jnp.square(jax.nn.relu(
        jnp.einsum("bsd,df->bsf", xk, p["wck"].astype(x.dtype))))
    return r * jnp.einsum("bsf,fd->bsd", k, p["wcv"].astype(x.dtype)), x[:, -1:]


def rwkv_apply(p, x, ctx):
    cfg = ctx["cfg"]
    tm, _, _ = rwkv_time_mix(p, rms_norm(x, p["ln1"], cfg.norm_eps), ctx)
    h = x + tm
    cm, _ = rwkv_channel_mix(p, rms_norm(h, p["ln2"], cfg.norm_eps))
    return h + cm


def rwkv_cache(cfg, batch, smax):
    h, n, d = cfg.n_heads, cfg.hd, cfg.d_model
    z = jnp.zeros
    return {"s": z((batch, h, n, n), jnp.float32),
            "x_tm": z((batch, 1, d), jnp.bfloat16),
            "x_cm": z((batch, 1, d), jnp.bfloat16)}


def rwkv_decode(p, x, cache, ctx):
    cfg = ctx["cfg"]
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    tm, last_x, s_new = rwkv_time_mix(
        p, xn, ctx, x_prev=cache["x_tm"].astype(xn.dtype), state=cache["s"])
    h = x + tm
    hn = rms_norm(h, p["ln2"], cfg.norm_eps)
    cm, last_cm = rwkv_channel_mix(p, hn, x_prev=cache["x_cm"].astype(hn.dtype))
    return h + cm, {"s": s_new, "x_tm": last_x.astype(cache["x_tm"].dtype),
                    "x_cm": last_cm.astype(cache["x_cm"].dtype)}


# --------------------------------------------------------------------- mamba

def mamba_defs(cfg):
    d = cfg.d_model
    ssm = cfg.ssm
    di = ssm.expand * d
    h = di // ssm.head_dim
    n = ssm.state_size
    conv_dim = di + 2 * n
    return {
        "ln": ParamDef((d,), ("embed",), "ones"),
        "in_proj": ParamDef((d, 2 * di + 2 * n + h), ("embed", "qkv")),
        "conv_w": ParamDef((ssm.conv_kernel, conv_dim), (None, "qkv")),
        "dt_bias": ParamDef((h,), (None,), "zeros"),
        "a_log": ParamDef((h,), (None,), "zeros"),
        "d_skip": ParamDef((h,), (None,), "zeros"),
        "norm": ParamDef((di,), ("qkv",), "ones"),
        "out_proj": ParamDef((di, d), ("qkv", "embed")),
    }


def _mamba_split(cfg, zxbcdt):
    ssm = cfg.ssm
    di = ssm.expand * cfg.d_model
    h = di // ssm.head_dim
    n = ssm.state_size
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
    return z, xbc, dt, di, h, n


def mamba_apply(p, x, ctx):
    cfg = ctx["cfg"]
    from repro.kernels.mamba2_ssd.ops import mamba2
    ssm = cfg.ssm
    b, s, d = x.shape
    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    zxbcdt = jnp.einsum("bsd,de->bse", xn, p["in_proj"].astype(x.dtype))
    z, xbc, dt, di, h, n = _mamba_split(cfg, zxbcdt)
    # causal depthwise conv over (x,B,C)
    k = ssm.conv_kernel
    xbc_pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(xbc_pad[:, i:i + s] * p["conv_w"][i].astype(x.dtype)
               for i in range(k))
    conv = jax.nn.silu(conv)
    xs, bm, c = jnp.split(conv, [di, di + n], axis=-1)
    dt_full = jax.nn.softplus(dt.astype(jnp.float32) +
                              p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    xh = xs.reshape(b, s, h, ssm.head_dim).transpose(0, 2, 1, 3)
    y, _ = mamba2(xh, dt_full.transpose(0, 2, 1), a, bm, c,
                  p["d_skip"].astype(jnp.float32), chunk=ssm.chunk,
                  impl=ctx.get("impl", "jnp"))
    y = y.transpose(0, 2, 1, 3).reshape(b, s, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    return x + jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))


def mamba_cache(cfg, batch, smax):
    ssm = cfg.ssm
    di = ssm.expand * cfg.d_model
    h = di // ssm.head_dim
    n = ssm.state_size
    z = jnp.zeros
    return {"conv": z((batch, ssm.conv_kernel - 1, di + 2 * n), jnp.bfloat16),
            "h": z((batch, h, ssm.head_dim, n), jnp.float32)}


def mamba_decode(p, x, cache, ctx):
    cfg = ctx["cfg"]
    from repro.kernels.mamba2_ssd.ops import mamba2_decode_step
    ssm = cfg.ssm
    b = x.shape[0]
    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    zxbcdt = jnp.einsum("bsd,de->bse", xn, p["in_proj"].astype(x.dtype))
    z, xbc, dt, di, h, n = _mamba_split(cfg, zxbcdt)
    xbc = xbc[:, 0]                                     # [B, convdim]
    window = jnp.concatenate([cache["conv"].astype(x.dtype),
                              xbc[:, None]], axis=1)    # [B, K, convdim]
    conv = jnp.einsum("bkc,kc->bc", window, p["conv_w"].astype(x.dtype))
    conv = jax.nn.silu(conv)
    xs, bm, c = jnp.split(conv, [di, di + n], axis=-1)
    dt_full = jax.nn.softplus(dt[:, 0].astype(jnp.float32) +
                              p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    xh = xs.reshape(b, h, ssm.head_dim)
    y, h_new = mamba2_decode_step(xh, dt_full, a, bm, c,
                                  p["d_skip"].astype(jnp.float32), cache["h"])
    y = y.reshape(b, 1, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = x + jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(x.dtype))
    return out, {"conv": window[:, 1:].astype(cache["conv"].dtype), "h": h_new}


# --------------------------------------------------------- shared_attn (zamba)

shared_attn_defs = attn_defs
shared_attn_apply = attn_apply
shared_attn_decode = attn_decode


# ------------------------------------------------------------- whisper enc/dec

def enc_defs(cfg):
    d, f = cfg.d_model, cfg.d_ff
    return {"ln1": ParamDef((d,), ("embed",), "ones"),
            "ln2": ParamDef((d,), ("embed",), "ones"),
            **_attn_defs(cfg),
            "w_in": ParamDef((d, f), ("embed", "mlp")),
            "w_out": ParamDef((f, d), ("mlp", "embed"))}


def _plain_mlp(p, x):
    hdn = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w_in"].astype(x.dtype)))
    return jnp.einsum("bsf,fd->bsd", hdn, p["w_out"].astype(x.dtype))


def enc_apply(p, x, ctx):
    cfg = ctx["cfg"]
    h = x + _self_attention(cfg, p, rms_norm(x, p["ln1"], cfg.norm_eps), ctx,
                            causal=False)
    return h + _plain_mlp(p, rms_norm(h, p["ln2"], cfg.norm_eps))


def dec_defs(cfg):
    d, f = cfg.d_model, cfg.d_ff
    return {"ln1": ParamDef((d,), ("embed",), "ones"),
            "ln_c": ParamDef((d,), ("embed",), "ones"),
            "ln2": ParamDef((d,), ("embed",), "ones"),
            **_attn_defs(cfg), **_attn_defs(cfg, cross=True),
            "w_in": ParamDef((d, f), ("embed", "mlp")),
            "w_out": ParamDef((f, d), ("mlp", "embed"))}


def _cross_attention(cfg, p, x, enc_out):
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dq->bsq", x, p["cwq"].astype(x.dtype)).reshape(
        b, s, cfg.n_heads, cfg.hd)
    k = jnp.einsum("bsd,dq->bsq", enc_out,
                   p["cwk"].astype(x.dtype)).reshape(
        b, -1, cfg.n_kv_heads, cfg.hd)
    v = jnp.einsum("bsd,dq->bsq", enc_out,
                   p["cwv"].astype(x.dtype)).reshape(
        b, -1, cfg.n_kv_heads, cfg.hd)
    out = attn_lib.chunked_attention(q, k, v, causal=False)
    out = out.reshape(b, s, cfg.n_heads * cfg.hd)
    return jnp.einsum("bsq,qd->bsd", out, p["cwo"].astype(x.dtype))


def dec_apply(p, x, ctx):
    cfg = ctx["cfg"]
    h = x + _self_attention(cfg, p, rms_norm(x, p["ln1"], cfg.norm_eps), ctx,
                            causal=True)
    h = h + _cross_attention(cfg, p, rms_norm(h, p["ln_c"], cfg.norm_eps),
                             ctx["enc_out"])
    return h + _plain_mlp(p, rms_norm(h, p["ln2"], cfg.norm_eps))


def dec_cache(cfg, batch, smax, kv_dtype=None):
    kh, hd = cfg.n_kv_heads, cfg.hd
    z = jnp.zeros
    dt = kv_dtype or jnp.bfloat16
    return {"k": z((batch, smax, kh, hd), dt),
            "v": z((batch, smax, kh, hd), dt),
            "ck": z((batch, cfg.enc_seq, kh, hd), jnp.bfloat16),
            "cv": z((batch, cfg.enc_seq, kh, hd), jnp.bfloat16)}


def dec_decode(p, x, cache, ctx):
    cfg, layer = ctx["cfg"], ctx["layer"]
    a, new = _decode_self_attention(p, x, cache, ctx)
    h = x + a
    # cross attention against the precomputed encoder K/V, read in place
    # and never rewritten
    ck = jax.lax.dynamic_index_in_dim(cache["ck"], layer, keepdims=False)
    cv = jax.lax.dynamic_index_in_dim(cache["cv"], layer, keepdims=False)
    xq = rms_norm(h, p["ln_c"], cfg.norm_eps)
    b = x.shape[0]
    qc = jnp.einsum("bsd,dq->bsq", xq, p["cwq"].astype(x.dtype)).reshape(
        b, 1, cfg.n_heads, cfg.hd)
    co = attn_lib.decode_attention(qc, ck, cv, ck.shape[1] - 1)
    co = co.reshape(b, 1, cfg.n_heads * cfg.hd)
    h = h + jnp.einsum("bsq,qd->bsd", co, p["cwo"].astype(x.dtype))
    h = h + _plain_mlp(p, rms_norm(h, p["ln2"], cfg.norm_eps))
    return h, dict(cache, **new)


BLOCKS: Dict[str, Dict[str, Any]] = {
    "attn": dict(defs=attn_defs, apply=attn_apply, decode=attn_decode,
                 cache=_attn_cache),
    "local": dict(defs=attn_defs, apply=local_apply, decode=local_decode,
                  cache=lambda cfg, b, smax, kv_dtype=None: _attn_cache(
                      cfg, b, min(smax, cfg.window), kv_dtype)),
    "moe": dict(defs=moe_defs, apply=moe_apply, decode=_moe_decode_impl,
                cache=_attn_cache),
    "rwkv": dict(defs=rwkv_defs, apply=rwkv_apply, decode=rwkv_decode,
                 cache=rwkv_cache),
    "mamba": dict(defs=mamba_defs, apply=mamba_apply, decode=mamba_decode,
                  cache=mamba_cache),
    "shared_attn": dict(defs=shared_attn_defs, apply=shared_attn_apply,
                        decode=shared_attn_decode, cache=_attn_cache),
    "enc": dict(defs=enc_defs, apply=enc_apply, decode=None, cache=None),
    "dec": dict(defs=dec_defs, apply=dec_apply, decode=dec_decode,
                cache=dec_cache),
}

# cache types whose rows are addressed by position: decode writes one row
# in place (see the module docstring); the rest are recurrent states
POSITIONAL_CACHE_TYPES = ("attn", "local", "moe", "shared_attn", "dec")
