"""Unified language model over heterogeneous layer patterns.

Layers are grouped by block type into *stacked* parameter groups and executed
as ``lax.scan`` runs (HLO size independent of depth — 94-layer qwen3 compiles
as fast as 6-layer whisper).  Heterogeneous patterns (gemma3 5:1 local:global,
zamba2 mamba + shared-attn) become consecutive runs over slices of the
per-type stacks; ``shared_attn`` keeps a single unstacked weight copy but
per-occurrence KV caches.
"""
from __future__ import annotations

import itertools
from collections import defaultdict
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import moe as moe_lib
from repro.models.blocks import BLOCKS, POSITIONAL_CACHE_TYPES
from repro.models.layers import rms_norm, sinusoidal_positions
from repro.models.params import (ParamDef, abstract_params, init_params,
                                 map_defs, param_specs, stacked)


# ----------------------------------------------------------------- structure

def pattern_runs(cfg: ArchConfig):
    """[(block_type, count, per-type offset), ...] over cfg.pattern."""
    runs, offsets = [], defaultdict(int)
    for t, grp in itertools.groupby(cfg.pattern):
        c = len(list(grp))
        runs.append((t, c, offsets[t]))
        offsets[t] += c
    return runs


def type_counts(cfg: ArchConfig) -> Dict[str, int]:
    counts: Dict[str, int] = defaultdict(int)
    for t in cfg.pattern:
        counts[t] += 1
    return dict(counts)


def model_defs(cfg: ArchConfig) -> Dict[str, Any]:
    d = {"embed": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                           "embed"),
         "final_ln": ParamDef((cfg.d_model,), ("embed",), "ones")}
    if not cfg.tie_embeddings:
        d["lm_head"] = ParamDef((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    for t, n in type_counts(cfg).items():
        bd = BLOCKS[t]["defs"](cfg)
        if t == "shared_attn":
            d[t] = bd                      # single shared copy
        else:
            d[t] = map_defs(lambda x: stacked(n, x), bd)
    if cfg.enc_layers:
        enc = BLOCKS["enc"]["defs"](cfg)
        d["enc"] = map_defs(lambda x: stacked(cfg.enc_layers, x), enc)
        d["enc_ln"] = ParamDef((cfg.d_model,), ("embed",), "ones")
    return d


def init(cfg: ArchConfig, key, dtype=jnp.float32):
    return init_params(model_defs(cfg), key, dtype)


def abstract(cfg: ArchConfig, dtype=jnp.float32):
    return abstract_params(model_defs(cfg), dtype)


def specs(cfg: ArchConfig, rules: Dict[str, Optional[str]]):
    return param_specs(model_defs(cfg), rules)


def n_moe_layers(cfg: ArchConfig) -> int:
    return type_counts(cfg).get("moe", 0)


def _slice_leaves(tree, off: int, count: int):
    return jax.tree.map(lambda x: jax.lax.slice_in_dim(x, off, off + count), tree)


# ------------------------------------------------------------------- forward

def _head(params, x, cfg: ArchConfig):
    """Final norm and logits, under the ``head`` device scope (with the
    sampler that follows in serving)."""
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_ln"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))


def _make_ctx(cfg: ArchConfig, b: int, s: int, batch: Dict[str, Any],
              impl: str, token_offset, mesh=None,
              tokens_sharded=True, layout="tp") -> Dict[str, Any]:
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    ctx = {"cfg": cfg, "positions": pos, "impl": impl,
           "token_offset": token_offset, "moe_metrics": [],
           "mesh": mesh, "tokens_sharded": tokens_sharded,
           "layout": layout}
    if cfg.mrope:
        p3 = batch.get("positions3")
        if p3 is None:
            p3 = jnp.broadcast_to(pos[..., None], (b, s, 3))
        ctx["positions3"] = p3
    return ctx


def _run_stack(x, params, cfg, ctx, plan, remat: str):
    """Execute the layer pattern; returns (x, stacked-moe-metrics list)."""
    all_metrics = []
    mesh, act_spec = ctx.get("mesh"), ctx.get("act_spec")

    def constrain(h):
        if mesh is not None and act_spec is not None:
            from jax.sharding import NamedSharding
            return jax.lax.with_sharding_constraint(
                h, NamedSharding(mesh, act_spec))
        return h

    def wrap(fn):
        if remat == "full":
            return jax.checkpoint(fn)
        if remat == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.checkpoint_dots)
        return fn

    for t, count, off in pattern_runs(cfg):
        apply = BLOCKS[t]["apply"]
        if t == "shared_attn":
            fn = wrap(lambda p, h: apply(p, h, ctx))
            for _ in range(count):
                x = fn(params[t], x)
        elif t == "moe":
            p_run = _slice_leaves(params[t], off, count)
            ps = jax.lax.slice_in_dim(plan.slots, off, off + count)
            pc = jax.lax.slice_in_dim(plan.cum, off, off + count)

            def moe_body(h, inp):
                p_l, ps_l, pc_l = inp
                ctx_l = dict(ctx, plan_slots=ps_l, plan_cum=pc_l,
                             moe_metrics=[])
                h = wrap(lambda p, hh: apply(p, hh, ctx_l))(p_l, h)
                return h, ctx_l["moe_metrics"][0]

            x, metrics = jax.lax.scan(moe_body, x, (p_run, ps, pc))
            all_metrics.append(metrics)
        else:
            p_run = _slice_leaves(params[t], off, count)

            def body(h, p_l):
                return wrap(lambda p, hh: apply(p, hh, ctx))(p_l, h), None

            x, _ = jax.lax.scan(body, x, p_run)
        x = constrain(x)
    return x, all_metrics


def encode(params, frames, cfg: ArchConfig, impl="jnp"):
    """Whisper encoder over (stubbed) frame embeddings [B,S,D]."""
    b, s, _ = frames.shape
    x = frames + sinusoidal_positions(s, cfg.d_model)[None].astype(frames.dtype)
    ctx = {"cfg": cfg, "positions": jnp.broadcast_to(jnp.arange(s)[None],
                                                     (b, s)), "impl": impl}

    def body(h, p_l):
        return BLOCKS["enc"]["apply"](p_l, h, ctx), None

    x, _ = jax.lax.scan(body, x, params["enc"])
    return rms_norm(x, params["enc_ln"], cfg.norm_eps)


def forward(params, batch: Dict[str, Any], cfg: ArchConfig, *,
            plan=None, impl: str = "jnp", token_offset=0,
            remat: str = "none", mesh=None, act_spec=None,
            tokens_sharded=True, layout: str = "tp"):
    """batch: tokens [B,S] (+ frames for audio, positions3 for vlm).
    Returns (logits [B,S,V], aux dict with moe metrics)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = params["embed"][tokens].astype(jnp.bfloat16)
    if mesh is not None and act_spec is not None:
        from jax.sharding import NamedSharding
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, act_spec))
    ctx = _make_ctx(cfg, b, s, batch, impl, token_offset, mesh,
                    tokens_sharded, layout)
    ctx["act_spec"] = act_spec
    if cfg.enc_layers:
        enc_out = encode(params, batch["frames"].astype(jnp.bfloat16), cfg,
                         impl)
        ctx["enc_out"] = enc_out
    if plan is None and n_moe_layers(cfg):
        plan = moe_lib.identity_plan(cfg, n_moe_layers(cfg))
    x, moe_metrics = _run_stack(x, params, cfg, ctx, plan, remat)
    logits = _head(params, x, cfg)
    aux: Dict[str, Any] = {}
    if moe_metrics:
        # one stacked entry per moe run; concat over layers
        cat = {k: jnp.concatenate([m[k][None] if m[k].ndim == 0 else m[k]
                                   for m in moe_metrics], axis=0)
               for k in moe_metrics[0]}
        aux["moe"] = cat
    return logits.astype(jnp.float32), aux


# -------------------------------------------------------------------- decode

def init_cache(cfg: ArchConfig, batch: int, smax: int, kv_dtype=None):
    caches = {}
    for t, n in type_counts(cfg).items():
        mk = BLOCKS[t]["cache"]
        if mk is None:
            continue
        one = mk(cfg, batch, smax, kv_dtype) \
            if t in POSITIONAL_CACHE_TYPES else mk(cfg, batch, smax)
        caches[t] = jax.tree.map(
            lambda x: jnp.zeros((n,) + x.shape, x.dtype), one)
    return {"caches": caches, "pos": jnp.zeros((), jnp.int32)}


def decode_step(params, state, token, cfg: ArchConfig, *, plan=None,
                impl: str = "jnp", mesh=None, tokens_sharded=True):
    """token [B,1] int32; state from init_cache.  Returns (logits, state)."""
    pos = state["pos"]
    b = token.shape[0]
    x = params["embed"][token].astype(jnp.bfloat16)
    ctx = {"cfg": cfg, "pos": pos, "impl": impl, "token_offset": pos,
           "positions": jnp.broadcast_to(pos[None, None], (b, 1)),
           "moe_metrics": [], "mesh": mesh,
           "tokens_sharded": tokens_sharded}
    if cfg.mrope:
        ctx["positions3"] = jnp.broadcast_to(pos[None, None, None], (b, 1, 3))
    if plan is None and n_moe_layers(cfg):
        plan = moe_lib.identity_plan(cfg, n_moe_layers(cfg))
    caches = dict(state["caches"])
    for t, count, off in pattern_runs(cfg):
        decode = BLOCKS[t]["decode"]
        # per-layer inputs: the run's weights (shared_attn has one copy,
        # closed over) and, for moe, its slice of the routing plan
        p_run = None if t == "shared_attn" else \
            _slice_leaves(params[t], off, count)
        plan_run = None
        if t == "moe":
            plan_run = (jax.lax.slice_in_dim(plan.slots, off, off + count),
                        jax.lax.slice_in_dim(plan.cum, off, off + count))

        def step(h, c, inp):
            p_l, plan_l, layer = inp
            ctx_l = dict(ctx, layer=layer)
            if plan_l is not None:
                ctx_l.update(plan_slots=plan_l[0], plan_cum=plan_l[1],
                             moe_metrics=[])
            return decode(params[t] if p_l is None else p_l, h, c, ctx_l)

        xs = (p_run, plan_run, jnp.arange(off, off + count))
        if t in POSITIONAL_CACHE_TYPES:
            # the type's whole stack rides in the carry and each layer
            # writes its token's row into it in place, at its own index
            (x, caches[t]), _ = jax.lax.scan(
                lambda hc, inp: (step(*hc, inp), None), (x, caches[t]), xs)
        else:
            x, c_out = jax.lax.scan(
                lambda h, inp: step(h, inp[1], inp[0]), x,
                (xs, _slice_leaves(caches[t], off, count)))
            caches[t] = jax.tree.map(
                lambda full, new: jax.lax.dynamic_update_slice_in_dim(
                    full, new, off, axis=0), caches[t], c_out)
    logits = _head(params, x, cfg)
    return logits[:, 0].astype(jnp.float32), {
        "caches": caches, "pos": pos + 1}
