"""Train-step builder: fwd/bwd with microbatch gradient accumulation (scan),
MoE Reshape plan as a jittable input, remat policy from the Maestro choice,
AdamW, and the load metrics (phi) as free step outputs."""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeCfg
from repro.models import lm
from repro.models import moe as moe_lib
from repro.optim import adamw


@dataclasses.dataclass(frozen=True)
class TrainHyper:
    opt: adamw.AdamWCfg = adamw.AdamWCfg()
    aux_coef: float = 0.01
    z_coef: float = 1e-4
    remat: str = "none"


def make_state(cfg: ArchConfig, key, dtype=jnp.float32) -> Dict[str, Any]:
    params = lm.init(cfg, key, dtype)
    return {"params": params, "opt": adamw.init(params),
            "step": jnp.zeros((), jnp.int32)}


def abstract_state(cfg: ArchConfig, dtype=jnp.float32) -> Dict[str, Any]:
    params = lm.abstract(cfg, dtype)
    zeros = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         params)
    return {"params": params,
            "opt": adamw.OptState(zeros, jax.tree.map(lambda x: x, zeros),
                                  jax.ShapeDtypeStruct((), jnp.int32)),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def loss_fn(params, batch, cfg: ArchConfig, hyper: TrainHyper, plan,
            token_offset, mesh=None, act_spec=None, tokens_sharded=True,
            layout="tp"):
    # mixed precision: compute in bf16 (one cast up front so the FSDP
    # all-gather of the layer stacks moves bf16, not fp32 master weights —
    # halves the gathered-stack footprint the compiler hoists out of scan)
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if x.dtype == jnp.float32 else x, params)
    logits, aux = lm.forward(params, batch, cfg, plan=plan,
                             token_offset=token_offset, remat=hyper.remat,
                             mesh=mesh, act_spec=act_spec,
                             tokens_sharded=tokens_sharded, layout=layout)
    tokens = batch["tokens"]
    labels = tokens[:, 1:]
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    ce = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    loss = ce.mean()
    metrics = {"ce": loss}
    if "moe" in aux:
        aux_l = aux["moe"]["aux_loss"].mean()
        z_l = aux["moe"]["router_z"].mean()
        loss = loss + hyper.aux_coef * aux_l + hyper.z_coef * z_l
        metrics["aux_loss"] = aux_l
        metrics["expert_counts"] = aux["moe"]["expert_counts"]  # [L, E]
        metrics["slot_counts"] = aux["moe"]["slot_counts"]      # [L, S]
        metrics["dropped"] = aux["moe"]["dropped"]              # [L]
    metrics["loss"] = loss
    return loss, metrics


def build_train_step(cfg: ArchConfig, shape: ShapeCfg, hyper: TrainHyper,
                     mesh=None, act_spec=None, layout="tp"):
    """Production step: microbatches scanned inside one jit."""
    n_mb = max(1, shape.microbatches)
    nl_moe = lm.n_moe_layers(cfg)

    def step(state, batch, plan_slots, plan_cum):
        plan = moe_lib.RoutingPlan(plan_slots, plan_cum) if nl_moe else None
        tokens = batch["tokens"]
        gb, s = tokens.shape
        mb = gb // n_mb

        def reshape_mb(x):
            return x.reshape((n_mb, mb) + x.shape[1:])

        mb_batch = {k: reshape_mb(v) for k, v in batch.items()
                    if k in ("tokens", "frames", "positions3")}
        grad_zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])

        def mb_body(carry, inp):
            gacc, i = carry
            mbd = inp
            offset = (state["step"].astype(jnp.int32) * n_mb + i) * (mb * s)
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state["params"], mbd, cfg, hyper,
                                       plan, offset, mesh, act_spec,
                                       True, layout)
            gacc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / n_mb, gacc, grads)
            return (gacc, i + 1), metrics

        (grads, _), metrics = jax.lax.scan(
            mb_body, (grad_zero, jnp.zeros((), jnp.int32)), mb_batch)
        metrics = jax.tree.map(
            lambda m: m.sum(0) if m.dtype in (jnp.int32, jnp.int64)
            else m.mean(0), metrics)
        with jax.named_scope("optimizer"):
            params, opt, opt_metrics = adamw.apply(
                state["params"], grads, state["opt"], hyper.opt)
        metrics.update(opt_metrics)
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        return new_state, metrics

    return step


def build_fused_step(cfg: ArchConfig, hyper: TrainHyper):
    """Fused fast path for the interactive loop: ALL microbatches run inside
    one jit via ``lax.scan`` with in-jit gradient accumulation, followed by
    the optimizer apply — one dispatch and one device->host metrics fetch per
    step instead of ``2 * n_mb`` dispatches plus per-microbatch syncs.

    Numerics mirror the granulated path exactly: per-microbatch grads are
    summed in fp32 in microbatch order, divided once by ``n_mb``, and fed to
    the same ``adamw.apply``.  Metrics come back STACKED per microbatch
    ``[n_mb, ...]`` so the host can still evaluate breakpoint predicates at
    microbatch granularity post hoc.

    The old state is donated (buffer reuse for params/opt moments) on
    accelerator backends; CPU ignores donation, so skip it there to avoid
    per-step warnings.
    """
    nl_moe = lm.n_moe_layers(cfg)
    donate = (0,) if jax.default_backend() != "cpu" else ()

    @partial(jax.jit, static_argnames=("n_mb",), donate_argnums=donate)
    def fused(state, batch, plan_slots, plan_cum, lr_scale, n_mb: int):
        plan = moe_lib.RoutingPlan(plan_slots, plan_cum) if nl_moe else None
        tokens = batch["tokens"]
        gb, s = tokens.shape
        mb = gb // n_mb

        mb_batch = {k: v.reshape((n_mb, mb) + v.shape[1:])
                    for k, v in batch.items()
                    if k in ("tokens", "frames", "positions3")}
        # hoist the fp32->bf16 params cast out of the scan: XLA does not
        # move it through value_and_grad, so the per-microbatch path would
        # re-cast every iteration.  Differentiating w.r.t. the bf16 tree
        # yields exactly the cotangents the fp32 cast's VJP would upcast,
        # so accumulating their fp32 upcast is bit-identical to the
        # granulated path (loss_fn's internal cast is a no-op on bf16).
        params_bf = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, state["params"])
        grad_zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])

        def mb_body(carry, mbd):
            gacc, i = carry
            offset = (state["step"].astype(jnp.int32) * n_mb + i) * (mb * s)
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params_bf, mbd, cfg, hyper,
                                       plan, offset)
            gacc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), gacc, grads)
            return (gacc, i + 1), metrics

        (grads, _), mb_metrics = jax.lax.scan(
            mb_body, (grad_zero, jnp.zeros((), jnp.int32)), mb_batch)
        grads = jax.tree.map(lambda g: g / n_mb, grads)
        with jax.named_scope("optimizer"):
            params, opt, opt_m = adamw.apply(state["params"], grads,
                                             state["opt"], hyper.opt,
                                             lr_scale)
        new_state = {"params": params, "opt": opt,
                     "step": state["step"] + 1}
        return new_state, mb_metrics, opt_m

    return fused


def build_grad_step(cfg: ArchConfig, hyper: TrainHyper, donate=None):
    """Interactive-mode pieces: one-microbatch grad + separate apply (the
    Amber granulated iteration: the loop polls control between microbatches).

    ``apply`` and ``migrate`` donate the incoming state (params + opt
    moments are overwritten in place on accelerator backends) — without it
    the granulated path allocated fresh params/opt buffers every step while
    the fused path reused them.  The loop's ``self.state = apply(...)`` /
    ``self.state = migrate(...)`` call pattern never touches the old state
    afterwards, which is what makes donation safe.  CPU ignores donation
    (and warns per compile), so it defaults off there; tests force it on
    via ``donate`` to audit the wiring.
    """
    nl_moe = lm.n_moe_layers(cfg)
    if donate is None:
        donate = jax.default_backend() != "cpu"
    donate_state = (0,) if donate else ()

    @jax.jit
    def grad_mb(params, batch, plan_slots, plan_cum, offset):
        plan = moe_lib.RoutingPlan(plan_slots, plan_cum) if nl_moe else None
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, cfg, hyper, plan, offset)
        return grads, metrics

    @partial(jax.jit, static_argnames=("n_mb",), donate_argnums=donate_state)
    def apply(state, grads, n_mb: int, lr_scale):
        grads = jax.tree.map(lambda g: g / n_mb, grads)
        with jax.named_scope("optimizer"):
            params, opt, m = adamw.apply(state["params"], grads,
                                         state["opt"], hyper.opt, lr_scale)
        return {"params": params, "opt": opt, "step": state["step"] + 1}, m

    @partial(jax.jit, donate_argnums=donate_state)
    def migrate(state, src_dst):
        """Expert state migration: copy slot src->dst on every expert-stacked
        leaf of params AND optimizer moments (layer, src, dst) int32 [M,3]."""
        def copy_leaf(leaf):
            if leaf.ndim >= 2:
                def one(carry, m):
                    lyr, src, dst = m[0], m[1], m[2]
                    row = jax.lax.dynamic_index_in_dim(
                        jax.lax.dynamic_index_in_dim(carry, lyr, 0, False),
                        src, 0, False)
                    carry = jax.lax.dynamic_update_index_in_dim(
                        carry, jax.lax.dynamic_update_index_in_dim(
                            jax.lax.dynamic_index_in_dim(carry, lyr, 0, False),
                            row, dst, 0), lyr, 0)
                    return carry, None
                leaf, _ = jax.lax.scan(one, leaf, src_dst)
            return leaf

        def on_moe(tree):
            return {k: (jax.tree.map(copy_leaf, v)
                        if k in ("w_gate", "w_up", "w_down") else v)
                    for k, v in tree.items()}

        params = dict(state["params"])
        opt = state["opt"]
        if "moe" in params:
            params["moe"] = on_moe(params["moe"])
            m = dict(opt.m)
            v = dict(opt.v)
            m["moe"] = on_moe(m["moe"])
            v["moe"] = on_moe(v["moe"])
            opt = adamw.OptState(m, v, opt.count)
        return {"params": params, "opt": opt, "step": state["step"]}

    return grad_mb, apply, migrate
