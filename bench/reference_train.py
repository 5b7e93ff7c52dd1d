"""The plain training reference: loss, gradients and AdamW in float32.

It imports nothing of the program.  It starts from the weights the
benchmark builds from the seed, reads the same token batches, and routes by
the Reshape plan the run applied (the controller's decision for each step,
with the expert-state copies that came with it); everything it computes from
them it computes itself, at ``highest`` matrix precision:

* attention as in ``reference.py``;
* the MoE layer as the configuration defines it for training: top-k of the
  router softmax, weights renormalised, each assignment sent to one replica
  of its expert by the plan's cumulative split of a hash of the token's
  index, and at most ``capacity`` assignments per slot kept, first come
  first kept in (token, k) order;
* loss = cross-entropy + aux_coef * load-balance loss + z_coef * router z;
* microbatch gradients averaged, clipped to a global norm, AdamW with
  warm-up and cosine decay, weight decay on every weight.

``quant="fp8"`` is the control: both operands of every product with a
weight rounded to float8 e4m3 in the forward pass (``reference.mm``), the
gradient passing straight through to the float32 weights.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import act, attention, mm, quantize, rms_norm

F32 = jnp.float32


def hash_unit(idx):
    """Token index -> [0, 1): Knuth's multiplicative hash, the split rule
    the configuration's Reshape plan is defined over."""
    h = idx.astype(jnp.uint32) * jnp.uint32(2654435761)
    return h.astype(F32) / F32(2 ** 32)


def capacity(c: Dict, tokens: int) -> int:
    k, e = c["num_experts_per_tok"], c["num_experts"]
    cf = c["program"]["capacity_factor"]
    return max(4, int(tokens * k * cf / e))


def moe(c, lp, h, ps, pc, offset, quant):
    """h [T, D] -> (y [T, D], aux, z)."""
    t, d = h.shape
    k, e = c["num_experts_per_tok"], c["num_experts"]
    s = lp["w_gate"].shape[0]
    cap = capacity(c, t)
    probs = jax.nn.softmax(mm(h, lp["router"], quant), -1)
    top_p, top_e = jax.lax.top_k(probs, k)
    w = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    u = hash_unit(offset + jnp.arange(t))
    r = (pc[top_e][..., :-1] <= u[:, None, None]).sum(-1)
    slot = jnp.take_along_axis(ps[top_e], r[..., None], -1)[..., 0]
    flat = slot.reshape(t * k)
    onehot = (flat[:, None] == jnp.arange(s)[None]).astype(jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, 0) - 1) * onehot, -1)
    keep = rank < cap
    dest = jnp.where(keep, flat * cap + rank, s * cap)
    tok = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((s * cap + 1, d), F32).at[dest].set(h[tok])
    buf = buf[:-1].reshape(s, cap, d)
    bq = act(buf, quant)
    g = jnp.einsum("scd,sdf->scf", bq, quantize(lp["w_gate"], quant))
    up = jnp.einsum("scd,sdf->scf", bq, quantize(lp["w_up"], quant))
    out = jnp.einsum("scf,sfd->scd", act(jax.nn.silu(g) * up, quant),
                     quantize(lp["w_down"], quant)).reshape(s * cap, d)
    contrib = out[jnp.where(keep, dest, 0)] * \
        (w.reshape(t * k) * keep)[:, None]
    y = jnp.zeros((t, d), F32).at[tok].add(contrib)
    counts = jnp.zeros((e,), F32).at[top_e.reshape(-1)].add(1.0)
    aux = e * jnp.sum(counts / (t * k) * probs.mean(0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(jnp.log(probs + 1e-9), -1)))
    return y, aux, z


def loss_fn(params, tokens, ps, pc, offset, cj, quant):
    c = _uncj(cj)
    eps = c["rms_norm_eps"]
    b, s = tokens.shape
    x = params["embed"][tokens]
    blk = params["moe"]
    auxs, zs = [], []

    @jax.checkpoint
    def layer(x, lp, ps_l, pc_l):
        x = x + attention(c, lp, rms_norm(x, lp["ln1"], eps), quant)
        h = rms_norm(x, lp["ln2"], eps).reshape(b * s, -1)
        y, aux, z = moe(c, lp, h, ps_l, pc_l, offset, quant)
        return x + y.reshape(x.shape), aux, z

    for l in range(c["num_hidden_layers"]):
        x, aux, z = layer(x, {k: v[l] for k, v in blk.items()}, ps[l], pc[l])
        auxs.append(aux)
        zs.append(z)
    x = rms_norm(x, params["final_ln"], eps)
    head = params["embed"].T if c["tie_word_embeddings"] \
        else params["lm_head"]
    logits = mm(x, head, quant)
    lp_ = jax.nn.log_softmax(logits[:, :-1], -1)
    ce = -jnp.take_along_axis(lp_, tokens[:, 1:, None], -1)[..., 0].mean()
    t = c["training"]
    return ce + t["aux_coef"] * jnp.mean(jnp.stack(auxs)) + \
        t["z_coef"] * jnp.mean(jnp.stack(zs))


@partial(jax.jit, static_argnames=("cj", "quant"))
def grad_mb(params, tokens, ps, pc, offset, cj, quant):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, ps, pc, offset,
                                           cj, quant)


def schedule(t: Dict, step: int) -> float:
    warm = min(1.0, (step + 1) / max(1, t["warmup_steps"]))
    prog = min(max((step - t["warmup_steps"])
                   / max(1, t["total_steps"] - t["warmup_steps"]), 0.0), 1.0)
    cos = 0.5 * (1 + math.cos(math.pi * prog))
    return t["lr"] * warm * (t["min_lr_frac"] + (1 - t["min_lr_frac"]) * cos)


@partial(jax.jit, static_argnames=("tj",))
def adamw(params, grads, m, v, count, lr, tj):
    t = dict(tj)
    with jax.default_matmul_precision("highest"):
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, t["clip_norm"] / (gnorm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        b1c = 1 - t["b1"] ** count
        b2c = 1 - t["b2"] ** count
        m = jax.tree.map(lambda a, g: t["b1"] * a + (1 - t["b1"]) * g, m,
                         grads)
        v = jax.tree.map(lambda a, g: t["b2"] * a + (1 - t["b2"]) * g * g,
                         v, grads)
        params = jax.tree.map(
            lambda p, a, b: p - lr * (a / b1c / (jnp.sqrt(b / b2c)
                                                 + t["eps"])
                                      + t["weight_decay"] * p),
            params, m, v)
    return params, m, v, grads


def migrate(tree, moves):
    """Copy expert slot src onto dst in layer ``layer`` of each expert leaf."""
    if not moves:
        return tree
    blk = dict(tree["moe"])
    for name in ("w_gate", "w_up", "w_down"):
        a = blk[name]
        for layer, src, dst in moves:
            a = a.at[layer, dst].set(a[layer, src])
        blk[name] = a
    return dict(tree, moe=blk)


def run_steps(c: Dict, params, batches: Sequence[np.ndarray],
              plans: Sequence, n_mb: int, quant=None,
              rows: slice = slice(None)) -> Dict:
    """Train ``len(batches)`` steps from ``params`` (float32, on device).
    ``plans[k]`` is (slots, cum, moves): the routing plan of step k and the
    expert copies made before it; a plan past the last step contributes
    its copies (the state the program is read from has them).  The first
    gradient is returned with the copies that followed it, as the
    optimizer's first moment holds it.  ``rows`` picks the rows of each microbatch that count (the half-batch
    fault uses it).  Returns losses, the first step's clipped gradient and
    the final params."""
    cfg_keys = {k: c[k] for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "hidden_size", "rope_theta", "rms_norm_eps", "num_experts",
        "num_experts_per_tok", "num_hidden_layers", "tie_word_embeddings")}
    cfg_keys["program"] = {"capacity_factor":
                           c["program"]["capacity_factor"]}
    cfg_keys["training"] = {k: c["training"][k]
                            for k in ("aux_coef", "z_coef")}
    cj = _cj(cfg_keys)
    t = c["training"]
    tj = tuple(sorted((k, float(t[k])) for k in (
        "b1", "b2", "eps", "weight_decay", "clip_norm")))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, g_first = [], None
    for step, tokens in enumerate(batches):
        ps, pc, moves = plans[step]
        params, m, v = migrate(params, moves), migrate(m, moves), \
            migrate(v, moves)
        if step == 1:
            g_first = migrate(g_first, moves)
        gb, s = tokens.shape
        mb = gb // n_mb
        acc, tot = None, 0.0
        for i in range(n_mb):
            x = jnp.asarray(tokens[i * mb:(i + 1) * mb][rows])
            off = jnp.asarray((step * n_mb + i) * (mb * s), jnp.int32)
            loss, g = grad_mb(params, x, jnp.asarray(ps), jnp.asarray(pc),
                              off, cj, quant)
            acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
            tot += float(loss)
        acc = jax.tree.map(lambda a: a / n_mb, acc)
        params, m, v, g_used = adamw(params, acc, m, v,
                                     jnp.asarray(step + 1, F32),
                                     jnp.asarray(schedule(t, step), F32), tj)
        if step == 0:
            g_first = g_used
        losses.append(tot / n_mb)
    if len(plans) > len(batches):
        # the copies the controller made after the last step followed
        params = migrate(params, plans[len(batches)][2])
        if len(batches) == 1:
            g_first = migrate(g_first, plans[1][2])
    return {"losses": losses, "g_first": g_first, "params": params}


def _cj(d):
    """A hashable form of a nested dict of numbers, for jit's static
    arguments; :func:`_uncj` restores it."""
    return tuple(sorted((k, _cj(v) if isinstance(v, dict) else v)
                        for k, v in d.items()))


def _uncj(cj) -> Dict:
    return {k: (_uncj(v) if isinstance(v, tuple) and v
                and isinstance(v[0], tuple) else v) for k, v in cj}
