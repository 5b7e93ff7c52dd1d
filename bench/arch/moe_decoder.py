"""The mixture-of-experts decoder: every layer a GQA attention and a softmax
top-k MoE, in one ``moe`` layer stack (olmoe-1b-7b, paper-moe-100m).

A configuration picks this module with ``"architecture": "moe_decoder"``;
``bench/model.py`` loads it by path and checks that it supplies the
architecture interface:

* ``arch(c)``: the program's ``ArchConfig``;
* ``layout(c)``: ``{name: (shape, init, fan_in)}`` in the program's
  parameter tree (its flatten order fixes each leaf's key);
* ``hidden(c, params, tokens, quant)``: the float32 reference forward to
  the final hidden state;
* ``active_params(c)`` and ``attn_flops(c, ctx)``: the FLOP count's
  weights per token and attention products per query;
* ``run_steps(c, params, batches, plans, n_mb, quant, rows)``, optional:
  the training reference, for training cells.

Reference, serving: one causal forward pass, every expert computed for
every token and weighted by the renormalised top-k router probabilities (no
capacity, no drops: one token at a time never overflows an expert).

Reference, training: attention as in serving; the MoE layer as the
configuration defines it for training: top-k of the router softmax, weights
renormalised, each assignment sent to one replica of its expert by the
plan's cumulative split of a hash of the token's index, and at most
``capacity`` assignments per slot kept, first come first kept in (token, k)
order; loss = cross-entropy + aux_coef * load-balance loss + z_coef *
router z; microbatch gradients averaged, then AdamW
(``reference_train.adamw``).  The plan's expert copies are applied to the
weights and optimizer state as the run made them.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import F32, act, mm, quantize, rms_norm, rope
from bench.reference_train import _cj, _uncj, adamw, schedule


# --- configuration -------------------------------------------------------

def n_slots(c: Dict) -> int:
    """Physical expert slots: the experts plus the program's spare slots
    (Reshape's helpers), which the serving plan leaves unused."""
    return c["num_experts"] + c["program"]["spare_slots"]


def arch(c: Dict):
    """The program's ``ArchConfig`` for configuration ``c``."""
    from repro.configs.base import ArchConfig, MoECfg
    prog = c["program"]
    moe_cfg = MoECfg(num_experts=c["num_experts"],
                     top_k=c["num_experts_per_tok"],
                     expert_d_ff=c["moe_intermediate_size"],
                     capacity_factor=prog["capacity_factor"],
                     spare_slots=prog["spare_slots"],
                     max_replicas=prog["max_replicas"])
    return ArchConfig(
        name=c["name"], family="moe",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        head_dim=c.get("head_dim", 0), moe=moe_cfg,
        rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"],
        norm_eps=c["rms_norm_eps"], source=c["source"])


def layout(c: Dict) -> Dict[str, Any]:
    """{name: (shape, init, fan_in)} in the program's parameter tree.
    Init laws: ``embed`` N(0, 0.02), ``ones``, ``normal`` N(0, 1/fan_in)."""
    d, v, n = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    h, kh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    tree: Dict[str, Any] = {"embed": ((v, d), "embed", d),
                            "final_ln": ((d,), "ones", d)}
    if not c["tie_word_embeddings"]:
        tree["lm_head"] = ((d, v), "normal", d)
    s, f = n_slots(c), c["moe_intermediate_size"]
    tree["moe"] = {"ln1": ((n, d), "ones", d), "ln2": ((n, d), "ones", d),
                   "wq": ((n, d, h * hd), "normal", d),
                   "wk": ((n, d, kh * hd), "normal", d),
                   "wv": ((n, d, kh * hd), "normal", d),
                   "wo": ((n, h * hd, d), "normal", h * hd),
                   "router": ((n, d, c["num_experts"]), "normal", d),
                   "w_gate": ((n, s, d, f), "normal", d),
                   "w_up": ((n, s, d, f), "normal", d),
                   "w_down": ((n, s, f, d), "normal", f)}
    return tree


# --- serving reference ---------------------------------------------------

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


# --- training reference --------------------------------------------------

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
    optimizer's first moment holds it.  ``rows`` picks the rows of each
    microbatch that count (the half-batch fault uses it).  Returns losses,
    the first step's clipped gradient and the final params."""
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


# --- FLOP count ----------------------------------------------------------

def _dims(c: Dict):
    d, h, kh = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    return d, h, kh, hd


def active_params(c: Dict) -> int:
    """Weights one token multiplies by: per layer the attention
    projections, the router and top-k experts (or the dense MLP), the two
    norms; plus the final norm and the head (the embedding lookup is not
    a multiplication)."""
    d, h, kh, hd = _dims(c)
    attn = d * (h + 2 * kh) * hd + h * hd * d
    if c.get("num_experts"):
        ffn = d * c["num_experts"] + c["num_experts_per_tok"] * 3 * d * \
            c["moe_intermediate_size"]
    else:
        ffn = 3 * d * c["intermediate_size"]
    layer = attn + ffn + 2 * d
    return c["num_hidden_layers"] * layer + d + d * c["vocab_size"]


def attn_flops(c: Dict, ctx: float) -> float:
    """Forward score and value products of one query over ``ctx`` keys,
    all layers."""
    d, h, kh, hd = _dims(c)
    return c["num_hidden_layers"] * 2 * 2 * ctx * h * hd
