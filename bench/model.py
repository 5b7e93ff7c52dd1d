"""A configuration file, turned into what the program and the reference run.

``configs/<name>.json`` holds a model's sizes under the names of its
published ``config.json`` (``hidden_size``, ``num_experts_per_tok``, ...).
Every configuration here is a mixture-of-experts decoder (one ``moe`` block
type), the one architecture the reference implements.
:func:`arch` maps them onto the program's ``ArchConfig``; :func:`layout`
describes the weights the benchmark builds, in the program's tree, and
:func:`make_params` builds them on the device from the seed in one jitted
call.  The reference reads the same weights through the same layout, so
nothing it uses is made by the program.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def n_slots(c: Dict) -> int:
    """Physical expert slots: the experts plus the program's spare slots
    (Reshape's helpers), which the serving plan leaves unused."""
    return c["num_experts"] + c["program"]["spare_slots"]


def arch(c: Dict):
    """The program's ``ArchConfig`` for configuration ``c``."""
    from repro.configs.base import ArchConfig, MoECfg
    prog = c["program"]
    moe = MoECfg(num_experts=c["num_experts"],
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
        head_dim=c.get("head_dim", 0), moe=moe,
        rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"],
        norm_eps=c["rms_norm_eps"], source=c["source"])


Leaf = Tuple[Tuple[int, ...], str, int]     # shape, init law, fan-in


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


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def make_params(c: Dict, seed: int, dtype=None):
    """Every weight, on the default device, in ``dtype`` (default: the
    configuration's ``torch_dtype``), from ``seed`` in one jitted call.
    Layer-stacked leaves are drawn one layer at a time (``lax.map``), so no
    temporary wider than one layer's leaf in float32 is ever live."""
    dtype = dtype or DTYPES[c["torch_dtype"]]
    leaves, treedef = jax.tree.flatten(layout(c), is_leaf=_is_leaf)

    def draw(key, shape, law, fan_in):
        if law == "ones":
            return jnp.ones(shape, dtype)
        std = 0.02 if law == "embed" else 1.0 / math.sqrt(fan_in)
        if len(shape) < 3:
            return (jax.random.normal(key, shape, jnp.float32)
                    * std).astype(dtype)
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape[1:], jnp.float32)
                       * std).astype(dtype), keys)

    @jax.jit
    def build(seed_arr):
        root = jax.random.PRNGKey(seed_arr)
        return [draw(jax.random.fold_in(root, i), *leaf)
                for i, leaf in enumerate(leaves)]

    out = build(jnp.asarray(seed, jnp.int32))
    return jax.tree.unflatten(treedef, out)


def check_layout(c: Dict, cfg) -> None:
    """Fail loudly where the program's parameter tree has moved away from
    the layout the benchmark builds."""
    from repro.models import lm
    want = jax.tree.map(lambda x: tuple(x.shape), lm.abstract(cfg))
    have = jax.tree.map(lambda x: x[0], layout(c), is_leaf=_is_leaf)
    if want != have:
        raise SystemExit(f"parameter layout differs from the program's: "
                         f"{have} != {want}")


def param_bytes(c: Dict, dtype=None) -> int:
    dtype = dtype or DTYPES[c["torch_dtype"]]
    leaves = jax.tree.leaves(layout(c), is_leaf=_is_leaf)
    return sum(math.prod(s) for s, _, _ in leaves) * jnp.dtype(dtype).itemsize
