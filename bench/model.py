"""A configuration file, turned into what the program and the reference run.

``configs/<name>.json`` holds a model's sizes under the names of its
published ``config.json`` (``hidden_size``, ``num_experts_per_tok``, ...),
and names its architecture with ``"architecture": "<arch>"``: the module
``arch/<arch>.py`` under the benchmark's root, which alone knows that
block.  :func:`module` loads it by path and checks that it supplies the
interface its docstring gives (``arch/moe_decoder.py``): ``arch``,
``layout``, ``hidden``, ``active_params``, ``attn_flops`` and, for training
cells, ``run_steps``.  A new block arrives as a new module and a
configuration naming it, with no file here edited.

:func:`arch` maps a configuration onto the program's ``ArchConfig`` and
:func:`layout` describes the weights the benchmark builds, in the program's
tree, both by the architecture's module; :func:`make_params` builds them on
the device from the seed in one jitted call.  The reference reads the same
weights through the same layout, so nothing it uses is made by the program.
"""
from __future__ import annotations

import importlib.util
import math
import re
from pathlib import Path
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from bench import spec

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
INTERFACE = ("arch", "layout", "hidden", "active_params", "attn_flops")
_MODULES: Dict[Path, Any] = {}


def module(c: Dict):
    """The module of configuration ``c``'s architecture,
    ``arch/<architecture>.py`` under the benchmark's root (``spec.ROOT``,
    read at each call), loaded once a process, so that its jitted
    functions compile once, and checked against the interface.
    Fails, naming the configuration and the file, where the key or the
    file is missing; nothing is picked by default."""
    name = c.get("architecture")
    if not isinstance(name, str) or not re.fullmatch(r"\w+", name):
        raise SystemExit(
            f"configuration {c.get('name')!r} names no architecture: give "
            f"it \"architecture\": \"<arch>\" for a module "
            f"{spec.BENCH.name}/arch/<arch>.py (have {name!r})")
    path = spec.ROOT / spec.BENCH.name / "arch" / f"{name}.py"
    if path not in _MODULES:
        if not path.is_file():
            raise SystemExit(
                f"configuration {c.get('name')!r} names architecture "
                f"{name!r}, but {path} does not exist")
        sp = importlib.util.spec_from_file_location(f"bench_arch_{name}",
                                                    path)
        mod = importlib.util.module_from_spec(sp)
        sp.loader.exec_module(mod)
        missing = [f for f in INTERFACE if not callable(getattr(mod, f,
                                                                 None))]
        if missing:
            raise SystemExit(f"{path} lacks {', '.join(missing)} of the "
                             f"architecture interface")
        _MODULES[path] = mod
    return _MODULES[path]


def arch(c: Dict):
    """The program's ``ArchConfig`` for configuration ``c``."""
    return module(c).arch(c)


Leaf = Tuple[Tuple[int, ...], str, int]     # shape, init law, fan-in


def layout(c: Dict) -> Dict[str, Any]:
    """{name: (shape, init, fan_in)} in the program's parameter tree.
    Init laws: ``embed`` N(0, 0.02), ``ones``, ``normal`` N(0, 1/fan_in)."""
    return module(c).layout(c)


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
