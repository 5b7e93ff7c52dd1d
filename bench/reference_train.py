"""The plain training reference: loss, gradients and AdamW in float32.

It imports nothing of the program.  It starts from the weights the
benchmark builds from the seed, reads the same token batches, and routes by
the Reshape plan the run applied (the controller's decision for each step,
with the expert-state copies that came with it); everything it computes from
them it computes itself, at ``highest`` matrix precision.

The model's loss and gradients, and how a plan's copies move its weights,
are the architecture's: :func:`run_steps` hands the steps to the
``run_steps`` of ``arch/<architecture>.py`` (``model.module``), which
averages the microbatch gradients and applies :func:`adamw` with
:func:`schedule`: clipped to a global norm, AdamW with warm-up and cosine
decay, weight decay on every weight.

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

from bench import model


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


def run_steps(c: Dict, params, batches: Sequence[np.ndarray],
              plans: Sequence, n_mb: int, quant=None,
              rows: slice = slice(None)) -> Dict:
    """Train ``len(batches)`` steps from ``params`` (float32, on device) by
    the configuration's architecture (``model.module``): ``plans[k]`` is
    the routing plan of step k with the expert copies made before it,
    ``rows`` the rows of each microbatch that count.  Returns losses, the
    first step's clipped gradient and the final params.  Exits with a
    message where the architecture has no training reference."""
    mod = model.module(c)
    if not callable(getattr(mod, "run_steps", None)):
        raise SystemExit(
            f"architecture {c['architecture']!r} of configuration "
            f"{c.get('name')!r} has no training reference (run_steps in "
            f"{mod.__file__}); a training cell cannot run it")
    return mod.run_steps(c, params, batches, plans, n_mb, quant, rows)


def _cj(d):
    """A hashable form of a nested dict of numbers, for jit's static
    arguments; :func:`_uncj` restores it."""
    return tuple(sorted((k, _cj(v) if isinstance(v, dict) else v)
                        for k, v in d.items()))


def _uncj(cj) -> Dict:
    return {k: (_uncj(v) if isinstance(v, tuple) and v
                and isinstance(v[0], tuple) else v) for k, v in cj}
