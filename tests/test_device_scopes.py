"""The device scopes the profiler groups device time by: the lowered
serving tick and training step name ``attention``, ``moe`` and ``head``,
and the tick its ``kv_pool`` masks, the step its ``optimizer``."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.engine import ServeEngine
from repro.engine.serve import build_slot_tick
from repro.models import lm
from repro.models import moe as moe_lib
from repro.runtime.train import TrainHyper, build_fused_step, make_state


def scopes(text: str) -> set:
    """The name-stack parts of a lowered program's op locations (a name
    location wraps its source location: ``loc("moe/dot"(#loc7))``), with
    transformation wrappers (``jvp(moe)``, ``transpose(jvp(moe))``)
    taken off."""
    out = set()
    for name in re.findall(r'loc\("([^"]+)"\(#loc', text):
        for part in name.split("/"):
            while (m := re.fullmatch(r"[\w.]+\((.*)\)", part)):
                part = m.group(1)
            out.add(part)
    return out


@pytest.fixture(scope="module")
def cfg():
    return get_arch("olmoe-1b-7b-smoke")


def test_tick_program_names_its_scopes(cfg):
    params = jax.eval_shape(lambda: lm.init(cfg, jax.random.PRNGKey(0)))
    eng = ServeEngine(cfg, lm.init(cfg, jax.random.PRNGKey(0)), max_len=32,
                      slots=2)
    sp = eng.pools[0]
    S, L = sp.slots, 2
    text = build_slot_tick(cfg).lower(
        params, sp.pool, sp.pos, jnp.zeros((S, L), jnp.int32),
        jnp.ones((S,), jnp.int32), jnp.ones((S,), bool),
        jnp.zeros((S,), bool), sp.keys,
        jnp.zeros((S,), jnp.float32)).as_text(debug_info=True)
    assert {"attention", "moe", "head", "kv_pool"} <= scopes(text)


def test_step_program_names_its_scopes(cfg):
    state = jax.eval_shape(lambda: make_state(cfg, jax.random.PRNGKey(0)))
    p = moe_lib.identity_plan(cfg, lm.n_moe_layers(cfg))
    text = build_fused_step(cfg, TrainHyper()).lower(
        state, {"tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32)},
        p.slots, p.cum, jnp.float32(1.0), n_mb=2).as_text(debug_info=True)
    assert {"attention", "moe", "head", "optimizer"} <= scopes(text)
