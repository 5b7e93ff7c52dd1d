"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip, with no chip attached: block shapes, primitives and shape
casts that Mosaic refuses fail here, where interpret mode runs them happily.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library, so only the
worker that runs this file loads it.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.moe_dispatch import ops as dops
from repro.kernels.moe_gating.moe_gating import gating_pallas
from repro.kernels.tiling import block_rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip can write compiled programs to the persistent cache
    # but never read them back: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text          # the kernel really is Mosaic
    return text


# T=4096 is an olmoe-1b-7b microbatch; 300 and 4104 give the unaligned
# blocks (150, 228 rows) the old largest-divisor rule chose
@pytest.mark.parametrize("t", [4096, 300, 4104])
def test_gating_compiles_olmoe(one_chip, t):
    logits = jax.ShapeDtypeStruct((t, 64), jnp.float32, sharding=one_chip)
    _compile(lambda x: gating_pallas(x, 8, bt=block_rows(t)), logits)


# (tokens, d_model, top_k, experts, slots): olmoe-1b-7b and paper-moe-100m
# microbatches, and an unaligned token count at paper-moe-100m widths
@pytest.mark.parametrize("t,d,k,e,s", [(4096, 2048, 8, 64, 80),
                                       (4096, 512, 2, 16, 18),
                                       (4104, 512, 2, 16, 18)],
                         ids=["olmoe", "paper-moe", "paper-moe-t4104"])
def test_dispatch_combine_compiles(one_chip, t, d, k, e, s):
    cap = max(4, int(t * k * 1.25 / e))

    def loss(x, slot, w):
        y, _ = dops.dispatch_combine(x, slot, w, jax.nn.silu, s, cap,
                                     impl="pallas")
        return y.astype(jnp.float32).sum()

    args = (jax.ShapeDtypeStruct((t, d), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((t, k), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((t, k), jnp.float32, sharding=one_chip))
    # forward: rank, scatter and gather; backward: gather and scatter again
    text = _compile(
        lambda x, sl, w: jax.value_and_grad(loss, (0, 2))(x, sl, w), *args)
    assert text.count("tpu_custom_call") >= 5


# ----------------------------------------------- the serving tick's KV cache

_COMPUTATION = re.compile(r"^(ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\w+\[[\d,]*\]|\(.*?\))\S* ([\w-]+)\((.*)$")


def _hlo_computations(text):
    """{computation: {instruction: (opcode, dims or None, operands, attrs)}}
    and the entry's name, from compiled HLO text."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(2), {})
            entry = m.group(2) if m.group(1) else entry
            continue
        m = _INSTRUCTION.match(line) if cur is not None else None
        if m:
            name, shape, op, rest = m.groups()
            dims = None if shape.startswith("(") else tuple(
                int(d) for d in shape[shape.index("[") + 1:-1].split(",")
                if d)
            args = rest.split(")", 1)[0]
            cur[name] = (op, dims, re.findall(r"%([\w.\-]+)", args),
                         dict(re.findall(r"(body|calls)=%([\w.\-]+)", rest)))
    return comps, entry


def _loop_body_moves(text, row):
    """Copies, dynamic slices and dynamic-update-slices inside the while
    bodies (at any depth) whose result is cache-sized, its dims ending in
    ``row`` (max_len, KV heads, head dim), where a write counts only when
    it writes a cache-sized update: the in-place row write passes."""
    comps, entry = _hlo_computations(text)

    def cache_sized(dims):
        kept = tuple(d for d in dims or () if d != 1)
        return kept[-len(row):] == row

    def moves(comp, name):
        op, dims, args, attrs = comps[comp][name]
        if op == "fusion":
            fused = comps[attrs["calls"]]
            root = list(fused)[-1]
            while fused[root][0] == "bitcast" and fused[root][2][0] in fused:
                root = fused[root][2][0]
            return moves(attrs["calls"], root)
        if op == "dynamic-update-slice":
            return cache_sized(comps[comp].get(args[1], (0, None))[1])
        return op in ("copy", "dynamic-slice")

    bodies, todo = [], [entry]
    while todo:
        for op, _, _, attrs in comps[todo.pop()].values():
            if op == "while" and attrs["body"] not in bodies:
                bodies.append(attrs["body"])
                todo.append(attrs["body"])
    return [name for body in bodies for name, (_, dims, _, _) in
            comps[body].items()
            if cache_sized(dims) and moves(body, name)]


# the plain tick (prefill chunks and decode) and the speculative verify tick
@pytest.mark.parametrize("spec_len", [0, 4], ids=["plain", "spec"])
def test_slot_tick_writes_kv_rows_in_place(one_chip, spec_len):
    """The serving tick's decode writes each token's K/V row into the slot
    pool in place: no whole layer of the cache, and not the whole pool, is
    copied, sliced out or written back inside its token and layer loops."""
    import dataclasses
    from repro.configs import get_arch
    from repro.engine.serve import build_slot_tick
    from repro.models import lm
    cfg = dataclasses.replace(get_arch("olmoe-1b-7b-smoke"), num_layers=2,
                              n_heads=16, n_kv_heads=16, head_dim=128)
    slots, chunk, max_len = 4, 16, 256

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: sds(x.shape, jnp.bfloat16),
                          lm.abstract(cfg))
    row = jax.eval_shape(lambda: lm.init_cache(cfg, 1, max_len))["caches"]
    pool = {"caches": jax.tree.map(
        lambda x: sds((slots,) + x.shape, x.dtype), row),
        "ng": sds((slots, cfg.serve.spec_table), jnp.int32),
        "ctx": sds((slots, cfg.serve.spec_ctx), jnp.int32)}
    key = jax.eval_shape(
        lambda: jax.random.split(jax.random.PRNGKey(0), slots))
    vec = functools.partial(sds, (slots,))
    text = build_slot_tick(cfg, spec_len).lower(
        params, pool, vec(jnp.int32), sds((slots, chunk), jnp.int32),
        vec(jnp.int32), vec(jnp.bool_), vec(jnp.bool_),
        sds(key.shape, key.dtype), vec(jnp.float32)).compile().as_text()
    assert " while(" in text
    assert _loop_body_moves(text, (max_len, cfg.n_kv_heads, cfg.hd)) == []
