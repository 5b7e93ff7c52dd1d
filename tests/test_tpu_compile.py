"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip, with no chip attached: block shapes, primitives and shape
casts that Mosaic refuses fail here, where interpret mode runs them happily.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library, so only the
worker that runs this file loads it.
"""
import os

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
