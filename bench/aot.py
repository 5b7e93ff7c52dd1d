"""Ahead-of-time memory check of a cell's programs for a described v5e.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload <cell>

Compiles, for one chip of a described ``v5e:2x2`` and without a chip, the
serving cell's largest tick (every slot, the prefill chunk's length) or the
training cell's fused step, at the cell's real sizes, and prints what XLA's
``memory_analysis`` says each needs beside the weights.  It cannot see what
a process keeps beside that one program (a second copy of the slot pool
during a compacted commit, the reference after the window).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}


def serve_tick(spec, shard) -> dict:
    import jax
    import jax.numpy as jnp
    from bench import model
    from repro.engine.serve import build_slot_tick
    from repro.models import lm
    c, tr = spec["config"], spec["traffic"]
    sv = dict(c["serving"])
    sv.update(tr.get("serving", {}))
    cfg = model.arch(c)
    S, L, T = sv["slots"], sv["prefill_chunk"], sv["max_len"]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=shard)

    params = jax.tree.map(lambda x: sds(x.shape, jnp.bfloat16),
                          lm.abstract(cfg))
    one = jax.eval_shape(lambda: lm.init_cache(cfg, 1, T))["caches"]
    pool = {"caches": jax.tree.map(lambda x: sds((S,) + x.shape, x.dtype),
                                   one),
            "ng": sds((S, cfg.serve.spec_table), jnp.int32),
            "ctx": sds((S, cfg.serve.spec_ctx), jnp.int32)}
    key = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), S))
    args = (params, pool, sds((S,), jnp.int32), sds((S, L), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.bool_),
            sds((S,), jnp.bool_), sds(key.shape, key.dtype),
            sds((S,), jnp.float32))
    compiled = build_slot_tick(cfg).lower(*args).compile()
    out = _mem(compiled)
    out["program"] = f"tick slots={S} L={L} max_len={T}"
    out["weights_bytes"] = model.param_bytes(c)
    return out


def train_step(spec, shard) -> dict:
    import jax
    import jax.numpy as jnp
    from bench import model
    from repro.runtime.train import abstract_state, build_fused_step
    from bench.train import hyper_for
    c, tr = spec["config"], spec["traffic"]
    cfg = model.arch(c)
    hyper = hyper_for(c)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard)

    state = jax.tree.map(sds, abstract_state(cfg))
    gb, s = tr["global_batch"], tr["seq_len"]
    batch = {"tokens": jax.ShapeDtypeStruct((gb, s), jnp.int32,
                                            sharding=shard)}
    e, r = cfg.moe.num_experts, cfg.moe.max_replicas
    nl = cfg.num_layers
    ps = jax.ShapeDtypeStruct((nl, e, r), jnp.int32, sharding=shard)
    pc = jax.ShapeDtypeStruct((nl, e, r), jnp.float32, sharding=shard)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=shard)
    step = build_fused_step(cfg, hyper)
    compiled = step.lower(state, batch, ps, pc, lr,
                          n_mb=tr["microbatches"]).compile()
    out = _mem(compiled)
    out["program"] = f"fused step batch={gb} seq={s} mb={tr['microbatches']}"
    return out


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench import spec as spec_lib
    jax.config.update("jax_enable_compilation_cache", False)
    spec = spec_lib.cell(spec_lib.benchmark(), args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    shard = SingleDeviceSharding(topo.devices[0])
    fn = {"serve": serve_tick, "train": train_step}[
        spec["traffic"]["mode"]]
    out = fn(spec, shard)
    out["workload"] = args.workload
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
