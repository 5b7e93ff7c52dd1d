"""Bring-up run of the serving and training main paths on TPU chips.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # slot pools placed across four chips

One chip, three phases in one process:

* serve — olmoe-1b-7b at its published widths (depth cut to SERVE_LAYERS of
  its 16 layers, random weights from the seed) through
  ``BatchedServer(...).engine()``: seeded greedy requests run to completion,
  and the cached-decode tokens of two of them are checked against one full
  causal forward pass over prompt + output;
* train (xla) and train (fused) — paper-moe-100m at full size through the
  TrainLoop that ``repro.launch.train`` builds (Reshape on, async
  checkpoints), once with the XLA dispatch and once with the Pallas MoE
  gating and dispatch kernels; the two arms are checked against each other.

Four chips (``--chips 4``): a ServeEngine with one slot pool placed on each
chip (tp=1) and one pool drained mid-stream, against the same requests on
one unplaced pool — greedy outputs must be identical.

Where JAX finds no TPU the script exits non-zero without running anything.
Any failed check exits non-zero.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

# olmoe-1b-7b layers served: 9.15 GB of f32 parameters (2.08 GB per layer
# plus 0.82 GB for the untied embedding and head) on a 16 GB v5e chip
SERVE_LAYERS = 4
# Logits come out of a bf16 head matmul (one bf16 ULP is 0.03 at the ~4.0
# top logit of these random weights), and the decode path (bf16 KV cache,
# one token at a time) and the full pass (chunked bf16 attention) round
# activations in different orders.  A gap under 0.25 absorbs near-ties; a
# token from a broken decode path sits ~4 logits below the top.
LOGIT_TOL = 0.25
# Both training arms compute in bf16.  The Pallas combine accumulates in f32
# and rounds once where the XLA path scatter-adds in bf16, so parameters
# drift apart by bf16 ULPs every step; losses (~10 at init) stay this close.
LOSS_TOL = 2e-2
CKPT_DIR = ROOT / ".smoke_ckpt"


class CompileLog:
    """Counts XLA compilations (or persistent-cache loads) and their
    seconds, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.n, self.seconds = 0, 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check(ok, msg):
    """A smoke check that stays on under ``python -O``."""
    if not ok:
        raise AssertionError(msg)


def _lengths(rng, n, lo, hi, multiple):
    return [int(x) for x in rng.integers(lo // multiple, hi // multiple + 1,
                                         n) * multiple]


def _requests(cfg, rng, n, prompt_range, new_range, multiple=1):
    import numpy as np
    plens = _lengths(rng, n, *prompt_range, multiple)
    news = [int(x) for x in rng.integers(new_range[0], new_range[1] + 1, n)]
    prompts = [rng.integers(1, cfg.vocab, (p,)).astype(np.int32)
               for p in plens]
    return prompts, news


def _check_outputs(cfg, outputs, news):
    import numpy as np
    for out, n in zip(outputs, news):
        check(len(out) == n, f"request produced {len(out)} of {n} tokens")
        check(int(np.min(out)) >= 0 and int(np.max(out)) < cfg.vocab,
              "token outside the vocabulary")


def forward_gaps(cfg, params, prompts, outputs):
    """Per request: the largest gap, over its generated positions, between
    the top logit and the logit of the token the engine chose, from one
    full causal forward pass over prompt + output."""
    import jax
    import numpy as np
    from repro.models import lm
    m = cfg.moe
    # decode routes one token at a time and never drops one; the full pass
    # computes the same function only if no token drops, so every expert
    # slot gets room for every token
    fcfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        m, capacity_factor=m.num_experts / m.top_k))
    # one padded length -> one compile; padding trails every real token
    # (causal) and the capacity above holds it too
    t = max(len(p) + len(o) for p, o in zip(prompts, outputs))
    fwd = jax.jit(lambda p, x: lm.forward(p, {"tokens": x}, fcfg))
    gaps = []
    for p, o in zip(prompts, outputs):
        seq = np.zeros((1, t), np.int32)
        seq[0, :len(p)] = p
        seq[0, len(p):len(p) + len(o)] = o
        logits, aux = fwd(params, seq)
        check(int(aux["moe"]["dropped"].sum()) == 0, "full pass dropped")
        # position j's logits predict token j+1: the first output token
        # follows the last prompt token
        lg = np.asarray(logits[0, len(p) - 1:len(p) - 1 + len(o)])
        gaps.append(float((lg.max(-1) - lg[np.arange(len(o)), o]).max()))
    return gaps


def serve_phase(cfg, *, seed=0, slots=8, max_len=1024, n_requests=8,
                prompt_range=(128, 512), new_range=(32, 64), n_checked=2):
    """Greedy requests through BatchedServer -> ServeEngine; every request
    must finish with its max_new in-vocabulary tokens, and the first
    ``n_checked`` must agree with a full forward pass within LOGIT_TOL."""
    import jax
    import numpy as np
    from repro.models import lm
    from repro.runtime.serve import BatchedServer
    params = lm.init(cfg, jax.random.PRNGKey(seed))
    prompts, news = _requests(cfg, np.random.default_rng(seed), n_requests,
                              prompt_range, new_range)
    engine = BatchedServer(cfg, params, max_len=max_len,
                           slots=slots).engine(seed)
    t0 = time.perf_counter()
    reqs = [engine.submit(p, max_new=n) for p, n in zip(prompts, news)]
    engine.run_until_done()
    wall = time.perf_counter() - t0
    outputs = [r.output() for r in reqs]
    _check_outputs(cfg, outputs, news)
    gaps = forward_gaps(cfg, params, prompts[:n_checked],
                        outputs[:n_checked])
    check(max(gaps) <= LOGIT_TOL, f"decode/forward logit gaps {gaps}")
    return {"param_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
            "requests": n_requests, "prompt_tokens": sum(map(len, prompts)),
            "new_tokens": sum(news), "ticks": engine.tick_no,
            "serve_wall_s": wall, "logit_gaps": gaps}


def train_phase(cfg, *, seq_len=512, global_batch=16, microbatches=2,
                steps=6, ckpt_every=2, ckpt_root=CKPT_DIR):
    """TrainLoop as ``repro.launch.train`` builds it, once per MoE dispatch
    arm: "xla", and "fused" with the Pallas gating kernel on as well."""
    import jax
    import numpy as np
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.launch.train import build_loop
    hists, walls = {}, {}
    tokens = seq_len * global_batch
    for arm in ("xla", "fused"):
        c = cfg if arm == "xla" else dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, fused_gating=True))
        ckpt_dir = Path(ckpt_root) / arm
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        loop = build_loop(c, steps=steps, seq_len=seq_len,
                          global_batch=global_batch,
                          microbatches=microbatches, reshape=True,
                          ckpt_dir=str(ckpt_dir), ckpt_every=ckpt_every,
                          dispatch_select=arm)
        param_bytes = sum(x.nbytes
                          for x in jax.tree.leaves(loop.state["params"]))
        t0 = time.perf_counter()
        hist = loop.run(steps)
        walls[arm] = time.perf_counter() - t0
        check(len(hist) == steps, f"{arm}: {len(hist)} of {steps} steps")
        for h in hist:
            check(np.isfinite(h["loss"]), f"{arm} step {h['step']}: loss")
            routed = np.asarray(h["slot_counts"]).sum(-1)       # per layer
            check((routed == tokens * c.moe.top_k).all(),
                  f"{arm} step {h['step']}: routed {routed}")
        acked = Checkpointer(str(ckpt_dir)).acked_steps()
        check(acked == set(range(ckpt_every, steps + 1, ckpt_every)),
              f"{arm}: acknowledged checkpoints {sorted(acked)}")
        hists[arm] = hist
        del loop
        gc.collect()
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    xla, fused = hists["xla"], hists["fused"]
    # same params and batch at the first step: routing, and so the drop
    # decisions and load metrics, are the kernels' bit-identical contract
    for key in ("dropped", "slot_counts", "expert_counts"):
        np.testing.assert_array_equal(xla[0][key], fused[0][key], key)
    diffs = [abs(a["loss"] - b["loss"]) for a, b in zip(xla, fused)]
    check(max(diffs) <= LOSS_TOL, f"loss gaps {diffs}")
    return {arm: {"wall_s": walls[arm],
                  "loss": [h["loss"] for h in hists[arm]],
                  "dropped": [int(np.sum(h["dropped"])) for h in hists[arm]]}
            for arm in hists} | {"param_bytes": param_bytes,
                                 "loss_gaps": diffs}


def placement_phase(cfg, devices, *, seed=0, slots=4, chunk=8, max_len=256,
                    n_requests=8, prompt_range=(64, 128), new_range=(17, 33),
                    drain_after=6):
    """The same greedy requests on one unplaced pool, then on one pool per
    device with the last pool drained mid-stream; outputs must match.
    Every pool has ``slots`` slots and compact decode is off, so both runs
    compile the same tick shapes.  Returns per-device bytes in use taken
    while the placed engine is live (None where a backend reports none)."""
    import jax
    import numpy as np
    from repro.engine.serve import ServeEngine
    from repro.models import lm
    params = lm.init(cfg, jax.random.PRNGKey(seed))
    prompts, news = _requests(cfg, np.random.default_rng(seed), n_requests,
                              prompt_range, new_range, multiple=chunk)

    def engine(placements):
        return ServeEngine(cfg, params, max_len=max_len, slots=slots,
                           prefill_chunk=chunk, decode_chunk=chunk,
                           seed=seed, compact_decode=False,
                           pools=max(len(placements), 1),
                           placements=placements)

    ref = engine({})
    reqs = [ref.submit(p, max_new=n) for p, n in zip(prompts, news)]
    ref.run_until_done()
    expect = [r.output() for r in reqs]
    _check_outputs(cfg, expect, news)
    del ref, reqs

    placed = engine(dict(enumerate(devices)))
    reqs = [placed.submit(p, max_new=n) for p, n in zip(prompts, news)]
    for _ in range(drain_after):
        placed.tick()
    drained = len(devices) - 1
    check(placed._pool(drained).free_slots() < slots,
          "the drained pool must hold requests in flight")
    placed.drain_pool(drained)
    placed.run_until_done()
    check(placed.migrated_slots > 0, "drain migrated no slot")
    for i, (a, b) in enumerate(zip(expect, (r.output() for r in reqs))):
        np.testing.assert_array_equal(a, b, f"request {i}")
    used = []
    for d in devices:
        stats = d.memory_stats()
        used.append(None if stats is None else stats["bytes_in_use"])
    return {"param_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
            "requests": n_requests, "migrated": placed.migrated_slots,
            "bytes_in_use": used}


def _device_json(jax):
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the placed-pool serving path across four "
                         "chips and its one-pool reference")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    import jax
    from repro.configs import get_arch
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"--chips {args.chips}: JAX sees {len(jax.devices())}",
              file=sys.stderr)
        return 2
    compiles = CompileLog()
    print(f"device: {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {cache}", flush=True)
    serve_cfg = dataclasses.replace(get_arch("olmoe-1b-7b"),
                                    num_layers=SERVE_LAYERS)

    def run(name, fn, *a, **kw):
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
        print(f"phase {name}: {time.perf_counter() - t0:.1f}s wall; "
              f"compiled {compiles.n} programs in {compiles.seconds:.1f}s "
              f"so far ({compiles.cache_hits} from cache); device 0 peak "
              f"{peak / 2**30:.2f} GiB; {json.dumps(out)}", flush=True)
        gc.collect()
        return out

    if args.chips == 4:
        devices = jax.devices()[:4]
        out = run("placed-serve", placement_phase, serve_cfg, devices)
        used = out["bytes_in_use"]
        for d, b in zip(devices, used):
            print(f"device {d.id}: {b / 2**30:.2f} GiB in use", flush=True)
        # each placed pool holds its own params copy and cache rows on its
        # own chip; device 0 holds no more than its one pool's share
        check(min(used) >= out["param_bytes"], f"bytes in use {used}")
        check(used[0] <= 1.25 * max(used[1:]), f"bytes in use {used}")
    else:
        run("serve", serve_phase, serve_cfg)
        run("train", train_phase, get_arch("paper-moe-100m"))
    print(json.dumps({"ok": True, "device": _device_json(jax)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
