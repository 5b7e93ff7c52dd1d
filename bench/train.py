"""Training: ``TrainLoop.run`` one step at a time.

The loop is composed as ``repro.launch.train.build_loop`` composes it
(AdamW with warm-up, Reshape on, no checkpoints) but with the benchmark's
own pieces where the launcher fixes them: the token batches come from the
seed (``gen.TokenBatches``), the weights are built by ``model.make_params``
from the seed, and the Reshape controller is wrapped so that its host time
and the plan it applies are recorded.

Set-up drives that one loop from the seed through its first steps (the
reference follows the first ``ref_steps`` of them), then on until no step
compiles anything, and hands the same loop to the window.  After the window
the loop is freed and ``check.train`` runs the reference.
"""
from __future__ import annotations

import gc
import time
from contextlib import ExitStack
from typing import Dict

import numpy as np

from bench import flops, gen, model, trace as trace_lib
from bench.common import CompileLog, Spans, memory_peak


def hyper_for(c: Dict):
    from repro.optim.adamw import AdamWCfg
    from repro.runtime.train import TrainHyper
    t = c["training"]
    return TrainHyper(
        opt=AdamWCfg(lr=t["lr"], b1=t["b1"], b2=t["b2"], eps=t["eps"],
                     weight_decay=t["weight_decay"],
                     clip_norm=t["clip_norm"],
                     warmup_steps=t["warmup_steps"],
                     total_steps=t["total_steps"],
                     min_lr_frac=t["min_lr_frac"]),
        aux_coef=t["aux_coef"], z_coef=t["z_coef"])


class TimedReshaper:
    """Delegates to a ``MoEReshaper``; times ``observe`` and ``step`` and
    keeps each plan and migration list ``step`` returns."""

    def __init__(self, inner):
        self.inner = inner
        self.seconds = 0.0
        self.plans = []
        self.initial = (np.array(inner.plan_slots), np.array(inner.plan_cum),
                        [])

    def observe(self, *a, **k):
        t = time.perf_counter()
        try:
            return self.inner.observe(*a, **k)
        finally:
            self.seconds += time.perf_counter() - t

    def step(self):
        t = time.perf_counter()
        try:
            ps, pc, migs = self.inner.step()
        finally:
            self.seconds += time.perf_counter() - t
        self.plans.append((np.array(ps), np.array(pc),
                           [(m.layer, m.src_slot, m.dst_slot)
                            for m in migs]))
        return ps, pc, migs

    def __getattr__(self, name):
        return getattr(self.inner, name)


def build(c: Dict, tr: Dict, seed: int):
    """The loop, its stream and wrapped reshaper, holding the benchmark's
    weights."""
    import jax.numpy as jnp
    from repro.core.reshape_moe import MoEReshaper
    from repro.core.skew import SkewParams
    from repro.models import lm
    from repro.optim import adamw
    from repro.runtime.loop import LoopConfig, TrainLoop
    cfg = model.arch(c)
    model.check_layout(c, cfg)
    t = c["training"]
    stream = gen.TokenBatches(tr, c["vocab_size"], seed)
    lc = LoopConfig(microbatches=int(tr["microbatches"]),
                    dispatch_select=tr.get("dispatch_select", "off"))
    reshaper = None
    if tr.get("reshape") and lm.n_moe_layers(cfg):
        reshaper = TimedReshaper(MoEReshaper(
            cfg, lm.n_moe_layers(cfg), ep_ranks=t["ep_ranks"],
            params=SkewParams(eta=t["reshape_eta"], tau=t["reshape_tau"])))
    loop = TrainLoop(cfg, stream, hyper_for(c), lc, reshaper=reshaper)
    params = model.make_params(c, gen.jax_seed(seed, "weights"))
    loop.state = {"params": params, "opt": adamw.init(params),
                  "step": jnp.zeros((), jnp.int32)}
    return loop, stream, reshaper


def warm_migrations(loop, max_moves: int) -> None:
    """Compile the expert-state migration for every batch size Reshape can
    emit, as copies of a slot onto itself (no change to the state).  The
    list goes to the device as the loop sends it, a Python list converted
    there, so the conversion's program for each size is compiled too."""
    import jax.numpy as jnp
    for m in range(1, max_moves + 1):
        loop.state = loop.migrate(
            loop.state, jnp.asarray([[0, 0, 0]] * m, jnp.int32))


def host(tree):
    import jax
    return jax.tree.map(np.asarray, jax.device_get(tree))


def run(spec: Dict, seed: int, seconds: float, traced: bool,
        t_start: float, trace_dir: str) -> Dict:
    import jax
    c, tr, chips = spec["config"], spec["traffic"], spec["chips"]
    loop, stream, reshaper = build(c, tr, seed)
    clog = CompileLog()
    spans = Spans(annotate=traced)
    n_ref = int(tr.get("ref_steps", 3))
    first = {"loss": [], "m1": None, "p_ref": None}
    for k in range(n_ref):
        loop.run(1)
        first["loss"].append(float(loop.history[-1]["loss"]))
        if k == 0:
            first["m1"] = host(loop.state["opt"].m)
    first["p_ref"] = host(loop.state["params"])
    # the plan each reference step routes by: the initial layout, then what
    # the controller returned after each step (with its migrations)
    plans = [(np.array(loop.plan_slots), np.array(loop.plan_cum), [])] \
        if reshaper is None else [reshaper.initial] + reshaper.plans[:n_ref]
    if reshaper is not None:
        # at most one new replica per spare slot and layer in one step
        warm_migrations(loop, c["num_hidden_layers"]
                        * c["program"]["spare_slots"])
    # on until a run of steps compiles nothing: the step-path and dispatch
    # choices settle, every program they reach is loaded
    quiet, extra = 0, 0
    while quiet < 3 and extra < int(tr.get("warm_max_steps", 12)):
        before = clog.count()
        loop.run(1)
        extra += 1
        quiet = quiet + 1 if clog.count() == before else 0

    t0 = time.perf_counter()
    setup_s = t0 - t_start
    c0 = clog.count()
    r0 = reshaper.seconds if reshaper else 0.0
    steps, losses, h0 = 0, [], len(loop.history)
    with ExitStack() as stack:
        if traced:
            stack.enter_context(trace_lib.capture(trace_dir))
        with spans(trace_lib.WINDOW_SPAN):
            while time.perf_counter() < t0 + seconds:
                with spans("bench.step"):
                    loop.run(1)
                losses.append(float(loop.history[-1]["loss"]))
                steps += 1
    t1 = time.perf_counter()
    window_s = t1 - t0
    compiled = clog.since(c0)
    paths = [d["choice"] for d in loop.engine.decisions
             if d["decision"] == "step_path"][-steps:] if steps else []
    tok_per_step = (stream.global_batch // int(tr["microbatches"])) * \
        int(tr["microbatches"]) * stream.seq_len
    tokens = steps * tok_per_step
    mem = memory_peak(chips)
    reshape_s = (reshaper.seconds - r0) if reshaper else None
    history = loop.history[h0:]
    del loop
    gc.collect()
    info = {"compiles_in_window": len(compiled),
            "compiled_in_window": sorted(set(compiled)),
            "warm_programs": c0,
            "warm_steps": n_ref + extra, "steps_in_window": steps,
            "step_paths": {p: paths.count(p) for p in sorted(set(paths))},
            "first_losses": first["loss"]}
    return {"kind": "train", "setup_s": setup_s, "window_s": window_s,
            "tokens": tokens, "steps": steps,
            "model_flops": flops.train_flops(c, tokens,
                                             stream.seq_len)["total"],
            "reshape_s": reshape_s, "memory_peak_bytes": mem,
            "history": history,
            "first": first, "plans": plans, "stream": stream,
            "attempted": steps,
            "failed": int(sum(not np.isfinite(x) for x in losses)),
            "info": info}


def check(run: Dict, spec: Dict, seed: int) -> Dict:
    from bench import check as check_lib
    return check_lib.train(run, spec, seed)
