"""Fused MoE dispatch/combine kernel family vs the XLA reference.

Covers: fwd equivalence of the jnp fused algorithm AND the Pallas kernel in
interpret mode against ``models.moe.dispatch_combine`` (bit-identical drop
decisions / Reshape load metrics, allclose outputs), capacity-overflow drop
parity, a skewed-routing case exercising the Reshape metrics under a
non-identity SBR plan, gradient equivalence through the custom VJP, the
full-model wiring behind ``cfg.moe.fused_dispatch``, vmap (the serve decode
path), and the engine's CostBook-driven kernel selection.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.kernels.moe_dispatch import ops as dops
from repro.kernels.moe_dispatch.moe_dispatch import (gather_pallas,
                                                     rank_pallas,
                                                     scatter_pallas)
from repro.kernels.moe_dispatch.ref import gather_ref, rank_ref, scatter_ref
from repro.models import moe as moe_lib

RNG = np.random.default_rng(0)


def _case(t, d, k, s, skew=False, valid_frac=None):
    x = jnp.asarray(RNG.standard_normal((t, d)), jnp.float32)
    slot_np = RNG.integers(0, s, (t, k))
    if skew:
        slot_np[: t // 2, 0] = min(3, s - 1)     # hot slot -> forced drops
    slot = jnp.asarray(slot_np, jnp.int32)
    w = jnp.asarray(RNG.uniform(0.1, 1.0, (t, k)), jnp.float32)
    valid = None if valid_frac is None else \
        jnp.asarray(RNG.random((t, k)) < valid_frac)
    return x, slot, w, valid


def _expert(buf):
    return jax.nn.silu(buf) * 1.5


# ------------------------------------------------------------ fwd equivalence

@pytest.mark.parametrize("t,d,k,s,cap", [(64, 16, 2, 10, 8),
                                         (48, 8, 4, 6, 4),     # heavy drops
                                         (37, 16, 2, 5, 16)])  # odd T
@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_dispatch_combine_matches_xla(t, d, k, s, cap, impl):
    for skew, vf in ((False, None), (True, None), (False, 0.7)):
        x, slot, w, valid = _case(t, d, k, s, skew, vf)
        y0, m0 = moe_lib.dispatch_combine(x, slot, w, _expert, s, cap,
                                          valid=valid)
        y1, m1 = dops.dispatch_combine(x, slot, w, _expert, s, cap,
                                       valid=valid, impl=impl)
        # drop decisions + Reshape load metrics are bit-identical
        for key in ("slot_counts", "kept_counts"):
            np.testing.assert_array_equal(np.asarray(m0[key]),
                                          np.asarray(m1[key]))
        assert int(m0["dropped"]) == int(m1["dropped"])
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   atol=1e-5, rtol=1e-5)


def test_capacity_overflow_drop_parity():
    """Every assignment's keep/drop decision (not just the counts) matches
    the XLA path's stable-sort rank under forced capacity overflow."""
    t, d, k, s, cap = 96, 8, 4, 6, 5
    x, slot, w, _ = _case(t, d, k, s, skew=True)
    ones_w = jnp.ones((t, k), jnp.float32)
    ones_v = jnp.ones((t, k), jnp.int32)
    _, rank, keep, routed, kept = dops.dispatch(x, ones_w, slot, ones_v, s,
                                                cap, "jnp")
    # reference ranks via the baseline's stable argsort
    flat = np.asarray(slot).reshape(-1)
    sort_idx = np.argsort(flat, kind="stable")
    pos = np.empty_like(flat)
    seg = np.searchsorted(flat[sort_idx], np.arange(s + 1))
    pos[sort_idx] = np.arange(t * k) - seg[flat[sort_idx]]
    np.testing.assert_array_equal(np.asarray(rank).reshape(-1), pos)
    np.testing.assert_array_equal(np.asarray(keep).reshape(-1),
                                  (pos < cap).astype(np.int32))
    assert int(kept.sum()) < int(routed.sum())   # overflow really happened


# cap=9 drops assignments; cap=40 gives 320 buffer rows, more than one
# 256-row tile and not a multiple of it (the last tile is padded)
@pytest.mark.parametrize("cap", [9, 40])
def test_pallas_interpret_matches_ref_raw(cap):
    """The Pallas kernels (interpret mode) against the jnp oracle at the
    raw rank/scatter/gather level, including the weighted-scatter operand."""
    t, d, k, s = 64, 16, 3, 8
    x, slot, wgt, _ = _case(t, d, k, s, skew=True)
    w = jnp.asarray(RNG.uniform(0.5, 2.0, (t, k)), jnp.float32)
    valid = jnp.asarray(RNG.random((t, k)) < 0.8).astype(jnp.int32)
    r0 = rank_ref(slot, valid, s, cap)
    r1 = rank_pallas(slot, valid, s, cap, bt=16, interpret=True)
    for a, b in zip(r0, r1):                                  # int outputs
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rank, keep = r0[0], r0[1]
    dest = jnp.where(keep != 0, slot * cap + rank, -1)
    b0 = scatter_ref(x, w, dest, s * cap)
    b1 = scatter_pallas(x, w, dest, s * cap, bt=16, interpret=True)
    np.testing.assert_allclose(np.asarray(b0), np.asarray(b1),
                               atol=1e-5, rtol=1e-5)          # buf
    y0 = gather_ref(b0, wgt, dest)
    y1 = gather_pallas(b0, wgt, dest, bt=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------------ gradients

@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_grad_matches_xla(impl):
    t, d, k, s, cap = 48, 12, 2, 8, 7
    x, slot, w, valid = _case(t, d, k, s, skew=True, valid_frac=0.8)
    probe = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def loss_xla(x, w):
        y, _ = moe_lib.dispatch_combine(x, slot, w, _expert, s, cap,
                                        valid=valid)
        return (y * probe).sum()

    def loss_fused(x, w):
        y, _ = dops.dispatch_combine(x, slot, w, _expert, s, cap,
                                     valid=valid, impl=impl)
        return (y * probe).sum()

    g0 = jax.grad(loss_xla, (0, 1))(x, w)
    g1 = jax.jit(jax.grad(loss_fused, (0, 1)))(x, w)
    np.testing.assert_allclose(np.asarray(g0[0]), np.asarray(g1[0]),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g0[1]), np.asarray(g1[1]),
                               atol=1e-5, rtol=1e-4)
    assert float(jnp.abs(g1[0]).sum()) > 0      # grads actually flow


# ----------------------------------------------------------- model-level wire

def _skewed_batch(cfg, t=64):
    """Token batch whose embeddings drive a skewed router distribution."""
    toks = (np.arange(t) % 7).astype(np.int32).reshape(4, t // 4)
    return {"tokens": jnp.asarray(toks)}


def test_moe_ffn_fused_dispatch_matches():
    from repro.models import lm
    cfg = get_arch("olmoe-1b-7b-smoke")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.0))
    cfg_f = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, fused_dispatch=True))
    params = lm.init(cfg, jax.random.PRNGKey(0))
    nl = lm.n_moe_layers(cfg)
    # non-identity SBR plan: expert 0 split across two slots (the Reshape
    # partitioning logic) so slot metrics differ from expert metrics
    plan = moe_lib.identity_plan(cfg, nl)
    slots = np.asarray(plan.slots).copy()
    cum = np.asarray(plan.cum).copy()
    spare = cfg.moe.num_experts          # first spare slot
    slots[:, 0, 1:] = spare
    cum[:, 0, 0] = 0.5
    batch = _skewed_batch(cfg)

    def fwd(c):
        return jax.jit(lambda p, b: lm.forward(
            p, b, c, plan=moe_lib.RoutingPlan(jnp.asarray(slots),
                                              jnp.asarray(cum))))(params,
                                                                  batch)

    l0, a0 = fwd(cfg)
    l1, a1 = fwd(cfg_f)
    # Reshape-visible load metrics bit-identical (incl. the replica split)
    for key in ("slot_counts", "kept_counts", "dropped", "expert_counts"):
        np.testing.assert_array_equal(np.asarray(a0["moe"][key]),
                                      np.asarray(a1["moe"][key]))
    assert int(np.asarray(a0["moe"]["dropped"]).sum()) > 0
    sc = np.asarray(a0["moe"]["slot_counts"])
    assert sc[:, spare].sum() > 0        # the replica slot really took load
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), atol=2e-5,
                               rtol=1e-4)


def test_moe_ffn_fused_dispatch_grads_close():
    from repro.models import lm
    cfg = get_arch("olmoe-1b-7b-smoke")
    cfg_f = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, fused_dispatch=True))
    params = lm.init(cfg, jax.random.PRNGKey(1))
    plan = moe_lib.identity_plan(cfg, lm.n_moe_layers(cfg))
    batch = _skewed_batch(cfg)

    def loss(p, c):
        lg, aux = lm.forward(p, batch, c, plan=plan)
        return (lg.astype(jnp.float32) ** 2).mean() + \
            aux["moe"]["aux_loss"].mean()

    g0 = jax.jit(lambda p: jax.grad(lambda q: loss(q, cfg))(p))(params)
    g1 = jax.jit(lambda p: jax.grad(lambda q: loss(q, cfg_f))(p))(params)
    # activations are bf16: the fused combine accumulates in f32 and rounds
    # once, where the XLA path scatter-adds in bf16 — bf16-ULP tolerance
    for (pth, a), b in zip(jax.tree_util.tree_flatten_with_path(g0)[0],
                           jax.tree.leaves(g1)):
        scale = max(float(jnp.abs(a).max()), 1e-3)
        assert float(jnp.abs(a - b).max()) <= 0.02 * scale, pth


def test_vmap_serve_decode_path():
    """dispatch_combine under vmap (the ServeEngine tick vmaps decode_step,
    which hits the fused path when cfg.moe.fused_dispatch is set)."""
    t, d, k, s, cap = 8, 8, 2, 6, 4
    xs = jnp.asarray(RNG.standard_normal((3, t, d)), jnp.float32)
    slots = jnp.asarray(RNG.integers(0, s, (3, t, k)), jnp.int32)
    ws = jnp.asarray(RNG.uniform(0.1, 1.0, (3, t, k)), jnp.float32)

    def one(x, slot, w, fused):
        return moe_lib.dispatch_combine(x, slot, w, _expert, s, cap,
                                        fused=fused)[0]

    y0 = jax.vmap(lambda x, sl, w: one(x, sl, w, False))(xs, slots, ws)
    y1 = jax.vmap(lambda x, sl, w: one(x, sl, w, True))(xs, slots, ws)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-5,
                               rtol=1e-5)


@pytest.mark.slow
def test_fused_dispatch_training_matches():
    """End-to-end loss trajectory with fused gating + dispatch vs stock."""
    from repro.data.synthetic import TokenStream
    from repro.runtime.loop import LoopConfig, TrainLoop
    from repro.runtime.train import TrainHyper
    cfg = get_arch("olmoe-1b-7b-smoke")
    cfg_f = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, fused_gating=True,
                                     fused_dispatch=True))
    hists = []
    for c in (cfg, cfg_f):
        stream = TokenStream(vocab=c.vocab, seq_len=32, global_batch=8,
                             seed=5, class_alpha=2.0)
        loop = TrainLoop(c, stream, TrainHyper(),
                         LoopConfig(microbatches=2, step_path="fused"))
        hists.append(loop.run(3))
    # first step: same params -> routing and load metrics bit-identical
    a0, b0 = hists[0][0], hists[1][0]
    np.testing.assert_array_equal(a0["expert_counts"], b0["expert_counts"])
    np.testing.assert_array_equal(a0["slot_counts"], b0["slot_counts"])
    assert a0["dropped"].sum() == b0["dropped"].sum()
    # trajectories track within bf16-accumulation tolerance: the fused
    # combine sums in f32 and rounds once, the XLA path scatter-adds in
    # bf16, so activations (and hence later-step params) differ at ULP
    for a, b in zip(*hists):
        assert abs(a["loss"] - b["loss"]) < 5e-3


# --------------------------------------------------- CostBook kernel selection

def test_costbook_selects_dispatch_impl():
    """The engine explores both dispatch workflows, then picks per shape
    from measured costs — and flips when the measurements flip."""
    from repro.engine.engine import Engine
    from repro.engine.jobs import Job, dispatch_kind

    eng = Engine()
    # bootstrap: unmeasured fused arm is explored first
    assert eng.choose_dispatch_impl(1024) == "fused"
    eng.observe(Job(dispatch_kind("fused", 1024)), 0.010)   # cold, skipped
    assert eng.choose_dispatch_impl(1024) == "fused"
    eng.observe(Job(dispatch_kind("fused", 1024)), 0.010)
    # fused measured, xla not: explore the other arm
    assert eng.choose_dispatch_impl(1024) == "xla"
    eng.observe(Job(dispatch_kind("xla", 1024)), 0.030)     # cold, skipped
    eng.observe(Job(dispatch_kind("xla", 1024)), 0.030)
    d = eng.choose_dispatch_impl(1024)
    assert d == "fused"
    assert eng.decisions[-1]["scores"]["fused"] < \
        eng.decisions[-1]["scores"]["xla"]
    # per-shape: a different token count starts its own bootstrap
    assert eng.choose_dispatch_impl(4096) == "fused"
    assert eng.decisions[-1]["why"] == "bootstrap"
    # measurements flip at the big shape -> the choice flips too
    for _ in range(3):
        eng.observe(Job(dispatch_kind("fused", 4096)), 0.200)
        eng.observe(Job(dispatch_kind("xla", 4096)), 0.050)
    assert eng.choose_dispatch_impl(4096) == "xla"
    # forcing bypasses the cost model
    assert eng.choose_dispatch_impl(1024, forced="xla") == "xla"
    # periodic re-explore: the losing arm is re-run every 16th scored round
    # so a stale/poisoned EMA cannot wedge the choice forever
    choices = [eng.choose_dispatch_impl(4096) for _ in range(20)]
    assert "fused" in choices
    assert any(d.get("why") == "re-explore" for d in eng.decisions
               if d["decision"] == "dispatch_impl")


@pytest.mark.slow
def test_trainloop_dispatch_select_end_to_end():
    """TrainLoop under dispatch_select=auto: both impls get measured (first
    run per impl jit is cold and skipped), decisions are recorded, and the
    cost book ends up with per-shape entries for both workflows."""
    from repro.data.synthetic import TokenStream
    from repro.runtime.loop import LoopConfig, TrainLoop
    from repro.runtime.train import TrainHyper
    cfg = get_arch("olmoe-1b-7b-smoke")
    stream = TokenStream(vocab=cfg.vocab, seq_len=16, global_batch=4, seed=1)
    loop = TrainLoop(cfg, stream, TrainHyper(),
                     LoopConfig(microbatches=1, dispatch_select="auto"))
    loop.run(8)
    snap = loop.engine.costs.snapshot()
    assert any(k.startswith("moe_dispatch_fused:") for k in snap)
    assert any(k.startswith("moe_dispatch_xla:") for k in snap)
    dec = [d for d in loop.engine.decisions
           if d["decision"] == "dispatch_impl"]
    assert any("scores" in d for d in dec)       # reached the measured phase
    # the step-path decision stayed fused: impl exploration compiles fresh
    # jits, and those cold steps must not poison the step-path cost model
    assert all(d["choice"] == "fused" for d in loop.engine.decisions
               if d["decision"] == "step_path")
    assert len(loop.history) == 8


# -------------------------------------------------------- serve compact batch

def test_serve_compact_decode_matches():
    """Lane-waste flag: gathering active decode slots into a compact batch
    yields bit-identical outputs while >= half the pool idles."""
    from repro.engine.serve import ServeEngine
    from repro.models import lm
    cfg = get_arch("gemma3-1b-smoke")
    params = lm.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    lens, news = [4, 12, 20, 6], [20, 6, 12, 24]
    prompts = [rng.integers(1, cfg.vocab, (l,)).astype(np.int32)
               for l in lens]
    outs = {}
    for compact in (False, True):
        eng = ServeEngine(cfg, params, max_len=96, slots=8,
                          prefill_chunk=16, decode_chunk=4,
                          compact_decode=compact)
        reqs = [eng.submit(p, max_new=n) for p, n in zip(prompts, news)]
        eng.run_until_done()
        outs[compact] = [r.output() for r in reqs]
        if compact:
            assert eng.compact_ticks > 0
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(a, b)
