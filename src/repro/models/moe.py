"""Mixture-of-Experts FFN with first-class Reshape skew handling.

TPU-native adaptation of the paper's partitioning layer (DESIGN.md §2):

* The **partitioning logic** the paper mutates via control messages is here a
  jittable input — a :class:`RoutingPlan` mapping each *logical* expert to up
  to R *physical slots* with split fractions.  The controller swaps the plan
  between steps (fast control path, **no recompile**).
* Physical expert slots = ``num_experts + spare_slots``.  Spare slots live on
  (underloaded) EP ranks and receive *replicas* of hot experts — the paper's
  helper workers.  SBR = fractional split of a hot expert across slots;
  SBK = moving a whole expert to a different slot.
* Load metrics (per-slot/per-expert token counts, overflow drops) are computed
  inside the layer — the paper's metric collection (§3.7.9, 1–2 % overhead)
  becomes a free side output.
* Dispatch is sort-based (segment ranks) + scatter-add into a capacity-bucketed
  ``[slots, capacity, d]`` buffer, then dense per-slot matmuls (MXU-friendly),
  not GPU-style atomics.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig


class RoutingPlan(NamedTuple):
    """Per-layer partitioning logic: logical expert -> physical slots."""
    slots: jnp.ndarray   # [L, E, R] int32 — physical slot of replica r
    cum: jnp.ndarray     # [L, E, R] f32  — cumulative split fractions (last=1)

    @property
    def num_replicas(self) -> int:
        return self.slots.shape[-1]


def identity_plan(cfg: ArchConfig, n_moe_layers: int) -> RoutingPlan:
    e, r = cfg.moe.num_experts, cfg.moe.max_replicas
    slots = jnp.broadcast_to(jnp.arange(e, dtype=jnp.int32)[None, :, None],
                             (n_moe_layers, e, r))
    cum = jnp.ones((n_moe_layers, e, r), jnp.float32)
    return RoutingPlan(slots, cum)


def num_slots(cfg: ArchConfig) -> int:
    return cfg.moe.num_experts + cfg.moe.spare_slots


def capacity(cfg: ArchConfig, tokens: int) -> int:
    m = cfg.moe
    return max(4, int(tokens * m.top_k * m.capacity_factor / m.num_experts))


def _hash_unit(idx):
    """Deterministic token -> [0,1) bucket (Knuth multiplicative hash)."""
    h = (idx.astype(jnp.uint32) * jnp.uint32(2654435761))
    return h.astype(jnp.float32) / jnp.float32(2 ** 32)


def route(router_w, x, plan_slots, plan_cum, cfg: ArchConfig, token_offset=0):
    """x [T,D] -> (slot [T,k], weight [T,k], probs [T,E], expert [T,k],
    counts [E] i32 from the fused gating kernel, or None)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x, router_w.astype(x.dtype))
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    counts = None
    if m.fused_gating:
        # Fused Pallas router: softmax + top-k + the Reshape load histogram
        # in one kernel, so metric collection costs zero extra passes.  The
        # kernel's outputs used here are integer (expert ids, counts); the
        # differentiable weights are re-gathered from `probs` below, so the
        # kernel itself needs no VJP rule.
        from repro.kernels.moe_gating.ops import gating
        impl = "pallas" if jax.default_backend() == "tpu" else "interpret"
        _, top_e, counts = gating(jax.lax.stop_gradient(logits), m.top_k,
                                  impl=impl)
        top_p = jnp.take_along_axis(probs, top_e, axis=-1)  # [T,k]
    else:
        top_p, top_e = jax.lax.top_k(probs, m.top_k)        # [T,k]
    weight = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # Reshape SBR replica choice: hash token index into [0,1), pick replica by
    # the plan's cumulative fractions (the "partitioning logic").
    t_idx = token_offset + jnp.arange(x.shape[0])
    u = _hash_unit(t_idx)                                  # [T]
    cum_g = plan_cum[top_e]                                # [T,k,R]
    r = (cum_g[..., :-1] <= u[:, None, None]).sum(-1)      # [T,k]
    slot = jnp.take_along_axis(plan_slots[top_e], r[..., None], -1)[..., 0]
    return slot.astype(jnp.int32), weight, probs, top_e, counts


def dispatch_combine(x, slot, weight, expert_fn, n_slots: int, cap: int,
                     valid=None, fused: bool = False, impl: str = "auto"):
    """Sort-based capacity dispatch -> per-slot expert_fn -> weighted combine.

    x [T,D]; slot/weight [T,k]; ``valid`` [T,k] masks assignments owned by
    this shard (EP: foreign experts are some other rank's problem, not
    drops).  Returns (y [T,D], metrics dict).

    ``fused=True`` routes through the Pallas dispatch/combine kernel
    family (``kernels/moe_dispatch``): a sort-free rank + capacity mask
    kernel and one-hot-matmul scatter/gather kernels instead of the
    argsort/searchsorted/scatter round-trip below, with bit-identical drop
    decisions and load metrics.
    """
    if fused:
        from repro.kernels.moe_dispatch.ops import \
            dispatch_combine as fused_dc
        return fused_dc(x, slot, weight, expert_fn, n_slots, cap,
                        valid=valid, impl=impl)
    t, d = x.shape
    k = slot.shape[1]
    tk = t * k
    flat_valid = (jnp.ones((tk,), bool) if valid is None
                  else valid.reshape(tk))
    # invalid assignments sort to a virtual segment past n_slots-1
    flat_slot = jnp.where(flat_valid, slot.reshape(tk), n_slots)

    # rank within slot segment via sort (no [TK, slots] one-hot materialized)
    sort_idx = jnp.argsort(flat_slot)
    sorted_slot = flat_slot[sort_idx]
    seg_start = jnp.searchsorted(sorted_slot, jnp.arange(n_slots + 1))
    pos_sorted = jnp.arange(tk, dtype=jnp.int32) - seg_start[
        jnp.minimum(sorted_slot, n_slots)]
    pos = jnp.zeros((tk,), jnp.int32).at[sort_idx].set(pos_sorted)

    keep = (pos < cap) & flat_valid
    dest = jnp.where(keep, flat_slot * cap + pos, n_slots * cap)  # drop bucket
    tok = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((n_slots * cap + 1, d), x.dtype).at[dest].add(
        x[tok] * keep[:, None].astype(x.dtype))
    buf = buf[:-1].reshape(n_slots, cap, d)

    out_buf = expert_fn(buf).reshape(n_slots * cap, d)     # [S,C,D] -> flat
    gathered = out_buf[jnp.where(keep, dest, 0)]           # [TK,D]
    contrib = gathered * (weight.reshape(tk, 1) * keep[:, None]).astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[tok].add(contrib)

    in_range = jnp.where(flat_valid, flat_slot, 0)
    slot_counts = jnp.zeros((n_slots,), jnp.int32).at[in_range].add(
        flat_valid.astype(jnp.int32))                      # routed (pre-drop)
    kept_counts = jnp.zeros((n_slots,), jnp.int32).at[in_range].add(
        keep.astype(jnp.int32))
    dropped = flat_valid.sum() - keep.sum()
    return y, {"slot_counts": slot_counts, "kept_counts": kept_counts,
               "dropped": dropped}


def moe_ffn_sharded(p, x, plan_slots, plan_cum, cfg: ArchConfig, mesh,
                    token_offset=0, tokens_sharded=True):
    """Expert-parallel MoE via full-manual ``shard_map`` (the production
    path; DESIGN.md §2 'TPU-idiomatic kernel choices').

    Experts are sharded over the ``model`` axis; tokens over data axes.  A
    device (row r, column c) owns row-r tokens and column-c expert slots, so
    dispatch is purely LOCAL (sort + scatter into the local capacity buffer)
    and the only collective is one psum over ``model`` for the combine —
    the same pattern as the dense-TP MLP all-reduce.  GSPMD never sees the
    scatter, avoiding its involuntary full rematerialization of the dispatch
    buffers (observed: 675 GB/device replicated under pure GSPMD).
    """
    from jax.sharding import PartitionSpec as P
    m = cfg.moe
    da = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    s_total = num_slots(cfg)
    mdl = mesh.shape["model"]
    assert s_total % mdl == 0, (s_total, mdl)
    spr = s_total // mdl                       # slots per EP rank
    t_global = x.shape[0]
    dp = 1
    for a in da:
        dp *= mesh.shape[a]
    tokens_sharded = tokens_sharded and (t_global % dp == 0) and \
        (t_global // dp) > 0
    x_spec = P(da, None) if tokens_sharded else P(None, None)

    def local_fn(xl, router_w, wg, wu, wd, ps, pc):
        t_loc = xl.shape[0]
        if tokens_sharded and da:
            row = jax.lax.axis_index(da[0])
            for a in da[1:]:
                row = row * mesh.shape[a] + jax.lax.axis_index(a)
            base = token_offset + row * t_loc
        else:
            base = token_offset
        slot, weight, probs, top_e, r_counts = route(router_w, xl, ps, pc,
                                                     cfg, base)
        col = jax.lax.axis_index("model")
        lo = col * spr
        mine = (slot >= lo) & (slot < lo + spr)
        local_slot = jnp.where(mine, slot - lo, 0)     # masked by `valid`
        cap = capacity(cfg, t_loc)

        def expert_fn(buf):                            # [spr, C, D]
            g = jax.nn.silu(jnp.einsum("scd,sdf->scf", buf,
                                       wg.astype(buf.dtype)))
            u = jnp.einsum("scd,sdf->scf", buf, wu.astype(buf.dtype))
            return jnp.einsum("scf,sfd->scd", g * u, wd.astype(buf.dtype))

        y, met = dispatch_combine(xl, local_slot.astype(jnp.int32),
                                  jnp.where(mine, weight, 0.0),
                                  expert_fn, spr, cap, valid=mine,
                                  fused=m.fused_dispatch)
        y = jax.lax.psum(y, "model")
        slot_counts = met["kept_counts"]
        routed = met["slot_counts"]
        dropped = (routed - slot_counts).sum()
        if da:
            dropped = jax.lax.psum(dropped, da)
        e_counts = r_counts if r_counts is not None else jnp.zeros(
            (m.num_experts,), jnp.int32).at[top_e.reshape(-1)].add(1)
        if da:
            e_counts = jax.lax.psum(e_counts, da)
            slot_counts = jax.lax.psum(slot_counts, da)
        f = e_counts.astype(jnp.float32) / jnp.maximum(
            e_counts.sum().astype(jnp.float32), 1.0)
        pbar = probs.mean(0)
        if da:
            pbar = jax.lax.pmean(pbar, da)
        aux = m.num_experts * jnp.sum(f * pbar)
        rz = jnp.mean(jnp.square(jax.nn.logsumexp(
            jnp.log(probs + 1e-9), axis=-1)))
        if da:
            rz = jax.lax.pmean(rz, da)
        return y, slot_counts, e_counts, dropped, aux, rz

    y, slot_counts, e_counts, dropped, aux, rz = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  P(None, None), P(None, None)),
        out_specs=(x_spec, P("model"), P(None), P(), P(), P()),
        axis_names=set(mesh.axis_names),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
      plan_slots, plan_cum)
    return y, {"slot_counts": slot_counts, "kept_counts": slot_counts,
               "dropped": dropped, "aux_loss": aux,
               "expert_counts": e_counts, "router_z": rz}


def moe_ffn_a2a(p, x, plan_slots, plan_cum, cfg: ArchConfig, mesh,
                token_offset=0):
    """Beyond-paper §Perf variant: full-DP activations (batch sharded over
    data x model) + true all-to-all expert parallelism.

    Each device owns T_loc tokens and spr expert slots.  Tokens are bucketed
    per destination EP rank, exchanged with ``lax.all_to_all`` over
    ``model``, FFN'd locally, and returned — per-device collective bytes are
    ~2 * T_loc * k * D * (m-1)/m, an order of magnitude below the TP-psum
    scheme whose all-reduce moves every token's full activation twice per
    layer regardless of routing sparsity."""
    from jax.sharding import PartitionSpec as P
    m_cfg = cfg.moe
    da = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    mdl = mesh.shape["model"]
    s_total = num_slots(cfg)
    spr = s_total // mdl
    t_global = x.shape[0]
    all_axes = da + ("model",)
    dpm = 1
    for a in all_axes:
        dpm *= mesh.shape[a]
    sharded = t_global % dpm == 0 and t_global >= dpm
    x_spec = P(all_axes, None) if sharded else P(None, None)

    def local_fn(xl, router_w, wg, wu, wd, ps, pc):
        t_loc, d = xl.shape
        base = token_offset
        if sharded:
            idx = jax.lax.axis_index(all_axes[0])
            for a in all_axes[1:]:
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
            base = token_offset + idx * t_loc
        slot, weight, probs, top_e, r_counts = route(router_w, xl, ps, pc,
                                                     cfg, base)
        col_of = (slot // spr).astype(jnp.int32)          # dest EP rank
        tk = t_loc * m_cfg.top_k
        flat_col = col_of.reshape(tk)
        flat_slot = slot.reshape(tk)
        flat_w = weight.reshape(tk)
        tok = jnp.repeat(jnp.arange(t_loc), m_cfg.top_k)

        # bucket per destination column (capacity-bounded, sort-based rank;
        # fused: the same rank/mask/scatter in one dispatch kernel)
        cap_s = max(4, int(tk * m_cfg.capacity_factor / mdl))
        if m_cfg.fused_dispatch:
            from repro.kernels.moe_dispatch import ops as _dops
            send_x3, rank2, keep2, _, _ = _dops.dispatch(
                xl, jnp.ones((t_loc, m_cfg.top_k), jnp.float32), col_of,
                jnp.ones((t_loc, m_cfg.top_k), jnp.int32), mdl, cap_s,
                "auto")
            pos = rank2.reshape(tk)
            keep = keep2.reshape(tk) != 0
            dest = jnp.where(keep, flat_col * cap_s + pos, mdl * cap_s)
        else:
            sort_idx = jnp.argsort(flat_col)
            sorted_col = flat_col[sort_idx]
            seg = jnp.searchsorted(sorted_col, jnp.arange(mdl))
            pos_sorted = jnp.arange(tk, dtype=jnp.int32) - seg[sorted_col]
            pos = jnp.zeros((tk,), jnp.int32).at[sort_idx].set(pos_sorted)
            keep = pos < cap_s
            dest = jnp.where(keep, flat_col * cap_s + pos, mdl * cap_s)
            send_x = jnp.zeros((mdl * cap_s + 1, d), xl.dtype).at[dest].set(
                xl[tok])
            send_x3 = send_x[:-1].reshape(mdl, cap_s, d)
        send_slot = jnp.full((mdl * cap_s + 1,), -1, jnp.int32).at[dest].set(
            jnp.where(keep, flat_slot, -1))
        # exchange: [m, C, D] -> every column receives my bucket for it
        rx = jax.lax.all_to_all(send_x3, "model", split_axis=0,
                                concat_axis=0, tiled=False)
        rs = jax.lax.all_to_all(send_slot[:-1].reshape(mdl, cap_s),
                                "model", split_axis=0, concat_axis=0,
                                tiled=False)
        rx = rx.reshape(mdl * cap_s, d)
        rs_flat = rs.reshape(mdl * cap_s)
        col = jax.lax.axis_index("model")
        local_slot = jnp.where(rs_flat >= 0, rs_flat - col * spr, 0)
        valid = (rs_flat >= 0)

        def expert_fn(buf):                                # [spr, C2, D]
            g = jax.nn.silu(jnp.einsum("scd,sdf->scf", buf,
                                       wg.astype(buf.dtype)))
            u = jnp.einsum("scd,sdf->scf", buf, wu.astype(buf.dtype))
            return jnp.einsum("scf,sfd->scd", g * u, wd.astype(buf.dtype))

        cap2 = max(4, int(mdl * cap_s * m_cfg.capacity_factor / spr))
        y_rx, met = dispatch_combine(rx, local_slot[:, None],
                                     valid[:, None].astype(jnp.float32),
                                     expert_fn, spr, cap2,
                                     valid=valid[:, None],
                                     fused=m_cfg.fused_dispatch)
        # return path + weighted combine at the source
        y_back = jax.lax.all_to_all(y_rx.reshape(mdl, cap_s, d), "model",
                                    split_axis=0, concat_axis=0, tiled=False)
        if m_cfg.fused_dispatch:
            y = _dops.combine(y_back, weight.astype(jnp.float32), col_of,
                              rank2, keep2, "auto")
            y = y.astype(xl.dtype)
        else:
            y_back = y_back.reshape(mdl * cap_s, d)
            gathered = y_back[jnp.where(keep, dest, 0)]
            y = jnp.zeros((t_loc, d), xl.dtype).at[tok].add(
                gathered * (flat_w * keep)[:, None].astype(xl.dtype))

        # metrics (global): slot counts live on the expert's column
        slot_counts = met["kept_counts"]
        if da:
            slot_counts = jax.lax.psum(slot_counts, da)
        e_counts = r_counts if r_counts is not None else jnp.zeros(
            (m_cfg.num_experts,), jnp.int32).at[top_e.reshape(-1)].add(1)
        e_counts = jax.lax.psum(e_counts, all_axes if sharded else da) \
            if (da or sharded) else e_counts
        dropped = (tk - keep.sum()) + met["dropped"]
        dropped = jax.lax.psum(dropped, all_axes) if sharded else dropped
        f = e_counts.astype(jnp.float32) / jnp.maximum(
            e_counts.sum().astype(jnp.float32), 1.0)
        pbar = probs.mean(0)
        pbar = jax.lax.pmean(pbar, all_axes) if sharded else pbar
        aux = m_cfg.num_experts * jnp.sum(f * pbar)
        rz = jnp.mean(jnp.square(jax.nn.logsumexp(
            jnp.log(probs + 1e-9), axis=-1)))
        rz = jax.lax.pmean(rz, all_axes) if sharded else rz
        return y, slot_counts, e_counts, dropped, aux, rz

    y, slot_counts, e_counts, dropped, aux, rz = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  P(None, None), P(None, None)),
        out_specs=(x_spec, P("model"), P(None), P(), P(), P()),
        axis_names=set(mesh.axis_names),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
      plan_slots, plan_cum)
    return y, {"slot_counts": slot_counts, "kept_counts": slot_counts,
               "dropped": dropped, "aux_loss": aux,
               "expert_counts": e_counts, "router_z": rz}


def moe_ffn(p, x, plan_slots, plan_cum, cfg: ArchConfig, token_offset=0,
            mesh=None, tokens_sharded=True, layout: str = "tp"):
    """Full MoE FFN.  p: dict(router, w_gate [S,D,F], w_up, w_down [S,F,D]).

    Returns (y [T,D], metrics).  metrics includes the Reshape load metric phi
    (per-slot token counts) and the aux load-balance loss.
    """
    if mesh is not None and layout == "dp":
        return moe_ffn_a2a(p, x, plan_slots, plan_cum, cfg, mesh,
                           token_offset)
    if mesh is not None:
        return moe_ffn_sharded(p, x, plan_slots, plan_cum, cfg, mesh,
                               token_offset, tokens_sharded)
    m = cfg.moe
    t = x.shape[0]
    slot, weight, probs, top_e, r_counts = route(
        p["router"], x, plan_slots, plan_cum, cfg, token_offset)
    cap = capacity(cfg, t)
    s = num_slots(cfg)

    def expert_fn(buf):                                    # [S,C,D]
        g = jax.nn.silu(jnp.einsum("scd,sdf->scf", buf,
                                   p["w_gate"].astype(buf.dtype)))
        u = jnp.einsum("scd,sdf->scf", buf, p["w_up"].astype(buf.dtype))
        return jnp.einsum("scf,sfd->scd", g * u, p["w_down"].astype(buf.dtype))

    y, metrics = dispatch_combine(x, slot, weight, expert_fn, s, cap,
                                  fused=m.fused_dispatch)

    # Switch-style load-balance aux loss over *logical* experts.  With fused
    # gating the histogram comes straight from the kernel.
    e_counts = r_counts.astype(jnp.float32) if r_counts is not None else \
        jnp.zeros((m.num_experts,), jnp.float32).at[
            top_e.reshape(-1)].add(1.0)
    f = e_counts / (t * m.top_k)
    pbar = probs.mean(0)
    metrics["aux_loss"] = m.num_experts * jnp.sum(f * pbar)
    metrics["expert_counts"] = e_counts.astype(jnp.int32)
    metrics["router_z"] = jnp.mean(
        jnp.square(jax.nn.logsumexp(jnp.log(probs + 1e-9), axis=-1)))
    return y, metrics
