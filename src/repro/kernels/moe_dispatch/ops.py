"""Dispatch wrappers + custom VJPs for the MoE rank/scatter/gather family.

Impl resolution mirrors ``moe_gating``: ``pallas`` on TPU, the vectorized
jnp implementation of the same algorithm (``ref.py``) elsewhere — Pallas
interpret mode stays available (``impl="interpret"``) for validating the
kernels themselves on CPU, but is a debugging mode, not a fast path.

Gradients: the routing decisions (rank, keep, counts, ``dest``) are integers
and carry no gradient; the differentiable dataflow is the weighted scatter
(dispatch) and the weighted gather (combine).  The two are transposes of
each other, so each one's VJP is the other re-applied at the same ``dest``:

* ``d scatter / d v``  = a gather of the buffer cotangent;
* ``d gather / d buf`` = a scatter of the output cotangent;
* ``d / d w`` (per-assignment weight) is a row-wise dot of the cotangent
  with the gathered counterpart rows.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.moe_dispatch.moe_dispatch import (gather_pallas,
                                                     rank_pallas,
                                                     scatter_pallas)
from repro.kernels.moe_dispatch.ref import gather_ref, rank_ref, scatter_ref
from repro.kernels.tiling import block_rows


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return impl


def _rank(slot, valid, n_slots, cap, impl):
    impl = _resolve(impl)
    if impl == "jnp":
        return rank_ref(slot, valid, n_slots, cap)
    return rank_pallas(slot, valid, n_slots, cap, block_rows(len(slot)),
                       interpret=(impl == "interpret"))


def _scatter_raw(v, w, dest, rows, impl):
    impl = _resolve(impl)
    if impl == "jnp":
        return scatter_ref(v, w, dest, rows)
    return scatter_pallas(v, w, dest, rows, block_rows(len(v)),
                          interpret=(impl == "interpret"))


def _gather_raw(buf, w, dest, impl):
    impl = _resolve(impl)
    if impl == "jnp":
        return gather_ref(buf, w, dest)
    return gather_pallas(buf, w, dest, block_rows(len(dest)),
                         interpret=(impl == "interpret"))


def _f0(a):
    """float0 cotangent for an integer primal."""
    return np.zeros(a.shape, jax.dtypes.float0)


def _row_dot(rows, other, dest):
    """dw[t, j] = <rows[t, j], other[t]> over kept assignments."""
    t, k = dest.shape
    rows = rows.reshape(t, k, -1).astype(jnp.float32)
    return (rows * other[:, None, :].astype(jnp.float32)).sum(-1) * (dest >= 0)


def _rows_at(buf, dest):
    return buf[jnp.maximum(dest, 0).reshape(-1)]


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def scatter(v, w, dest, rows, impl):
    """Bucketed scatter: v [T,D]; w [T,k]; dest [T,k] -> buf [rows, D]."""
    return _scatter_raw(v, w, dest, rows, impl)


def _scatter_fwd(v, w, dest, rows, impl):
    return _scatter_raw(v, w, dest, rows, impl), (v, w, dest)


def _scatter_bwd(rows, impl, res, g_buf):
    v, w, dest = res
    dv = _gather_raw(g_buf, w, dest, impl)
    dw = _row_dot(_rows_at(g_buf, dest), v, dest)
    return dv.astype(v.dtype), dw.astype(w.dtype), _f0(dest)


scatter.defvjp(_scatter_fwd, _scatter_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def gather(buf, w, dest, impl):
    """Weighted gather: buf [rows, D]; w [T,k]; dest [T,k] -> y [T, D]."""
    return _gather_raw(buf, w, dest, impl)


def _gather_fwd(buf, w, dest, impl):
    return _gather_raw(buf, w, dest, impl), (buf, w, dest)


def _gather_bwd(impl, res, g_y):
    buf, w, dest = res
    d_buf = _scatter_raw(g_y, w, dest, buf.shape[0], impl)
    dw = _row_dot(_rows_at(buf, dest), g_y, dest)
    return d_buf.astype(buf.dtype), dw.astype(w.dtype), _f0(dest)


gather.defvjp(_gather_fwd, _gather_bwd)


def _dest(slot, rank, keep, cap):
    return jnp.where(keep != 0, slot * cap + rank, -1)


def dispatch(v, w, slot, valid, n_slots, cap, impl):
    """Rank + capacity mask + bucketed scatter.  v [T,D]; w/slot/valid
    [T,k] -> (buf [S,C,D], rank [T,k], keep [T,k], routed [S], kept [S])."""
    rank, keep, routed, kept = _rank(slot, valid, n_slots, cap, impl)
    buf = scatter(v, w, _dest(slot, rank, keep, cap), n_slots * cap, impl)
    return buf.reshape(n_slots, cap, -1), rank, keep, routed, kept


def combine(buf, w, slot, rank, keep, impl):
    """Weighted gather of buf [S,C,D] back to token rows -> y [T,D]."""
    s, cap, d = buf.shape
    return gather(buf.reshape(s * cap, d), w, _dest(slot, rank, keep, cap),
                  impl)


def dispatch_combine(x, slot, weight, expert_fn, n_slots: int, cap: int,
                     valid=None, impl: str = "auto"):
    """Drop-in for ``models.moe.dispatch_combine`` on the kernel family.

    Returns (y [T,D], metrics) with bit-identical token-drop decisions and
    Reshape load metrics (slot_counts = routed phi, kept_counts, dropped)
    vs the XLA argsort/searchsorted/scatter path.
    """
    t, _ = x.shape
    k = slot.shape[1]
    valid_i = (jnp.ones((t, k), jnp.int32) if valid is None
               else valid.astype(jnp.int32))
    ones = jnp.ones((t, k), jnp.float32)
    buf, rank, keep, routed, kept = dispatch(x, ones, slot, valid_i,
                                             n_slots, cap, impl)
    out_buf = expert_fn(buf)
    y = combine(out_buf, weight.astype(jnp.float32), slot, rank, keep, impl)
    dropped = valid_i.sum() - keep.sum()
    return y.astype(x.dtype), {"slot_counts": routed, "kept_counts": kept,
                               "dropped": dropped}
