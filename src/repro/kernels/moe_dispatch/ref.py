"""Pure-jnp oracle for the MoE rank/scatter/gather kernel family.

Implements the algorithm the Pallas kernels run in vectorized jnp, so it
doubles as the fast off-TPU execution path:

* **in-segment rank without a sort** — the XLA baseline in
  ``models.moe.dispatch_combine`` ranks assignments inside their slot segment
  via stable ``argsort`` + ``searchsorted``; for a stable sort that rank is
  exactly "number of earlier assignments (in flat T*k order) with the same
  slot", i.e. an exclusive running histogram.  We compute it directly from an
  exclusive cumsum of the slot one-hot — bit-identical ranks, no sort.
* **capacity mask** — ``keep = valid & (rank < cap)``; identical drop
  decisions to the baseline by construction.
* **bucketed scatter / weighted gather** — each kept assignment owns a unique
  buffer row ``dest = slot * cap + rank``, so scatter-add is single-writer
  and the combine is a plain gather + per-token weighted reduction.

The Reshape load metrics (routed counts phi, kept counts) fall out of the
same one-hot, matching the baseline's metrics exactly.
"""
from __future__ import annotations

import jax.numpy as jnp


def rank_ref(slot, valid, n_slots: int, cap: int):
    """slot/valid [T,k] i32 -> (rank [T,k], keep [T,k], routed [S],
    kept [S]), all i32."""
    t, k = slot.shape
    n = t * k
    flat_valid = valid.reshape(n) != 0
    # invalid assignments rank in a virtual segment past n_slots-1, exactly
    # like the baseline's sort-to-the-end trick
    s_eff = jnp.where(flat_valid, slot.reshape(n), n_slots)
    oh = (s_eff[:, None] == jnp.arange(n_slots + 1)[None, :]).astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(oh, 0) - oh, s_eff[:, None],
                               1)[:, 0]
    keep = flat_valid & (rank < cap)
    rank = jnp.where(flat_valid, rank, 0)   # invalid ranks are meaningless
    routed = oh[:, :n_slots].sum(0)
    kept = (oh[:, :n_slots] * keep[:, None].astype(jnp.int32)).sum(0)
    return (rank.reshape(t, k).astype(jnp.int32),
            keep.reshape(t, k).astype(jnp.int32), routed, kept)


def scatter_ref(v, w, dest, rows: int):
    """v [T,D]; w [T,k] f32; dest [T,k] i32 -> buf [rows, D] with
    ``buf[dest[t,j]] = w[t,j] * v[t]`` (zeros where nothing lands)."""
    t, d = v.shape
    k = dest.shape[1]
    flat = dest.reshape(t * k)
    hit = flat >= 0
    tok = jnp.repeat(jnp.arange(t), k)
    wm = (w.reshape(t * k) * hit).astype(v.dtype)
    buf = jnp.zeros((rows + 1, d), v.dtype).at[
        jnp.where(hit, flat, rows)].add(v[tok] * wm[:, None])
    return buf[:-1]


def gather_ref(buf, w, dest):
    """buf [rows, D]; w [T,k] f32; dest [T,k] i32 -> y [T,D] with
    ``y[t] = sum_j w[t,j] * buf[dest[t,j]]`` over dest >= 0."""
    d = buf.shape[1]
    t, k = dest.shape
    flat = dest.reshape(t * k)
    hit = flat >= 0
    gathered = buf[jnp.where(hit, flat, 0)]
    wm = (w.reshape(t * k) * hit).astype(buf.dtype)
    tok = jnp.repeat(jnp.arange(t), k)
    return jnp.zeros((t, d), buf.dtype).at[tok].add(gathered * wm[:, None])
