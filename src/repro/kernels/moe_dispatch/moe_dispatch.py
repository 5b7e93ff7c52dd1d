"""Pallas TPU kernels: MoE routing rank, bucketed scatter (dispatch) and
weighted gather (combine).

The XLA baseline (``models.moe.dispatch_combine``) runs the rank/bucket
pipeline as ``argsort`` -> ``searchsorted`` -> masked scatter-add into the
``[slots, cap, D]`` buffer -> gather/combine.  Here it is three kernels:

* **rank** walks token blocks sequentially.  A running histogram of routed
  assignments per slot lives in the resident count output across grid
  steps; within a block, the number of earlier same-slot assignments is one
  MXU matmul of a strictly-lower-triangular ``[bt, bt]`` mask against the
  block's per-token slot multi-hot (Mosaic has no ``cumsum``).  For a
  *stable* sort this equals the baseline's sorted position within the slot
  segment, so drop decisions (``keep = valid & rank < cap``) and the
  Reshape load metrics (routed/kept counts per slot) are bit-identical.
* **scatter** writes the buffer, flattened to ``[slots*cap, D]`` rows.  The
  grid walks row tiles and, inside each, token blocks; VMEM holds one
  ``[R, D]`` row tile and one token block, never the whole buffer.  TPU has
  no fast vector scatter, so each step is a one-hot matmul: the block's
  weighted destination one-hot ``[bt, R]`` (transposed) against the
  ``[bt, D]`` activations.  Each kept assignment owns a unique
  ``(slot, rank)`` row, so every row has at most one writer (bit-exact).
* **gather** (combine) is the transpose: per token block, the weighted
  one-hot ``[bt, R]`` against each row tile, accumulated in f32 and rounded
  once.

Scatter and gather both take a per-assignment weight, which makes them each
other's VJP (see ``ops.py``).  Assignments are addressed by ``dest`` [T, k]:
the flat buffer row ``slot * cap + rank`` of a kept assignment, -1 for a
dropped or invalid one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import round_up

# buffer rows per scatter/gather tile: [256, D] bf16 is 1 MiB at D=2048,
# so tile + token block + f32 accumulator stay far below the 16 MiB scoped
# VMEM default of v5e
ROW_TILE = 256


def _rank_kernel(slot_ref, valid_ref, rank_ref, keep_ref, routed_ref,
                 kept_ref, *, k: int, s: int, cap: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        routed_ref[...] = jnp.zeros_like(routed_ref)
        kept_ref[...] = jnp.zeros_like(kept_ref)

    f32 = jnp.float32
    bt, sp = slot_ref.shape[0], routed_ref.shape[1]
    valid = valid_ref[...] != 0
    s_eff = jnp.where(valid, slot_ref[...], s)     # invalid -> virtual slot s
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (bt, sp), 1)
    ohs = [(s_eff[:, j:j + 1] == iota_s).astype(f32) for j in range(k)]
    multi = sum(ohs)                                         # [bt, sp]
    row = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    lower = (col < row).astype(jnp.bfloat16)
    # earlier same-slot assignments: previous blocks (the running histogram)
    # plus earlier tokens of this block; 0/1 and small counts are exact in
    # bf16 and the f32 accumulation is exact below 2**24
    before = routed_ref[...].astype(f32) + jnp.dot(
        lower, multi.astype(jnp.bfloat16), preferred_element_type=f32)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (bt, k), 1)
    rank = jnp.zeros((bt, k), f32)
    kept = jnp.zeros((bt, sp), f32)
    for j in range(k):
        r_j = (ohs[j] * before).sum(1, keepdims=True)          # [bt, 1]
        rank = jnp.where(iota_k == j, r_j, rank)
        kept = kept + ohs[j] * (r_j < cap).astype(f32)
        before = before + ohs[j]       # token-major order: column j precedes
    rank = rank.astype(jnp.int32)
    keep = valid & (rank < cap)
    # invalid ranks are meaningless (the virtual slot's base is not carried)
    rank_ref[...] = jnp.where(valid, rank, 0)
    keep_ref[...] = keep.astype(jnp.int32)
    routed_ref[...] += multi.sum(0, keepdims=True).astype(jnp.int32)
    kept_ref[...] += kept.sum(0, keepdims=True).astype(jnp.int32)


def rank_pallas(slot, valid, n_slots: int, cap: int, bt: int,
                interpret: bool = False):
    """slot/valid [T,k] i32 -> (rank [T,k], keep [T,k], routed [S],
    kept [S]), all i32."""
    t, k = slot.shape
    assert t % bt == 0, (t, bt)
    sp = round_up(n_slots + 1, 128)        # slots (+ virtual) on the lanes
    tok = pl.BlockSpec((bt, k), lambda i: (i, 0))
    hist = pl.BlockSpec((1, sp), lambda i: (0, 0))
    rank, keep, routed, kept = pl.pallas_call(
        functools.partial(_rank_kernel, k=k, s=n_slots, cap=cap),
        out_shape=(jax.ShapeDtypeStruct((t, k), jnp.int32),
                   jax.ShapeDtypeStruct((t, k), jnp.int32),
                   jax.ShapeDtypeStruct((1, sp), jnp.int32),
                   jax.ShapeDtypeStruct((1, sp), jnp.int32)),
        grid=(t // bt,),
        in_specs=[tok, tok],
        out_specs=(tok, tok, hist, hist),
        interpret=interpret,
    )(slot, valid)
    return rank, keep, routed[0, :n_slots], kept[0, :n_slots]


def _onehot(dest, w, base, rows: int, k: int):
    """Weighted destination one-hot [bt, rows] of buffer rows
    ``base .. base+rows``: entry (t, r) is w[t, j] where dest[t, j] hits."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (dest.shape[0], rows), 1) \
        + base
    hot = jnp.zeros((dest.shape[0], rows), jnp.float32)
    for j in range(k):
        hot = hot + jnp.where(dest[:, j:j + 1] == iota, w[:, j:j + 1], 0.0)
    return hot


def _precision(dtype):
    # f32 operands keep full precision on the MXU; bf16 ones are exact
    # products with f32 accumulation anyway
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _scatter_kernel(dest_ref, w_ref, v_ref, buf_ref, acc_ref, *, k: int):
    r, i = pl.program_id(0), pl.program_id(1)
    rows = buf_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    v = v_ref[...]
    hot = _onehot(dest_ref[...], w_ref[...], r * rows, rows, k)
    acc_ref[...] += jax.lax.dot_general(
        hot.astype(v.dtype), v, (((0,), (0,)), ((), ())),
        precision=_precision(v.dtype), preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(1) - 1)
    def _store():
        buf_ref[...] = acc_ref[...].astype(buf_ref.dtype)


def scatter_pallas(v, w, dest, rows: int, bt: int, interpret: bool = False):
    """v [T,D]; w [T,k] f32; dest [T,k] i32 -> buf [rows, D] with
    ``buf[dest[t,j]] = w[t,j] * v[t]`` (zeros where no assignment lands)."""
    t, d = v.shape
    k = dest.shape[1]
    assert t % bt == 0, (t, bt)
    tile = min(ROW_TILE, round_up(rows, 8))
    padded = round_up(rows, tile)          # pad rows nobody writes
    tok = pl.BlockSpec((bt, k), lambda r, i: (i, 0))
    buf = pl.pallas_call(
        functools.partial(_scatter_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((padded, d), v.dtype),
        grid=(padded // tile, t // bt),
        in_specs=[tok, tok, pl.BlockSpec((bt, d), lambda r, i: (i, 0))],
        out_specs=pl.BlockSpec((tile, d), lambda r, i: (r, 0)),
        scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)],
        interpret=interpret,
    )(dest, w, v)
    return buf if padded == rows else buf[:rows]


def _gather_kernel(dest_ref, w_ref, buf_ref, y_ref, acc_ref, *, k: int):
    r = pl.program_id(1)
    rows = buf_ref.shape[0]

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = buf_ref[...]
    hot = _onehot(dest_ref[...], w_ref[...], r * rows, rows, k)
    acc_ref[...] += jnp.dot(hot.astype(b.dtype), b,
                            precision=_precision(b.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(r == pl.num_programs(1) - 1)
    def _store():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def gather_pallas(buf, w, dest, bt: int, interpret: bool = False):
    """buf [rows, D]; w [T,k] f32; dest [T,k] i32 -> y [T,D] with
    ``y[t] = sum_j w[t,j] * buf[dest[t,j]]`` over dest >= 0."""
    rows, d = buf.shape
    t, k = dest.shape
    assert t % bt == 0, (t, bt)
    tile = min(ROW_TILE, round_up(rows, 8))
    padded = round_up(rows, tile)
    if padded != rows:
        buf = jnp.pad(buf, ((0, padded - rows), (0, 0)))
    tok = pl.BlockSpec((bt, k), lambda i, r: (i, 0))
    return pl.pallas_call(
        functools.partial(_gather_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((t, d), buf.dtype),
        grid=(t // bt, padded // tile),
        in_specs=[tok, tok, pl.BlockSpec((tile, d), lambda i, r: (r, 0))],
        out_specs=pl.BlockSpec((bt, d), lambda i, r: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interpret,
    )(dest, w, buf)
