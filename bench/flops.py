"""The operations a model needs, from its configuration's sizes.

Copied from the analytic convention of ``repro.analysis.flops`` (2 FLOPs per
multiply-add, 6 * N_active per trained token, 2 * N_active per served
token): the work the model requires, not what an implementation computes,
so capacity padding, spare expert slots and recomputation are not counted,
and the reading stays the same for any later kernel doing the same work.
Attention's score and value products are added per token over the context
that token attends to.
"""
from __future__ import annotations

from typing import Dict


def _dims(c: Dict):
    d, h, kh = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    return d, h, kh, hd


def active_params(c: Dict) -> int:
    """Weights one token multiplies by: per layer the attention
    projections, the router and top-k experts (or the dense MLP), the two
    norms; plus the final norm and the head (the embedding lookup is not
    a multiplication)."""
    d, h, kh, hd = _dims(c)
    attn = d * (h + 2 * kh) * hd + h * hd * d
    if c.get("num_experts"):
        ffn = d * c["num_experts"] + c["num_experts_per_tok"] * 3 * d * \
            c["moe_intermediate_size"]
    else:
        ffn = 3 * d * c["intermediate_size"]
    layer = attn + ffn + 2 * d
    return c["num_hidden_layers"] * layer + d + d * c["vocab_size"]


def attn_flops(c: Dict, ctx: float) -> float:
    """Forward score and value products of one query over ``ctx`` keys,
    all layers."""
    d, h, kh, hd = _dims(c)
    return c["num_hidden_layers"] * 2 * 2 * ctx * h * hd


def serve_flops(c: Dict, pos_lo: int, pos_hi: int) -> float:
    """Forward work of feeding positions [pos_lo, pos_hi) of one sequence:
    2 * N_active each, plus attention over the pos + 1 keys each sees."""
    n = max(pos_hi - pos_lo, 0)
    if n == 0:
        return 0.0
    keys = (pos_lo + pos_hi + 1) * n / 2.0          # sum of (pos + 1)
    return 2.0 * active_params(c) * n + attn_flops(c, 1.0) * keys


def train_flops(c: Dict, tokens: int, seq_len: int) -> Dict[str, float]:
    """Forward and backward work of ``tokens`` trained tokens in sequences of
    ``seq_len``: 6 * N_active each, plus three times the forward attention
    over the causal context (seq_len / 2 keys on average)."""
    dense = 6.0 * active_params(c) * tokens
    attn = 3.0 * attn_flops(c, seq_len / 2.0) * tokens
    return {"dense": dense, "attention": attn, "total": dense + attn}
