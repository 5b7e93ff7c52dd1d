"""Dispatch wrapper for fused MoE gating."""
from __future__ import annotations

import jax

from repro.kernels.moe_gating.moe_gating import gating_pallas
from repro.kernels.moe_gating.ref import gating_ref
from repro.kernels.tiling import block_rows


def gating(logits, k: int, impl: str = "auto"):
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl == "jnp":
        return gating_ref(logits, k)
    return gating_pallas(logits, k, bt=block_rows(len(logits)),
                         interpret=(impl == "interpret"))
