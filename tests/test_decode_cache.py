"""Where decode writes its K/V rows: after N ``lm.decode_step``s from
``init_cache``, each positional cache holds, at row ``pos`` (or ``pos %
window`` for a rolling window), the K/V projection of the token fed at
``pos``, computed independently by the full-sequence path, and every other
row is still zero: a decode step writes its own row and nothing else."""
import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import lm
from repro.models import moe as moe_lib
from repro.models.blocks import (BLOCKS, POSITIONAL_CACHE_TYPES,
                                 _project_qkv, _rope)
from repro.models.layers import rms_norm

# one smoke configuration per positional cache type, how many tokens to
# feed, and a layer pattern where the smoke one will not do: gemma3's local
# layers roll over a window of 8, so 11 tokens wrap it; zamba2's shared
# attention runs twice, on either side of a recurrent layer (its smoke
# pattern puts it behind five mamba layers, whose chunked forward and
# step-by-step decode drift apart by more than the rows' rounding)
CASES = {"attn": ("yi-34b", 5, None), "local": ("gemma3-1b", 11, None),
         "moe": ("olmoe-1b-7b", 5, None),
         "shared_attn": ("zamba2-7b", 5, ("shared_attn", "mamba",
                                          "shared_attn")),
         "dec": ("whisper-base", 5, None)}
SMAX = 16
# bf16 K/V rows, |k| < 4: the first layer reads the embeddings in both
# paths and matches exactly; deeper layers see residual streams that the
# full-sequence path and the decode path round differently, and may differ
# by up to eight bf16 ulps (2**-6 each in [2, 4)).  Another token's row
# differs by O(1).
TOL = 8 * 2 ** -6


def _cfg(arch, pattern):
    cfg = get_arch(arch + "-smoke")
    if pattern:
        cfg = dataclasses.replace(cfg, layer_pattern=pattern,
                                  num_layers=len(pattern))
    if cfg.moe is not None:
        # no capacity drops in the 1-token decode or the N-token forward
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    return cfg


def _expected_kv(cfg, params, tokens, enc_out):
    """{(type, index in the type's stack): (k, v) [B,N,KH,hd]} from the
    full-sequence forward, layer by layer."""
    b, s = tokens.shape
    x = params["embed"][tokens].astype(jnp.bfloat16)
    ctx = lm._make_ctx(cfg, b, s, {"tokens": tokens}, "jnp", 0)
    ctx["enc_out"] = enc_out
    plan = moe_lib.identity_plan(cfg, lm.n_moe_layers(cfg)) \
        if lm.n_moe_layers(cfg) else None
    seen, out = collections.Counter(), {}
    for t in cfg.pattern:
        i = seen[t]
        seen[t] += 1
        p = params[t] if t == "shared_attn" else \
            jax.tree.map(lambda a: a[i], params[t])
        ctx_l = ctx if t != "moe" else dict(
            ctx, plan_slots=plan.slots[i], plan_cum=plan.cum[i],
            moe_metrics=[])
        if t in POSITIONAL_CACHE_TYPES:
            _, k, v = _project_qkv(cfg, p, rms_norm(x, p["ln1"], cfg.norm_eps))
            out[t, i] = (np.asarray(_rope(cfg, k, ctx), np.float32),
                         np.asarray(v, np.float32))
        x = BLOCKS[t]["apply"](p, x, ctx_l)
    return out


@pytest.mark.parametrize("kind", sorted(CASES))
def test_decode_writes_only_its_rows(kind):
    arch, n, pattern = CASES[kind]
    cfg = _cfg(arch, pattern)
    assert kind in cfg.pattern
    params = lm.init(cfg, jax.random.PRNGKey(0))
    b = 2
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (b, n)), jnp.int32)
    state = lm.init_cache(cfg, b, SMAX)
    enc_out = None
    if cfg.enc_layers:
        frames = jnp.asarray(
            rng.standard_normal((b, cfg.enc_seq, cfg.d_model)) * 0.1,
            jnp.bfloat16)
        enc_out = lm.encode(params, frames, cfg)
        p = params["dec"]
        cross = {nm: jnp.einsum("bsd,ldq->lbsq", enc_out, p[w]).reshape(
            cfg.num_layers, b, cfg.enc_seq, cfg.n_kv_heads, cfg.hd).astype(
            jnp.bfloat16) for nm, w in (("ck", "cwk"), ("cv", "cwv"))}
        state["caches"]["dec"].update(cross)
    step = jax.jit(lambda st, tok: lm.decode_step(params, st, tok, cfg)[1])
    for j in range(n):
        state = step(state, tokens[:, j:j + 1])
    assert int(state["pos"]) == n

    want = _expected_kv(cfg, params, tokens, enc_out)
    assert {t for t, _ in want} == \
        {t for t in cfg.pattern if t in POSITIONAL_CACHE_TYPES}
    for (t, i), (k_want, v_want) in want.items():
        for got_all, exp in ((state["caches"][t]["k"], k_want),
                             (state["caches"][t]["v"], v_want)):
            got = np.asarray(got_all[i], np.float32)     # [B, S, KH, hd]
            rows = got.shape[1]
            # the newest token fed at each row (rolling: pos % rows)
            fed = {j % rows: j for j in range(n)}
            assert t != "local" or n > rows    # the window has wrapped
            tol = 0 if (t, i) == (cfg.pattern[0], 0) else TOL
            for r in range(rows):
                if r in fed:
                    np.testing.assert_allclose(
                        got[:, r], exp[:, fed[r]], atol=tol, rtol=0,
                        err_msg=f"{t}[{i}] row {r}")
                else:
                    assert not got[:, r].any(), f"{t}[{i}] row {r} written"
    if cfg.enc_layers:
        # the cross-attention K/V are read in place, never rewritten
        for nm, c in cross.items():
            assert np.array_equal(np.asarray(state["caches"]["dec"][nm]),
                                  np.asarray(c))
