"""Per-kernel validation: Pallas (interpret=True) + chunked-jnp vs the pure
sequential/naive oracle, swept over shapes and dtypes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

RNG = np.random.default_rng(0)


def randn(*s, dtype=np.float32, scale=1.0):
    return (RNG.standard_normal(s) * scale).astype(dtype)


# ------------------------------------------------------------ flash attention

ATTN_SHAPES = [(1, 2, 128, 64), (2, 3, 256, 64), (1, 1, 256, 128)]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(shape, causal, window, dtype):
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_pallas
    from repro.kernels.flash_attention.ref import attention_ref
    b, h, s, d = shape
    q, k, v = (randn(b, h, s, d).astype(dtype) for _ in range(3))
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 bq=128, bk=128, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_chunked_jnp_matches_ref():
    from repro.models.attention import chunked_attention
    from repro.kernels.flash_attention.ref import attention_ref
    b, s, h, kh, d = 2, 192, 4, 2, 32
    q = randn(b, s, h, d)
    k = randn(b, s, kh, d)
    v = randn(b, s, kh, d)
    out = chunked_attention(q, k, v, causal=True, kv_chunk=64)
    from repro.models.attention import repeat_kv
    kr = repeat_kv(jnp.asarray(k), 2).transpose(0, 2, 1, 3)
    vr = repeat_kv(jnp.asarray(v), 2).transpose(0, 2, 1, 3)
    ref = attention_ref(q.transpose(0, 2, 1, 3), kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(out.transpose(0, 2, 1, 3)),
                               np.asarray(ref), atol=2e-5, rtol=1e-3)


# -------------------------------------------------------------------- gating

@pytest.mark.parametrize("t,e,k", [(256, 16, 4), (512, 64, 8), (128, 8, 2)])
@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_gating_kernel(t, e, k, ties):
    from repro.kernels.moe_gating.moe_gating import gating_pallas
    from repro.kernels.moe_gating.ref import gating_ref
    logits = randn(t, e)
    if ties:                # coarse logits: equal values in most rows
        logits = np.round(logits * 2) / 2
    w1, e1, c1 = gating_pallas(logits, k, bt=128, interpret=True)
    w2, e2, c2 = gating_ref(logits, k)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_allclose(np.sort(np.asarray(w1), -1),
                               np.sort(np.asarray(w2), -1), atol=1e-5,
                               rtol=1e-4)
    # same experts per row, ties going to the lower index as in lax.top_k
    np.testing.assert_array_equal(np.sort(np.asarray(e1), -1),
                                  np.sort(np.asarray(e2), -1))


# ---------------------------------------------------------------- rwkv6 scan

@pytest.mark.parametrize("b,h,t,n,chunk", [(2, 2, 128, 32, 32),
                                           (1, 4, 64, 64, 16),
                                           (2, 1, 96, 16, 32)])
def test_rwkv6_chunked_and_pallas(b, h, t, n, chunk):
    from repro.kernels.rwkv6_scan.ref import rwkv6_ref
    from repro.kernels.rwkv6_scan.ops import rwkv6_chunked
    from repro.kernels.rwkv6_scan.rwkv6_scan import rwkv6_pallas
    r, k, v = (randn(b, h, t, n, scale=0.5) for _ in range(3))
    w = RNG.uniform(0.9, 0.999, (b, h, t, n)).astype(np.float32)
    u = randn(h, n, scale=0.1)
    s0 = randn(b, h, n, n, scale=0.1)
    y0, sT0 = rwkv6_ref(r, k, v, w, u, s0)
    y1, sT1 = rwkv6_chunked(r, k, v, w, u, s0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=2e-3,
                               rtol=2e-2)
    np.testing.assert_allclose(np.asarray(sT1), np.asarray(sT0), atol=2e-3,
                               rtol=2e-2)
    y2, sT2 = rwkv6_pallas(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y0), atol=2e-3,
                               rtol=2e-2)
    np.testing.assert_allclose(np.asarray(sT2), np.asarray(sT0), atol=2e-3,
                               rtol=2e-2)


def test_rwkv6_decode_step_matches_scan():
    from repro.kernels.rwkv6_scan.ref import rwkv6_ref
    from repro.kernels.rwkv6_scan.ops import rwkv6_decode_step
    b, h, t, n = 1, 2, 8, 16
    r, k, v = (randn(b, h, t, n, scale=0.5) for _ in range(3))
    w = RNG.uniform(0.9, 0.99, (b, h, t, n)).astype(np.float32)
    u = randn(h, n, scale=0.1)
    y_ref, _ = rwkv6_ref(r, k, v, w, u)
    s = jnp.zeros((b, h, n, n))
    ys = []
    for i in range(t):
        y, s = rwkv6_decode_step(r[:, :, i], k[:, :, i], v[:, :, i],
                                 w[:, :, i], jnp.asarray(u), s)
        ys.append(np.asarray(y))
    np.testing.assert_allclose(np.stack(ys, 2), np.asarray(y_ref), atol=1e-4,
                               rtol=1e-3)


# ---------------------------------------------------------------- mamba2 ssd

@pytest.mark.parametrize("b,h,t,p,n,chunk", [(2, 2, 128, 16, 8, 32),
                                             (1, 4, 64, 32, 16, 16)])
def test_mamba2_chunked_and_pallas(b, h, t, p, n, chunk):
    from repro.kernels.mamba2_ssd.ref import mamba2_ref
    from repro.kernels.mamba2_ssd.ops import mamba2_chunked
    from repro.kernels.mamba2_ssd.mamba2_ssd import mamba2_pallas
    x = randn(b, h, t, p)
    dt = RNG.uniform(0.01, 0.2, (b, h, t)).astype(np.float32)
    a = -RNG.uniform(0.5, 2.0, h).astype(np.float32)
    bm = randn(b, t, n)
    c = randn(b, t, n)
    d = randn(h, scale=0.1)
    h0 = randn(b, h, p, n, scale=0.1)
    y0, hT0 = mamba2_ref(x, dt, a, bm, c, d, h0)
    y1, hT1 = mamba2_chunked(x, dt, a, bm, c, d, h0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-3,
                               rtol=1e-2)
    y2, hT2 = mamba2_pallas(x, dt, a, bm, c, d, h0, chunk=chunk,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y0), atol=1e-3,
                               rtol=1e-2)
    np.testing.assert_allclose(np.asarray(hT2), np.asarray(hT0), atol=1e-3,
                               rtol=1e-2)


def test_mamba2_decode_matches_scan():
    from repro.kernels.mamba2_ssd.ref import mamba2_ref
    from repro.kernels.mamba2_ssd.ops import mamba2_decode_step
    b, h, t, p, n = 1, 2, 8, 8, 4
    x = randn(b, h, t, p)
    dt = RNG.uniform(0.01, 0.2, (b, h, t)).astype(np.float32)
    a = -RNG.uniform(0.5, 2.0, h).astype(np.float32)
    bm = randn(b, t, n)
    c = randn(b, t, n)
    d = randn(h, scale=0.1)
    y_ref, _ = mamba2_ref(x, dt, a, bm, c, d)
    hs = jnp.zeros((b, h, p, n))
    ys = []
    for i in range(t):
        y, hs = mamba2_decode_step(x[:, :, i], dt[:, :, i], jnp.asarray(a),
                                   bm[:, i], c[:, i], jnp.asarray(d), hs)
        ys.append(np.asarray(y))
    np.testing.assert_allclose(np.stack(ys, 2), np.asarray(y_ref), atol=1e-4,
                               rtol=1e-3)


# ------------------------------------------------------------ block helper

@pytest.mark.parametrize("t", [1, 7, 8, 37, 64, 150, 300, 512, 4096, 4097,
                               4104, 8 * 263])
def test_block_rows_is_mosaic_legal(t):
    """A multiple of 8 dividing t (the largest <= 256), or t itself when
    no multiple of 8 divides it."""
    from repro.kernels.tiling import block_rows
    bt = block_rows(t)
    assert t % bt == 0
    aligned = [d for d in range(8, min(256, t) + 1, 8) if t % d == 0]
    assert bt == (max(aligned) if aligned else t)
