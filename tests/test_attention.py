"""Attention implementations agree: blockwise (flash-style jnp) == naive,
local block attention == naive windowed, decode == last row of naive, and
the decode edges the serve engine leans on (kv_len=0 slots, scalar-vs-
vector kv_len, ring caches, int8 scales, q_offset threading)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests.util import given, settings, st

from repro.models.attention import (attention, blockwise_attention,
                                    decode_attention,
                                    dense_decode_attention,
                                    local_block_attention, naive_attention)


def _qkv(b, sq, skv, h, kh, d, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, skv, kh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, skv, kh, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("case", [
    (2, 64, 64, 4, 2, 32, True, 0, 16),
    (1, 100, 100, 4, 4, 16, True, 0, 32),
    (1, 64, 64, 8, 2, 32, True, 24, 16),
    (2, 48, 48, 2, 1, 64, True, 0, 48),
])
def test_blockwise_vs_naive(case):
    b, sq, skv, h, kh, d, causal, window, chunk = case
    q, k, v = _qkv(b, sq, skv, h, kh, d, seed=hash(case) % 2**31)
    out = blockwise_attention(q, k, v, causal=causal, window=window, chunk=chunk)
    ref = naive_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dv,chunk", [(16, 16), (48, 32), (24, 64)])
def test_blockwise_v_width_differs_from_qk(dv, chunk, monkeypatch):
    """Latent attention's v is narrower than q/k (128 against 192): the
    KV-chunk scan and the causal query blocks (taken once the probabilities
    outgrow the scan's limit) both match the naive oracle, forward and
    gradients."""
    import repro.models.attention as attn_mod
    q, k, _ = _qkv(2, 64, 64, 4, 2, 32, seed=dv)
    v = jnp.asarray(np.random.default_rng(dv + 1).standard_normal(
        (2, 64, 2, dv)), jnp.float32)
    ref = naive_attention(q, k, v, causal=True)
    assert ref.shape == (2, 64, 4, dv)
    grad = lambda f: jax.grad(lambda q_, k_, v_: jnp.sum(jnp.sin(
        f(q_, k_, v_))), argnums=(0, 1, 2))(q, k, v)
    want = grad(lambda *a: naive_attention(*a, causal=True))
    for limit in (attn_mod.KV_SCAN_MAX_SCORE_BYTES, 0):
        monkeypatch.setattr(attn_mod, "KV_SCAN_MAX_SCORE_BYTES", limit)
        out = blockwise_attention(q, k, v, causal=True, chunk=chunk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)
        for a, b in zip(grad(lambda *a: blockwise_attention(
                *a, causal=True, chunk=chunk)), want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3), st.integers(2, 6), st.integers(1, 2),
       st.sampled_from([8, 16]), st.integers(0, 2**31 - 1))
def test_blockwise_property(b, sq8, gq, d, seed):
    """hypothesis sweep: blockwise == naive for random shapes/chunks."""
    sq = sq8 * 8
    kh = 2
    h = kh * gq
    q, k, v = _qkv(b, sq, sq, h, kh, d, seed=seed)
    chunk = 8
    out = blockwise_attention(q, k, v, causal=True, chunk=chunk)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("window,s", [(16, 64), (8, 100), (32, 32), (16, 40)])
def test_local_block_vs_naive(window, s):
    q, k, v = _qkv(1, s, s, 4, 2, 16, seed=window * s)
    out = local_block_attention(q, k, v, window=window)
    ref = naive_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_decode_vs_naive():
    b, s, h, kh, d = 2, 32, 4, 2, 16
    q, k, v = _qkv(b, s, s, h, kh, d, seed=7)
    full = naive_attention(q, k, v, causal=True)
    # decode for the last position given the full cache
    out = decode_attention(q[:, -1:], k, v, kv_len=s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, -1:]),
                               atol=3e-5, rtol=3e-5)


def test_decode_kv_len_masking():
    b, s, h, kh, d = 1, 16, 2, 2, 8
    q, k, v = _qkv(b, s, s, h, kh, d, seed=9)
    out8 = decode_attention(q[:, :1], k, v, kv_len=8)
    ref = naive_attention(q[:, :1], k[:, :8], v[:, :8], causal=False)
    np.testing.assert_allclose(np.asarray(out8), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_decode_scalar_vs_vector_kv_len():
    """A [B] kv_len vector with equal entries is byte-identical to the
    scalar broadcast (the slot-batched decode's contract)."""
    b, s, h, kh, d = 3, 32, 4, 2, 16
    q, k, v = _qkv(b, s, s, h, kh, d, seed=11)
    out_s = decode_attention(q[:, -1:], k, v, kv_len=20)
    out_v = decode_attention(q[:, -1:], k, v,
                             kv_len=jnp.full((b,), 20, jnp.int32))
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_v))


def test_decode_per_slot_kv_len_rows_independent():
    """Each row of a kv_len vector matches a B=1 decode at that length —
    the row-independence the engine's join/evict churn relies on."""
    b, s, h, kh, d = 4, 24, 4, 2, 16
    q, k, v = _qkv(b, s, s, h, kh, d, seed=12)
    lens = [1, 7, 16, 24]
    out = decode_attention(q[:, -1:], k, v,
                           kv_len=jnp.asarray(lens, jnp.int32))
    for i, L in enumerate(lens):
        ref = decode_attention(q[i:i + 1, -1:], k[i:i + 1], v[i:i + 1],
                               kv_len=L)
        np.testing.assert_allclose(np.asarray(out[i:i + 1]), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)


def test_decode_empty_slot_is_finite_zero():
    """kv_len=0 rows (inactive serve slots): exact zeros, never NaN — one
    contract for the dense path and the flash kernel."""
    b, s, h, kh, d = 2, 16, 2, 2, 8
    q, k, v = _qkv(b, s, s, h, kh, d, seed=13)
    out = decode_attention(q[:, :1], k, v,
                           kv_len=jnp.asarray([0, 9], jnp.int32))
    assert np.isfinite(np.asarray(out)).all()
    assert np.all(np.asarray(out[0]) == 0.0)
    ref = decode_attention(q[1:2, :1], k[1:2], v[1:2], kv_len=9)
    np.testing.assert_allclose(np.asarray(out[1:2]), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_decode_ring_cache_recency():
    """Window/ring caches: once the ring is full every slot is valid
    (kv_len=Smax) and the output matches attention over the ring content —
    positional recency is expressed by the ring write, not the mask."""
    b, w, h, kh, d = 1, 8, 2, 2, 8
    rng = np.random.default_rng(14)
    # a ring holding positions [pos-w+1 .. pos], rotated so slot i holds
    # position (pos - w + 1 + ((i - pos - 1) % w))... simpler: fill slots
    # by writing pos % w like the decode path does
    ks = jnp.asarray(rng.standard_normal((b, w, kh, d)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((b, w, kh, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    out = decode_attention(q, ks, vs, kv_len=w)
    # all w slots valid; order does not matter to softmax attention
    perm = np.roll(np.arange(w), 3)
    out_rot = decode_attention(q, ks[:, perm], vs[:, perm], kv_len=w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_rot),
                               atol=3e-5, rtol=3e-5)
    # partially-filled ring: only the first kv_len slots count
    out_p = decode_attention(q, ks, vs, kv_len=5)
    ref_p = naive_attention(q, ks[:, :5], vs[:, :5], causal=False)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(ref_p),
                               atol=3e-5, rtol=3e-5)


def test_decode_int8_scales_dense():
    """Dense path with int8 codes + per-row scales == dense over the
    dequantized cache (bit-for-bit the same multiplies)."""
    from repro.models.kvquant import dequantize_kv_leaf, quantize_kv_leaf
    b, s, h, kh, d = 2, 32, 4, 2, 16
    q, k, v = _qkv(b, s, s, h, kh, d, seed=15)
    k8, ks = quantize_kv_leaf(k)
    v8, vs = quantize_kv_leaf(v)
    kvl = jnp.asarray([10, 32], jnp.int32)
    out = dense_decode_attention(q[:, -1:], k8, v8, kvl,
                                 k_scale=ks, v_scale=vs)
    ref = dense_decode_attention(q[:, -1:], dequantize_kv_leaf(k8, ks),
                                 dequantize_kv_leaf(v8, vs), kvl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # quantization error bounded vs the f32 cache
    f32 = dense_decode_attention(q[:, -1:], k, v, kvl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f32),
                               atol=0.05, rtol=0.05)


@pytest.mark.parametrize("q_offset", [0, 4, 12])
def test_attention_pallas_q_offset(q_offset):
    """Regression: attention(impl="pallas") used to silently drop q_offset.
    All three impls must agree on a partial-cache call (chunked prefill
    shape: queries at absolute positions [q_offset, q_offset+Sq))."""
    b, sq, skv, h, kh, d = 1, 8, 32, 4, 2, 16
    q, k, v = _qkv(b, sq, skv, h, kh, d, seed=16 + q_offset)
    ref = naive_attention(q, k, v, causal=True, q_offset=q_offset)
    for impl in ("blockwise", "pallas"):
        out = attention(q, k, v, causal=True, impl=impl, q_offset=q_offset)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5,
            err_msg=f"impl={impl} q_offset={q_offset}")
