"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
output shapes + finite values; prefill/decode consistency with the full
forward pass."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import Model

B, S = 2, 16


def make_batch(cfg):
    if cfg.family == "vlm":
        return {"embeds": jnp.asarray(
            np.random.default_rng(0).standard_normal((B, S, cfg.d_model)) * 0.1,
            jnp.bfloat16),
            "positions3": jnp.tile(jnp.arange(S)[None, None], (3, B, 1)),
            "labels": jnp.ones((B, S), jnp.int32)}
    if cfg.family == "audio":
        return {"enc_embeds": jnp.asarray(
            np.random.default_rng(0).standard_normal(
                (B, cfg.encoder_seq, cfg.d_model)) * 0.1, jnp.bfloat16),
            "tokens": jnp.ones((B, S), jnp.int32),
            "labels": jnp.ones((B, S), jnp.int32)}
    return {"tokens": jnp.ones((B, S), jnp.int32),
            "labels": jnp.ones((B, S), jnp.int32)}


@pytest.mark.parametrize("arch", sorted(ARCH_IDS))
def test_train_step_smoke(arch):
    cfg = get_smoke_config(arch)
    model = Model(cfg, attn_impl="naive")
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg)
    logits, aux = model.forward(params, batch, no_remat=True)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    loss, metrics = model.loss(params, batch)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    gsum = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
               for g in jax.tree.leaves(grads))
    assert np.isfinite(gsum) and gsum > 0


@pytest.mark.parametrize("arch", sorted(ARCH_IDS))
def test_remat_matches_no_remat(arch):
    cfg = get_smoke_config(arch)
    model = Model(cfg, attn_impl="naive")
    params = model.init(jax.random.key(1))
    batch = make_batch(cfg)
    l1, _ = model.loss(params, batch, no_remat=True)
    l2, _ = model.loss(params, batch, no_remat=False)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-2)


@pytest.mark.parametrize("arch", ["olmo-1b", "mamba2-1.3b",
                                  "recurrentgemma-9b", "whisper-tiny",
                                  "qwen2.5-14b"])
def test_prefill_decode_consistency(arch):
    """prefill(S tokens) then decode(token S) must match forward(S+1)."""
    cfg = get_smoke_config(arch)
    model = Model(cfg, attn_impl="naive")
    params = model.init(jax.random.key(2))
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + 1)), jnp.int32)
    if cfg.family == "audio":
        enc = jnp.asarray(rng.standard_normal(
            (B, cfg.encoder_seq, cfg.d_model)) * 0.1, jnp.bfloat16)
        full_batch = {"enc_embeds": enc, "tokens": toks,
                      "labels": jnp.zeros_like(toks)}
        pre_batch = {"enc_embeds": enc, "tokens": toks[:, :S]}
        dec_batch = {"tokens": toks[:, S:S + 1]}
    else:
        full_batch = {"tokens": toks, "labels": jnp.zeros_like(toks)}
        pre_batch = {"tokens": toks[:, :S]}
        dec_batch = {"tokens": toks[:, S:S + 1]}
    logits_full, _ = model.forward(params, full_batch, no_remat=True)
    _, cache = model.prefill(params, pre_batch, cache_len=S + 4)
    logits_dec, _ = model.decode_step(params, cache, dec_batch, jnp.int32(S))
    np.testing.assert_allclose(
        np.asarray(logits_dec, np.float32),
        np.asarray(logits_full[:, S], np.float32), atol=0.15, rtol=0.1)


def test_ring_cache_local_attention():
    """RecurrentGemma window cache: decoding past the window must match the
    full forward (window masks older tokens anyway)."""
    cfg = get_smoke_config("recurrentgemma-9b")  # window 16
    model = Model(cfg, attn_impl="naive")
    params = model.init(jax.random.key(4))
    rng = np.random.default_rng(5)
    total = 24  # > window
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, total + 1)), jnp.int32)
    full_batch = {"tokens": toks, "labels": jnp.zeros_like(toks)}
    logits_full, _ = model.forward(params, full_batch, no_remat=True)
    _, cache = model.prefill(params, {"tokens": toks[:, :S]}, cache_len=total + 4)
    logits = None
    for t in range(S, total + 1):
        logits, cache = model.decode_step(
            params, cache, {"tokens": toks[:, t:t + 1]}, jnp.int32(t))
    np.testing.assert_allclose(
        np.asarray(logits, np.float32),
        np.asarray(logits_full[:, total], np.float32), atol=0.15, rtol=0.1)


def test_latent_attention_models_refuse_serving():
    """MLA trains only: prefill, decode and the serve engine refuse it with
    a clear error instead of building a cache it does not have."""
    from repro.serve.engine import ServeEngine
    cfg = get_smoke_config("moonlight-16b-a3b")
    model = Model(cfg, attn_impl="naive")
    params = model.init(jax.random.key(0))
    toks = jnp.ones((B, S), jnp.int32)
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        model.prefill(params, {"tokens": toks})
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        model.decode_step(params, {}, {"tokens": toks[:, :1]}, jnp.int32(0))
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        ServeEngine(model, None, slots=2, max_len=32, params=params)
