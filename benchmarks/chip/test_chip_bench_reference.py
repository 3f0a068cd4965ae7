"""CPU tests of the plain reference against the program at smoke size:
the same seed gives the same weights, and the loss and every gradient
agree to the program's bfloat16 rounding."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from repro.configs import get_smoke_config
from repro.models.model import Model

ref_mod = harness.reference_module("dense_decoder")
KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size", "norm_type", "norm_eps", "mlp_act", "use_bias",
        "qkv_bias", "rope_theta", "tie_embeddings")
SEED = 2 ** 31 + 77


def _case(arch):
    cfg = get_smoke_config(arch)
    m = {k: getattr(cfg, k) for k in KEYS}
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], (4, 65))
    batch = {"tokens": toks[:, :-1].astype(np.int32),
             "labels": toks[:, 1:].astype(np.int32)}
    return cfg, m, batch


@pytest.mark.parametrize("arch", ["olmo-1b", "starcoder2-7b"])
def test_reference_draws_the_programs_weights_from_the_seed(arch):
    cfg, m, _ = _case(arch)
    program = jax.tree_util.tree_flatten_with_path(
        jax.jit(Model(cfg).init)(jax.random.key(SEED)))[0]
    ref = ref_mod.init_params(m, SEED)
    assert len(program) == len(ref)
    for (path, leaf), (name, arr) in zip(program, ref.items()):
        assert harness._leaf_name(path)[0].replace("{}.", "") == name
        np.testing.assert_allclose(np.asarray(leaf, np.float32),
                                   np.asarray(arr), rtol=0, atol=1e-7)


@pytest.mark.parametrize("arch", ["olmo-1b", "starcoder2-7b"])
def test_reference_loss_and_gradients_match_the_program(arch):
    cfg, m, batch = _case(arch)
    model = Model(dataclasses.replace(cfg), attn_impl="naive")
    params = jax.jit(model.init)(jax.random.key(SEED))
    (loss, _), grads = jax.value_and_grad(
        lambda p: model.loss(p, batch, no_remat=True), has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        ref = ref_mod.Reference(m, {}, SEED)
        ref_loss, ref_grads, _ = ref.grads(ref_mod.init_params(m, SEED),
                                           batch)
    assert abs(float(loss) - ref_loss) < 2e-4 * ref_loss
    names = list(ref_mod.init_params(m, SEED))
    for name, g in zip(names, jax.tree.leaves(grads)):
        r = np.asarray(jnp.stack(ref_grads[name])
                       if isinstance(ref_grads[name], list) else ref_grads[name])
        rel = np.linalg.norm(np.asarray(g, np.float32) - r) / \
            max(np.linalg.norm(r), 1e-12)
        assert rel < 3e-2, (name, rel)
