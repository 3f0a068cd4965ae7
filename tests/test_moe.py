"""MoE: the held-experts layer (dropless, grouped matmul) against a dense
oracle, the chip-share identity, sigmoid routing with a selection bias and
the bias rule, and the grouped matmul itself."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import override
from repro.configs import get_smoke_config
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.grouped_matmul.ref import gmm_ref
from repro.models.layers import apply_mlp, tree_init
from repro.models.moe import (apply_moe, chunk_rows, moe_defs, route,
                              shared_defs)
from repro.train.steps import update_router_bias
from tests.util import run_py


def _setup(arch="qwen3-moe-235b-a22b", seed=0, **kw):
    cfg = override(get_smoke_config(arch), **kw)
    params = tree_init(jax.random.key(seed), moe_defs(cfg))
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 16, cfg.d_model)) * 0.5, jnp.float32)
    return cfg, params, x


def dense_oracle(cfg, p, x, bias=None):
    """Every held expert on every token, times its routing weight (zero
    where the token did not pick it)."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    _, top_i, w = route(cfg, p["router"], xf, bias)
    weight = jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
        top_i, cfg.num_experts, dtype=jnp.float32))
    weight = weight[:, cfg.experts_offset:cfg.experts_offset + cfg.held]
    g = jnp.einsum("td,edf->tef", xf, p["w_gate"])
    u = jnp.einsum("td,edf->tef", xf, p["w_up"])
    out_e = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, p["w_down"])
    return jnp.einsum("te,ted->td", weight, out_e).reshape(b, s, d)


def test_capacity_dispatch_matches_dense_when_ample():
    """There is no capacity any more: the grouped dispatch equals the dense
    oracle at every load."""
    cfg, params, x = _setup()
    y, aux, picks, rows = apply_moe(cfg, params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        dense_oracle(cfg, params, x)), atol=2e-5, rtol=2e-5)
    assert float(aux) > 0
    t = x.shape[0] * x.shape[1]
    assert float(picks.sum()) == t * cfg.experts_per_token == int(rows)


def test_low_capacity_drops_but_finite():
    """Routing skewed onto a few experts (the load that overflowed a
    capacity buffer) drops nothing: still the dense oracle."""
    cfg, params, x = _setup()
    x = jnp.abs(x)
    skew = jnp.zeros_like(params["router"]).at[:, :cfg.experts_per_token].set(1.0)
    params = {**params, "router": params["router"] + skew}
    y, _, picks, _ = apply_moe(cfg, params, x)
    assert float(picks[:cfg.experts_per_token].sum()) > 0.9 * float(picks.sum())
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        dense_oracle(cfg, params, x)), atol=2e-5, rtol=2e-5)


def test_moe_grads_flow():
    cfg, params, x = _setup()

    def loss(p):
        y, aux, _, _ = apply_moe(cfg, p, x)
        return jnp.sum(y.astype(jnp.float32) ** 2) + 0.01 * aux

    g = jax.grad(loss)(params)
    gsum = sum(float(jnp.abs(l.astype(jnp.float32)).sum())
               for l in jax.tree.leaves(g))
    assert np.isfinite(gsum) and gsum > 0
    # router must receive gradient (through combine weights + aux loss)
    assert float(jnp.abs(g["router"].astype(jnp.float32)).sum()) > 0


def _held_bias(cfg, push: float):
    """A selection bias that favours the held experts by `push` (10: every
    pick goes to them, when they are at least k)."""
    lo, hi = cfg.experts_offset, cfg.experts_offset + cfg.held
    small = np.random.default_rng(2).standard_normal(cfg.num_experts) * 5e-3
    return jnp.asarray(small, jnp.float32).at[lo:hi].add(push)


# (experts_held, experts_offset, push onto the held experts, chunks) with
# 32 experts, k 3 and 512 tokens: C = 512 of the 1536 picks unless every
# expert is held (C = T*k); a push of 10 sends all 1536 picks to 4 held
# experts, three chunks
HELD_CASES = {"one_chunk-offset0": (4, 0, 0.0, 1),
              "one_chunk-offset28": (4, 28, 0.0, 1),
              "overflow": (4, 12, 10.0, 3),
              "whole_layer": (0, 0, 0.0, 1)}


@pytest.mark.parametrize("case", list(HELD_CASES))
def test_held_experts_forward_and_grads_match_dense_oracle(case):
    """Sigmoid routing with a selection bias, 512 tokens over 32 experts:
    output and every gradient (x, the experts, the router) equal the dense
    oracle's, in one chunk of held picks, in three when the bias sends
    every pick to the held experts, and with every expert held (one chunk
    of every pick)."""
    held, offset, push, chunks = HELD_CASES[case]
    cfg, params, _ = _setup("moonlight-16b-a3b", num_experts=32,
                            experts_held=held, experts_offset=offset)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 256, cfg.d_model)) * 0.5, jnp.float32)
    bias = _held_bias(cfg, push)
    tk = x.shape[0] * x.shape[1] * cfg.experts_per_token
    c = chunk_rows(cfg, tk // cfg.experts_per_token)
    assert (c == tk) == (held == 0)
    rows = int(apply_moe(cfg, params, x, bias)[3])
    assert -(-rows // c) == chunks and (push == 0 or rows == tk)
    if chunks > 1:
        # an expert's weight gradient is then a sum of one partial a chunk,
        # each rounded to the weights' bf16 where the oracle rounds once:
        # compare the two in f32, where that rounding is gone
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    def loss(fn, p, x_):
        return jnp.sum(jnp.sin(fn(p, x_)))

    prog = lambda p, x_: apply_moe(cfg, p, x_, bias)[0]
    oracle = lambda p, x_: dense_oracle(cfg, p, x_, bias)
    np.testing.assert_allclose(np.asarray(prog(params, x)),
                               np.asarray(oracle(params, x)), atol=2e-5)
    gp = jax.grad(lambda p, x_: loss(prog, p, x_), argnums=(0, 1))(params, x)
    go = jax.grad(lambda p, x_: loss(oracle, p, x_), argnums=(0, 1))(params, x)
    assert float(jnp.abs(gp[0]["router"]).sum()) > 0
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(go)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)


@pytest.mark.parametrize("push,chunks", [(0.0, 1), (10.0, 3)],
                         ids=["one_chunk", "overflow"])
def test_held_chunks_metric_sums_chunks_over_layers(push, chunks):
    """`moe_held_chunks` is the sum over the MoE layers of ceil(held rows /
    C): one a layer under balanced routing, three when every pick is
    held."""
    from repro.models.model import Model
    cfg = override(get_smoke_config("moonlight-16b-a3b"), num_experts=32,
                   experts_held=4)
    model = Model(cfg, attn_impl="naive")
    params = model.init(jax.random.key(0))
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 257))
    batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
             "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
    bias = jnp.stack([_held_bias(cfg, push)] * cfg.stack_layers)
    _, mets = model.loss(params, batch, router_bias=bias)
    rows = model._forward(params, batch, router_bias=bias)[2][1]
    c = chunk_rows(cfg, 512)
    assert c == 512 and rows.shape == (cfg.stack_layers,)
    want = sum(-(-int(r) // c) for r in rows)
    assert float(mets["moe_held_chunks"]) == want == chunks * cfg.stack_layers
    assert float(mets["moe_held_rows"]) == float(rows.sum())


def test_trainer_sets_the_held_chunks_gauge_at_a_flush(tmp_path):
    """Every expert held at 128 tokens: one chunk of every pick a layer,
    so the step's row and the `moe.held_chunks` gauge read the MoE
    layers' count."""
    from repro.config.base import LMSConfig, MeshSpec, ShapeConfig, TrainConfig
    from repro.train.trainer import Trainer
    cfg = get_smoke_config("moonlight-16b-a3b")
    tr = Trainer(TrainConfig(
        model=cfg, shape=ShapeConfig("t", "train", 32, 4),
        mesh=MeshSpec((1, 1), ("data", "model")), lms=LMSConfig(enabled=True),
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=0,
        log_every=1), attn_impl="naive")
    _, hist = tr.train(steps=2)
    assert [r["moe_held_chunks"] for r in hist] == [cfg.stack_layers] * 2
    assert tr.obs.registry.gauge("moe.held_chunks").value == cfg.stack_layers
    assert tr.obs.registry.gauge("moe.held_rows").value == \
        hist[-1]["moe_held_rows"] == 128 * 3 * cfg.stack_layers


def test_chip_shares_add_up_to_the_uncut_layer():
    """EP over 4 chips of 2 experts each: the routed parts the 4 shares
    compute, plus the shared experts counted once, equal the whole layer."""
    cfg, params, x = _setup("moonlight-16b-a3b")
    shared = tree_init(jax.random.key(7), shared_defs(cfg))
    whole, *_ = apply_moe(cfg, params, x, shared=shared)
    held = 2
    parts = []
    for c in range(cfg.num_experts // held):
        sc = dataclasses.replace(cfg, experts_held=held,
                                 experts_offset=c * held)
        sp = {"router": params["router"],
              **{k: params[k][c * held:(c + 1) * held]
                 for k in ("w_gate", "w_up", "w_down")}}
        parts.append(apply_moe(sc, sp, x)[0])
    total = sum(parts) + apply_mlp(cfg, shared, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=3e-5, rtol=3e-5)


def test_sigmoid_routing_selects_by_bias_and_weighs_by_score():
    cfg, params, x = _setup("moonlight-16b-a3b")
    xf = x.reshape(-1, cfg.d_model)
    scores, top_i, w = route(cfg, params["router"], xf)
    assert float(scores.min()) > 0 and float(scores.max()) < 1  # sigmoid
    np.testing.assert_allclose(np.asarray(w.sum(-1)), cfg.router_scale,
                               rtol=1e-5)
    bias = jnp.full((cfg.num_experts,), -1.0).at[3].set(1.0)
    _, top_b, w_b = route(cfg, params["router"], xf, bias)
    assert bool((top_b == 3).any(-1).all())        # the bias forces expert 3
    picked = jnp.take_along_axis(scores, top_b, -1)
    np.testing.assert_allclose(np.asarray(w_b), np.asarray(
        picked / picked.sum(-1, keepdims=True) * cfg.router_scale), rtol=1e-5)


def test_bias_rule_over_two_steps():
    """b_i += gamma * sign(mean load - load_i): overloaded experts fall,
    underloaded rise, each step by gamma."""
    cfg = get_smoke_config("moonlight-16b-a3b")
    g = cfg.router_bias_rate
    bias = jnp.zeros((2, 4))
    picks = jnp.array([[8.0, 0.0, 4.0, 4.0], [1.0, 2.0, 4.0, 5.0]])
    bias = update_router_bias(cfg, bias, picks)
    np.testing.assert_allclose(np.asarray(bias),
                               [[-g, g, 0, 0], [g, g, -g, -g]], rtol=1e-6)
    bias = update_router_bias(cfg, bias, picks)
    np.testing.assert_allclose(np.asarray(bias),
                               [[-2 * g, 2 * g, 0, 0],
                                [2 * g, 2 * g, -2 * g, -2 * g]], rtol=1e-6)


@pytest.mark.parametrize("sizes", [(50, 0, 100, 106), (256, 0, 0, 0),
                                   (0, 0, 0, 256)])
def test_grouped_matmul_kernel_matches_oracle(sizes):
    """The megablox kernels (Pallas interpreter) against the masked-dense
    oracle, forward and both gradients; rows of the last, uncomputed group
    are zero."""
    r = np.random.default_rng(0)
    m, k, n, groups = 256, 256, 128, 3
    lhs = jnp.asarray(r.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(r.standard_normal((groups, k, n)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    kern = lambda a, b: grouped_matmul(a, b, gs, interpret=True)
    ref = lambda a, b: gmm_ref(a, b, gs)
    np.testing.assert_allclose(np.asarray(kern(lhs, rhs)),
                               np.asarray(ref(lhs, rhs)), atol=1e-3, rtol=1e-4)
    assert float(jnp.abs(ref(lhs, rhs)[sum(sizes[:3]):]).max(initial=0)) == 0
    grad = lambda f: jax.grad(lambda a, b: jnp.sum(jnp.sin(f(a, b))),
                              argnums=(0, 1))(lhs, rhs)
    for a, b in zip(grad(kern), grad(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3,
                                   rtol=1e-4)


_EXPERT_PARALLEL = r'''
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels.grouped_matmul import grouped_matmul, ops
from repro.kernels.grouped_matmul.ref import gmm_ref
seen = []
inner = ops.gmm_fwd
def spy(lhs, rhs, gs, **kw):
    seen.append(rhs.shape[0])
    return inner(lhs, rhs, gs, **kw)
ops.gmm_fwd = spy
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
r = np.random.default_rng(0)
m, k, n = 512, 256, 128
gs = jnp.asarray((60, 0, 130, 200, 122), jnp.int32)
lhs = jax.device_put(jnp.asarray(r.standard_normal((m, k)), jnp.float32),
                     NamedSharding(mesh, P()))
rhs = jax.device_put(jnp.asarray(r.standard_normal((4, k, n)), jnp.float32),
                     NamedSharding(mesh, P("model")))
def vg(f):
    return jax.jit(jax.value_and_grad(
        lambda a, b: jnp.sum(jnp.sin(f(a, b))), argnums=(0, 1)))
kern = lambda a, b: grouped_matmul(a, b, gs, expert_axes=("model",),
                                   interpret=True)
ref = lambda a, b: gmm_ref(a, b, gs)
want = vg(ref)(lhs, rhs)
with jax.set_mesh(mesh):
    got = vg(kern)(lhs, rhs)
# as under the data-parallel step: manual over data, model left auto
def ddl(f):
    return jax.shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                         axis_names={"data"}, check_vma=False)
with jax.set_mesh(mesh):
    got2 = vg(ddl(kern))(lhs, rhs)
print("seen", seen)
for g in (got, got2):
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-4)
print("ok")
'''


def test_grouped_matmul_expert_parallel_matches_oracle():
    """Expert stacks split over a 2-way model axis, as sharding.py places
    qwen3-moe's and grok's: each shard's kernel sees only its own experts,
    and the forward and both gradients match the oracle, at the top level
    and inside a shard_map manual over data (the data-parallel step); four
    emulated CPU devices, Pallas interpreter."""
    out = run_py(_EXPERT_PARALLEL, devices=4)
    assert "seen [2, 2]" in out and out.rstrip().endswith("ok")


_EXPERT_PARALLEL_MOE = r'''
import os
os.environ["REPRO_PALLAS_INTERPRET"] = "1"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.config.base import override
from repro.configs import get_smoke_config
from repro.kernels.grouped_matmul import ops
from repro.models.layers import tree_init
from repro.models.moe import apply_moe, chunk_rows, moe_defs
from repro.models.sharding import sharding_env
from tests.test_moe import dense_oracle
seen = []
inner = ops.gmm_fwd
def spy(lhs, rhs, gs, **kw):
    seen.append((lhs.shape[0], rhs.shape[0]))
    return inner(lhs, rhs, gs, **kw)
ops.gmm_fwd = spy
cfg = override(get_smoke_config("moonlight-16b-a3b"), num_experts=16,
               experts_held=4, experts_offset=8)
params = jax.tree.map(lambda a: a.astype(jnp.float32),
                      tree_init(jax.random.key(0), moe_defs(cfg)))
x = jnp.asarray(np.random.default_rng(1).standard_normal(
    (2, 256, cfg.d_model)) * 0.5, jnp.float32)
# every pick to the four held experts: 1536 rows, two chunks of 1024
bias = jnp.zeros(cfg.num_experts).at[8:12].set(10.0)
c = chunk_rows(cfg, 512)
assert c == 1024 and int(apply_moe(cfg, params, x, bias)[3]) == 1536
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
def vg(f):
    return jax.jit(jax.value_and_grad(
        lambda p, x_: jnp.sum(jnp.sin(f(p, x_))), argnums=(0, 1)))
want = vg(lambda p, x_: dense_oracle(cfg, p, x_, bias))(params, x)
placed = {k: jax.device_put(v, NamedSharding(
    mesh, P("model") if k.startswith("w_") else P())) for k, v in params.items()}
seen.clear()
with jax.set_mesh(mesh), sharding_env(mesh):
    got = vg(lambda p, x_: apply_moe(cfg, p, x_, bias)[0])(placed, x)
print("seen", sorted(set(seen)))
for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
print("ok")
'''


def test_held_experts_expert_parallel_match_dense_oracle():
    """The chunked layer with its four held experts split over a 2-way
    model axis (two a shard, megablox's group offset), every pick held so
    that two chunks run: the loss and every gradient match the dense
    oracle; four emulated CPU devices, Pallas interpreter."""
    out = run_py(_EXPERT_PARALLEL_MOE, devices=4)
    assert "seen [(1024, 2)]" in out and out.rstrip().endswith("ok")
