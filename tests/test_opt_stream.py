"""Streamed optimizer sweep + gradient host sink — the EXECUTED half of
`residency["optimizer"] / ["grads"] == "host"`.

The headline contract: the per-layer streamed optimizer sweep must be
numerically BYTE-IDENTICAL to the resident monolithic update (the shared
per-slice kernels in optim/adamw.py are elementwise, and elementwise math is
slicing-invariant; on a single memory space every swap op is the identity).
Plus the new planner invariant: a plan may not report `fits` for a residency
class no executor stream exists for."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.util import run_py

from repro.config.base import (DDLConfig, LMSConfig, MeshSpec, SHAPES,
                               SINGLE_POD, ShapeConfig, TrainConfig)
from repro.configs import get_config, get_smoke_config
from repro.core.lms.planner import (MemoryPlan, check_schedule_invariant,
                                    make_swap_schedule, plan_memory)
from repro.launch.mesh import make_mesh
from repro.models import Model


def _plan(cfg, residency, depth=2):
    sched = make_swap_schedule(residency, cfg.num_layers, "train",
                               prefetch_depth=depth)
    return MemoryPlan({}, dict(residency), 1, 1, 1, 1, True,
                      swap_schedule=sched)


def _batch(cfg, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}


def _run_steps(model, tcfg, mesh, plan, batch, steps=3):
    from repro.train.steps import build_train_step, init_train_state
    fn, ssh, bsh = build_train_step(model, tcfg, mesh, plan=plan,
                                    donate=False)
    state = jax.device_put(init_train_state(model, tcfg, jax.random.key(1)),
                           ssh)
    b = jax.device_put(batch, bsh)
    ms = []
    for _ in range(steps):
        state, m = fn(state, b)
        ms.append(m)
    return ms, state


# ---------------------------------------------------------------------------
# Exact streamed-vs-resident parity (single device; adamw + sgdm). The
# sweep is a pipeline over layers: a 1-layer stack is its first unit and
# last write-back alone, an odd layer count leaves nothing to pair; params
# on host add the param write-back (and sgdm's param swap-in) to it.
# ---------------------------------------------------------------------------

PARITY_CASES = [
    pytest.param("adamw", 1, 2, "device", id="1-adamw"),
    pytest.param("sgdm", 1, 2, "device", id="1-sgdm"),
    pytest.param("adamw", 2, 2, "device", id="2-adamw"),
    pytest.param("sgdm", 2, 2, "device", id="2-sgdm"),
    pytest.param("adamw", 1, 1, "device", id="1-adamw-one_layer"),
    pytest.param("sgdm", 1, 3, "device", id="1-sgdm-odd_layers"),
    pytest.param("adamw", 1, 3, "host", id="1-adamw-odd_layers-params_host"),
    pytest.param("sgdm", 1, 1, "host", id="1-sgdm-one_layer-params_host"),
    pytest.param("sgdm", 2, 2, "host", id="2-sgdm-params_host"),
]


@pytest.mark.parametrize("optimizer,microbatches,layers,params", PARITY_CASES)
def test_streamed_opt_exactly_matches_resident(optimizer, microbatches,
                                               layers, params):
    cfg = dataclasses.replace(get_smoke_config("olmo-1b"), num_layers=layers)
    model = Model(cfg, attn_impl="naive")
    mesh_spec = MeshSpec((1, 1), ("data", "model"))
    mesh = make_mesh(mesh_spec)
    tcfg = TrainConfig(model=cfg, shape=ShapeConfig("smoke", "train", 16, 2),
                       mesh=mesh_spec, ddl=DDLConfig(mode="allreduce"),
                       warmup_steps=1, learning_rate=1e-2, total_steps=10,
                       optimizer=optimizer, microbatches=microbatches)
    # streamed params at depth 1: a deeper prefetch regroups the layer
    # scan, which is not bitwise the resident scan
    plan = _plan(cfg, {"params": params, "grads": "device",
                       "optimizer": "host", "kvcache": "device"},
                 depth=1 if params == "host" else 2)
    assert plan.swap_schedule.streams_optimizer
    assert plan.swap_schedule.streams_params == (params == "host")
    batch = _batch(cfg)
    ms_res, st_res = _run_steps(model, tcfg, mesh, None, batch)
    ms_str, st_str = _run_steps(model, tcfg, mesh, plan, batch)
    for a, b in zip(ms_res, ms_str):
        assert float(a["loss"]) == float(b["loss"])
        assert float(a["grad_norm"]) == float(b["grad_norm"])
    for x, y in zip(jax.tree.leaves(st_res), jax.tree.leaves(st_str)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_full_residency_streamed_step_exact_single_device():
    """params+grads+optimizer all host at prefetch depth 1 (the structure-
    preserving depth): the whole residency map executes and the trajectory
    is bitwise the resident one. dp=1 forces overlap off, so this also
    exercises the post-hoc grads-host placement fallback."""
    cfg = get_smoke_config("olmo-1b")
    model = Model(cfg, attn_impl="naive")
    mesh_spec = MeshSpec((1, 1), ("data", "model"))
    mesh = make_mesh(mesh_spec)
    tcfg = TrainConfig(model=cfg, shape=ShapeConfig("smoke", "train", 16, 2),
                       mesh=mesh_spec, ddl=DDLConfig(mode="allreduce"),
                       warmup_steps=1, learning_rate=1e-2, total_steps=10)
    plan = _plan(cfg, {"params": "host", "grads": "host",
                       "optimizer": "host", "kvcache": "device"}, depth=1)
    assert plan.swap_schedule.streams_params
    assert plan.swap_schedule.streams_grads
    batch = _batch(cfg)
    ms_res, st_res = _run_steps(model, tcfg, mesh, None, batch)
    ms_str, st_str = _run_steps(model, tcfg, mesh, plan, batch)
    for a, b in zip(ms_res, ms_str):
        assert float(a["loss"]) == float(b["loss"])
    for x, y in zip(jax.tree.leaves(st_res), jax.tree.leaves(st_str)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


@pytest.mark.parametrize("rows,optimizer,params_host", [
    pytest.param(4096, "adamw", False, id="4096"),
    pytest.param(4099, "adamw", False, id="4099"),
    pytest.param(1, "adamw", False, id="1-one_block"),
    pytest.param(2, "sgdm", False, id="2-two_blocks-sgdm"),
    pytest.param(4099, "sgdm", True, id="4099-sgdm-params_host"),
])
def test_streamed_rest_chunking_exact(rows, optimizer, params_host):
    """Remainder leaves >= 1M elements take the row-block path (the fp32
    embedding state must not land in HBM whole); blocks are slices around
    the same elementwise kernel, so it stays exact. 4099 rows leave a
    3-row tail after 16 blocks (an odd vocab): the tail block is the
    pipeline's last unit. One row is one block (the one-shot path); two
    rows are two blocks, the pipeline's first and last units alone."""
    from repro.optim.adamw import (OPTIMIZERS, clip_by_global_norm,
                                   clip_scale, global_norm)
    from repro.train.steps import _streamed_opt_update
    cfg = get_smoke_config("olmo-1b")          # stack_plan: one 2-layer scan
    rng = np.random.default_rng(0)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    params = {"embed": {"w": f32(rows, max(256, (1 << 20) // rows))},
              "decoder": {"stack0": {"w": f32(cfg.num_layers, 8, 8)}},
              "final_norm": {"scale": f32(8)}}
    grads = jax.tree.map(lambda p: f32(*p.shape), params)
    init, update = OPTIMIZERS[optimizer]
    state = init(params)
    kw = dict(lr=0.1, beta1=0.9, beta2=0.95, weight_decay=0.1)

    # jit both legs, as the step builder does — eager op-by-op dispatch vs
    # a compiled scan body differ by FMA fusion (1 ulp), not by the sweep
    @jax.jit
    def ref(g, s, p):
        gc, _ = clip_by_global_norm(g, 1.0)
        return update(gc, s, p, **kw)

    @jax.jit
    def streamed(g, s, p):
        return _streamed_opt_update(
            optimizer, g, s, p, cfg=cfg,
            clip_scale=clip_scale(global_norm(g), 1.0),
            params_host=params_host, grads_host=False, **kw)

    ref_p, ref_s = ref(grads, state, params)
    new_p, new_s = streamed(grads, state, params)
    for a, b in zip(jax.tree.leaves((ref_p, ref_s)),
                    jax.tree.leaves((new_p, new_s))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sweep_unit_counters_and_swapped_units():
    """The traced sweep loops count their units and the units whose
    swap-in shares the link with the previous unit's write-back: all but
    the first, (n-1)/n. Each sweep traces its swap-in twice, for unit 0 in
    a loop of its own and for unit i in the pipeline's body, so the
    optimizer bytes swapped in at trace time are two units of each sweep:
    the units the planner prices as in HBM at once."""
    from repro import obs
    from repro.core.lms.planner import OPT_SWEEP_UNITS, opt_sweep_elements
    from repro.train.steps import build_train_step, init_train_state
    cfg = dataclasses.replace(get_smoke_config("olmo-1b"), num_layers=4,
                              vocab_size=16384)   # 16384 x 64: 16 blocks
    model = Model(cfg, attn_impl="naive")
    mesh_spec = MeshSpec((1, 1), ("data", "model"))
    tcfg = TrainConfig(model=cfg, shape=ShapeConfig("smoke", "train", 16, 2),
                       mesh=mesh_spec, ddl=DDLConfig(mode="allreduce"))
    plan = _plan(cfg, {"params": "device", "grads": "device",
                       "optimizer": "host", "kvcache": "device"})
    reg = obs.reset().registry
    fn, _, _ = build_train_step(model, tcfg, make_mesh(mesh_spec), plan=plan,
                                donate=False)
    state = jax.eval_shape(
        lambda: init_train_state(model, tcfg, jax.random.key(0)))
    fn.lower(state, jax.eval_shape(lambda: _batch(cfg)))
    got = {k: reg.counter(f"lms.sweep_units{a}.{k}").value
           for k in ("stack", "rows") for a in ("", "_ahead")}
    assert reg.counter("lms.sweep_units.stack").value == 4
    assert reg.counter("lms.sweep_units_ahead.stack").value == 3
    assert reg.counter("lms.sweep_units.rows").value == 16
    assert reg.counter("lms.sweep_units_ahead.rows").value == 15, got
    layer = sum(int(np.prod(x.shape[1:]))
                for x in jax.tree.leaves(state.params["decoder"]["stack0"]))
    block = 16384 // 16 * cfg.d_model
    assert OPT_SWEEP_UNITS == 2
    assert opt_sweep_elements(cfg) == OPT_SWEEP_UNITS * (layer + block)
    assert (reg.counter("lms.swap_in_bytes.optimizer").value
            == 12 * OPT_SWEEP_UNITS * (layer + block))
    obs.reset()


def test_planner_prices_the_olmo_1b_sweep():
    """The benchmark cell's plan (olmo-1b, 4 x 2048 tokens, one v5e): the
    AdamW state on host and streamed once each way a step, params and
    grads in HBM; the sweep priced at the executor's units in flight, one
    layer (4 d^2 attention + 3 d d_ff MLP) and one of the embedding's 16
    row blocks each, 12 bytes of AdamW state per parameter."""
    from repro import hw
    from repro.core.lms.planner import (OPT_SWEEP_UNITS, PlanRequest, plan,
                                        opt_sweep_elements)
    cfg = get_config("olmo-1b")
    p = plan(PlanRequest(cfg=cfg, shape=ShapeConfig("c", "train", 2048, 4),
                         mesh=MeshSpec((1, 1), ("data", "model")),
                         hw=hw.TPU_V5E))
    assert {k: p.residency[k] for k in ("optimizer", "params", "grads")} == {
        "optimizer": "host", "params": "device", "grads": "device"}
    assert p.swap_bytes_per_step == 28_242_345_984
    assert p.swap_schedule.bytes_for("optimizer") == 28_242_345_984
    assert p.fits
    d, d_ff = 2048, 8192
    layer = 4 * d * d + 3 * d * d_ff
    block = 50304 // 16 * d
    assert opt_sweep_elements(cfg) == OPT_SWEEP_UNITS * (layer + block)
    assert 12 * opt_sweep_elements(cfg) == 1_765_146_624


# ---------------------------------------------------------------------------
# Multi-device parity: full residency map under the overlapped backward
# (hooks sink each reduced cotangent; sweep consumes layer by layer)
# ---------------------------------------------------------------------------

OPT_STREAM_MESH = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import Model
from repro.config.base import TrainConfig, ShapeConfig, MeshSpec, DDLConfig
from repro.core.lms.planner import MemoryPlan, make_swap_schedule
from repro.train.steps import build_train_step, init_train_state
from repro.launch.mesh import make_mesh
mesh_spec = MeshSpec(MESHSHAPE, MESHAXES)
mesh = make_mesh(mesh_spec)
cfg = get_smoke_config("olmo-1b")
model = Model(cfg, attn_impl="naive")
shape = ShapeConfig("smoke", "train", 32, 8)
rng = np.random.default_rng(0)
toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
residency = {"params": "host", "grads": "host", "optimizer": "host",
             "kvcache": "device"}
sched = make_swap_schedule(residency, cfg.num_layers, "train",
                           prefetch_depth=1)
assert sched.streams_params and sched.streams_optimizer and sched.streams_grads
plan = MemoryPlan({}, residency, 1, 1, 1, 1, True, swap_schedule=sched)

def run_steps(microbatches, plan, steps=3):
    tcfg = TrainConfig(model=cfg, shape=shape, mesh=mesh_spec,
                       ddl=DDLConfig(mode="allreduce"), warmup_steps=1,
                       learning_rate=1e-2, total_steps=50,
                       microbatches=microbatches)
    fn, ssh, bsh = build_train_step(model, tcfg, mesh, donate=False,
                                    overlap_grads=True, plan=plan)
    s = jax.device_put(init_train_state(model, tcfg, jax.random.key(0)), ssh)
    b = jax.device_put(batch, bsh)
    ms = []
    for _ in range(steps):
        s, m = fn(s, b)
        ms.append(m)
    return ms, s

# identical collectives in both legs; the only delta is placement ops
# (identity on one memory space) + elementwise slicing: exact equality
for m in MICROBATCHES:
    ms_res, s_res = run_steps(m, None)
    ms_str, s_str = run_steps(m, plan)
    for a, b in zip(ms_res, ms_str):
        assert float(a["loss"]) == float(b["loss"]), (m, a, b)
        assert float(a["grad_norm"]) == float(b["grad_norm"]), (m, a, b)
    for x, y in zip(jax.tree.leaves(s_res), jax.tree.leaves(s_str)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
print("OPT-STREAM-MESH-OK")
"""


def test_opt_stream_parity_1d_mesh_overlapped():
    code = (OPT_STREAM_MESH
            .replace("MESHSHAPE", "(4,)")
            .replace("MESHAXES", '("data",)')
            .replace("MICROBATCHES", "(1, 2)"))
    assert "OPT-STREAM-MESH-OK" in run_py(code, devices=4)


def test_opt_stream_parity_2d_mesh_overlapped():
    code = (OPT_STREAM_MESH
            .replace("MESHSHAPE", "(2, 2)")
            .replace("MESHAXES", '("pod", "data")')
            .replace("MICROBATCHES", "(1,)"))
    assert "OPT-STREAM-MESH-OK" in run_py(code, devices=4)


# ---------------------------------------------------------------------------
# Planner invariant: no fits=True for residency the executor can't deliver
# ---------------------------------------------------------------------------

def test_schedule_invariant_raises_for_unexecutable_residency():
    residency = {"params": "device", "grads": "device",
                 "optimizer": "host", "kvcache": "device"}
    with pytest.raises(AssertionError, match="optimizer"):
        check_schedule_invariant(residency, None)
    # a schedule that streams the class satisfies it
    check_schedule_invariant(
        residency, make_swap_schedule(residency, 4, "train"))
    # so does declaring it placement-only by design
    check_schedule_invariant(residency, None, placement_only=("optimizer",))


def test_planner_streams_every_host_class():
    """The original bug: the plan priced optimizer/grads host residency and
    reported fits=True with no executor stream. Now every host class of a
    train plan must stream (or be placement-only by documented design)."""
    plan = plan_memory(get_config("qwen2-72b"), SHAPES["train_4k"],
                       SINGLE_POD, LMSConfig())
    assert plan.residency["optimizer"] == "host"
    assert plan.residency["grads"] == "host"
    s = plan.swap_schedule
    assert s.streams_optimizer and s.streams_grads and s.streams_params
    assert s.bytes_for("optimizer") > 0 and s.bytes_for("grads") > 0
    assert plan.fits


def test_planner_gates_grads_host_on_executability():
    """The sink only exists for overlap + microbatches==1 + streamed
    optimizer; in any other configuration promising grads host residency
    would be the fits=True fiction again."""
    # microbatch accumulation: the accumulator all-gathers the full f32
    # tree on device — no per-layer sink exists
    plan = plan_memory(get_config("qwen2-72b"), SHAPES["train_4k"],
                       SINGLE_POD, LMSConfig(), microbatches=4)
    assert plan.residency["grads"] == "device"
    assert plan.swap_schedule is None or not plan.swap_schedule.streams_grads
    # resident optimizer: the monolithic update would re-read the whole
    # sunk tree at once, so no sink is promised either
    plan = plan_memory(get_config("qwen2-72b"), SHAPES["train_4k"],
                       SINGLE_POD, LMSConfig(offload_optimizer="never"))
    assert plan.residency["optimizer"] == "device"
    assert plan.residency["grads"] == "device"


def test_planner_zero1_optimizer_is_placement_only():
    plan = plan_memory(get_config("grok-1-314b"), SHAPES["train_4k"],
                       SINGLE_POD, LMSConfig(), zero1=True)
    assert plan.residency["optimizer"] == "host"
    assert plan.placement_only == ("optimizer",)  # flat 1/|data| shard
    assert not plan.swap_schedule.streams_optimizer
    # zero1 grads are consumed as in-step reduce-scattered shards: the
    # planner must not promise (or price) host residency for them
    assert plan.residency["grads"] == "device"
    assert plan.swap_schedule.bytes_for("grads") == 0
    # the invariant itself still holds at plan time (plan_memory ran it)
    check_schedule_invariant(plan.residency, plan.swap_schedule,
                             plan.placement_only)


# ---------------------------------------------------------------------------
# Satellite regressions: _microbatch_split + real model metrics
# ---------------------------------------------------------------------------

def test_microbatch_split_rejects_non_divisible_leading_dim():
    from repro.train.steps import _microbatch_split
    batch = {"tokens": jnp.ones((6, 4), jnp.int32),
             "labels": jnp.ones((6, 4), jnp.int32)}
    out = _microbatch_split(batch, 3)
    assert out["tokens"].shape == (3, 2, 4)
    # scalars broadcast (the only legitimate broadcast)
    out = _microbatch_split({"tokens": jnp.ones((6, 4)), "pos": jnp.int32(7)}, 2)
    assert out["pos"].shape == (2,)
    # a non-divisible leading dim must raise, naming the leaf — the old
    # broadcast_to fallback silently trained on m duplicated batches
    with pytest.raises(ValueError, match="labels"):
        _microbatch_split({"tokens": jnp.ones((6, 4), jnp.int32),
                           "labels": jnp.ones((7, 4), jnp.int32)}, 3)


@pytest.mark.parametrize("microbatches", [1, 2])
def test_step_metrics_carry_real_model_aux(microbatches):
    """`per_replica` used to rebuild metrics from scratch (m==1) or
    fabricate {"ce","aux"} (microbatch paths). A MoE model's load-balance
    loss must survive into the step metrics on every path."""
    cfg = get_smoke_config("qwen3-moe-235b-a22b")
    model = Model(cfg, attn_impl="naive")
    mesh_spec = MeshSpec((1, 1), ("data", "model"))
    mesh = make_mesh(mesh_spec)
    tcfg = TrainConfig(model=cfg, shape=ShapeConfig("smoke", "train", 16, 2),
                       mesh=mesh_spec, ddl=DDLConfig(mode="allreduce"),
                       warmup_steps=1, learning_rate=1e-2, total_steps=10,
                       microbatches=microbatches)
    ms, _ = _run_steps(model, tcfg, mesh, None, _batch(cfg), steps=1)
    m = ms[0]
    assert set(m) >= {"loss", "grad_norm", "lr", "ce", "aux"}
    assert float(m["aux"]) > 0.0          # MoE balance loss, not a 0.0 stub
    # loss = ce + aux_weight * aux (model.loss contract)
    np.testing.assert_allclose(float(m["loss"]),
                               float(m["ce"]) + 0.01 * float(m["aux"]),
                               rtol=1e-5)


@pytest.mark.parametrize("params", ["device", "host"],
                         ids=["resident_scan", "streamed_scan"])
def test_step_phases_carry_their_named_scopes(params):
    """The benchmark splits the step's device time by JAX name scope: the
    forward layer scan, the backward one (remat recompute included) and the
    optimizer sweep must each carry theirs in the compiled step's op_name
    metadata, on both decoder scan paths."""
    import re
    from repro.train.steps import build_train_step, init_train_state
    cfg = get_smoke_config("olmo-1b")
    model = Model(cfg, attn_impl="naive")
    mesh_spec = MeshSpec((1, 1), ("data", "model"))
    tcfg = TrainConfig(model=cfg, shape=ShapeConfig("smoke", "train", 16, 2),
                       mesh=mesh_spec, ddl=DDLConfig(mode="allreduce"))
    plan = _plan(cfg, {"params": params, "grads": "device",
                       "optimizer": "host", "kvcache": "device"})
    fn, _, _ = build_train_step(model, tcfg, make_mesh(mesh_spec), plan=plan,
                                donate=False)
    state = jax.eval_shape(
        lambda: init_train_state(model, tcfg, jax.random.key(0)))
    batch = jax.eval_shape(lambda: _batch(cfg))
    text = fn.lower(state, batch).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    fwd = [n for n in names if "/jvp(train.layers)/" in n]
    bwd = [n for n in names if "/transpose(jvp(train.layers))/" in n]
    assert fwd and any("dot_general" in n for n in fwd)
    assert any("/rematted_computation/" in n and "dot_general" in n
               for n in bwd)
    assert any("/lms.opt_sweep/" in n for n in names)
    # the sweep is its own phase, outside the layer scans
    assert not any("train.layers" in n and "lms.opt_sweep" in n
                   for n in names)
