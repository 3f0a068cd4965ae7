"""Overlapped backward (core/ddl/overlap.py): the reduce-as-you-go hook must
be numerically a reordering of the post-hoc `ddl_reduce_tree` pass — parity
at the reduction level (bucketed vs per-leaf, compress_dcn incl. the
error-feedback path), at the train-step level (overlap on vs off, allreduce
and zero1, 1D and 2D meshes, microbatch accumulation), and layout round
trips for the shard-major ShardSpec the zero1 state / sharded accumulator
live in."""
import numpy as np

from tests.util import run_py


# ---------------------------------------------------------------------------
# Pure-layout round trips (no devices)
# ---------------------------------------------------------------------------

def test_shard_spec_pack_global_roundtrip():
    import jax
    import jax.numpy as jnp
    from repro.core.ddl.overlap import pack_global, shard_spec, unpack_global
    tree = {"stack": jnp.arange(24.0, dtype=jnp.float32).reshape(4, 3, 2),
            "embed": jnp.arange(7.0, dtype=jnp.bfloat16),      # pads: 7 % 4
            "scale": jnp.float32(2.5)}                         # scalar leaf
    stacked = {"stack": True, "embed": False, "scale": False}
    spec = shard_spec(tree, data_size=4, stacked=stacked)
    # stacked leaf: rows = leading layer axis; rowsize padded per layer
    i = spec.shapes.index((4, 3, 2))
    assert spec.rows[i] == 4 and spec.rowsizes[i] == 6
    assert all(p % 4 == 0 for p in spec.padded_rows)
    assert spec.padded == 4 * spec.local_size
    flat = pack_global(tree, spec)
    assert flat.shape == (spec.padded,)
    out = unpack_global(flat, spec)
    for k in tree:
        np.testing.assert_allclose(np.asarray(out[k]),
                                   np.asarray(tree[k], np.float32))
    # shard-major: rank 0's slice holds column block 0 of every leaf, at the
    # leaf's offset in flatten order
    local0 = np.asarray(flat[:spec.local_size])
    off = sum(r * (p // 4) for r, p in
              list(zip(spec.rows, spec.padded_rows))[:i])
    sl = spec.padded_rows[i] // 4
    stack_rows = np.asarray(tree["stack"], np.float32).reshape(4, 6)
    padded = np.pad(stack_rows, ((0, 0), (0, spec.padded_rows[i] - 6)))
    np.testing.assert_allclose(local0[off:off + 4 * sl].reshape(4, sl),
                               padded[:, :sl])


# ---------------------------------------------------------------------------
# Reduction-level parity (bucketed hook backward vs post-hoc tree pass)
# ---------------------------------------------------------------------------

REDUCE_PARITY = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.config.base import DDLConfig
from repro.core.ddl import ddl_reduce_tree
from repro.core.ddl.overlap import (allgather_local_shards,
                                    collect_local_shards,
                                    reduce_tree_bucketed, shard_spec)
mesh = compat.make_mesh((2, 4), ("pod", "data"))
rng = np.random.default_rng(0)
tree = {"w": jnp.asarray(rng.standard_normal((8, 6)), jnp.float32),
        "b": {"h": jnp.asarray(rng.standard_normal(10), jnp.bfloat16),
              "s": jnp.float32(1.25)},
        "v": jnp.asarray(rng.standard_normal(4096), jnp.float32)}
kw = dict(data_axis="data", pod_axis="pod", data_size=4, pod_size=2)

def sm(f):
    return jax.jit(compat.shard_map(
        f, mesh=mesh, in_specs=(compat.tree.map(lambda _: P(), tree),),
        out_specs=compat.tree.map(lambda _: P(), tree), check_vma=False,
        axis_names={"pod", "data"}))

# 1) full mode == post-hoc per-leaf reduction (pure reordering)
cfg = DDLConfig(mode="allreduce")
ov = sm(lambda t: reduce_tree_bucketed(t, cfg, keep="full", **kw))(tree)
ph = sm(lambda t: ddl_reduce_tree(t, cfg, data_axis="data", pod_axis="pod",
                                  data_size=4, pod_size=2)[0])(tree)
for ka, (a, b) in {k: (ov[k], ph[k]) for k in ("w", "v")}.items():
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6, err_msg=ka)
np.testing.assert_allclose(np.asarray(ov["b"]["h"], np.float32),
                           np.asarray(ph["b"]["h"], np.float32), rtol=1e-2)

# 2) compress_dcn: a single 1-D leaf makes bucket == leaf, so the stateless
# in-hook compression must equal the post-hoc path with zero-initialized
# error feedback (first step of EF-SGD), and the post-hoc path must hand
# back the nonzero quantization residual for the NEXT step
ctree = {"v": tree["v"]}
ccfg = DDLConfig(mode="allreduce", compress_dcn=True)
smc = lambda f, out_t: jax.jit(compat.shard_map(
    f, mesh=mesh, in_specs=(P(),), out_specs=out_t, check_vma=False,
    axis_names={"pod", "data"}))
ovc = smc(lambda v: reduce_tree_bucketed({"v": v}, ccfg, keep="full",
                                         **kw)["v"], P())(ctree["v"])
def posthoc_ef(v):
    ef0 = [jnp.zeros(v.size // 4, jnp.float32)]
    out, ef = ddl_reduce_tree({"v": v}, ccfg, data_axis="data",
                              pod_axis="pod", data_size=4, pod_size=2,
                              error_feedback=ef0)
    return out["v"], ef[0]
phc, ef = smc(posthoc_ef, (P(), P()))(ctree["v"])
np.testing.assert_allclose(np.asarray(ovc), np.asarray(phc), rtol=1e-5,
                           atol=1e-6)
assert float(jnp.abs(ef).max()) > 0.0  # quantization residual captured

# 3) shard mode + collect + all-gather == the full reduction
scfg = DDLConfig(mode="zero1")
spec = shard_spec(tree, 4, compat.tree.map(lambda _: False, tree))
def via_shards(t):
    red = reduce_tree_bucketed(t, scfg, keep="shard", **kw)
    loc = collect_local_shards(red, spec, compat.tree.map(lambda _: True, t),
                               data_axis="data", pod_axis="pod", mean_over=8)
    return allgather_local_shards(loc, spec, data_axis="data")
sh = sm(via_shards)(tree)
for ka in ("w", "v"):
    np.testing.assert_allclose(np.asarray(sh[ka]),
                               np.asarray(ph[ka], np.float32), rtol=1e-5,
                               atol=1e-6, err_msg=ka)
print("REDUCE-PARITY-OK")
"""


def test_bucketed_reduce_matches_posthoc():
    assert "REDUCE-PARITY-OK" in run_py(REDUCE_PARITY, devices=8)


# ---------------------------------------------------------------------------
# Train-step parity: overlapped vs serialized (allreduce, 1D mesh),
# including the reduce-scattered microbatch accumulator
# ---------------------------------------------------------------------------

STEP_PARITY_1D = """
import dataclasses
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.models import Model
from repro.config.base import (TrainConfig, ShapeConfig, MeshSpec, DDLConfig,
                               LMSConfig)
from repro.core.lms.planner import plan_memory
from repro.train.steps import build_train_step, init_train_state
from repro.launch.mesh import make_mesh
mesh_spec = MeshSpec((4,), ("data",))
mesh = make_mesh(mesh_spec)
cfg = get_smoke_config("olmo-1b")
model = Model(cfg, attn_impl="naive")
shape = ShapeConfig("smoke", "train", 32, 8)
batch = {"tokens": jnp.ones((8, 32), jnp.int32),
         "labels": jnp.ones((8, 32), jnp.int32)}

def run_steps(microbatches, overlap, steps=3, plan=None):
    tcfg = TrainConfig(model=cfg, shape=shape, mesh=mesh_spec,
                       ddl=DDLConfig(mode="allreduce"), warmup_steps=1,
                       learning_rate=1e-2, total_steps=50,
                       microbatches=microbatches)
    fn, ssh, bsh = build_train_step(model, tcfg, mesh, donate=False,
                                    overlap_grads=overlap, plan=plan)
    s = jax.device_put(init_train_state(model, tcfg, jax.random.key(0)), ssh)
    b = jax.device_put(batch, bsh)
    ms = []
    for _ in range(steps):
        s, m = fn(s, b)
        ms.append(m)
    return ms

def check(ov, ser, tag):
    for i, (a, b) in enumerate(zip(ov, ser)):
        # same math, different reduction order (in-scan bucketed vs post-hoc
        # per-leaf): trajectories may drift by f32 rounding, nothing more
        assert abs(float(a["loss"]) - float(b["loss"])) < 2e-3, (tag, i, a, b)
        assert abs(float(a["grad_norm"]) - float(b["grad_norm"])) \\
            < 2e-2 * (1 + float(b["grad_norm"])), (tag, i, a, b)

for m in (1, 2):
    check(run_steps(m, True), run_steps(m, False), m)

# streamed x overlapped: the hook sits after the per-layer swap-in inside
# _scan_streamed, so the bwd sweep reduces each cotangent before it hits the
# swap-in transpose (grads stream out reduced as params stream in). On CPU
# the swap ops are identity, so this exercises the regrouped-scan + remat +
# hook graph; parity vs the same plan serialized must still hold.
resident = plan_memory(cfg, shape, mesh_spec, LMSConfig(hbm_budget=1 << 40))
plan = plan_memory(cfg, shape, mesh_spec,
                   LMSConfig(hbm_budget=max(resident.peak_bytes // 8, 1)))
assert plan.swap_schedule is not None and plan.swap_schedule.streams_params
check(run_steps(1, True, plan=plan), run_steps(1, False, plan=plan),
      "streamed")
print("STEP-1D-OK")
"""


def test_train_step_overlap_parity_1d_and_microbatch():
    assert "STEP-1D-OK" in run_py(STEP_PARITY_1D, devices=4)


# ---------------------------------------------------------------------------
# zero1 parity on a 2D ("pod","data") mesh: shard-major state layout,
# per-layer in-scan reduce-scatter, params all-gather
# ---------------------------------------------------------------------------

ZERO1_PARITY_2D = """
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.models import Model
from repro.config.base import TrainConfig, ShapeConfig, MeshSpec, DDLConfig
from repro.train.steps import build_zero1_train_step, init_zero1_state
from repro.launch.mesh import make_mesh
mesh_spec = MeshSpec((2, 4), ("pod", "data"))
mesh = make_mesh(mesh_spec)
cfg = get_smoke_config("olmo-1b")
model = Model(cfg, attn_impl="naive")
shape = ShapeConfig("smoke", "train", 32, 8)
batch = {"tokens": jnp.ones((8, 32), jnp.int32),
         "labels": jnp.ones((8, 32), jnp.int32)}

def run_steps(overlap, steps=3):
    tcfg = TrainConfig(model=cfg, shape=shape, mesh=mesh_spec,
                       ddl=DDLConfig(mode="zero1", overlap_grads=overlap),
                       warmup_steps=1, learning_rate=1e-2, total_steps=50)
    fn, ssh, bsh, spec = build_zero1_train_step(model, tcfg, mesh,
                                                donate=False)
    st = jax.device_put(init_zero1_state(model, tcfg, jax.random.key(0), 4),
                        ssh)
    b = jax.device_put(batch, bsh)
    ms = []
    for _ in range(steps):
        st, m = fn(st, b)
        ms.append(m)
    return ms

ov = run_steps(True)
ser = run_steps(False)
for i, (a, b) in enumerate(zip(ov, ser)):
    # identical update math on differently laid-out shards: f32-order drift
    assert abs(float(a["loss"]) - float(b["loss"])) < 2e-3, (i, a, b)
    assert abs(float(a["grad_norm"]) - float(b["grad_norm"])) \\
        < 2e-2 * (1 + float(b["grad_norm"])), (i, a, b)
print("ZERO1-2D-OK")
"""


def test_zero1_overlap_parity_2d():
    assert "ZERO1-2D-OK" in run_py(ZERO1_PARITY_2D, devices=8)


# ---------------------------------------------------------------------------
# the reductions carry the ddl.reduce name scope: the per-layer hook's
# inside the backward layer scan, the post-hoc tree pass outside it
# ---------------------------------------------------------------------------

REDUCE_SCOPE = """
import re
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.models import Model
from repro.config.base import TrainConfig, ShapeConfig, MeshSpec, DDLConfig
from repro.train.steps import build_train_step, init_train_state
from repro.launch.mesh import make_mesh
mesh_spec = MeshSpec((2,), ("data",))
cfg = get_smoke_config("olmo-1b")
model = Model(cfg, attn_impl="naive")
tcfg = TrainConfig(model=cfg, shape=ShapeConfig("smoke", "train", 16, 4),
                   mesh=mesh_spec, ddl=DDLConfig(mode="allreduce"))
state = jax.eval_shape(lambda: init_train_state(model, tcfg,
                                                jax.random.key(0)))
batch = {k: jax.ShapeDtypeStruct((4, 16), jnp.int32)
         for k in ("tokens", "labels")}
for overlap in (True, False):
    fn, _, _ = build_train_step(model, tcfg, make_mesh(mesh_spec),
                                donate=False, overlap_grads=overlap)
    text = fn.lower(state, batch).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*ddl[.]reduce/[^"]*)"', text))
    in_scan = {n for n in names if "transpose(jvp(train.layers))/" in n}
    assert any("reduce_scatter" in n for n in names - in_scan), overlap
    assert bool(in_scan) == overlap, (overlap, sorted(in_scan)[:3])
    if overlap:
        assert any("reduce_scatter" in n for n in in_scan)
print("REDUCE-SCOPE-OK")
"""


def test_reductions_carry_the_ddl_reduce_scope():
    assert "REDUCE-SCOPE-OK" in run_py(REDUCE_SCOPE, devices=2)
