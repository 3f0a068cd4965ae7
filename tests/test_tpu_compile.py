"""Compile the main-path Pallas kernels for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
described (not attached) v5e chip, which refuses what interpret mode
accepts — block shapes off the (8, 128) tiling, layouts Mosaic cannot
match, more VMEM than a kernel may use. Shapes are olmo-1b's attention
(H=K=16, D=128, Smax=2048) and mamba2-1.3b's SSD scan (64 heads of 64,
state 128, chunk 256). The training step with its AdamW state in pinned
host memory — the plan olmo-1b needs on one chip — compiles at smoke size,
and so does the data=4 step with the overlapped DDL hooks, with the TPU
collective fusions that miscompiled it turned off.

The topology is described inside a module-scoped fixture, never at import:
only the process given this file loads the TPU compiler library.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import compat
from repro.config.base import DDLConfig, MeshSpec, ShapeConfig, TrainConfig
from repro.configs import get_config, get_smoke_config
from repro.core.lms.planner import MemoryPlan, make_swap_schedule
from repro.kernels.flash_attention.decode_kernel import (
    flash_decode_fwd, flash_decode_paged_fwd)
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.quantize.kernel import dequantize_fwd, quantize_fwd
from repro.kernels.rmsnorm.kernel import rmsnorm_fwd
from repro.kernels.ssd_scan.kernel import ssd_scan_fwd
from repro.models.model import Model
from repro.train.steps import build_train_step, init_train_state

OLMO = get_config("olmo-1b")
MAMBA = get_config("mamba2-1.3b")
H, K, D = OLMO.num_heads, OLMO.num_kv_heads, OLMO.head_dim
SMAX = 2048
B_DECODE = 8
PAGE = 64


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_fwd_compiles(one_chip):
    s = 2048
    fn = functools.partial(flash_attention_fwd, causal=True)
    _compile(fn, one_chip, ((1, H, s, D), jnp.bfloat16),
             ((1, K, s, D), jnp.bfloat16), ((1, K, s, D), jnp.bfloat16))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_flash_decode_compiles(one_chip, kv_dtype):
    cache = (B_DECODE, SMAX, K, D)
    q = ((B_DECODE, H, D), jnp.bfloat16)
    kvl = ((B_DECODE,), jnp.int32)
    if kv_dtype == "int8":
        scale = ((B_DECODE, SMAX, K), jnp.float32)
        fn = lambda q, k, ks, v, vs, n: flash_decode_fwd(
            q, k, v, n, k_scale=ks, v_scale=vs)
        _compile(fn, one_chip, q, (cache, jnp.int8), scale,
                 (cache, jnp.int8), scale, kvl)
    else:
        _compile(flash_decode_fwd, one_chip, q, (cache, jnp.bfloat16),
                 (cache, jnp.bfloat16), kvl)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_flash_decode_paged_compiles(one_chip, kv_dtype):
    max_pages = SMAX // PAGE
    arena = (B_DECODE * max_pages + 1, PAGE, K, D)
    q = ((B_DECODE, H, D), jnp.bfloat16)
    kvl = ((B_DECODE,), jnp.int32)
    table = ((B_DECODE, max_pages), jnp.int32)
    if kv_dtype == "int8":
        scale = (arena[:3], jnp.float32)
        fn = lambda q, k, ks, v, vs, n, t: flash_decode_paged_fwd(
            q, k, v, n, t, k_scale=ks, v_scale=vs)
        _compile(fn, one_chip, q, (arena, jnp.int8), scale,
                 (arena, jnp.int8), scale, kvl, table)
    else:
        _compile(flash_decode_paged_fwd, one_chip, q, (arena, jnp.bfloat16),
                 (arena, jnp.bfloat16), kvl, table)


def test_rmsnorm_compiles(one_chip):
    _compile(rmsnorm_fwd, one_chip, ((4096, OLMO.d_model), jnp.bfloat16),
             ((OLMO.d_model,), jnp.float32))


def test_quantize_roundtrip_compiles(one_chip):
    rows, cols = 4096, OLMO.d_model
    _compile(quantize_fwd, one_chip, ((rows, cols), jnp.bfloat16))
    _compile(dequantize_fwd, one_chip, ((rows, cols), jnp.int8),
             ((rows,), jnp.float32))


def test_ssd_scan_compiles(one_chip):
    b, l = 1, 2048
    h, p, n = MAMBA.ssm_nheads, MAMBA.ssm_headdim, MAMBA.ssm_state
    g = MAMBA.ssm_ngroups
    fn = functools.partial(ssd_scan_fwd, chunk=MAMBA.ssm_chunk)
    _compile(fn, one_chip, ((b, l, h, p), jnp.bfloat16),
             ((b, l, h), jnp.float32), ((h,), jnp.float32),
             ((b, l, g, n), jnp.bfloat16), ((b, l, g, n), jnp.bfloat16))


def test_train_step_with_host_optimizer_compiles(one_chip, monkeypatch):
    """Host residency is real on TPU only; steer the placement decision to
    the TPU branch so the step carries pinned-host shardings, in-jit
    transfers and the streamed optimizer sweep, as it does on the chip."""
    monkeypatch.setattr(compat, "host_memory_kind", lambda: "pinned_host")
    cfg = get_smoke_config("olmo-1b")
    model = Model(cfg)
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    tcfg = TrainConfig(model=cfg, shape=ShapeConfig("s", "train", 128, 4),
                       mesh=MeshSpec((1, 1), ("data", "model")))
    residency = {"params": "device", "grads": "device",
                 "optimizer": "host", "kvcache": "device"}
    plan = MemoryPlan({}, residency, 1, 1, 1, 1, True,
                      swap_schedule=make_swap_schedule(
                          residency, cfg.num_layers, "train"))
    step, state_sh, batch_sh = build_train_step(model, tcfg, mesh, plan=plan)
    init = functools.partial(init_train_state, model, tcfg)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype,
                               sharding=NamedSharding(mesh, PartitionSpec()))
    init_c = jax.jit(init, out_shardings=state_sh).lower(key).compile()
    assert init_c.memory_analysis().host_output_size_in_bytes > 0
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        jax.eval_shape(init, jax.random.key(0)), state_sh)
    specs, _ = model.input_specs(tcfg.shape, mesh)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=batch_sh[k])
             for k, v in specs.items()}
    compiled = step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.host_argument_size_in_bytes > 0
    assert mem.host_alias_size_in_bytes == mem.host_argument_size_in_bytes
    # the TPU compiler keeps the step's phase scopes in the op metadata
    # that the profiler reports for each device op
    text = compiled.as_text()
    for scope in ("/jvp(train.layers)/", "/transpose(jvp(train.layers))/",
                  "/lms.opt_sweep/"):
        assert scope in text, scope


def test_overlapped_ddl_step_compiles_without_collective_fusions(topo):
    """The data=4 train step with the in-backward DDL hooks compiles for
    v5e:2x2 with the TPU collective fusions off (`hook_compiler_options`):
    with them on, the olmo-1b step's gradients came out wrong on the chip."""
    cfg = get_smoke_config("olmo-1b")
    model = Model(cfg)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    tcfg = TrainConfig(model=cfg, shape=ShapeConfig("s", "train", 128, 4),
                       mesh=MeshSpec((4, 1), ("data", "model")),
                       ddl=DDLConfig(overlap_grads=True))
    step, state_sh, batch_sh = build_train_step(model, tcfg, mesh)
    init = functools.partial(init_train_state, model, tcfg)
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        jax.eval_shape(init, jax.random.key(0)), state_sh)
    specs, _ = model.input_specs(tcfg.shape, mesh)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=batch_sh[k])
             for k, v in specs.items()}
    hlo = step.lower(state, batch).compile().as_text()
    assert "reduce-scatter(" in hlo
    assert "async_collective_fusion" not in hlo
