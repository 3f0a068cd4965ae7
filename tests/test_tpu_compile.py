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
import dataclasses
import functools
import re

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
from repro.kernels.grouped_matmul.kernel import gmm_fwd
from repro.kernels.quantize.kernel import dequantize_fwd, quantize_fwd
from repro.kernels.rmsnorm.kernel import rmsnorm_fwd
from repro.kernels.ssd_scan.kernel import ssd_scan_fwd
from repro.models.model import Model
from repro.models.moe import chunk_rows
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


@pytest.mark.parametrize("kn", [(2048, 1408), (1408, 2048)],
                         ids=["gate_up", "down"])
def test_grouped_matmul_compiles(one_chip, kn):
    """Moonlight's held experts (8 of 64, d 2048, expert width 1408): the
    megablox gmm forward and its VJP (gmm for the rows, tgmm for the
    weights) with the repo's tiling; 8192 rows sorted into 8 groups plus
    the uncomputed rest."""
    k, n = kn

    def fwd_bwd(lhs, rhs, sizes):
        out, vjp = jax.vjp(lambda a, b: gmm_fwd(a, b, sizes), lhs, rhs)
        return out, vjp(out)

    _compile(fwd_bwd, one_chip, ((8192, k), jnp.bfloat16),
             ((8, k, n), jnp.bfloat16), ((9,), jnp.int32))


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


# Pinned-host transfers of the v5e link as the recorded chip trace of the
# olmo-1b cell shows them: 14.2 GB/s in one direction alone, 13.3 GB/s in
# each direction when both carry traffic (benchmarks/chip/fixtures).
LINK_ONE_WAY, LINK_EACH_WAY = 14.2e9, 13.3e9


def _hlo(text):
    """Compiled HLO text -> {computation: [instruction]}; an instruction
    is a dict with its name, shape, opcode, operand names and line."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps[head.group(1)] = []
            continue
        m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = ", line)
        if not m or cur is None:
            continue
        i, depth = m.end(), 0
        while True:               # the shape: a tuple in parens or a word
            depth += {"(": 1, ")": -1}.get(line[i], 0)
            i += 1
            if depth == 0 and line[i] == " ":
                break
        op, _, rest = line[i + 1:].partition("(")
        args, depth = "", 1
        for ch in rest:
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
            args += ch
        cur.append({"name": m.group(1), "shape": line[m.end():i],
                    "op": op, "args": re.findall(r"%([\w.\-]+)", args),
                    "line": line})
    return comps


def _nbytes(shape):
    dt, dims = re.match(r"(\w+)\[([\d,]*)\]", shape).groups()
    return (int(np.prod([int(d) for d in dims.split(",") if d]))
            * {"f32": 4, "bf16": 2}[dt])


def _host_transfers(body):
    """The body's pinned-host transfers in schedule order: (kind, start
    name, bytes), kind R> / R< a read (host to HBM) issued / waited for,
    W> / W< a write."""
    by = {x["name"]: x for x in body}
    out = []
    for x in body:
        if x["op"].endswith("-start") and "S(5)" in x["shape"]:
            shapes = re.findall(r"\w+\[[\d,]*\]", x["shape"])
            if x["op"] == "dynamic-slice-start":
                out.append(("R>", x["name"], _nbytes(shapes[-2])))
            elif x["op"] == "dynamic-update-slice-start":
                out.append(("W>", x["name"], _nbytes(shapes[1])))
        elif x["op"].endswith("-done") and x["args"][0] in by:
            start = by[x["args"][0]]
            if "S(5)" in start["shape"] and start["op"] in (
                    "dynamic-slice-start", "dynamic-update-slice-start"):
                kind = "R<" if start["op"] == "dynamic-slice-start" else "W<"
                out.append((kind, start["name"], 0))
    return out


def _link_seconds(transfers):
    """One iteration on the host link: each direction serves its transfers
    in the order they are issued, a -done waits for its transfer, compute
    is free. The model reproduces the recorded chip trace's iteration
    times of the sweep this step had before its pipeline (147.5 ms against
    147.7 for two layers of olmo-1b's stack, 10.9 against 11.0 for a block
    of its embedding rows)."""
    queues, done, t = {"R": [], "W": []}, set(), 0.0

    def run_until(finished):
        nonlocal t
        while not finished():
            busy = [k for k in queues if queues[k]]
            bw = LINK_ONE_WAY if len(busy) == 1 else LINK_EACH_WAY
            dt = min(queues[k][0][1] for k in busy) / bw
            for k in busy:
                queues[k][0][1] -= dt * bw
                if queues[k][0][1] <= 1.0:
                    done.add(queues[k].pop(0)[0])
            t += dt

    for kind, name, nbytes in transfers:
        if kind[1] == ">":
            queues[kind[0]].append([name, float(nbytes)])
        else:
            run_until(lambda: name in done)
    run_until(lambda: not queues["R"] and not queues["W"])
    return t


def _sweep_loops(comps):
    """-> [(while instruction, body)] of the streamed optimizer sweep."""
    loops = []
    for ins in comps.values():
        for x in ins:
            if x["op"] == "while" and "/lms.opt_sweep/" in x["line"]:
                body = re.search(r"body=%([\w.\-]+)", x["line"]).group(1)
                loops.append((x, comps[body]))
    return loops


def _check_pipelined_sweep(text, num_layers):
    """The sweep loops of a compiled host-optimizer step are software
    pipelines: each iteration swaps a unit in while the previous unit's
    results go back out, so both link directions carry traffic at once."""
    comps = _hlo(text)
    # the host-resident state stacks are sliced in place, never copied
    assert not [x["name"] for ins in comps.values() for x in ins
                if x["op"] in ("copy", "copy-start")
                and re.search(rf"\[{num_layers},[^\]]*\]\{{[^}}]*S\(5\)",
                              x["shape"])]
    kinds = set()
    for loop, body in _sweep_loops(comps):
        by = {x["name"]: x for x in body}
        transfers = _host_transfers(body)
        reads = {n for k, n, _ in transfers if k == "R>"}
        writes = [n for k, n, _ in transfers if k == "W>"]
        assert reads
        if not writes:             # the loop of unit 0
            continue
        carried = re.findall(r"\w+\[(\d+)[\d,]*\]\{[^}]*S\(5\)\}",
                             loop["shape"])
        kind = "stack" if str(num_layers) in carried else "rows"
        kinds.add(kind)
        if kind == "stack":        # a unit is one layer
            for n in reads:
                moved = re.findall(r"\w+\[([\d,]*)\]", by[n]["shape"])[-2]
                assert moved.startswith("1,"), moved
        # what each write-back carries was computed in the previous
        # iteration, not from a swap-in of this one: the write-backs need
        # not wait for the swap-ins
        for w in writes:
            seen, todo = set(), [by[w]["args"][1]]
            while todo:
                x = by.get(todo.pop())
                if x is None or x["name"] in seen:
                    continue
                seen.add(x["name"])
                assert not (x["op"] == "dynamic-slice-done"
                            and x["args"][0] in reads), (w, x["name"])
                todo += x["args"]
        if len({by[n]["args"][0] for n in reads}) < 2:
            continue               # one array's transfers are serial
        # write-backs are issued before the swap-ins have landed, and the
        # modelled link moves the iteration's bytes in well under the time
        # of moving them one direction after the other
        order = [k for k, _, _ in transfers]
        assert order.index("W>") < len(order) - order[::-1].index("R<") - 1
        serial = sum(b for _, _, b in transfers) / LINK_ONE_WAY
        assert _link_seconds(transfers) <= 0.8 * serial
    assert kinds == {"stack", "rows"}, kinds


@pytest.mark.parametrize("case", ["smoke", "adamw_deep", "sgdm_deep",
                                  "adamw_biases"])
def test_train_step_with_host_optimizer_compiles(one_chip, monkeypatch, case):
    """Host residency is real on TPU only; steer the placement decision to
    the TPU branch so the step carries pinned-host shardings, in-jit
    transfers and the streamed optimizer sweep, as it does on the chip.
    The other cases (8 layers, 128-wide heads, an embedding of 4096 x 256
    that the sweep takes in 16 row blocks) check the compiled schedule of
    the sweep: `_check_pipelined_sweep`. The biases case has starcoder2's
    small [L, 36, 128] query-bias stacks, whose layer axis the TPU layout
    tiles, and one-shot norm leaves."""
    monkeypatch.setattr(compat, "host_memory_kind", lambda: "pinned_host")
    cfg = get_smoke_config("olmo-1b")
    optimizer = case.split("_")[0] if case != "smoke" else "adamw"
    if case.endswith("_deep"):
        cfg = dataclasses.replace(cfg, num_layers=8, d_model=256,
                                  num_heads=2, num_kv_heads=2, head_dim=128,
                                  d_ff=256, vocab_size=4096)
    elif case.endswith("_biases"):
        cfg = dataclasses.replace(get_smoke_config("starcoder2-7b"),
                                  num_layers=8, d_model=256, num_heads=36,
                                  num_kv_heads=4, head_dim=128, d_ff=256,
                                  vocab_size=4096)
    model = Model(cfg)
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    tcfg = TrainConfig(model=cfg, shape=ShapeConfig("s", "train", 128, 4),
                       mesh=MeshSpec((1, 1), ("data", "model")),
                       optimizer=optimizer)
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
    if case != "smoke":
        _check_pipelined_sweep(text, cfg.num_layers)


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


def test_moonlight_step_with_host_optimizer_compiles(one_chip, monkeypatch):
    """Latent attention at its real head widths (q/k 192, v 128, latent 512,
    rope 64), held experts through the grouped-matmul kernels (inside the
    step's shard_map, manual over the axes the step leaves automatic), a
    leading dense layer swept in row blocks, AdamW state in pinned host:
    the TPU compiler takes it. Host arrays [d, 576] or [d, 16, 192] would
    be refused here (lane-slice write-backs of their row blocks). The
    expert dispatch holds chunks of held picks, not a row for every
    pick."""
    import repro.kernels.gates as gates
    monkeypatch.setattr(compat, "host_memory_kind", lambda: "pinned_host")
    monkeypatch.setattr(gates, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(
        get_config("moonlight-16b-a3b"), num_layers=3, d_model=2048,
        experts_held=2, vocab_size=4096, dense_d_ff=1024)
    model = Model(cfg)
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    tcfg = TrainConfig(model=cfg, shape=ShapeConfig("s", "train", 512, 1),
                       mesh=MeshSpec((1, 1), ("data", "model")))
    residency = {"params": "device", "grads": "device",
                 "optimizer": "host", "kvcache": "device"}
    plan = MemoryPlan({}, residency, 1, 1, 1, 1, True,
                      swap_schedule=make_swap_schedule(
                          residency, cfg.num_layers, "train"))
    step, state_sh, batch_sh = build_train_step(model, tcfg, mesh, plan=plan)
    init = functools.partial(init_train_state, model, tcfg)
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        jax.eval_shape(init, jax.random.key(0)), state_sh)
    specs, _ = model.input_specs(tcfg.shape, mesh)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=batch_sh[k])
             for k, v in specs.items()}
    text = step.lower(state, batch).compile().as_text()
    assert text.count("tpu_custom_call") >= 6      # gmm and tgmm
    for scope in ("/attn.mla/", "/moe.experts/", "/lms.opt_sweep/"):
        assert scope in text, scope
    # 12 grouped matmuls a layer: 3 forward, 9 in the backward (the chunk
    # recomputed, the rows' and the weights' gradients); the remat of the
    # layer's forward drops the unused chunk loop
    assert len(re.findall(r"%t?gmm\.\d+ = ", text)) == 12
    # the dispatch holds chunks of C = 512 held picks, never a row for each
    # of the T*k = 512 x 6 picks
    assert chunk_rows(cfg, 512) == 512
    assert "[512,1408]" in text
    for rows in ("[3072,2048]", "[3072,1408]"):
        assert rows not in text, rows
