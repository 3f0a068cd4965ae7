"""CPU tests of the Moonlight-16B-A3B cell at a small size: the unchanged
harness builds the cell, the plain reference draws the program's weights
and agrees with its loss and gradients (up to routing flips near ties),
the trainer's readings agree with the reference's as the run compares
them, and the grouped-matmul readers reduce a recorded chip trace."""
import copy
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops
import harness
import oracle

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "moonlight-16b-a3b-l14.train.s4096b4"
SEED = 2 ** 31 + 515
SMALL = dict(num_layers=3, d_model=128, num_heads=4, num_kv_heads=4,
             head_dim=48, d_ff=64, vocab_size=512, num_experts=16,
             experts_per_token=4, experts_held=4, experts_offset=4,
             dense_d_ff=256, kv_lora_rank=64, qk_rope_dim=16, v_head_dim=32)
ref_mod = harness.reference_module("mla_moe_decoder")


def small_cell():
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell["config"]["model"].update(SMALL)
    cell["traffic"].update(seq_len=64, global_batch=4)
    return cell


def test_the_harness_builds_the_cell_at_full_size():
    cell = harness.load_cell(CELL)
    tcfg = harness.train_config(cell, SEED)
    m = tcfg.model
    assert (m.num_layers, m.stack_layers, m.held, m.num_experts) == (14, 13, 8,
                                                                      64)
    assert m.is_mla and m.layer_kinds() == ("mla",) * 13
    assert (tcfg.shape.seq_len, tcfg.shape.global_batch) == (4096, 4)
    assert abs(m.param_count() - 1.472e9) < 1e6
    # the harness computes this for every cell; the dense count it gives
    # feeds only `mfu`, which the cell does not report
    assert flops.flops_per_token(cell["config"]["model"], 4096) > 0
    assert cell["per_layer"] == ["moe_gmm_ms", "moe_gmm_roofline"]


def test_the_full_size_plan_puts_the_optimizer_on_host():
    """AdamW state (17.7 GB fp32) and the fully resident step exceed the
    chip; the plan streams the optimizer and fits."""
    from repro.core.lms.planner import PlanRequest, plan
    tcfg = harness.train_config(harness.load_cell(CELL), SEED)
    req = dict(cfg=tcfg.model, shape=tcfg.shape, mesh=tcfg.mesh,
               optimizer=tcfg.optimizer)
    p = plan(PlanRequest(lms=tcfg.lms, **req))
    assert p.residency["optimizer"] == "host"
    assert p.residency["params"] == "device" and p.fits
    resident = plan(PlanRequest(lms=type(tcfg.lms)(enabled=False), **req))
    assert resident.peak_bytes > 16e9 > p.peak_bytes


def test_reference_draws_the_programs_weights_from_the_seed():
    cell = small_cell()
    model = harness.train_config(cell, SEED).model
    from repro.models.model import Model
    program = jax.tree_util.tree_flatten_with_path(
        jax.jit(Model(model).init)(jax.random.key(SEED)))[0]
    ref = ref_mod.init_params(cell["config"]["model"], SEED)
    assert len(program) == len(ref)
    for (path, leaf), (name, arr) in zip(program, ref.items()):
        assert harness._leaf_name(path)[0].replace("{}.", "") == name
        np.testing.assert_allclose(np.asarray(leaf, np.float32),
                                   np.asarray(arr), rtol=0, atol=1e-7)


def test_reference_loss_and_gradients_match_the_program():
    """bf16 program against the fp32 reference: the loss to 2e-4, every
    leaf's gradient to 3e-2 except the routed experts' and the router's,
    which a few routing flips near ties move by more (each flip moves a
    token between two experts; at this size an expert sees ~40 tokens)."""
    from repro.models.model import Model
    cell = small_cell()
    m = cell["config"]["model"]
    model = Model(harness.train_config(cell, SEED).model, attn_impl="naive")
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], (4, 65))
    batch = {"tokens": toks[:, :-1].astype(np.int32),
             "labels": toks[:, 1:].astype(np.int32)}
    bias = jnp.zeros((m["num_layers"] - 1, m["num_experts"]))
    params = jax.jit(model.init)(jax.random.key(SEED))
    (loss, mets), grads = jax.value_and_grad(
        lambda p: model.loss(p, batch, no_remat=True, router_bias=bias),
        has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        ref = ref_mod.Reference(m, {}, SEED)
        ref_loss, ref_grads, _, picks = ref.grads(
            ref_mod.init_params(m, SEED), batch, bias)
    assert abs(float(loss) - ref_loss) < 2e-4 * ref_loss
    flips = float(jnp.abs(mets["moe_picks"] - picks).sum()) / 2
    assert flips <= 0.01 * float(picks.sum())
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        name = harness._leaf_name(path)[0].replace("{}.", "")
        r = ref_grads[name]
        r = np.asarray(jnp.stack(r) if isinstance(r, list) else r)
        rel = np.linalg.norm(np.asarray(g, np.float32) - r) / \
            max(np.linalg.norm(r), 1e-12)
        routed = name.startswith("layers.ffn.") or name == "layers.ln2.scale"
        assert rel < (0.15 if routed and flips else 3e-2), (name, rel)


def test_trainer_readings_match_the_reference():
    """The timed path (Trainer, LMS plan, step, harness leaf naming) trains
    the checked steps as the reference does: every leaf is compared, and
    the gaps are bf16 rounding and routing flips at this size."""
    cell = small_cell()
    trainer, held, prog = harness.set_up(cell, SEED)
    del trainer, held
    ref = harness.reference_readings(cell, SEED)
    assert set(prog["grad"]) == set(ref["grad"])
    assert any(k.startswith("lead.") for k in ref["grad"])
    nums = oracle.compare(prog, ref)
    assert nums["loss_gap"][0] < 5e-3, nums
    assert nums["grad_norm_gap"][0] < 0.03, nums
    assert nums["update_norm_gap"][0] < 0.03, nums


def test_the_fp8_control_reads_further_off_than_the_program():
    cell = small_cell()
    ref = harness.reference_readings(cell, SEED)
    fp8 = harness.reference_readings(cell, SEED, variant="fp8")
    nums = oracle.compare(fp8, ref)
    assert nums["loss_gap"][0] > 1e-3 and nums["grad_norm_gap"][0] > 0.01


def test_grouped_matmul_work_by_hand():
    """One op over [8, 2048, 1408] at 16384 tokens, k 6 of 64 experts:
    12288 rows, 2 x 12288 x 2048 x 1408 FLOPs; bytes: the rows in and out
    and the weights once, bf16."""
    import moe_flops
    m = harness.load_cell(CELL)["config"]["model"]
    assert moe_flops.rows_per_pass(m, 16384) == 12288
    flops_, nbytes = moe_flops.op_work(m, 16384, 2048, 1408)
    assert flops_ == 2 * 12288 * 2048 * 1408
    assert nbytes == 2 * (12288 * (2048 + 1408) + 8 * 2048 * 1408)
    gmm = ("%gmm.55 = bf16[98304,1408]{1,0} custom-call(bf16[98304,2048]"
           "{1,0} %a, bf16[8,2048,1408]{2,1,0} %w, s32[10]{0} %g), "
           "custom_call_target=\"tpu_custom_call\"")
    tgmm = ("%tgmm.13 = bf16[8,1408,2048]{2,1,0} custom-call(bf16[1408,"
            "98304]{1,0} %a, bf16[98304,2048]{1,0} %g), custom_call_target="
            "\"tpu_custom_call\"")
    join = ("%custom-call.1332 = bf16[8,1408,2048]{2,1,0} custom-call(bf16"
            "[2,1408,2048]{2,1,0} %a, bf16[2,1408,2048]{2,1,0} %b)")
    dot = ("%fusion.3 = bf16[8,2048,1408]{2,1,0} fusion(bf16[8,2048,1408]"
           "{2,1,0} %w), kind=kLoop")
    assert moe_flops.gmm_dims(m, gmm) == (2048, 1408)
    assert moe_flops.gmm_dims(m, tgmm) == (1408, 2048)
    assert moe_flops.gmm_dims(m, join) is None
    assert moe_flops.gmm_dims(m, dot) is None


def test_grouped_matmul_readers_on_a_recorded_moonlight_trace():
    """Three traced steps of the cell on a v5e (the step program's runs and
    its grouped-matmul ops): 12 kernels a layer (forward, remat and the
    rows' gradient through gmm, the weights' through tgmm), 13 layers."""
    data = json.load(open(os.path.join(
        HERE, "fixtures", "moonlight-16b-a3b-l14.train.s4096b4.gmm.trace.json")))
    for plane in data["trace"]["devices"].values():
        plane["ops"] = [[data["names"][i], s, e] for i, s, e in plane["ops"]]
    cell = harness.load_cell(CELL)
    ctx = types.SimpleNamespace(
        trace=data["trace"], tokens_per_step=16384, chips=1,
        peak=json.load(open(os.path.join(HERE, "peaks.json")))["TPU v5 lite"],
        model=cell["config"]["model"], microbatches=1)
    ops = next(iter(data["trace"]["devices"].values()))["ops"]
    steps = 3
    assert len(ops) == 12 * 13 * steps
    took_ms = sum(e - s for _, s, e in ops) / 1e6
    ms = harness.metric_reader("moe_gmm_ms")(ctx)
    assert ms == pytest.approx(took_ms / steps)
    flops_, nbytes = 2 * 12288 * 2048 * 1408, 2 * (12288 * 3456 + 8 * 2048 * 1408)
    best = len(ops) * max(flops_ / 197e12, nbytes / 819e9)   # compute-bound
    roof = harness.metric_reader("moe_gmm_roofline")(ctx)
    assert roof == pytest.approx(100 * best / (took_ms / 1e3))
    assert 0 < roof < 100
