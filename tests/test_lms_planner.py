"""LMS planner invariants (hypothesis property tests) + behaviour on the
assigned architectures."""
import dataclasses
import importlib.util
import json
import os

import pytest
from tests.util import REPO, given, settings, st

from repro import hw as hwlib
from repro.config.base import (SHAPES, SINGLE_POD, MULTI_POD, LMSConfig,
                               ShapeConfig)
from repro.configs import ARCH_IDS, get_config
from repro.core.lms.planner import (OPT_STATE_MULT, PlanRequest,
                                    activation_classes, hbm_traffic_model,
                                    layer_work_bytes, plan, plan_memory,
                                    plan_serve_memory, plan_to_policy,
                                    validate_optimizer)
from repro.train.steps import StepSpec


@pytest.mark.parametrize("arch", sorted(ARCH_IDS))
@pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
def test_plan_fits_or_reports(arch, shape):
    cfg = get_config(arch)
    plan = plan_memory(cfg, SHAPES[shape], SINGLE_POD, LMSConfig())
    # with LMS enabled every assigned arch must fit the v5e budget
    assert plan.fits, f"{arch} x {shape}: {plan.summary()}"
    policy = plan_to_policy(plan)  # must build without error
    assert plan.peak_bytes > 0
    assert hbm_traffic_model(cfg, SHAPES[shape], SINGLE_POD, plan) > 0


def test_large_models_offload():
    """The paper's thesis: models beyond device memory train via host
    residency. 72B/314B params cannot sit in 16 GiB HBM at TP=16."""
    for arch in ("qwen2-72b", "grok-1-314b", "qwen3-moe-235b-a22b"):
        plan = plan_memory(get_config(arch), SHAPES["train_4k"], SINGLE_POD,
                           LMSConfig())
        assert plan.residency["params"] == "host", arch
        assert plan.swap_bytes_per_step > 0, arch
        assert plan.fits, plan.summary()


def test_small_model_stays_on_device():
    plan = plan_memory(get_config("olmo-1b"), SHAPES["train_4k"], SINGLE_POD,
                       LMSConfig())
    assert plan.residency["params"] == "device"
    assert plan.swap_bytes_per_step == 0


def test_lms_disabled_overflows_for_large():
    plan = plan_memory(get_config("qwen2-72b"), SHAPES["train_4k"], SINGLE_POD,
                       LMSConfig(enabled=False))
    assert not plan.fits  # without LMS the 72B cannot fit — the paper's point


@settings(max_examples=30, deadline=None)
@given(st.sampled_from(sorted(ARCH_IDS)),
       st.sampled_from(["train_4k", "prefill_32k", "decode_32k"]),
       st.integers(8, 64))
def test_planner_monotone_in_budget(arch, shape, budget_gb):
    """More HBM never increases swap traffic (hypothesis)."""
    cfg = get_config(arch)
    small = plan_memory(cfg, SHAPES[shape], SINGLE_POD,
                        LMSConfig(hbm_budget=budget_gb * 1024**3))
    large = plan_memory(cfg, SHAPES[shape], SINGLE_POD,
                        LMSConfig(hbm_budget=2 * budget_gb * 1024**3))
    assert large.swap_bytes_per_step <= small.swap_bytes_per_step


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(sorted(ARCH_IDS)))
def test_activation_classes_positive(arch):
    cfg = get_config(arch)
    acts = activation_classes(cfg, SHAPES["train_4k"], SINGLE_POD)
    assert all(a.bytes_dev > 0 for a in acts)
    names = [a.name for a in acts]
    assert "resid" in names
    assert len(set(names)) == len(names)


def test_remat_preferred_on_slow_link():
    """With a very slow host link the planner must remat rematerializable
    tensors instead of swapping them (the paper's PCIe-stall lesson). The
    residual stream is exempt: it cannot be rematerialized, so swapping it
    is the only way to fit at all."""
    cfg = get_config("qwen2.5-14b")
    slow = hwlib.HardwareSpec(**{**hwlib.TPU_V5E.__dict__, "host_bw": 1e9})
    plan = plan_memory(cfg, SHAPES["train_4k"], SINGLE_POD,
                       LMSConfig(hbm_budget=8 * 1024**3), hw=slow)
    fast = plan_memory(cfg, SHAPES["train_4k"], SINGLE_POD,
                       LMSConfig(hbm_budget=8 * 1024**3), hw=hwlib.TPU_V5E)
    slow_offloads = {k for k, v in plan.assignment.items()
                     if v == "offload" and k != "resid"}
    fast_offloads = {k for k, v in fast.assignment.items() if v == "offload"}
    assert not slow_offloads, slow_offloads
    assert fast_offloads  # the fast link does swap


@pytest.mark.parametrize("arch,priced", [
    ("olmo-1b", False), ("qwen3-moe-235b-a22b", True), ("grok-1-314b", True),
    ("moonlight-16b-a3b", True)])
def test_layer_work_prices_dispatch_and_latent_scores(arch, priced):
    """The dropless dispatch's [T*k] rows are priced for every MoE model,
    latent attention's score block for MLA; a dense MHA layer adds
    nothing (olmo-1b's plan is unchanged by the term)."""
    from repro.config.base import MeshSpec
    cfg = get_config(arch)
    one_chip = MeshSpec((1, 1), ("data", "model"))
    work = layer_work_bytes(cfg, ShapeConfig("t", "train", 4096, 4),
                            one_chip)
    assert (work > 0) == priced
    if cfg.num_experts:
        rows = 4 * 4096 * cfg.experts_per_token
        assert work >= 2 * 4 * rows * cfg.d_model
    if cfg.is_mla:
        assert work >= 3 * 4 * 4 * cfg.num_heads * 512 * 4096


# ---- facade / wrapper identity -------------------------------------------

WRAPPED_ARCH = "qwen2.5-14b"


def test_plan_memory_wrapper_identity():
    cfg = get_config(WRAPPED_ARCH)
    shape = SHAPES["train_4k"]
    legacy = plan_memory(cfg, shape, SINGLE_POD, LMSConfig())
    facade = plan(PlanRequest(cfg=cfg, shape=shape, mesh=SINGLE_POD,
                              lms=LMSConfig()))
    assert legacy == facade


def test_plan_serve_wrapper_identity():
    cfg = get_config(WRAPPED_ARCH)
    shape = SHAPES["decode_32k"]
    legacy = plan_serve_memory(cfg, shape, SINGLE_POD, slots=8, page_size=64)
    facade = plan(PlanRequest(cfg=cfg, shape=shape, mesh=SINGLE_POD,
                              serve=True, slots=8, page_size=64))
    assert legacy == facade


# ---- optimizer validation (the raw string-compare bugfix) ----------------

def test_validate_optimizer_rejects_unknown():
    with pytest.raises(ValueError, match="sgdm"):
        validate_optimizer("sgd")
    assert validate_optimizer("adamw") == "adamw"
    assert set(OPT_STATE_MULT) == {"adamw", "sgdm"}


def test_plan_memory_rejects_unknown_optimizer():
    cfg = get_config(WRAPPED_ARCH)
    with pytest.raises(ValueError):
        plan_memory(cfg, SHAPES["train_4k"], SINGLE_POD, LMSConfig(),
                    optimizer="adam")
    good = plan_memory(cfg, SHAPES["train_4k"], SINGLE_POD, LMSConfig(),
                       optimizer="sgdm")
    with pytest.raises(ValueError):
        hbm_traffic_model(cfg, SHAPES["train_4k"], SINGLE_POD, good,
                          optimizer="rmsprop")


# ---- StepSpec ------------------------------------------------------------

def test_stepspec_kv_dtype_resolution():
    assert StepSpec().resolved_kv_dtype() == "model"
    assert StepSpec(kv_dtype="int8").resolved_kv_dtype() == "int8"
    with pytest.raises(ValueError):
        StepSpec(kv_dtype="fp4").resolved_kv_dtype()
    # the plan's priced knob fills in only when the arg is unset
    cfg = get_config(WRAPPED_ARCH)
    sp = plan_serve_memory(cfg, SHAPES["decode_32k"], SINGLE_POD,
                           slots=8, page_size=64, kv_dtype="int8")
    if sp.kv_paging is not None:
        assert StepSpec(plan=sp).resolved_kv_dtype() == "int8"
        assert StepSpec(plan=sp,
                        kv_dtype="model").resolved_kv_dtype() == "model"


# ---- plans pinned to a recorded snapshot -----------------------------------
#
# tests/fixtures/plan_snapshot.json holds `_plan_fields` of every request
# `_snapshot_requests` builds, recorded from the planner as it stood when
# it could also re-price a plan from a measured host-clock profile. Each
# plan must still come out field for field the same: the hardware-constant
# pricing that remains is the one every plan was made by.

SNAPSHOT = os.path.join(os.path.dirname(__file__), "fixtures",
                        "plan_snapshot.json")
BENCH_CELLS = ("olmo-1b.train.s2048b4", "moonlight-16b-a3b-l14.train.s4096b4")


def _bench_harness():
    """benchmarks/chip/harness.py as a module (its configs are read, not
    edited, from benchmarks/chip/)."""
    path = os.path.join(REPO, "benchmarks", "chip", "harness.py")
    spec = importlib.util.spec_from_file_location("chip_harness", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _snapshot_requests(case: str) -> dict:
    """{plan name: PlanRequest} of one case: a catalog arch's train, train
    at half the budget (which drives the activation greedy), decode and
    serve plans; or the request `Trainer` builds for a benchmark cell."""
    hw = hwlib.DEFAULT
    if case in BENCH_CELLS:
        harness = _bench_harness()
        tcfg = harness.train_config(harness.load_cell(case), seed=0)
        return {"train": PlanRequest(
            cfg=tcfg.model, shape=tcfg.shape, mesh=tcfg.mesh, lms=tcfg.lms,
            hw=hw, optimizer=tcfg.optimizer,
            zero1=(tcfg.ddl.mode == "zero1"),
            microbatches=tcfg.microbatches)}
    cfg = get_config(case)
    train, decode = SHAPES["train_4k"], SHAPES["decode_32k"]
    return {
        "train_4k": PlanRequest(cfg=cfg, shape=train, mesh=SINGLE_POD, hw=hw),
        "train_4k_half_budget": PlanRequest(
            cfg=cfg, shape=train, mesh=SINGLE_POD, hw=hw,
            lms=LMSConfig(hbm_budget=hw.hbm_bytes // 2)),
        "decode_32k": PlanRequest(cfg=cfg, shape=decode, mesh=SINGLE_POD,
                                  hw=hw),
        "serve_32k": PlanRequest(cfg=cfg, shape=decode, mesh=SINGLE_POD,
                                 hw=hw, serve=True, slots=8, page_size=64,
                                 kv_dtype="int8"),
    }


def _plan_fields(p) -> dict:
    """The plan's decisions and prices as JSON values, and the summary
    text the trainer logs."""
    return json.loads(json.dumps({
        "assignment": p.assignment, "residency": p.residency,
        "peak_bytes": p.peak_bytes, "host_bytes": p.host_bytes,
        "swap_bytes_per_step": p.swap_bytes_per_step, "fits": p.fits,
        "notes": p.notes,
        "swap_schedule": (dataclasses.asdict(p.swap_schedule)
                          if p.swap_schedule is not None else None),
        "overlap_grads": p.overlap_grads,
        "placement_only": p.placement_only,
        "kv_paging": (dataclasses.asdict(p.kv_paging)
                      if p.kv_paging is not None else None),
        "summary": p.summary(),
    }))


@pytest.mark.parametrize("case", sorted(ARCH_IDS) + list(BENCH_CELLS))
def test_plan_matches_parent_snapshot(case):
    with open(SNAPSHOT) as f:
        want = json.load(f)[case]
    got = {name: _plan_fields(plan(req))
           for name, req in _snapshot_requests(case).items()}
    assert sorted(got) == sorted(want)
    for name in want:
        for key, value in want[name].items():
            assert got[name][key] == value, f"{case} {name}: {key}"
