"""LMS planner invariants (hypothesis property tests) + behaviour on the
assigned architectures."""
import pytest
from tests.util import given, settings, st

from repro import hw as hwlib
from repro.config.base import (SHAPES, SINGLE_POD, MULTI_POD, LMSConfig,
                               ShapeConfig)
from repro.configs import ARCH_IDS, get_config
from repro.core.lms.planner import (activation_classes, layer_work_bytes,
                                    plan_memory, plan_to_policy,
                                    hbm_traffic_model)


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
