"""Config registry: all 11 architectures, parameter counts against
their published sizes, shape applicability rules."""
import pytest

from repro.config.base import SHAPES, shape_applicable
from repro.configs import ARCH_IDS, REGISTRY, get_config, get_smoke_config

EXPECTED_ARCHS = {
    "qwen2.5-14b", "olmo-1b", "starcoder2-7b", "qwen2-72b", "mamba2-1.3b",
    "grok-1-314b", "qwen3-moe-235b-a22b", "recurrentgemma-9b", "qwen2-vl-2b",
    "whisper-tiny", "moonlight-16b-a3b",
}

# published total param counts (tolerance: naming conventions vary on
# embedding/bias accounting)
PARAM_TARGETS = {
    "qwen2.5-14b": (14.8e9, 0.15),
    "olmo-1b": (1.2e9, 0.25),
    "starcoder2-7b": (7.2e9, 0.15),
    "qwen2-72b": (72.7e9, 0.15),
    "mamba2-1.3b": (1.3e9, 0.25),
    "grok-1-314b": (314e9, 0.20),
    "qwen3-moe-235b-a22b": (235e9, 0.20),
    "recurrentgemma-9b": (9.2e9, 0.30),
    "qwen2-vl-2b": (2.2e9, 0.35),
    "whisper-tiny": (39e6, 0.50),
    "moonlight-16b-a3b": (15.96e9, 0.05),
}


def test_registry_complete():
    assert set(ARCH_IDS) == EXPECTED_ARCHS


@pytest.mark.parametrize("arch", sorted(EXPECTED_ARCHS))
def test_param_counts(arch):
    cfg = get_config(arch)
    n = cfg.param_count()
    target, tol = PARAM_TARGETS[arch]
    assert abs(n - target) / target < tol, \
        f"{arch}: {n:.3e} params vs published {target:.3e}"


@pytest.mark.parametrize("arch", sorted(EXPECTED_ARCHS))
def test_smoke_configs_small(arch):
    cfg = get_smoke_config(arch)
    assert cfg.param_count() < 5e6, "smoke config should be tiny"
    assert cfg.family == get_config(arch).family


def test_moe_active_params():
    cfg = get_config("qwen3-moe-235b-a22b")
    active = cfg.active_param_count()
    assert abs(active - 22e9) / 22e9 < 0.35, f"active {active:.3e} vs ~22e9"
    grok = get_config("grok-1-314b")
    assert grok.active_param_count() < grok.param_count() / 2
    moon = get_config("moonlight-16b-a3b")      # "A3B": ~3B active
    assert abs(moon.active_param_count() - 2.9e9) / 2.9e9 < 0.1


def test_moonlight_chip_share_sizes():
    """One chip's EP-8 share of 14 layers: the dense layer 83.0 M, 13 MoE
    layers of 100.4 M each, embedding plus head 83.9 M; 1,472 M in all."""
    import dataclasses
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), num_layers=14,
                              experts_held=8, vocab_size=20480)
    parts = dict(cfg.param_breakdown())
    assert cfg.stack_layers == 13 and cfg.layer_kinds() == ("mla",) * 13
    assert parts["embed"] + parts["lm_head"] == 83_886_080
    assert abs(cfg.param_count() - 1_472e6) < 0.5e6


def test_long500k_applicability():
    long = SHAPES["long_500k"]
    runnable = {a for a in ARCH_IDS
                if shape_applicable(get_config(a), long)[0]}
    assert runnable == {"mamba2-1.3b", "recurrentgemma-9b"}
    for a in ARCH_IDS:
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert shape_applicable(get_config(a), SHAPES[s])[0]


def test_gqa_configs():
    c = get_config("qwen2.5-14b")
    assert (c.num_heads, c.num_kv_heads, c.head_dim) == (40, 8, 128)
    c = get_config("starcoder2-7b")
    assert (c.num_heads, c.num_kv_heads) == (36, 4)
    c = get_config("recurrentgemma-9b")
    assert c.num_kv_heads == 1 and c.window == 2048
    assert c.layer_kinds()[:3] == ("rglru", "rglru", "local_attn")
