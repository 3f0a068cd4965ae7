"""Architecture registry: --arch <id> -> (config(), smoke_config())."""
from repro.configs import (
    qwen2_5_14b,
    olmo_1b,
    starcoder2_7b,
    qwen2_72b,
    mamba2_1_3b,
    grok_1_314b,
    qwen3_moe_235b,
    recurrentgemma_9b,
    qwen2_vl_2b,
    whisper_tiny,
    moonlight_16b_a3b,
)

_MODULES = (
    qwen2_5_14b, olmo_1b, starcoder2_7b, qwen2_72b, mamba2_1_3b,
    grok_1_314b, qwen3_moe_235b, recurrentgemma_9b, qwen2_vl_2b, whisper_tiny,
    moonlight_16b_a3b,
)

REGISTRY = {m.ARCH_ID: m for m in _MODULES}
ARCH_IDS = tuple(REGISTRY)


def get_config(arch_id: str):
    if arch_id not in REGISTRY:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(REGISTRY)}")
    return REGISTRY[arch_id].config()


def get_smoke_config(arch_id: str):
    if arch_id not in REGISTRY:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(REGISTRY)}")
    return REGISTRY[arch_id].smoke_config()
