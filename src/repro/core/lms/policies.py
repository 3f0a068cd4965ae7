"""LMS activation tagging + remat policy construction.

This is the JAX analogue of TFLMS's graph rewriting: instead of inserting
swap-out/swap-in `Identity` nodes, activations are *named* with
`checkpoint_name`, and a `jax.remat` policy decides per name whether the
tensor is (a) saved in HBM, (b) offloaded to pinned host memory (the swap),
or (c) rematerialized in the backward pass. The LMS planner chooses the
assignment; this module turns the assignment into a policy object.
"""
from __future__ import annotations

from typing import Dict, Iterable

import jax
from jax.ad_checkpoint import checkpoint_name

# Every activation class a decoder layer produces, in rough size order.
# The planner reasons about these names; blocks tag tensors with them.
ACTIVATION_NAMES = (
    "resid",        # residual stream entering each layer   [B,S,d]
    "attn_norm",    # post-norm attn input                  [B,S,d]
    "mlp_norm",     # post-norm mlp input                   [B,S,d]
    "qkv",          # projected q (k,v smaller w/ GQA)      [B,S,H,D]
    "attn_out",     # attention output pre-proj             [B,S,H,D]
    "mlp_hidden",   # MLP hidden                            [B,S,f]
    "moe_hidden",   # expert hidden, priced only: moe.py    [C,f]
                    # recomputes it in its own backward
    "router_probs", # router softmax                        [B,S,E]
    "ssd_state",    # per-chunk SSD states                  [B,nc,H,P,N]
    "ssd_xz",       # ssm in-proj output                    [B,S,2*di]
    "lru_h",        # RG-LRU hidden sequence                [B,S,w]
    "logits",       # never offloaded; listed for the planner's size model
)


def tag(x, name: str):
    return checkpoint_name(x, name)


def build_policy(assignment: Dict[str, str]):
    """assignment: name -> "save" | "offload" | "remat".

    Returns a jax.remat policy. Anything unnamed or marked "remat" is
    recomputed during backward. The offload side emits device-placement
    annotations the XLA:CPU SPMD partitioner cannot handle inside shard_map
    ("Side-effect HLO must have sharding"), so on CPU offloaded names are
    compiled as saved — the graph is otherwise identical and the planner's
    swap accounting is unchanged (see DESIGN.md §2 caveat 2).
    """
    from repro.core.lms.offload import effective_kind
    saved = sorted(n for n, v in assignment.items() if v == "save")
    offl = sorted(n for n, v in assignment.items() if v == "offload")
    if offl and effective_kind("pinned_host") is None:
        saved = sorted(set(saved) | set(offl))
        offl = []
    if not offl:
        return jax.checkpoint_policies.save_only_these_names(*saved)
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=saved,
        names_which_can_be_offloaded=offl,
        offload_src="device",
        offload_dst="pinned_host",
    )


def policy_from_preset(preset: str):
    if preset == "none":
        return None  # no remat wrapper at all
    if preset == "full":
        return jax.checkpoint_policies.nothing_saveable
    if preset == "save_all":
        return jax.checkpoint_policies.everything_saveable
    if preset == "offload":
        return build_policy({n: "offload" for n in ("resid", "mlp_hidden", "qkv")})
    raise ValueError(preset)
