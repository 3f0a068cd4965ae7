"""Logical-axis sharding: params and activations carry *logical* axis names;
a rule table maps them to mesh axes. GSPMD handles non-divisible dims (e.g.
40 heads on a 16-way `model` axis) by padding — which is why the model runs
under GSPMD while DDL owns the data-parallel collectives in manual shard_map.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes). Axes absent from the
# mesh are dropped at spec-build time, so the same rules serve 1-device
# smoke tests, the (data, model) pod mesh, and the (pod, data, model) mesh.
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ff": ("model",),
    "experts": ("model",),
    "d_inner": ("model",),
    "ssm_heads": ("model",),
    "lru": ("model",),
    # deliberately unsharded logical axes
    "layers": (), "seq": (), "d_model": (), "head_dim": (), "state": (),
    "conv": (), "pos3": (), "window": (), "chunk": (),
    # decode KV-cache sequence dim: unsharded by default; the flash-decode
    # optimization maps it to ("model",) so each TP rank holds a slice of
    # the cache and attention reduces partial softmax stats (see §Perf)
    "kv_seq": (),
}

KV_SEQ_SHARDED_RULES = {**DEFAULT_RULES, "kv_seq": ("model",)}

# Megatron-style sequence parallelism: the residual stream / norm inputs are
# sharded over `model` along the sequence dim; GSPMD then lowers the
# TP boundary to all-gather (entering attention/MLP) + reduce-scatter
# (leaving), halving boundary traffic vs all-reduce AND shrinking the saved
# residual stream (the LMS swap volume) by the TP degree.
DEFAULT_RULES["seq_resid"] = ()
SEQ_PARALLEL_RULES = {**DEFAULT_RULES, "seq_resid": ("model",)}

def rules_without(axes=("pod", "data"), rules: Optional[dict] = None) -> dict:
    """Rule table with the given mesh axes removed — for use INSIDE a
    shard_map manual over those axes (with_sharding_constraint there may only
    mention auto axes)."""
    rules = rules or DEFAULT_RULES
    drop = set(axes)
    return {k: tuple(a for a in v if a not in drop) for k, v in rules.items()}


_ctx = threading.local()


def _get_env():
    return getattr(_ctx, "env", None)


@contextlib.contextmanager
def sharding_env(mesh: Optional[Mesh], rules: Optional[dict] = None):
    """Activate a mesh + rule table for `spec`/`constrain` below."""
    prev = _get_env()
    _ctx.env = (mesh, rules or DEFAULT_RULES)
    try:
        yield
    finally:
        _ctx.env = prev


def spec(*logical_axes: Optional[str], mesh: Optional[Mesh] = None,
         rules: Optional[dict] = None) -> P:
    """Build a PartitionSpec from logical axis names (None = replicated dim)."""
    env = _get_env()
    if mesh is None and env is not None:
        mesh, env_rules = env
        rules = rules or env_rules
    rules = rules or DEFAULT_RULES
    mesh_axes = set(mesh.axis_names) if mesh is not None else set()
    parts = []
    used = set()
    for ax in logical_axes:
        if ax is None:
            parts.append(None)
            continue
        mapped = tuple(a for a in rules.get(ax, ()) if a in mesh_axes
                       and a not in used)  # a mesh axis may appear only once
        used.update(mapped)
        parts.append(mapped if len(mapped) > 1 else (mapped[0] if mapped else None))
    # trim trailing Nones (canonical form)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def split_axes(logical_axis: str, dim: int) -> tuple:
    """The active mesh's axes that split a dimension of size `dim` named
    `logical_axis` (as `prune_spec` would keep them); () without a mesh,
    where the split is one way or where it does not divide `dim`."""
    env = _get_env()
    if env is None or env[0] is None:
        return ()
    mesh, rules = env
    axes = tuple(a for a in rules.get(logical_axis, ())
                 if a in mesh.axis_names)
    f = 1
    for a in axes:
        f *= mesh.shape[a]
    return axes if f > 1 and dim % f == 0 else ()


def named_sharding(mesh: Mesh, *logical_axes, memory_kind: Optional[str] = None,
                   rules: Optional[dict] = None) -> NamedSharding:
    s = NamedSharding(mesh, spec(*logical_axes, mesh=mesh, rules=rules))
    if memory_kind:
        s = s.with_memory_kind(memory_kind)
    return s


def constrain(x, *logical_axes):
    """with_sharding_constraint when a mesh is active; no-op otherwise."""
    env = _get_env()
    if env is None or env[0] is None:
        return x
    mesh, rules = env
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec(*logical_axes, mesh=mesh, rules=rules)))


def prune_spec(shape: Sequence[int], s: P, mesh: Optional[Mesh]) -> P:
    """Drop spec entries whose dimension is not divisible by the mapped mesh
    extent. jit in_shardings (unlike with_sharding_constraint) reject
    non-divisible shardings, so e.g. 6 kv-heads on a 16-way model axis or a
    batch of 1 on the 32-way DP axes fall back to replication."""
    if mesh is None:
        return s
    parts = list(s) + [None] * (len(shape) - len(s))
    out = []
    used = set()
    for dim, ax in zip(shape, parts):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        f = 1
        for a in axes:
            f *= mesh.shape[a]
        ok = f > 0 and dim % f == 0
        # a mesh axis may appear once per spec: first divisible dim wins
        # (e.g. MoE [E, d, ff] with experts->model AND ff->model: grok's 8
        # experts don't divide 16 -> EP pruned, TP on ff survives; qwen3's
        # 128 experts divide -> EP kept, ff entry dropped)
        if ok and any(a in used for a in axes):
            ok = False
        if ok:
            used.update(axes)
        out.append(ax if ok else None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard_factor(mesh: Optional[Mesh], logical_axis: str,
                 rules: Optional[dict] = None) -> int:
    """How many ways `logical_axis` is split on `mesh` (for the LMS planner)."""
    if mesh is None:
        return 1
    rules = rules or DEFAULT_RULES
    f = 1
    for a in rules.get(logical_axis, ()):
        if a in mesh.axis_names:
            f *= mesh.shape[a]
    return f
