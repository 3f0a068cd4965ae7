"""Common layers: param declaration, norms, MLPs, RoPE / M-RoPE, embeddings.

Params are plain pytrees (nested dicts of jnp arrays). A single declarative
source of truth — ParamDef — yields shapes, logical sharding axes, and init,
from which both `init_params` (real arrays) and `abstract_params`
(ShapeDtypeStruct + PartitionSpec; used by the dry-run) are derived.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.sharding import constrain

# ---------------------------------------------------------------------------
# Param declaration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis names, len == len(shape)
    init: str = "normal"              # normal | zeros | ones
    scale: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def init_array(key, d: ParamDef):
    dt = jnp.dtype(d.dtype)
    if d.init == "zeros":
        return jnp.zeros(d.shape, dt)
    if d.init == "ones":
        return jnp.ones(d.shape, dt)
    if d.init == "normal":
        return (jax.random.normal(key, d.shape, jnp.float32) * d.scale).astype(dt)
    if d.init == "ssm_a":   # A_log init: log of uniform [1, 16]
        u = jax.random.uniform(key, d.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(jnp.float32)
    if d.init == "lru_lambda":  # RG-LRU Lambda param: softplus-inverse of decay
        u = jax.random.uniform(key, d.shape, jnp.float32, 0.9, 0.999)
        # a = sigmoid(L)^(c) parametrization handled in block; store raw
        return jnp.log(u / (1 - u)).astype(jnp.float32)
    raise ValueError(d.init)


def tree_init(key, defs):
    """defs: nested dict of ParamDef -> same-structure dict of arrays."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    arrs = [init_array(k, d) for k, d in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, arrs)


def tree_abstract(defs, mesh=None, rules=None):
    """-> (pytree of ShapeDtypeStruct, pytree of PartitionSpec). Specs are
    pruned to divisible dims (jit in_shardings reject padding)."""
    from repro.models.sharding import spec as mkspec, prune_spec
    is_def = lambda x: isinstance(x, ParamDef)
    shapes = jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype)),
                          defs, is_leaf=is_def)
    specs = jax.tree.map(
        lambda d: prune_spec(d.shape, mkspec(*d.axes, mesh=mesh, rules=rules), mesh),
        defs, is_leaf=is_def)
    return shapes, specs


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_defs(cfg, dim: int, logical: str = "d_model"):
    if cfg.norm_type == "rmsnorm":
        return {"scale": ParamDef((dim,), (logical,), init="ones", dtype="float32")}
    if cfg.norm_type == "layernorm":
        return {"scale": ParamDef((dim,), (logical,), init="ones", dtype="float32"),
                "bias": ParamDef((dim,), (logical,), init="zeros", dtype="float32")}
    if cfg.norm_type == "layernorm_nonparam":
        return {}
    raise ValueError(cfg.norm_type)


def apply_norm(cfg, p, x, eps=None):
    eps = eps or cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        if cfg.norm_type == "layernorm":
            out = out * p["scale"] + p["bias"]
    return out.astype(x.dtype)


def gated_rmsnorm(p, x, gate, eps=1e-5):
    """Mamba-2 output norm: RMSNorm(x * silu(gate))."""
    xf = (x * jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype)).astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_defs(cfg, f: Optional[int] = None):
    d, f = cfg.d_model, f or cfg.d_ff
    scale_out = 0.02 / math.sqrt(2 * cfg.num_layers)
    if cfg.mlp_act in ("swiglu", "geglu"):
        defs = {"w_gate": ParamDef((d, f), ("d_model", "ff")),
                "w_up": ParamDef((d, f), ("d_model", "ff")),
                "w_down": ParamDef((f, d), ("ff", "d_model"), scale=scale_out)}
        if cfg.use_bias:
            defs["b_gate"] = ParamDef((f,), ("ff",), init="zeros")
            defs["b_up"] = ParamDef((f,), ("ff",), init="zeros")
            defs["b_down"] = ParamDef((d,), ("d_model",), init="zeros")
    else:
        defs = {"w_up": ParamDef((d, f), ("d_model", "ff")),
                "w_down": ParamDef((f, d), ("ff", "d_model"), scale=scale_out)}
        if cfg.use_bias:
            defs["b_up"] = ParamDef((f,), ("ff",), init="zeros")
            defs["b_down"] = ParamDef((d,), ("d_model",), init="zeros")
    return defs


def apply_mlp(cfg, p, x):
    from repro.core.lms.policies import tag  # activation checkpoint names
    if cfg.mlp_act in ("swiglu", "geglu"):
        # tag the projection outputs: remat otherwise re-runs both matmuls
        g = tag(constrain(x @ p["w_gate"], "batch", "seq", "ff"), "mlp_hidden")
        u = tag(constrain(x @ p["w_up"], "batch", "seq", "ff"), "mlp_hidden")
        if cfg.use_bias:
            g = g + p["b_gate"]
            u = u + p["b_up"]
        act = jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu
        h = act(g) * u
    else:
        u = tag(constrain(x @ p["w_up"], "batch", "seq", "ff"), "mlp_hidden")
        if cfg.use_bias:
            u = u + p["b_up"]
        h = jax.nn.gelu(u)
    h = tag(constrain(h, "batch", "seq", "ff"), "mlp_hidden")
    out = h @ p["w_down"]
    if cfg.use_bias:
        out = out + p["b_down"]
    return out


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, D]; positions: [..., S] int32 (broadcastable)."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta))                      # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs         # [..., S, D/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
                           axis=-1).astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections: Tuple[int, ...]):
    """Qwen2-VL M-RoPE. positions3: [3, ..., S] (t/h/w). `sections` splits the
    D/2 rotary frequencies among the three position streams."""
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    freqs = jnp.asarray(rope_freqs(d, theta))                      # [half]
    # per-frequency position source
    sec_ids = np.repeat(np.arange(3), np.asarray(sections))       # [half]
    pos = jnp.stack([positions3[i] for i in range(3)], axis=0)     # [3, ..., S]
    pos_per_freq = jnp.take(pos, jnp.asarray(sec_ids), axis=0)     # [half, ..., S]
    pos_per_freq = jnp.moveaxis(pos_per_freq, 0, -1)               # [..., S, half]
    ang = pos_per_freq.astype(jnp.float32) * freqs                 # [..., S, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
                           axis=-1).astype(x.dtype)


def sinusoidal_row(pos, d: int):
    """Single sinusoidal position row for a traced scalar position."""
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    ang = pos.astype(jnp.float32) * div
    return jnp.stack([jnp.sin(ang), jnp.cos(ang)], axis=-1).reshape(d)


def sinusoidal_positions(seq: int, d: int, offset: int = 0):
    pos = np.arange(offset, offset + seq, dtype=np.float32)[:, None]
    div = np.exp(np.arange(0, d, 2, dtype=np.float32) * (-math.log(10000.0) / d))
    out = np.zeros((seq, d), np.float32)
    out[:, 0::2] = np.sin(pos * div)
    out[:, 1::2] = np.cos(pos * div)
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_defs(cfg):
    defs = {"embedding": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "d_model"),
                                  scale=0.02, dtype="float32")}
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size), ("d_model", "vocab"))
    return defs


def embed_tokens(cfg, p, tokens):
    emb = p["embedding"].astype(jnp.bfloat16)
    out = jnp.take(emb, tokens, axis=0)
    return constrain(out, "batch", "seq", None)


def lm_logits(cfg, p, x):
    if cfg.tie_embeddings:
        w = p["embedding"].astype(jnp.bfloat16).T
    else:
        w = p["lm_head"]
    logits = x @ w
    return constrain(logits, "batch", "seq", "vocab")


def cross_entropy(logits, labels, ignore_id: int = -1):
    """Mean token CE in fp32; labels == ignore_id are masked."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None].clip(0), axis=-1)[..., 0]
    mask = (labels != ignore_id).astype(jnp.float32)
    loss = (lse - ll) * mask
    return loss.sum() / jnp.maximum(mask.sum(), 1.0)
