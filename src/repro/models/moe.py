"""Mixture-of-Experts FFN: a held-experts layer that drops no token.

The router scores every token against all `num_experts` experts (softmax,
or sigmoid with a per-layer selection bias that only picks the top k) and
the layer computes the `experts_held` experts from `experts_offset` on:
the expert-parallel share of one chip. Each token's assignments to the
held experts are sorted by expert and run through the grouped matmul
(kernels/grouped_matmul) with no capacity limit; the weighted results are
summed back per token and the shared experts, which every token passes,
are added. What the absent experts would add is left out: on one chip the
layer runs without its exchange. With every expert held it is the whole
layer.

Balance: `apply_moe` returns the sequence-wise balance loss
sum_i f_i P_i over all experts (f_i the share of a sequence's top-k picks
that went to expert i, times E/k; P_i its mean normalised score) and the
step's count of picks per expert, from which the training step moves the
selection bias (steps.update_router_bias).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.lms.policies import tag
from repro.kernels.grouped_matmul import grouped_matmul
from repro.models.layers import ParamDef, apply_mlp, mlp_defs
from repro.models.sharding import split_axes
from repro.obs import get_obs


def moe_defs(cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.held
    scale_out = 0.02 / math.sqrt(2 * cfg.num_layers)
    return {
        "router": ParamDef((d, cfg.num_experts), ("d_model", None),
                           dtype="float32"),
        "w_gate": ParamDef((e, d, f), ("experts", "d_model", "ff")),
        "w_up": ParamDef((e, d, f), ("experts", "d_model", "ff")),
        "w_down": ParamDef((e, f, d), ("experts", "ff", "d_model"),
                           scale=scale_out),
    }


def shared_defs(cfg):
    """The shared experts as one SwiGLU of width shared_experts * d_ff."""
    return mlp_defs(cfg, cfg.shared_experts * cfg.d_ff)


def route(cfg, router, xf, bias=None):
    """xf [T, d] -> (scores [T, E] f32, top_i [T, k], weights [T, k] f32).
    The top k are chosen by score + bias; the weights are the chosen scores
    renormalised, times router_scale."""
    logits = xf.astype(jnp.float32) @ router
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    scores = tag(scores, "router_probs")
    sel = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    _, top_i = jax.lax.top_k(jax.lax.stop_gradient(sel),
                             cfg.experts_per_token)
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    w = top_s / jnp.maximum(top_s.sum(-1, keepdims=True), 1e-20)
    return scores, top_i, w * cfg.router_scale


def balance(cfg, scores, top_i, batch: int):
    """-> (sequence-wise balance loss, picks per expert [E] f32)."""
    e, k = cfg.num_experts, cfg.experts_per_token
    picks = jax.nn.one_hot(top_i, e, dtype=jnp.float32).sum(1)     # [T, E]
    picks = picks.reshape(batch, -1, e)
    s = picks.shape[1]
    f = picks.sum(1) * (e / (k * s))                               # [B, E]
    p = (scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-20))
    p = p.reshape(batch, s, e).mean(1)                             # [B, E]
    aux = jnp.mean(jnp.sum(f * p, -1))
    return aux, jax.lax.stop_gradient(picks.sum((0, 1)))


def held_experts(cfg, p, xf, top_i, w):
    """The held experts' weighted sum per token: xf [T, d] -> (y [T, d]
    f32, rows routed to the held experts)."""
    t, d = xf.shape
    k, held, off = cfg.experts_per_token, cfg.held, cfg.experts_offset
    local = top_i.reshape(-1) - off                                # [T*k]
    mine = (local >= 0) & (local < held)
    # held experts' assignments first, by expert; the rest form one last
    # group that the grouped matmul never computes
    group = jnp.where(mine, local, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=held + 1).astype(jnp.int32)
    rows = jnp.take(xf, order // k, axis=0)                        # [T*k, d]
    # the expert stacks' split over the mesh (experts -> model when it
    # divides): each shard computes its own experts' rows
    ep = split_axes("experts", held)
    g = grouped_matmul(rows, p["w_gate"], sizes, expert_axes=ep)
    u = grouped_matmul(rows, p["w_up"], sizes, expert_axes=ep)
    act = jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu
    h = tag(act(g) * u, "moe_hidden")
    out = grouped_matmul(h, p["w_down"], sizes, expert_axes=ep)    # [T*k, d]
    # back to (token, pick) order, weighted and summed over the picks
    back = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    per_pick = jnp.take(out, back, axis=0).reshape(t, k, d)
    wm = jnp.where(mine.reshape(t, k), w, 0.0)
    # elementwise in f32: a product would round the weights to bf16
    y = jnp.sum(per_pick.astype(jnp.float32) * wm[..., None], axis=1)
    return y, sizes[:held].sum()


def apply_moe(cfg, p, x, bias=None, shared=None):
    """x [B,S,d] -> (y [B,S,d], balance loss, picks per expert [E], rows
    routed to the held experts). `bias` [E]: the selection bias (sigmoid
    routing); `shared`: the shared experts' params."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    get_obs().registry.counter("moe.experts_held").inc(cfg.held)
    with jax.named_scope("moe.route"):
        scores, top_i, w = route(cfg, p["router"], xf, bias)
        aux, picks = balance(cfg, scores, top_i, b)
    with jax.named_scope("moe.experts"):
        y, rows = held_experts(cfg, p, xf, top_i, w)
    y = y.reshape(b, s, d)
    if shared is not None:
        with jax.named_scope("moe.shared"):
            y = y + apply_mlp(cfg, shared, x).astype(jnp.float32)
    return y.astype(x.dtype), aux, picks, rows
