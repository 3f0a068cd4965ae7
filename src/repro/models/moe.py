"""Mixture-of-Experts FFN: a held-experts layer that drops no token.

The router scores every token against all `num_experts` experts (softmax,
or sigmoid with a per-layer selection bias that only picks the top k) and
the layer computes the `experts_held` experts from `experts_offset` on:
the expert-parallel share of one chip. Each token's assignments to the
held experts are sorted by expert and run through the grouped matmul
(kernels/grouped_matmul) in chunks sized to the held experts' share, as
many as they fill, so there is no capacity limit; the weighted results
are summed back per token and the shared experts, which every token
passes, are added. What the absent experts would add is left out: on one chip the
layer runs without its exchange. With every expert held it is the whole
layer.

Balance: `apply_moe` returns the sequence-wise balance loss
sum_i f_i P_i over all experts (f_i the share of a sequence's top-k picks
that went to expert i, times E/k; P_i its mean normalised score) and the
step's count of picks per expert, from which the training step moves the
selection bias (steps.update_router_bias).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.lms.policies import tag
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.grouped_matmul.kernel import TM
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


# A chunk holds 1.5x the held experts' mean share of the picks: routing
# balanced by the selection bias keeps a layer's held share within a few
# percent of that mean, and a layer that exceeds a chunk runs another one:
# overflow costs time, never tokens.
CHUNK_SLACK = 1.5


def chunk_rows(cfg, tokens: int) -> int:
    """C, the rows of one chunk of held picks for `tokens` tokens: the
    slack times the held experts' mean share of the tokens x k picks,
    rounded up to the grouped matmul's row tile, and at most every pick
    (one chunk is then the whole layer's row buffer)."""
    picks = tokens * cfg.experts_per_token
    c = math.ceil(CHUNK_SLACK * picks * cfg.held / cfg.num_experts)
    return min(picks, -(-c // TM) * TM)


def _sort(cfg, top_i, c: int):
    """-> (the picks' flat indices sorted by held expert, the rest after
    them, padded with pick 0 to whole chunks; the held experts' bounds in
    that order [held + 1], the last the held rows)."""
    k, held = cfg.experts_per_token, cfg.held
    local = top_i.reshape(-1) - cfg.experts_offset                 # [T*k]
    group = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(group, stable=True)
    order = jnp.pad(order, (0, (-order.shape[0]) % c))
    sizes = jnp.bincount(group, length=held + 1)[:held]
    bounds = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(sizes).astype(jnp.int32)])
    return order, bounds


def _chunk_picks(cfg, order, bounds, wf, start, c: int):
    """Sorted positions [start, start + c) -> (their picks [c], their
    tokens [c], their weights [c] f32, 0 past the held picks, each held
    expert's rows in the chunk with the rest as one last group
    [held + 1])."""
    pick = jax.lax.dynamic_slice_in_dim(order, start, c)
    wt = jnp.where(start + jnp.arange(c) < bounds[-1], wf[pick], 0.0)
    ends = jnp.clip(bounds - start, 0, c)
    sizes = jnp.append(jnp.diff(ends), c - ends[-1])
    return pick, pick // cfg.experts_per_token, wt, sizes


def _experts(cfg, sizes, rows, wt, wg, wu, wd):
    """One chunk's rows [c, d] through their experts' SwiGLU, times their
    weights [c] f32 -> [c, d] f32; rows of the last group are zero."""
    # the expert stacks' split over the mesh (experts -> model when it
    # divides): each shard computes its own experts' rows
    ep = split_axes("experts", cfg.held)
    g = grouped_matmul(rows, wg, sizes, expert_axes=ep)
    u = grouped_matmul(rows, wu, sizes, expert_axes=ep)
    act = jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu
    out = grouped_matmul(act(g) * u, wd, sizes, expert_axes=ep)
    # elementwise in f32: a product would round the weights to bf16
    return out.astype(jnp.float32) * wt[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_sum(cfg, c, xf, top_i, w, wg, wu, wd):
    """The held experts' weighted sum per token, y [T, d] f32, one chunk
    of c sorted held picks at a time."""
    return _held_sum_fwd(cfg, c, xf, top_i, w, wg, wu, wd)[0]


def _held_sum_fwd(cfg, c, xf, top_i, w, wg, wu, wd):
    order, bounds = _sort(cfg, top_i, c)
    wf = w.reshape(-1)

    def body(carry):
        j, y = carry
        _, tok, wt, sizes = _chunk_picks(cfg, order, bounds, wf, j * c, c)
        out = _experts(cfg, sizes, jnp.take(xf, tok, axis=0), wt, wg, wu, wd)
        return j + 1, y.at[tok].add(out)

    y0 = jnp.zeros(xf.shape, jnp.float32)
    _, y = jax.lax.while_loop(lambda s: s[0] * c < bounds[-1], body, (0, y0))
    # the inputs alone: the backward sorts again and recomputes each chunk
    return y, (xf, top_i, w, wg, wu, wd)


def _held_sum_bwd(cfg, c, res, dy):
    xf, top_i, w, wg, wu, wd = res
    order, bounds = _sort(cfg, top_i, c)
    wf = w.reshape(-1)

    def body(carry):
        j, dx, dw, *dws = carry
        pick, tok, wt, sizes = _chunk_picks(cfg, order, bounds, wf, j * c, c)
        _, vjp = jax.vjp(functools.partial(_experts, cfg, sizes),
                         jnp.take(xf, tok, axis=0), wt, wg, wu, wd)
        # rows past the held picks come out zero, and so do their
        # weights' gradients: the padding's pick 0 gains nothing
        drows, dwt, *dch = vjp(jnp.take(dy, tok, axis=0))
        return (j + 1, dx.at[tok].add(drows), dw.at[pick].add(dwt),
                *(a + b for a, b in zip(dws, dch)))

    init = (0, jnp.zeros_like(xf), jnp.zeros_like(wf),
            *(jnp.zeros_like(a) for a in (wg, wu, wd)))
    _, dx, dw, *dws = jax.lax.while_loop(lambda s: s[0] * c < bounds[-1],
                                         body, init)
    return (dx, None, dw.reshape(w.shape), *dws)


_held_sum.defvjp(_held_sum_fwd, _held_sum_bwd)


def held_experts(cfg, p, xf, top_i, w):
    """The held experts' weighted sum per token: xf [T, d] -> (y [T, d]
    f32, rows routed to the held experts).

    Dropless over the held picks only: the picks are sorted by held expert
    (the rest after them) and run in chunks of `chunk_rows` sorted picks,
    as many as the held rows fill (one when the routing is balanced); each
    chunk gathers its tokens' rows, runs the grouped matmuls and adds its
    weighted results to its tokens. No array has a row for every pick
    unless one chunk is every pick (every expert held, or few tokens). The
    backward runs the same chunks again (the inputs are the only
    residuals), so memory is bounded by the chunk at any load."""
    c = chunk_rows(cfg, xf.shape[0])
    get_obs().registry.counter("moe.chunk_rows").inc(c)
    local = top_i - cfg.experts_offset
    rows = ((local >= 0) & (local < cfg.held)).sum()
    return _held_sum(cfg, c, xf, top_i, w, p["w_gate"], p["w_up"],
                     p["w_down"]), rows


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
