"""Decoder stacks: scan-over-layers with stacked params (fast compile at
80+ layers), heterogeneous hybrid patterns via pattern-group scanning,
whisper-style encoder-decoder, and cache-threaded decode paths.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.config.base import ModelConfig
from repro.core.lms.offload import stream_layer_to_device
from repro.core.lms.policies import tag
from repro.models import attention as attn_mod
from repro.models import kvquant
from repro.models import paging
from repro.models.attention import (attention_defs, project_qkv, out_proj,
                                    decode_attention, mla_defs, project_mla)
from repro.models.layers import (ParamDef, apply_mlp, apply_norm, mlp_defs,
                                 norm_defs, apply_rope, apply_mrope)
from repro.models.moe import moe_defs, apply_moe, shared_defs
from repro.models.rglru import (rglru_defs, apply_rglru, decode_rglru,
                                rglru_cache_defs)
from repro.models.sharding import constrain
from repro.models.ssm import (ssm_defs, apply_ssm, decode_ssm, ssm_cache_defs)

MLA_SERVING = ("latent attention (MLA) trains only: serving needs a latent KV "
               "cache, which is not implemented")

# ---------------------------------------------------------------------------
# Stack planning
# ---------------------------------------------------------------------------

def stack_plan(cfg: ModelConfig):
    """[("scan", pattern_kinds, n_groups)] + optional ("unroll", rem_kinds)."""
    kinds = cfg.layer_kinds()
    if len(set(kinds)) > 1:
        p = len(cfg.block_pattern)
        nfull = cfg.num_layers // p
        rem = kinds[nfull * p:]
        plan = [("scan", tuple(cfg.block_pattern), nfull)]
        if rem:
            plan.append(("unroll", tuple(rem)))
        return plan
    return [("scan", (kinds[0],), len(kinds))]


def _stack(defs, n: int):
    """Add a leading ("layers", n) axis to every ParamDef in a tree."""
    is_def = lambda x: isinstance(x, ParamDef)
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.axes,
                           init=d.init, scale=d.scale, dtype=d.dtype),
        defs, is_leaf=is_def)


def _ffn_defs(cfg: ModelConfig, dense: bool = False):
    """The layer's FFN group(s): experts (with their shared experts) or an
    MLP; `dense`: a leading dense layer's MLP."""
    if dense:
        return {"ffn": mlp_defs(cfg, cfg.dense_d_ff)}
    if not cfg.num_experts:
        return {"ffn": mlp_defs(cfg)}
    out = {"ffn": moe_defs(cfg)}
    if cfg.shared_experts:
        out["shared"] = shared_defs(cfg)
    return out


def layer_defs(cfg: ModelConfig, kind: str, dense: bool = False):
    if kind in ("attn", "local_attn", "enc_attn", "mla"):
        return {"ln1": norm_defs(cfg, cfg.d_model),
                "attn": mla_defs(cfg) if kind == "mla" else attention_defs(cfg),
                "ln2": norm_defs(cfg, cfg.d_model),
                **_ffn_defs(cfg, dense)}
    if kind == "xattn":  # whisper decoder layer: self + cross + mlp
        return {"ln1": norm_defs(cfg, cfg.d_model),
                "attn": attention_defs(cfg),
                "lnx": norm_defs(cfg, cfg.d_model),
                "xattn": attention_defs(cfg, cross=True),
                "ln2": norm_defs(cfg, cfg.d_model),
                "ffn": mlp_defs(cfg)}
    if kind == "ssd":
        return {"ln1": norm_defs(cfg, cfg.d_model), "ssm": ssm_defs(cfg)}
    if kind == "rglru":
        return {"ln1": norm_defs(cfg, cfg.d_model),
                "rec": rglru_defs(cfg),
                "ln2": norm_defs(cfg, cfg.d_model),
                "ffn": moe_defs(cfg) if cfg.num_experts else mlp_defs(cfg)}
    raise ValueError(kind)


def lead_defs(cfg: ModelConfig):
    """The leading dense layer, kept outside the scanned stack (its FFN
    differs from the stack's); None when the model has none."""
    if not cfg.first_dense_layers:
        return None
    if cfg.first_dense_layers != 1:
        raise NotImplementedError("one leading dense layer at most")
    return layer_defs(cfg, cfg.layer_kinds()[0], dense=True)


def decoder_defs(cfg: ModelConfig):
    defs = {}
    for gi, entry in enumerate(stack_plan(cfg)):
        if entry[0] == "scan":
            _, pattern, n = entry
            group = {f"{k}_{i}": layer_defs(cfg, k) for i, k in enumerate(pattern)}
            defs[f"stack{gi}"] = _stack(group, n)
        else:
            _, rem = entry
            defs[f"rem{gi}"] = {f"layer{i}_{k}": layer_defs(cfg, k)
                                for i, k in enumerate(rem)}
    return defs


def encoder_defs(cfg: ModelConfig):
    group = {"enc_attn_0": layer_defs(cfg, "enc_attn")}
    return {"stack0": _stack(group, cfg.encoder_layers),
            "final_norm": norm_defs(cfg, cfg.d_model)}


# ---------------------------------------------------------------------------
# Forward (train / prefill) layer application
# ---------------------------------------------------------------------------

def _rope_qk(cfg, q, k, ctx):
    if cfg.frontend == "audio":
        return q, k  # whisper: absolute sinusoidal positions at embedding
    if cfg.mrope_sections:
        q = apply_mrope(q, ctx["positions3"], cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, ctx["positions3"], cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, ctx["positions"], cfg.rope_theta)
        k = apply_rope(k, ctx["positions"], cfg.rope_theta)
    return q, k


def _ffn(cfg, p, x, ctx=None):
    """-> (x, aux_loss). An expert layer reads its selection bias from
    ctx["router_bias"] and appends (picks per expert, held rows) to
    ctx["moe_stats"] when the context has them."""
    h = apply_norm(cfg, p.get("ln2", {}), x)
    h = tag(constrain(h, "batch", "seq_resid", None), "mlp_norm")
    if "router" in p["ffn"]:
        ctx = ctx or {}
        y, aux, picks, rows = apply_moe(cfg, p["ffn"], h,
                                        ctx.get("router_bias"),
                                        p.get("shared"))
        if "moe_stats" in ctx:
            ctx["moe_stats"].append((picks, rows))
    else:
        y, aux = apply_mlp(cfg, p["ffn"], h), jnp.float32(0.0)
    return x + y, aux


def apply_layer(cfg, kind, p, x, ctx):
    """-> (x, aux_loss)."""
    x = tag(constrain(x, "batch", "seq_resid", None), "resid")
    if kind == "mla":
        with jax.named_scope("attn.mla"):
            h = tag(apply_norm(cfg, p.get("ln1", {}), x), "attn_norm")
            q, k, v = project_mla(cfg, p["attn"], h, ctx["positions"])
            o = attn_mod.attention(q, k, v, causal=True,
                                   impl=ctx["attn_impl"],
                                   chunk=ctx["attn_chunk"])
            o = tag(constrain(o, "batch", "seq", "heads", None), "attn_out")
            x = x + out_proj(cfg, p["attn"], o)
        return _ffn(cfg, p, x, ctx)
    if kind in ("attn", "local_attn", "enc_attn"):
        h = apply_norm(cfg, p.get("ln1", {}), x)
        h = tag(h, "attn_norm")
        q, k, v = project_qkv(cfg, p["attn"], h)
        causal = kind != "enc_attn"
        if causal:
            q, k = _rope_qk(cfg, q, k, ctx)
        window = cfg.window if kind == "local_attn" else 0
        o = attn_mod.attention(q, k, v, causal=causal, window=window,
                               impl=ctx["attn_impl"], chunk=ctx["attn_chunk"])
        o = tag(constrain(o, "batch", "seq", "heads", None), "attn_out")
        x = x + out_proj(cfg, p["attn"], o)
        return _ffn(cfg, p, x)
    if kind == "xattn":
        h = apply_norm(cfg, p.get("ln1", {}), x)
        q, k, v = project_qkv(cfg, p["attn"], h)
        o = attn_mod.attention(q, k, v, causal=True, impl=ctx["attn_impl"],
                               chunk=ctx["attn_chunk"])
        x = x + out_proj(cfg, p["attn"], o)
        hx = apply_norm(cfg, p.get("lnx", {}), x)
        q2, k2, v2 = project_qkv(cfg, p["xattn"], hx, kv_x=ctx["enc_out"])
        o2 = attn_mod.attention(q2, k2, v2, causal=False, impl=ctx["attn_impl"],
                                chunk=ctx["attn_chunk"])
        x = x + out_proj(cfg, p["xattn"], o2)
        return _ffn(cfg, p, x)
    if kind == "ssd":
        h = apply_norm(cfg, p.get("ln1", {}), x)
        y, _ = apply_ssm(cfg, p["ssm"], h, ssd_impl=ctx.get("ssd_impl", "ref"))
        return x + y, jnp.float32(0.0)
    if kind == "rglru":
        h = apply_norm(cfg, p.get("ln1", {}), x)
        x = x + apply_rglru(cfg, p["rec"], h)
        return _ffn(cfg, p, x)
    raise ValueError(kind)


def _stream_depth(stream, n_iter: int) -> int:
    """Effective prefetch depth for a scan group: the schedule's depth when
    it divides the trip count, else 1 (plain per-layer streaming)."""
    d = max(int(getattr(stream, "prefetch_depth", 1)), 1)
    return d if n_iter % d == 0 else 1


def _layer_ctx(ctx, bias, i: int):
    """The context of layer i of a scan iteration: its selection bias and a
    list for its expert statistics, when the model routes over experts."""
    if bias is None:
        return ctx
    return {**ctx, "router_bias": bias[i], "moe_stats": []}


def _per_iter(bias, n_iter: int):
    """[layers, E] selection bias -> [n_iter, layers per iteration, E]."""
    return None if bias is None else bias.reshape(n_iter, -1, bias.shape[-1])


def _stack_stats(stats):
    """[(picks [E], rows)] of one iteration's layers -> (picks [n, E],
    rows [n]), or None."""
    if not stats:
        return None
    return (jnp.stack([p for p, _ in stats]), jnp.stack([r for _, r in stats]))


def _scan_streamed(cfg, stack, carry, ctx, pattern, n_iter, *, policy,
                   no_remat, stream, grad_hook=None, bias=None):
    """Layer-streaming executor for one scan group (the LMS swap, executed).

    The stacked group params arrive host-resident (jit in_shardings carry the
    pinned-host memory kind); the scan visits `prefetch_depth` layers per
    iteration and issues ALL of the group's swap-ins before any of its
    compute, so with depth 2 the copy of layer i+1 is in flight while layer i
    computes — a double buffer XLA's latency-hiding scheduler can overlap.
    The body is remat-wrapped as usual, which makes the backward sweep
    re-issue the same swap-ins in reverse layer order (the mirrored bwd sweep
    of SwapSchedule.bwd_order) instead of pinning all layers in HBM.

    grad_hook: identity-forward reduce-as-you-go wrapper (DDL overlapped
    backward, core/ddl/overlap.py) applied per layer AFTER the swap-in, so in
    the backward sweep the cotangent is DDL-reduced on device first and only
    then hits the swap-in's transpose (the device→host grad stream-out):
    grads stream out reduced as the next layer's params stream in. On
    grads-host plans the hook itself sinks the reduced cotangent to pinned
    host (the gradient host sink), so the bwd sweep keeps only
    ~prefetch_depth layers of gradients device-resident.
    """
    d = _stream_depth(stream, n_iter)
    grouped = compat.tree.map(
        lambda t: t.reshape((n_iter // d, d) + t.shape[1:]), stack)

    def body(c, xs, _pattern=pattern, _d=d, _hook=grad_hook):
        h, a = c
        lp_group, bias = xs
        stats = []
        # swap-in first, compute second: the fetches are independent of the
        # compute below, so copy k+1 overlaps compute k
        bufs = [stream_layer_to_device(compat.tree.map(lambda t: t[k], lp_group))
                for k in range(_d)]
        if _hook is not None:
            bufs = [_hook(b) for b in bufs]
        for k in range(_d):
            for i, kname in enumerate(_pattern):
                lctx = _layer_ctx(ctx, bias, k * len(_pattern) + i)
                h, da = apply_layer(cfg, kname, bufs[k][f"{kname}_{i}"], h,
                                    lctx)
                a = a + da
                stats.extend(lctx.get("moe_stats", ()))
        return (h, a), _stack_stats(stats)

    if not no_remat:
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)
    return jax.lax.scan(body, carry, (grouped, _per_iter(bias, n_iter // d)))


def apply_decoder(cfg, params, x, ctx, *, policy=None, no_remat=False,
                  unroll: bool = False, stream=None, grad_hooks=None,
                  router_bias=None):
    """-> (x, aux_loss, expert statistics). Scans pattern groups with
    optional remat policy.
    unroll=True fully unrolls the layer scan — used by the dry-run so
    compiled.cost_analysis() counts every layer (XLA tallies a while-loop
    body once, ignoring the trip count). stream: a SwapSchedule whose
    params class streams — switches the scan groups to the layer-streaming
    executor (host-resident params, per-layer double-buffered swap-in).
    grad_hooks: {stack group name -> reduce-as-you-go hook} — the DDL
    overlapped backward (per-layer gradient reduction issued inside the
    scan's backward sweep instead of a post-hoc tree pass).
    router_bias: [stack layers, E] selection bias of an expert stack; the
    statistics are then (picks per expert [stack layers, E], rows routed
    to the held experts [stack layers]), else None."""
    aux = jnp.float32(0.0)
    stats = None
    for gi, entry in enumerate(stack_plan(cfg)):
        if entry[0] == "scan":
            _, pattern, n_iter = entry
            stack = params[f"stack{gi}"]
            hook = (grad_hooks or {}).get(f"stack{gi}")

            # the scans' device ops carry train.layers: the forward's as
            # jvp(train.layers), the backward's (remat recompute included)
            # as transpose(jvp(train.layers))
            if stream is not None and not unroll:
                with jax.named_scope("train.layers"):
                    (x, aux), st = _scan_streamed(
                        cfg, stack, (x, aux), ctx, pattern, n_iter,
                        policy=policy, no_remat=no_remat, stream=stream,
                        grad_hook=hook, bias=router_bias)
                stats = _flat_stats(st)
                continue

            def body(carry, xs, _pattern=pattern, _hook=hook):
                h, a = carry
                lp, b = xs
                if _hook is not None:
                    lp = _hook(lp)
                st = []
                for i, k in enumerate(_pattern):
                    lctx = _layer_ctx(ctx, b, i)
                    h, da = apply_layer(cfg, k, lp[f"{k}_{i}"], h, lctx)
                    a = a + da
                    st.extend(lctx.get("moe_stats", ()))
                return (h, a), _stack_stats(st)

            if not no_remat:
                body = jax.checkpoint(body, policy=policy, prevent_cse=False)
            with jax.named_scope("train.layers"):
                (x, aux), st = jax.lax.scan(
                    body, (x, aux), (stack, _per_iter(router_bias, n_iter)),
                    unroll=n_iter if unroll else 1)
            stats = _flat_stats(st)
        else:
            _, rem = entry
            for i, k in enumerate(rem):
                x, da = apply_layer(cfg, k, params[f"rem{gi}"][f"layer{i}_{k}"], x, ctx)
                aux = aux + da
    return x, aux, stats


def _flat_stats(st):
    """Scanned statistics [iterations, layers per iteration, ...] -> one
    row per layer, or None."""
    if st is None:
        return None
    picks, rows = st
    return picks.reshape(-1, picks.shape[-1]), rows.reshape(-1)


def apply_lead(cfg, p, x, ctx, *, policy=None, no_remat=False):
    """The leading dense layer, rematerialized as a scanned layer is."""
    def layer(p_, x_):
        return apply_layer(cfg, cfg.layer_kinds()[0], p_, x_, ctx)

    if not no_remat:
        layer = jax.checkpoint(layer, policy=policy, prevent_cse=False)
    with jax.named_scope("train.lead"):
        return layer(p, x)


def apply_encoder(cfg, params, x, ctx):
    enc_ctx = dict(ctx)

    def body(h, lp):
        h, _ = apply_layer(cfg, "enc_attn", lp["enc_attn_0"], h, enc_ctx)
        return h, None

    x, _ = jax.lax.scan(body, x, params["stack0"])
    return apply_norm(cfg, params["final_norm"], x)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _attn_cache_defs(cfg, batch: int, cache_len: int, window: int = 0):
    s = min(window, cache_len) if window else cache_len
    kd = ParamDef((batch, s, cfg.num_kv_heads, cfg.head_dim),
                  ("batch", "kv_seq", "kv_heads", None), init="zeros")
    return {"k": kd, "v": kd}


def _xattn_cache_defs(cfg, batch: int, cache_len: int):
    d = _attn_cache_defs(cfg, batch, cache_len)
    enc = ParamDef((batch, cfg.encoder_seq, cfg.num_kv_heads, cfg.head_dim),
                   ("batch", "kv_seq", "kv_heads", None), init="zeros")
    d.update({"xk": enc, "xv": enc})
    return d


def layer_cache_defs(cfg, kind, batch: int, cache_len: int):
    if kind == "attn":
        return _attn_cache_defs(cfg, batch, cache_len)
    if kind == "local_attn":
        return _attn_cache_defs(cfg, batch, cache_len, window=cfg.window)
    if kind == "xattn":
        return _xattn_cache_defs(cfg, batch, cache_len)
    if kind == "ssd":
        return ssm_cache_defs(cfg, batch)
    if kind == "rglru":
        return rglru_cache_defs(cfg, batch)
    if kind == "mla":
        raise NotImplementedError(MLA_SERVING)
    raise ValueError(kind)


def cache_defs(cfg, batch: int, cache_len: int):
    defs = {}
    for gi, entry in enumerate(stack_plan(cfg)):
        if entry[0] == "scan":
            _, pattern, n = entry
            group = {f"{k}_{i}": layer_cache_defs(cfg, k, batch, cache_len)
                     for i, k in enumerate(pattern)}
            defs[f"stack{gi}"] = _stack(group, n)
        else:
            _, rem = entry
            defs[f"rem{gi}"] = {f"layer{i}_{k}": layer_cache_defs(cfg, k, batch, cache_len)
                                for i, k in enumerate(rem)}
    return defs


# ---------------------------------------------------------------------------
# Prefill-time cache population helpers
# ---------------------------------------------------------------------------

def _ring_write(cache_k, k_new, seq_len: int):
    """Write the last min(S, W) keys of k_new [B,S,K,D] into ring cache
    [B,W,K,D] at slots abs_pos % W."""
    w = cache_k.shape[1]
    s = k_new.shape[1]
    n = min(s, w)
    src = k_new[:, s - n:]
    slots = (jnp.arange(n) + (s - n)) % w
    return cache_k.at[:, slots].set(src)


def apply_layer_prefill(cfg, kind, p, x, ctx, cache_len: int):
    """Like apply_layer but also returns the populated cache for the layer."""
    x_out_aux = None
    if kind in ("attn", "local_attn"):
        xi = constrain(x, "batch", "seq", None)
        h = apply_norm(cfg, p.get("ln1", {}), xi)
        q, k, v = project_qkv(cfg, p["attn"], h)
        q, k = _rope_qk(cfg, q, k, ctx)
        window = cfg.window if kind == "local_attn" else 0
        o = attn_mod.attention(q, k, v, causal=True, window=window,
                               impl=ctx["attn_impl"], chunk=ctx["attn_chunk"])
        x2 = xi + out_proj(cfg, p["attn"], o)
        x2, aux = _ffn(cfg, p, x2)
        s = min(window, cache_len) if window else cache_len
        b = x.shape[0]
        ck = jnp.zeros((b, s, cfg.num_kv_heads, cfg.head_dim), k.dtype)
        cv = jnp.zeros_like(ck)
        if window:
            ck = _ring_write(ck, k, x.shape[1])
            cv = _ring_write(cv, v, x.shape[1])
        else:
            n = min(x.shape[1], s)
            ck = jax.lax.dynamic_update_slice(ck, k[:, :n], (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v[:, :n], (0, 0, 0, 0))
        return x2, {"k": ck, "v": cv}, aux
    if kind == "xattn":
        xi = constrain(x, "batch", "seq", None)
        h = apply_norm(cfg, p.get("ln1", {}), xi)
        q, k, v = project_qkv(cfg, p["attn"], h)
        o = attn_mod.attention(q, k, v, causal=True, impl=ctx["attn_impl"],
                               chunk=ctx["attn_chunk"])
        x2 = xi + out_proj(cfg, p["attn"], o)
        hx = apply_norm(cfg, p.get("lnx", {}), x2)
        q2, k2, v2 = project_qkv(cfg, p["xattn"], hx, kv_x=ctx["enc_out"])
        o2 = attn_mod.attention(q2, k2, v2, causal=False, impl=ctx["attn_impl"],
                                chunk=ctx["attn_chunk"])
        x2 = x2 + out_proj(cfg, p["xattn"], o2)
        x2, aux = _ffn(cfg, p, x2)
        b = x.shape[0]
        ck = jnp.zeros((b, cache_len, cfg.num_kv_heads, cfg.head_dim), k.dtype)
        cv = jnp.zeros_like(ck)
        n = min(x.shape[1], cache_len)
        ck = jax.lax.dynamic_update_slice(ck, k[:, :n], (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v[:, :n], (0, 0, 0, 0))
        return x2, {"k": ck, "v": cv, "xk": k2, "xv": v2}, aux
    if kind == "ssd":
        h = apply_norm(cfg, p.get("ln1", {}), x)
        l = x.shape[1]
        y, h_final = apply_ssm(cfg, p["ssm"], h, ssd_impl="ref")
        # conv history for decode: last (K-1) pre-conv channels
        from repro.models.ssm import _split_proj
        _, xr, bc, _ = _split_proj(cfg, p["ssm"], h)
        conv_in = jnp.concatenate([xr, bc], axis=-1)
        km1 = cfg.ssm_conv - 1
        if l >= km1:
            conv_hist = conv_in[:, -km1:]
        else:
            conv_hist = jnp.pad(conv_in, ((0, 0), (km1 - l, 0), (0, 0)))
        return x + y, {"h": h_final, "conv": conv_hist}, jnp.float32(0.0)
    if kind == "rglru":
        h = apply_norm(cfg, p.get("ln1", {}), x)
        # replicate apply_rglru but keep final state + conv history
        from repro.models.rglru import _causal_conv as rg_conv, _lru_gates
        gate = jax.nn.gelu(h @ p["rec"]["w_gate_branch"])
        u_pre = h @ p["rec"]["w_x_branch"]
        u = rg_conv(u_pre, p["rec"]["conv_w"], p["rec"]["conv_b"])
        log_a, x_in = _lru_gates(p["rec"], u)
        a = jnp.exp(log_a)

        def combine(c1, c2):
            a1, b1 = c1
            a2_, b2 = c2
            return a1 * a2_, b1 * a2_ + b2

        hseq = jax.lax.associative_scan(combine, (a, x_in), axis=1)[1]
        out = (hseq.astype(x.dtype) * gate) @ p["rec"]["w_out"]
        x2 = x + out
        x2, aux = _ffn(cfg, p, x2)
        l = x.shape[1]
        conv_hist = u_pre[:, -3:]
        if l < 3:
            conv_hist = jnp.pad(u_pre, ((0, 0), (3 - l, 0), (0, 0)))
        return x2, {"h": hseq[:, -1], "conv": conv_hist}, aux
    raise ValueError(kind)


def apply_decoder_prefill(cfg, params, x, ctx, cache_len: int,
                          unroll: bool = False, stream=None):
    """-> (x, cache, aux). Scanned groups also emit stacked caches.
    stream: SwapSchedule — host-resident params are swapped in per layer
    (depth 1 in serving: the per-layer cache emission pins the scan shape)."""
    aux = jnp.float32(0.0)
    cache = {}
    for gi, entry in enumerate(stack_plan(cfg)):
        if entry[0] == "scan":
            _, pattern, _ = entry
            stack = params[f"stack{gi}"]

            def body(carry, lp, _pattern=pattern):
                h, a = carry
                if stream is not None and stream.streams_params:
                    lp = stream_layer_to_device(lp)
                caches = {}
                for i, k in enumerate(_pattern):
                    h, c, da = apply_layer_prefill(cfg, k, lp[f"{k}_{i}"], h, ctx, cache_len)
                    caches[f"{k}_{i}"] = c
                    a = a + da
                return (h, a), caches

            (x, aux), stack_cache = jax.lax.scan(
                body, (x, aux), stack, unroll=entry[2] if unroll else 1)
            cache[f"stack{gi}"] = stack_cache
        else:
            _, rem = entry
            cache[f"rem{gi}"] = {}
            for i, k in enumerate(rem):
                x, c, da = apply_layer_prefill(
                    cfg, k, params[f"rem{gi}"][f"layer{i}_{k}"], x, ctx, cache_len)
                cache[f"rem{gi}"][f"layer{i}_{k}"] = c
                aux = aux + da
    return x, cache, aux


# ---------------------------------------------------------------------------
# Decode layer application
# ---------------------------------------------------------------------------

def apply_layer_decode(cfg, kind, p, x, cache, pos, ctx):
    """x [B,1,d]; -> (x, new_cache)."""
    if kind in ("attn", "local_attn"):
        h = apply_norm(cfg, p.get("ln1", {}), x)
        q, k, v = project_qkv(cfg, p["attn"], h)
        q, k = _rope_qk(cfg, q, k, ctx)
        window = cfg.window if kind == "local_attn" else 0
        smax = cache["k"].shape[1]
        slot = (pos % smax) if window else jnp.minimum(pos, smax - 1)
        # keep the cache layout stable through the in-place update: without
        # the constraints GSPMD reshapes the whole cache (all-to-all) around
        # the dynamic-update-slice every layer
        cache_axes = ("batch", "kv_seq", "kv_heads", None)
        ck = jax.lax.dynamic_update_slice(
            constrain(cache["k"], *cache_axes), k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            constrain(cache["v"], *cache_axes), v, (0, slot, 0, 0))
        ck = constrain(ck, *cache_axes)
        cv = constrain(cv, *cache_axes)
        kv_len = jnp.minimum(pos + 1, smax)
        o = decode_attention(q, ck, cv, kv_len)
        x = x + out_proj(cfg, p["attn"], o)
        x, _ = _ffn(cfg, p, x)
        return x, {"k": ck, "v": cv}
    if kind == "xattn":
        h = apply_norm(cfg, p.get("ln1", {}), x)
        q, k, v = project_qkv(cfg, p["attn"], h)
        smax = cache["k"].shape[1]
        slot = jnp.minimum(pos, smax - 1)
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        o = decode_attention(q, ck, cv, jnp.minimum(pos + 1, smax))
        x = x + out_proj(cfg, p["attn"], o)
        hx = apply_norm(cfg, p.get("lnx", {}), x)
        q2 = jnp.einsum("bsd,dhk->bshk", hx, p["xattn"]["wq"])
        if "bq" in p["xattn"]:
            q2 = q2 + p["xattn"]["bq"]
        o2 = decode_attention(q2, cache["xk"], cache["xv"], cache["xk"].shape[1])
        x = x + out_proj(cfg, p["xattn"], o2)
        x, _ = _ffn(cfg, p, x)
        return x, {"k": ck, "v": cv, "xk": cache["xk"], "xv": cache["xv"]}
    if kind == "ssd":
        h = apply_norm(cfg, p.get("ln1", {}), x)
        y, new_cache = decode_ssm(cfg, p["ssm"], h, cache)
        return x + y, new_cache
    if kind == "rglru":
        h = apply_norm(cfg, p.get("ln1", {}), x)
        y, new_cache = decode_rglru(cfg, p["rec"], h, cache)
        x = x + y
        x, _ = _ffn(cfg, p, x)
        return x, new_cache
    raise ValueError(kind)


def _slot_write(cache_t, new_t, slots, active):
    """Per-slot cache write: cache [B,S,...], new [B,1,...], slots [B] write
    positions, active [B] bool. Inactive rows keep their current value, so a
    freed slot's cache region stays byte-stable until its next occupant's
    pages are attached."""
    b = cache_t.shape[0]
    bidx = jnp.arange(b)
    cur = cache_t[bidx, slots]
    val = jnp.where(active.reshape((b,) + (1,) * (cur.ndim - 1)),
                    new_t[:, 0], cur)
    return cache_t.at[bidx, slots].set(val)


def _gate_state(active, new_tree, old_tree):
    """Slot-batched state update gate: inactive rows keep the old state."""
    def sel(n, o):
        m = active.reshape((n.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return compat.tree.map(sel, new_tree, old_tree)


def apply_layer_decode_slots(cfg, kind, p, x, cache, positions, active, ctx):
    """Slot-batched variant of apply_layer_decode: every batch row is an
    independent request at its own position. positions [B] int32, active [B]
    bool. Attention math is row-independent, so an active row's output is
    identical to what a whole-batch decode at that row's position produces —
    the token-parity property the serve engine's join/evict churn relies on.
    """
    b = x.shape[0]
    table = ctx.get("page_table")
    ps = ctx.get("page_size")
    # paged criterion, static and identical to the pool/builder shape rule
    # (a leaf pages iff its seq axis spans the full cache capacity): full
    # attention always pages when a table is present; a local_attn ring
    # pages only when its window covers the whole capacity (the ring never
    # wraps), i.e. its cache width == max_len == max_pages * page_size.
    cap = table.shape[1] * ps if table is not None else 0

    if kind in ("attn", "local_attn"):
        h = apply_norm(cfg, p.get("ln1", {}), x)
        q, k, v = project_qkv(cfg, p["attn"], h)
        q, k = _rope_qk(cfg, q, k, ctx)
        window = cfg.window if kind == "local_attn" else 0
        if table is not None and (window == 0 or window >= cap):
            # paged arena layout (DESIGN.md §9): the new token's row is
            # written THROUGH the page table — no per-slot cache region
            # exists; kv_len masking makes stale page contents unreadable
            arena_axes = (None, None, "kv_heads", None)
            kv_len = jnp.where(active, jnp.minimum(positions + 1, cap), 0)
            scales = {}
            if "k_scale" in cache:
                scale_axes = (None, None, "kv_heads")
                k, ks = kvquant.quantize_kv_leaf(k)
                v, vs = kvquant.quantize_kv_leaf(v)
                scales["k_scale"] = paging.paged_write(
                    constrain(cache["k_scale"], *scale_axes), ks, table,
                    positions, active, ps)
                scales["v_scale"] = paging.paged_write(
                    constrain(cache["v_scale"], *scale_axes), vs, table,
                    positions, active, ps)
            ck = paging.paged_write(constrain(cache["k"], *arena_axes), k,
                                    table, positions, active, ps)
            cv = paging.paged_write(constrain(cache["v"], *arena_axes), v,
                                    table, positions, active, ps)
            ck = constrain(ck, *arena_axes)
            cv = constrain(cv, *arena_axes)
            o = decode_attention(q, ck, cv, kv_len,
                                 k_scale=scales.get("k_scale"),
                                 v_scale=scales.get("v_scale"),
                                 page_table=table)
            x = x + out_proj(cfg, p["attn"], o)
            x, _ = _ffn(cfg, p, x)
            return x, {"k": ck, "v": cv, **scales}
        smax = cache["k"].shape[1]
        slots = (positions % smax) if window else jnp.minimum(positions, smax - 1)
        cache_axes = ("batch", "kv_seq", "kv_heads", None)
        # inactive rows mask every key (kv_len 0): finite garbage, never read
        kv_len = jnp.where(active, jnp.minimum(positions + 1, smax), 0)
        scales = {}
        if "k_scale" in cache:
            # int8 KV pages (serve engine, kv_dtype="int8"): quantize the
            # new token's k/v rows and write codes + per-row scales; the
            # flash-decode kernel fuses the dequantize into the block load
            scale_axes = ("batch", "kv_seq", "kv_heads")
            k, ks = kvquant.quantize_kv_leaf(k)
            v, vs = kvquant.quantize_kv_leaf(v)
            scales["k_scale"] = _slot_write(
                constrain(cache["k_scale"], *scale_axes), ks, slots, active)
            scales["v_scale"] = _slot_write(
                constrain(cache["v_scale"], *scale_axes), vs, slots, active)
        ck = _slot_write(constrain(cache["k"], *cache_axes), k, slots, active)
        cv = _slot_write(constrain(cache["v"], *cache_axes), v, slots, active)
        ck = constrain(ck, *cache_axes)
        cv = constrain(cv, *cache_axes)
        o = decode_attention(q, ck, cv, kv_len,
                             k_scale=scales.get("k_scale"),
                             v_scale=scales.get("v_scale"))
        x = x + out_proj(cfg, p["attn"], o)
        x, _ = _ffn(cfg, p, x)
        return x, {"k": ck, "v": cv, **scales}
    if kind == "xattn":
        h = apply_norm(cfg, p.get("ln1", {}), x)
        q, k, v = project_qkv(cfg, p["attn"], h)
        if table is not None:
            # the decoder self-attention k/v of an encdec layer page like
            # full attention; the encoder cross-KV (xk/xv) stays wholesale
            kv_len = jnp.where(active, jnp.minimum(positions + 1, cap), 0)
            ck = paging.paged_write(cache["k"], k, table, positions,
                                    active, ps)
            cv = paging.paged_write(cache["v"], v, table, positions,
                                    active, ps)
            o = decode_attention(q, ck, cv, kv_len, page_table=table)
            x = x + out_proj(cfg, p["attn"], o)
            hx = apply_norm(cfg, p.get("lnx", {}), x)
            q2 = jnp.einsum("bsd,dhk->bshk", hx, p["xattn"]["wq"])
            if "bq" in p["xattn"]:
                q2 = q2 + p["xattn"]["bq"]
            o2 = decode_attention(q2, cache["xk"], cache["xv"],
                                  cache["xk"].shape[1])
            x = x + out_proj(cfg, p["xattn"], o2)
            x, _ = _ffn(cfg, p, x)
            return x, {"k": ck, "v": cv, "xk": cache["xk"], "xv": cache["xv"]}
        smax = cache["k"].shape[1]
        slots = jnp.minimum(positions, smax - 1)
        ck = _slot_write(cache["k"], k, slots, active)
        cv = _slot_write(cache["v"], v, slots, active)
        kv_len = jnp.where(active, jnp.minimum(positions + 1, smax), 0)
        o = decode_attention(q, ck, cv, kv_len)
        x = x + out_proj(cfg, p["attn"], o)
        hx = apply_norm(cfg, p.get("lnx", {}), x)
        q2 = jnp.einsum("bsd,dhk->bshk", hx, p["xattn"]["wq"])
        if "bq" in p["xattn"]:
            q2 = q2 + p["xattn"]["bq"]
        o2 = decode_attention(q2, cache["xk"], cache["xv"], cache["xk"].shape[1])
        x = x + out_proj(cfg, p["xattn"], o2)
        x, _ = _ffn(cfg, p, x)
        return x, {"k": ck, "v": cv, "xk": cache["xk"], "xv": cache["xv"]}
    if kind == "ssd":
        h = apply_norm(cfg, p.get("ln1", {}), x)
        y, new_cache = decode_ssm(cfg, p["ssm"], h, cache)
        act = active.reshape((b,) + (1,) * (y.ndim - 1))
        return x + jnp.where(act, y, 0), _gate_state(active, new_cache, cache)
    if kind == "rglru":
        h = apply_norm(cfg, p.get("ln1", {}), x)
        y, new_cache = decode_rglru(cfg, p["rec"], h, cache)
        act = active.reshape((b,) + (1,) * (y.ndim - 1))
        x = x + jnp.where(act, y, 0)
        x, _ = _ffn(cfg, p, x)
        return x, _gate_state(active, new_cache, cache)
    raise ValueError(kind)


def apply_decoder_decode_slots(cfg, params, caches, x, positions, active, ctx,
                               unroll: bool = False, stream=None):
    """Slot-batched decode sweep (the serve engine's fixed-shape step):
    -> (x, new_caches). stream: SwapSchedule — host-resident PARAMS swap in
    per layer as in apply_decoder_decode; the KV cache is deliberately NOT
    per-layer streamed here — in serving its host residency is executed by
    the paged pool (serve/kvpool.py), which keeps active slots' pages in HBM
    and spills the backlog, so the decode step always sees a device-resident
    cache."""
    new_caches = {}
    for gi, entry in enumerate(stack_plan(cfg)):
        if entry[0] == "scan":
            _, pattern, _ = entry
            stack = params[f"stack{gi}"]

            def body(h, inp, _pattern=pattern):
                lp, lc = inp
                if stream is not None and stream.streams_params:
                    lp = stream_layer_to_device(lp)
                ncs = {}
                for i, k in enumerate(_pattern):
                    h, ncs[f"{k}_{i}"] = apply_layer_decode_slots(
                        cfg, k, lp[f"{k}_{i}"], h, lc[f"{k}_{i}"],
                        positions, active, ctx)
                return h, ncs

            x, nc = jax.lax.scan(body, x, (stack, caches[f"stack{gi}"]),
                                 unroll=entry[2] if unroll else 1)
            new_caches[f"stack{gi}"] = nc
        else:
            _, rem = entry
            new_caches[f"rem{gi}"] = {}
            for i, k in enumerate(rem):
                key = f"layer{i}_{k}"
                x, nc = apply_layer_decode_slots(
                    cfg, k, params[f"rem{gi}"][key], x,
                    caches[f"rem{gi}"][key], positions, active, ctx)
                new_caches[f"rem{gi}"][key] = nc
    return x, new_caches


# ---------------------------------------------------------------------------
# Chunked prefill (serve engine: prompt processed in fixed-size chunks)
# ---------------------------------------------------------------------------

def apply_layer_prefill_chunk(cfg, kind, p, x, cache, start, length, ctx):
    """One prompt chunk against an already-partially-populated cache.

    x [B,C,d] holds tokens [start, start+C) (tail rows may be padding when
    the prompt length is not a chunk multiple); `length` is the total valid
    token count after this chunk. The chunk's keys land in the cache at
    their absolute positions, then the chunk queries attend over the cache
    with the causal + kv_len masks — per valid query row this is exactly the
    full-prefill softmax (masked slots contribute exact zeros), so chunked
    and whole-prompt prefill produce bitwise-equal logits. Gated to pure
    "attn" stacks: ring (local_attn) and recurrent (ssd/rglru) caches have
    no absolute-position write, so those families prefill in one chunk."""
    if kind != "attn":
        raise ValueError(
            f"chunked prefill supports 'attn' layers only, got {kind!r}")
    xi = constrain(x, "batch", "seq", None)
    h = apply_norm(cfg, p.get("ln1", {}), xi)
    q, k, v = project_qkv(cfg, p["attn"], h)
    q, k = _rope_qk(cfg, q, k, ctx)
    cache_axes = ("batch", "kv_seq", "kv_heads", None)
    ck = jax.lax.dynamic_update_slice(
        constrain(cache["k"], *cache_axes), k, (0, start, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        constrain(cache["v"], *cache_axes), v, (0, start, 0, 0))
    ck = constrain(ck, *cache_axes)
    cv = constrain(cv, *cache_axes)
    o = attn_mod.naive_attention(q, ck, cv, causal=True, q_offset=start,
                                 kv_len=length)
    x2 = xi + out_proj(cfg, p["attn"], o)
    x2, aux = _ffn(cfg, p, x2)
    return x2, {"k": ck, "v": cv}, aux


def apply_decoder_prefill_chunk(cfg, params, caches, x, start, length, ctx,
                                unroll: bool = False, stream=None):
    """-> (x, new_caches): one chunk of the prompt through every layer, the
    cache threaded through the scan like decode (the chunk reads earlier
    chunks' keys and appends its own)."""
    new_caches = {}
    for gi, entry in enumerate(stack_plan(cfg)):
        if entry[0] == "scan":
            _, pattern, _ = entry
            stack = params[f"stack{gi}"]

            def body(h, inp, _pattern=pattern):
                lp, lc = inp
                if stream is not None and stream.streams_params:
                    lp = stream_layer_to_device(lp)
                ncs = {}
                for i, k in enumerate(_pattern):
                    h, ncs[f"{k}_{i}"], _ = apply_layer_prefill_chunk(
                        cfg, k, lp[f"{k}_{i}"], h, lc[f"{k}_{i}"],
                        start, length, ctx)
                return h, ncs

            x, nc = jax.lax.scan(body, x, (stack, caches[f"stack{gi}"]),
                                 unroll=entry[2] if unroll else 1)
            new_caches[f"stack{gi}"] = nc
        else:
            _, rem = entry
            new_caches[f"rem{gi}"] = {}
            for i, k in enumerate(rem):
                key = f"layer{i}_{k}"
                x, nc, _ = apply_layer_prefill_chunk(
                    cfg, k, params[f"rem{gi}"][key], x,
                    caches[f"rem{gi}"][key], start, length, ctx)
                new_caches[f"rem{gi}"][key] = nc
    return x, new_caches


def apply_decoder_decode(cfg, params, caches, x, pos, ctx,
                         unroll: bool = False, stream=None):
    """-> (x, new_caches). stream: SwapSchedule — host-resident params and/or
    KV cache are swapped in per layer inside the scan (depth 1: the cache is
    threaded through the same scan, so there is exactly one live layer slot).
    The updated cache's swap-OUT is the jit out_shardings' host placement."""
    new_caches = {}
    for gi, entry in enumerate(stack_plan(cfg)):
        if entry[0] == "scan":
            _, pattern, _ = entry
            stack = params[f"stack{gi}"]

            def body(h, inp, _pattern=pattern):
                lp, lc = inp
                if stream is not None and stream.streams_params:
                    lp = stream_layer_to_device(lp)
                if stream is not None and stream.streams_kvcache:
                    lc = stream_layer_to_device(lc, cls="kvcache")
                ncs = {}
                for i, k in enumerate(_pattern):
                    h, ncs[f"{k}_{i}"] = apply_layer_decode(
                        cfg, k, lp[f"{k}_{i}"], h, lc[f"{k}_{i}"], pos, ctx)
                return h, ncs

            x, nc = jax.lax.scan(body, x, (stack, caches[f"stack{gi}"]),
                                 unroll=entry[2] if unroll else 1)
            new_caches[f"stack{gi}"] = nc
        else:
            _, rem = entry
            new_caches[f"rem{gi}"] = {}
            for i, k in enumerate(rem):
                key = f"layer{i}_{k}"
                x, nc = apply_layer_decode(
                    cfg, k, params[f"rem{gi}"][key], x, caches[f"rem{gi}"][key], pos, ctx)
                new_caches[f"rem{gi}"][key] = nc
    return x, new_caches
