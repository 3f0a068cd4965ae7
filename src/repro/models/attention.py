"""GQA and latent (MLA) attention: parameter defs, three interchangeable implementations
(naive oracle / blockwise online-softmax / Pallas flash kernel), causal and
local-window masking, and KV-cache decode paths.

The blockwise implementation is the dry-run default: it never materializes
the [S, S] score matrix (memory O(S·chunk)), matching the Pallas kernel's
HBM traffic shape, and XLA:CPU can lower it (TPU Pallas cannot lower on CPU).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import ParamDef, apply_rope, apply_mrope
from repro.models.sharding import constrain
from repro.core.lms.policies import tag

NEG_INF = -1e30


def attention_defs(cfg, cross: bool = False):
    d, h, k, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale_out = 0.02 / math.sqrt(2 * cfg.num_layers)
    bias = cfg.qkv_bias or cfg.use_bias
    defs = {
        "wq": ParamDef((d, h, hd), ("d_model", "heads", "head_dim")),
        "wk": ParamDef((d, k, hd), ("d_model", "kv_heads", "head_dim")),
        "wv": ParamDef((d, k, hd), ("d_model", "kv_heads", "head_dim")),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "d_model"), scale=scale_out),
    }
    if bias:
        defs["bq"] = ParamDef((h, hd), ("heads", "head_dim"), init="zeros")
        defs["bk"] = ParamDef((k, hd), ("kv_heads", "head_dim"), init="zeros")
        defs["bv"] = ParamDef((k, hd), ("kv_heads", "head_dim"), init="zeros")
    if cfg.use_bias:
        defs["bo"] = ParamDef((d,), ("d_model",), init="zeros")
    return defs


def project_qkv(cfg, p, x, kv_x=None):
    """-> q [B,S,H,D], k/v [B,Skv,K,D]. kv_x!=None => cross attention."""
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    # tag all three projections: saving/offloading them spares the backward
    # pass from re-running the projection matmuls under remat
    q = tag(constrain(q, "batch", "seq", "heads", None), "qkv")
    k = tag(constrain(k, "batch", "seq", "kv_heads", None), "qkv")
    v = tag(constrain(v, "batch", "seq", "kv_heads", None), "qkv")
    return q, k, v


def mla_defs(cfg):
    """Latent attention without q-LoRA: q = x wq; c = x wkv_a; k_pe =
    x wk_rope (k_pe is shared by the heads); [k_nope; v] = RMSNorm(c)
    wkv_b per head. The published [d, 576] wkv_a is kept as its two parts
    and wq as [d, heads * (nope + rope)]: host arrays [d, 576] and
    [d, heads, 192] get layouts whose row blocks the TPU cannot stream."""
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dr, dv = cfg.qk_rope_dim, cfg.v_head_dim
    dn = cfg.head_dim - dr
    scale_out = 0.02 / math.sqrt(2 * cfg.num_layers)
    return {
        "wq": ParamDef((d, h * (dn + dr)), ("d_model", "heads")),
        "wkv_a": ParamDef((d, r), ("d_model", None)),
        "wk_rope": ParamDef((d, dr), ("d_model", None)),
        "kv_norm": ParamDef((r,), (None,), init="ones", dtype="float32"),
        "wkv_b": ParamDef((r, h, dn + dv), (None, "heads", "head_dim")),
        "wo": ParamDef((h, dv, d), ("heads", "head_dim", "d_model"),
                       scale=scale_out),
    }


def project_mla(cfg, p, x, positions):
    """-> q, k [B,S,H,nope+rope] (RoPE on the rope part), v [B,S,H,dv]."""
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    dn = cfg.head_dim - dr
    q = (x @ p["wq"]).reshape(x.shape[:2] + (cfg.num_heads, dn + dr))
    k_pe = x @ p["wk_rope"]
    cf = (x @ p["wkv_a"]).astype(jnp.float32)
    cf = cf * jax.lax.rsqrt(jnp.mean(cf * cf, -1, keepdims=True)
                            + cfg.norm_eps) * p["kv_norm"]
    kvb = jnp.einsum("bsr,rhk->bshk", cf.astype(x.dtype), p["wkv_b"])
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    q_pe = apply_rope(q[..., dn:], positions, cfg.rope_theta)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], q_pe], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, k_nope.shape[:3] + (dr,))], -1)
    q = tag(constrain(q, "batch", "seq", "heads", None), "qkv")
    k = tag(constrain(k, "batch", "seq", "heads", None), "qkv")
    v = tag(constrain(v, "batch", "seq", "heads", None), "qkv")
    return q, k, v


def out_proj(cfg, p, o):
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if "bo" in p:
        out = out + p["bo"]
    return constrain(out, "batch", "seq", None)


# ---------------------------------------------------------------------------
# Naive oracle (tests / tiny shapes)
# ---------------------------------------------------------------------------

def _gqa_expand(q, k_heads):
    """[B,S,H,D] -> [B,S,K,G,D] grouped view for GQA einsums."""
    b, s, h, d = q.shape
    g = h // k_heads
    return q.reshape(b, s, k_heads, g, d)


def naive_attention(q, k, v, *, causal: bool, window: int = 0,
                    q_offset: int = 0, kv_len: Optional[jnp.ndarray] = None):
    """q [B,Sq,H,D], k/v [B,Skv,K,D]. fp32 softmax. Exact oracle."""
    b, sq, h, d = q.shape
    kh = k.shape[2]
    qg = _gqa_expand(q, kh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(d)
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask &= kpos < kv_len
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return o.reshape(b, sq, h, v.shape[-1])


# ---------------------------------------------------------------------------
# Blockwise (flash-style, pure jnp: causal query blocks or a scan over KV
# chunks)
# ---------------------------------------------------------------------------

# the largest f32 probability matrix, [B,H,Sq,Skv], that causal
# self-attention keeps for its backward through the KV-chunk scan
# (olmo-1b's 4 x 2048 tokens of 16 heads: 1.07 GB); past it, query blocks
KV_SCAN_MAX_SCORE_BYTES = 2 << 30


def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        chunk: int = 512, q_offset: int = 0):
    """Exact attention in blocks of `chunk`; matches naive_attention to
    fp32-accumulation tolerance. v may be narrower or wider than q and k
    (latent attention: 128 against 192).

    An online softmax over KV chunks, O(Sq·chunk) live memory; its
    backward keeps every chunk's f32 probabilities, [B,H,Sq,Skv] in all.
    Causal self-attention from position 0 with no window whose
    probabilities would outgrow `KV_SCAN_MAX_SCORE_BYTES` runs by query
    blocks instead (`_causal_query_blocks`), which keep none."""
    b, sq, h, d = q.shape
    if (causal and not window and q_offset == 0 and sq == k.shape[1]
            and 4 * b * h * sq * sq > KV_SCAN_MAX_SCORE_BYTES):
        return _causal_query_blocks(q, k, v, block=chunk)
    dv = v.shape[-1]
    kh = k.shape[2]
    g = h // kh
    skv = k.shape[1]
    chunk = min(chunk, skv)
    pad = (-skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkc = k.shape[1] // chunk
    qg = _gqa_expand(q, kh).astype(jnp.float32) / math.sqrt(d)
    qpos = jnp.arange(sq) + q_offset

    kc = k.reshape(b, nkc, chunk, kh, d)
    vc = v.reshape(b, nkc, chunk, kh, dv)

    def body(carry, inputs):
        m, l, acc = carry
        kb, vb, cidx = inputs
        kpos = cidx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kb.astype(jnp.float32))
        mask = jnp.ones((sq, chunk), bool)
        mask &= (kpos[None, :] < skv)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kh, g, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(nkc)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    o = jnp.moveaxis(o, 3, 1).reshape(b, sq, h, dv)  # [B,K,G,Sq,D] -> [B,Sq,H,D]
    return o.astype(q.dtype)


def _causal_query_blocks(q, k, v, *, block: int):
    """Causal self-attention one block of `block` query rows at a time, each
    against the keys up to its last row (about half the score work of a
    full square), every block rematerialized: the backward keeps no score
    block, where a KV-chunk scan keeps every chunk's f32 probabilities
    (4 GB a layer at 4 x 4096 tokens of 16 heads). Products in the inputs'
    dtype with f32 accumulation, an f32 softmax."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    if g > 1:   # grouped kv heads: each query head gets its group's k, v
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scale = 1.0 / math.sqrt(d)

    def one(qb, kb, vb, q0):
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                        preferred_element_type=jnp.float32) * scale
        qpos = q0 + jnp.arange(qb.shape[1])[:, None]
        sc = jnp.where(jnp.arange(kb.shape[1])[None, :] <= qpos, sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vb.dtype), vb,
                       preferred_element_type=jnp.float32)
        return o.astype(q.dtype)

    one = jax.checkpoint(one, static_argnums=(3,))
    outs = []
    for q0 in range(0, s, block):
        q1 = min(q0 + block, s)
        outs.append(one(q[:, q0:q1], k[:, :q1], v[:, :q1], q0))
    return jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# Block-local attention (RecurrentGemma local_attn, train/prefill)
# ---------------------------------------------------------------------------

def local_block_attention(q, k, v, *, window: int, q_offset: int = 0):
    """Exact sliding-window causal attention for window <= block size.
    Queries in block i attend to keys in blocks {i-1, i}: O(S·2w) compute."""
    b, s, h, d = q.shape
    kh = k.shape[2]
    w = window
    pad = (-s) % w
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = q.shape[1]
    nb = sp // w
    g = h // kh
    qb = q.reshape(b, nb, w, kh, g, d).astype(jnp.float32) / math.sqrt(d)
    kb = k.reshape(b, nb, w, kh, d)
    vb = v.reshape(b, nb, w, kh, d)
    k2 = jnp.concatenate([jnp.pad(kb[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0))), kb], axis=2)
    v2 = jnp.concatenate([jnp.pad(vb[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0))), vb], axis=2)
    s_ = jnp.einsum("bnqkgd,bnskd->bnkgqs", qb, k2.astype(jnp.float32))
    qpos = jnp.arange(w)[:, None] + w                 # position within 2w context
    kpos = jnp.arange(2 * w)[None, :]
    mask = (kpos <= qpos) & (kpos > qpos - w)         # [w, 2w] causal+window
    # global key validity: first block's "previous" keys are padding
    blk = jnp.arange(nb)[:, None]
    kglob = blk * w + (jnp.arange(2 * w)[None, :] - w)   # [nb, 2w]
    valid = (kglob >= 0) & (kglob < s)
    full = mask[None, :, :] & valid[:, None, :]          # [nb, w, 2w]
    s_ = jnp.where(full[None, :, None, None, :, :], s_, NEG_INF)
    p = jax.nn.softmax(s_, axis=-1)
    o = jnp.einsum("bnkgqs,bnskd->bnqkgd", p.astype(v2.dtype), v2)
    o = o.reshape(b, sp, h, d)[:, :s]
    return o


# ---------------------------------------------------------------------------
# Decode (single new token against a cache)
# ---------------------------------------------------------------------------

def dense_decode_attention(q, k_cache, v_cache, kv_len, *, k_scale=None,
                           v_scale=None):
    """Dense decode oracle: q [B,1,H,D]; caches [B,Smax,K,D]; kv_len scalar
    or [B] (per-slot lengths). k_scale/v_scale [B,Smax,K] iff the caches
    hold int8 codes. Reads all Smax positions. ONE implementation shared
    with the kernel-test oracle (flash_decode_ref) so the shipped CPU
    lowering and the reference the Pallas kernel is tested against cannot
    drift — including the kv_len==0 exact-zero contract."""
    from repro.kernels.flash_attention.ref import flash_decode_ref
    o = flash_decode_ref(q[:, 0], k_cache, v_cache, kv_len,
                         k_scale=k_scale, v_scale=v_scale)
    return o[:, None]


def decode_attention(q, k_cache, v_cache, kv_len, *, window: int = 0,
                     k_scale=None, v_scale=None, page_table=None):
    """Decode-attention entry (the serve hot path): q [B,1,H,D]; caches
    [B,Smax,K,D]; kv_len: count of valid slots — a scalar (whole-batch
    decode) or a [B] vector (slot-batched decode, each request at its own
    position). For window caches (ring buffers) validity is positional
    recency, so kv_len covers them too. k_scale/v_scale: per-row f32 scales
    iff the caches hold int8 codes (int8 KV pages).

    page_table [B,max_pages] int32: when given, the caches (and scales)
    are a shared page arena [P,page_size,K,D] and slot b's position p lives
    at (page_table[b, p // page_size], p % page_size) — the serve engine's
    paged layout (DESIGN.md §9). The arena layout carries no window rings,
    so window is rejected with a table.

    Dispatch: the split-KV flash-decode Pallas kernel on TPU (or under
    REPRO_PALLAS_INTERPRET=1 off TPU) — online softmax, fused
    dequantize, length-aware blocking so a slot at position p streams ~p
    positions, not Smax, the paged variant routing its BlockSpecs through
    the table; the dense einsum elsewhere (XLA:CPU cannot lower TPU Pallas
    natively)."""
    from repro.kernels.gates import use_pallas
    if page_table is not None:
        from repro.kernels.flash_attention import ops as fa_ops
        from repro.kernels.flash_attention.ref import flash_decode_paged_ref
        if use_pallas():
            return fa_ops.flash_decode_paged(q, k_cache, v_cache, kv_len,
                                             page_table, k_scale=k_scale,
                                             v_scale=v_scale)
        o = flash_decode_paged_ref(q[:, 0], k_cache, v_cache, kv_len,
                                   page_table, k_scale=k_scale,
                                   v_scale=v_scale)
        return o[:, None]
    if use_pallas():
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_decode(q, k_cache, v_cache, kv_len,
                                   k_scale=k_scale, v_scale=v_scale)
    return dense_decode_attention(q, k_cache, v_cache, kv_len,
                                  k_scale=k_scale, v_scale=v_scale)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def attention(q, k, v, *, causal: bool = True, window: int = 0,
              impl: str = "blockwise", chunk: int = 512, q_offset: int = 0):
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal, window=window, q_offset=q_offset)
    if impl == "blockwise":
        if window and not causal:
            raise ValueError("window requires causal")
        if window and q.shape[1] == k.shape[1]:
            return local_block_attention(q, k, v, window=window, q_offset=q_offset)
        return blockwise_attention(q, k, v, causal=causal, window=window,
                                   chunk=chunk, q_offset=q_offset)
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        # q_offset threads through (it used to be silently dropped, which
        # broke chunked prefill / partial-cache calls under impl="pallas")
        return fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                      q_offset=q_offset)
    raise ValueError(impl)
