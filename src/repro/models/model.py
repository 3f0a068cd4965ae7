"""Public model API: `Model(cfg)` with init / loss / prefill / decode_step,
abstract params + shardings for the dry-run, and per-arch `input_specs`.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig, ShapeConfig
from repro.models import transformer as tr
from repro.models.layers import (embed_defs, embed_tokens, lm_logits,
                                 cross_entropy, norm_defs, apply_norm,
                                 sinusoidal_positions, tree_init, tree_abstract,
                                 ParamDef)
from repro.models.moe import chunk_rows
from repro.models.sharding import constrain, prune_spec, spec as mkspec
from repro.core.lms.offload import stream_layer_to_device


def stream_in_unscanned(params, stream):
    """With host-resident params the decoder scan groups swap in layer by
    layer; every other leaf compute reads — the embedding (and tied head),
    the final norm, unrolled tail layers, the encoder — is brought to the
    device once, up front. Identity unless `stream` streams params."""
    if stream is None or not stream.streams_params:
        return params
    out = {k: v if k == "decoder" else stream_layer_to_device(v)
           for k, v in params.items()}
    out["decoder"] = {k: v if k.startswith("stack")
                      else stream_layer_to_device(v)
                      for k, v in params["decoder"].items()}
    return out


class Model:
    def __init__(self, cfg: ModelConfig, *, attn_impl: str = "blockwise",
                 attn_chunk: int = 512, ssd_impl: str = "ref",
                 unroll: bool = False):
        self.cfg = cfg
        self.attn_impl = attn_impl
        self.attn_chunk = attn_chunk
        self.ssd_impl = ssd_impl
        self.unroll = unroll

    # ---- params ----------------------------------------------------------
    def param_defs(self):
        cfg = self.cfg
        defs = {"embed": embed_defs(cfg),
                "decoder": tr.decoder_defs(cfg),
                "final_norm": norm_defs(cfg, cfg.d_model)}
        lead = tr.lead_defs(cfg)
        if lead is not None:
            # outside "decoder": the stacks' leaves are named per layer of
            # their leading axis, which a one-layer stack would collide with
            defs["lead"] = lead
        if cfg.is_encdec:
            defs["encoder"] = tr.encoder_defs(cfg)
        return defs

    def init(self, rng):
        return tree_init(rng, self.param_defs())

    def abstract_params(self, mesh=None, rules=None):
        return tree_abstract(self.param_defs(), mesh=mesh, rules=rules)

    # ---- inputs ----------------------------------------------------------
    def input_specs(self, shape: ShapeConfig, mesh=None, rules=None):
        """ShapeDtypeStruct stand-ins + PartitionSpecs for every model input
        of the given shape. No device allocation."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        sd = jax.ShapeDtypeStruct
        bspec = mkspec("batch", mesh=mesh, rules=rules)

        def tok(bb, ss):
            return sd((bb, ss), jnp.int32)

        if shape.kind in ("train", "prefill"):
            if cfg.family == "vlm":
                specs = {"embeds": sd((b, s, cfg.d_model), jnp.bfloat16),
                         "positions3": sd((3, b, s), jnp.int32),
                         "labels": tok(b, s)}
                shards = {"embeds": mkspec("batch", None, None, mesh=mesh, rules=rules),
                          "positions3": mkspec(None, "batch", None, mesh=mesh, rules=rules),
                          "labels": bspec}
            elif cfg.family == "audio":
                specs = {"enc_embeds": sd((b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16),
                         "tokens": tok(b, s), "labels": tok(b, s)}
                shards = {"enc_embeds": mkspec("batch", None, None, mesh=mesh, rules=rules),
                          "tokens": bspec, "labels": bspec}
            else:
                specs = {"tokens": tok(b, s), "labels": tok(b, s)}
                shards = {"tokens": bspec, "labels": bspec}
            shards = {k: prune_spec(specs[k].shape, v, mesh)
                      for k, v in shards.items()}
            return specs, shards

        # decode: one new token against a cache of length s
        if cfg.family == "vlm":
            specs = {"embeds": sd((b, 1, cfg.d_model), jnp.bfloat16),
                     "positions3": sd((3, b, 1), jnp.int32)}
            shards = {"embeds": mkspec("batch", None, None, mesh=mesh, rules=rules),
                      "positions3": mkspec(None, "batch", None, mesh=mesh, rules=rules)}
        else:
            specs = {"tokens": tok(b, 1)}
            shards = {"tokens": bspec}
        shards = {k: prune_spec(specs[k].shape, v, mesh) for k, v in shards.items()}
        specs["pos"] = sd((), jnp.int32)
        shards["pos"] = mkspec(mesh=mesh, rules=rules)
        return specs, shards

    def cache_abstract(self, shape: ShapeConfig, mesh=None, rules=None):
        defs = tr.cache_defs(self.cfg, shape.global_batch, shape.seq_len)
        return tree_abstract(defs, mesh=mesh, rules=rules)

    def init_cache(self, batch: int, cache_len: int):
        defs = tr.cache_defs(self.cfg, batch, cache_len)
        is_def = lambda x: isinstance(x, ParamDef)
        return jax.tree.map(
            lambda d: jnp.zeros(d.shape, jnp.dtype(d.dtype)), defs, is_leaf=is_def)

    # ---- context ---------------------------------------------------------
    def _ctx(self, batch: Dict, seq: int, pos=None, offset=None):
        cfg = self.cfg
        ctx = {"attn_impl": self.attn_impl, "attn_chunk": self.attn_chunk,
               "ssd_impl": self.ssd_impl}
        if cfg.family == "vlm":
            ctx["positions3"] = batch["positions3"]
        else:
            if pos is None:
                # offset: chunked prefill — the chunk's tokens sit at
                # absolute positions [offset, offset+seq)
                positions = jnp.arange(seq)[None, :] + \
                    (0 if offset is None else offset)
            else:
                positions = jnp.full((1, 1), 0, jnp.int32) + pos
            ctx["positions"] = positions
        return ctx

    def _embed_in(self, params, batch, *, decode=False):
        cfg = self.cfg
        if cfg.family == "vlm":
            x = batch["embeds"]
        else:
            x = embed_tokens(cfg, params["embed"], batch["tokens"])
        return x

    # ---- train forward ----------------------------------------------------
    def forward(self, params, batch, *, policy=None, no_remat=False,
                stream=None, grad_hooks=None):
        """-> (logits [B,S,V], aux_loss). stream: SwapSchedule for the
        layer-streaming executor (host-resident params swapped in per layer).
        grad_hooks: per-stack-group DDL reduce-as-you-go hooks (overlapped
        backward — see core/ddl/overlap.py)."""
        logits, aux, _ = self._forward(params, batch, policy=policy,
                                       no_remat=no_remat, stream=stream,
                                       grad_hooks=grad_hooks)
        return logits, aux

    def _forward(self, params, batch, *, policy=None, no_remat=False,
                 stream=None, grad_hooks=None, router_bias=None):
        """-> (logits, aux_loss, expert statistics of the stack: see
        transformer.apply_decoder)."""
        cfg = self.cfg
        params = stream_in_unscanned(params, stream)
        x = self._embed_in(params, batch)
        seq = x.shape[1]
        ctx = self._ctx(batch, seq)
        if cfg.is_encdec:
            enc = batch["enc_embeds"] + sinusoidal_positions(
                cfg.encoder_seq, cfg.d_model).astype(x.dtype)[None]
            ctx["enc_out"] = tr.apply_encoder(cfg, params["encoder"], enc, ctx)
            x = x + sinusoidal_positions(seq, cfg.d_model).astype(x.dtype)[None]
        if "lead" in params:     # a dense layer: no balance loss
            x, _ = tr.apply_lead(cfg, params["lead"], x, ctx, policy=policy,
                                 no_remat=no_remat)
        x, aux, stats = tr.apply_decoder(
            cfg, params["decoder"], x, ctx, policy=policy, no_remat=no_remat,
            unroll=self.unroll, stream=stream, grad_hooks=grad_hooks,
            router_bias=router_bias)
        x = apply_norm(cfg, params["final_norm"], x)
        return lm_logits(cfg, params["embed"], x), aux, stats

    def loss(self, params, batch, *, policy=None, no_remat=False,
             stream=None, grad_hooks=None, router_bias=None):
        """-> (ce + router_aux_weight * balance loss, metrics). With a
        selection bias the metrics also hold `moe_picks` (picks per expert,
        [stack layers, E]), `moe_held_rows` (rows routed to the held
        experts, all layers) and `moe_held_chunks` (the chunks of
        `moe.chunk_rows` the layers ran them in, all layers)."""
        logits, aux, stats = self._forward(
            params, batch, policy=policy, no_remat=no_remat, stream=stream,
            grad_hooks=grad_hooks, router_bias=router_bias)
        ce = cross_entropy(logits, batch["labels"])
        metrics = {"ce": ce, "aux": aux}
        if stats is not None:
            metrics["moe_picks"] = stats[0]
            metrics["moe_held_rows"] = stats[1].sum().astype(jnp.float32)
            c = chunk_rows(self.cfg, math.prod(logits.shape[:-1]))
            metrics["moe_held_chunks"] = (-(-stats[1] // c)).sum().astype(
                jnp.float32)
        return ce + self.cfg.router_aux_weight * aux, metrics

    # ---- serving ----------------------------------------------------------
    def _check_servable(self):
        if self.cfg.is_mla:
            raise NotImplementedError(tr.MLA_SERVING)

    def prefill(self, params, batch, cache_len: Optional[int] = None,
                stream=None):
        """-> (last-token logits [B,V], cache)."""
        self._check_servable()
        cfg = self.cfg
        params = stream_in_unscanned(params, stream)
        x = self._embed_in(params, batch)
        seq = x.shape[1]
        cache_len = cache_len or seq
        ctx = self._ctx(batch, seq)
        if cfg.is_encdec:
            enc = batch["enc_embeds"] + sinusoidal_positions(
                cfg.encoder_seq, cfg.d_model).astype(x.dtype)[None]
            ctx["enc_out"] = tr.apply_encoder(cfg, params["encoder"], enc, ctx)
            x = x + sinusoidal_positions(seq, cfg.d_model).astype(x.dtype)[None]
        x, cache, _ = tr.apply_decoder_prefill(cfg, params["decoder"], x, ctx,
                                               cache_len, unroll=self.unroll,
                                               stream=stream)
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x[:, -1:])
        return logits[:, 0], cache

    def prefill_chunk(self, params, cache, batch, start, length,
                      stream=None):
        """One chunked-prefill step (serve engine): run the C-token chunk in
        `batch` at absolute positions [start, start+C) against the
        already-populated cache. `length` is the total valid prompt tokens
        after this chunk (tail rows past it are padding). -> (chunk logits
        [B,C,V], cache). Bitwise-equal to whole-prompt prefill per valid row
        (see apply_layer_prefill_chunk)."""
        self._check_servable()
        cfg = self.cfg
        params = stream_in_unscanned(params, stream)
        x = self._embed_in(params, batch)
        seq = x.shape[1]
        ctx = self._ctx(batch, seq, offset=start)
        x, cache = tr.apply_decoder_prefill_chunk(
            cfg, params["decoder"], cache, x, start, length, ctx,
            unroll=self.unroll, stream=stream)
        x = apply_norm(cfg, params["final_norm"], x)
        return lm_logits(cfg, params["embed"], x), cache

    def decode_slots(self, params, cache, batch, positions, active,
                     stream=None, page_size: Optional[int] = None):
        """Slot-batched decode (serve engine): each batch row is an
        independent request. positions [B] int32 per-slot positions,
        active [B] bool slot mask (inactive rows compute but their cache is
        held byte-stable). When the cache carries a top-level "page_table"
        leaf, the pageable k/v leaves are a shared page arena (DESIGN.md §9)
        and `page_size` must be the arena's page length; the table rides
        through unchanged so the jitted step can donate it in place.
        -> (logits [B,V], new_cache)."""
        self._check_servable()
        cfg = self.cfg
        params = stream_in_unscanned(params, stream)
        x = self._embed_in(params, batch, decode=True)
        ctx = self._ctx(batch, 1)
        if cfg.family != "vlm":
            ctx["positions"] = positions[:, None]
        cache = dict(cache)
        table = cache.pop("page_table", None)
        if table is not None:
            assert page_size is not None, "paged cache needs page_size"
            ctx["page_table"] = table
            ctx["page_size"] = page_size
        if cfg.is_encdec:
            from repro.models.layers import sinusoidal_row
            rows = jax.vmap(lambda p: sinusoidal_row(p, cfg.d_model))(positions)
            x = x + rows[:, None, :].astype(x.dtype)
        x, new_cache = tr.apply_decoder_decode_slots(
            cfg, params["decoder"], cache, x, positions, active, ctx,
            unroll=self.unroll, stream=stream)
        if table is not None:
            new_cache["page_table"] = table
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x)
        return logits[:, 0], new_cache

    def decode_step(self, params, cache, batch, pos, stream=None):
        """batch: {"tokens" [B,1]} (or vlm embeds); pos: scalar int32.
        -> (logits [B,V], new_cache)."""
        self._check_servable()
        cfg = self.cfg
        params = stream_in_unscanned(params, stream)
        x = self._embed_in(params, batch, decode=True)
        ctx = self._ctx(batch, 1, pos=pos)
        if cfg.is_encdec:
            from repro.models.layers import sinusoidal_row
            x = x + sinusoidal_row(pos, cfg.d_model).astype(x.dtype)[None, None]
        x, new_cache = tr.apply_decoder_decode(cfg, params["decoder"], cache, x,
                                               pos, ctx, unroll=self.unroll,
                                               stream=stream)
        x = apply_norm(cfg, params["final_norm"], x)
        logits = lm_logits(cfg, params["embed"], x)
        return logits[:, 0], new_cache


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)
