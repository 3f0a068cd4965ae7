"""Serving driver. Default path is the continuous-batching engine
(repro.serve): chunked prefill, slot-batched decode, paged host-spilling KV
pool, temperature/top-k sampling. `--static` runs the old whole-batch
prefill-then-decode loop (the baseline the engine is benchmarked and
parity-tested against).

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
        --requests 8 --slots 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import MeshSpec, ShapeConfig
from repro.configs import get_config, get_smoke_config
from repro.launch.cache import configure_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import kvquant
from repro.models.model import Model
from repro.obs import configure, get_obs, profiler_trace
from repro.serve import (ServeEngine, decode_step_batch,
                         static_batch_from_requests, synth_requests)
from repro.train.steps import (StepSpec, build_decode_step,
                               build_prefill_step)


def run_static(model, mesh, reqs, prompt_len: int, gen: int, params=None):
    """Static whole-batch greedy baseline: one prefill over every request's
    prompt, then `gen-1` lockstep decode steps. The ONE jitted prefill path
    (`build_prefill_step(cache_len=...)`) emits the decode-capacity cache
    directly. -> (params, tokens [N, gen], timings dict)."""
    cfg = model.cfg
    n = len(reqs)
    total = prompt_len + gen
    prefill_shape = ShapeConfig("serve_prefill", "prefill", prompt_len, n)
    prefill_fn, params_sh, _, _ = build_prefill_step(
        model, prefill_shape, mesh, spec=StepSpec(cache_len=total))
    decode_shape = ShapeConfig("serve", "decode", total, n)
    decode_fn, _, _, _ = build_decode_step(model, decode_shape, mesh,
                                           spec=StepSpec(donate=True))
    if params is None:
        params = jax.device_put(model.init(jax.random.key(0)), params_sh)
    batch = static_batch_from_requests(cfg, reqs)

    t0 = time.monotonic()
    logits, cache = prefill_fn(params, batch)
    logits.block_until_ready()
    t_prefill = time.monotonic() - t0

    toks = jnp.argmax(logits, axis=-1)[:, None]
    out_tokens = [toks]
    t0 = time.monotonic()
    for i in range(gen - 1):
        pos = jnp.int32(prompt_len + i)
        step_batch = decode_step_batch(
            cfg, toks, jnp.full((n,), prompt_len + i, jnp.int32))
        logits, cache = decode_fn(params, cache, step_batch, pos)
        toks = jnp.argmax(logits, axis=-1)[:, None]
        out_tokens.append(toks)
    jax.block_until_ready(toks)
    t_decode = time.monotonic() - t0
    gen_toks = np.asarray(jnp.concatenate(out_tokens, axis=1))
    return params, gen_toks, {
        "prefill_s": t_prefill, "decode_s": t_decode,
        "decode_tok_s": (gen - 1) * n / max(t_decode, 1e-9)}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--requests", "--batch", dest="requests", type=int,
                   default=8, help="request-trace length")
    p.add_argument("--slots", type=int, default=4,
                   help="concurrent decode slots (engine)")
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--mesh", default="1x1")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy; >0 samples")
    p.add_argument("--top-k", type=int, default=0,
                   help="top-k filter for sampling (0 = full vocab)")
    p.add_argument("--page-size", type=int, default=16,
                   help="KV pool page size in tokens")
    p.add_argument("--prefill-chunk", type=int, default=32,
                   help="chunked-prefill width (0 = whole prompt)")
    p.add_argument("--kv-dtype", choices=("model", "int8"), default="model",
                   help="KV page storage width: int8 stores codes + per-row "
                        "scales (~half the page bytes, DESIGN.md §8)")
    p.add_argument("--static", action="store_true",
                   help="run the whole-batch baseline loop instead")
    # observability (DESIGN.md §12)
    p.add_argument("--obs-jsonl", default="",
                   help="stream span events to this JSONL file as they "
                        "are recorded")
    p.add_argument("--trace", default="",
                   help="run the engine under the JAX profiler, writing its "
                        "trace (host spans and device ops on one clock) "
                        "under this directory")
    args = p.parse_args(argv)
    configure_compile_cache()
    if args.static and (args.temperature > 0 or args.top_k):
        p.error("--temperature/--top-k sample in the engine only; the "
                "--static baseline loop is greedy by construction")
    if args.static and kvquant.validate_kv_dtype(args.kv_dtype) != "model":
        p.error("--kv-dtype applies to the engine's paged pool; the "
                "--static baseline decodes a model-width cache")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    dims = tuple(int(x) for x in args.mesh.split("x"))
    mesh_spec = MeshSpec(dims, ("data", "model")[:len(dims)] if len(dims) <= 2
                         else ("pod", "data", "model"))
    mesh = make_mesh(mesh_spec)
    model = Model(cfg, attn_impl="naive" if args.smoke else "blockwise")
    rng = np.random.default_rng(0)
    reqs = synth_requests(cfg, args.requests, args.prompt_len, args.gen, rng)

    if args.static:
        _, gen_toks, t = run_static(model, mesh, reqs, args.prompt_len,
                                    args.gen)
        print(f"prefill: {t['prefill_s']*1e3:.1f} ms | decode: "
              f"{t['decode_s']*1e3:.1f} ms ({t['decode_tok_s']:.1f} tok/s)")
        print("generated token ids (first row):", gen_toks[0][:16])
        return 0

    configure(jsonl_path=args.obs_jsonl or None)
    obs = get_obs()
    total = args.prompt_len + args.gen
    slots = min(args.slots, args.requests)
    eng = ServeEngine(model, mesh, slots=slots,
                      max_len=total, page_size=args.page_size,
                      prefill_chunk=args.prefill_chunk,
                      temperature=args.temperature, top_k=args.top_k,
                      kv_dtype=args.kv_dtype, obs=obs)
    with profiler_trace(args.trace):
        results = eng.run(reqs)
    m = eng.metrics()
    returned = int(m["pool_fetched_pages"] + m["pool_prefetched_pages"])
    print(f"served {len(results)} requests | decode {m['decode_tok_s']:.1f} "
          f"tok/s | ttft {m.get('ttft_mean_s', 0)*1e3:.1f} ms | "
          f"tpot p50/p95 {m.get('tpot_p50_s', 0)*1e3:.1f}/"
          f"{m.get('tpot_p95_s', 0)*1e3:.1f} ms | "
          f"concurrency {m['mean_concurrency']:.2f} | pages spilled/returned "
          f"{int(m['pool_spilled_pages'])}/{returned} "
          f"({int(m['pool_prefetched_pages'])} staged ahead)")
    print("generated token ids (first request):",
          np.asarray(results[reqs[0].rid])[:16])
    if args.trace:
        print(f"profiler trace: {args.trace}")
    print("-- metrics --")
    for line in eng.obs.registry.summary_lines():
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
