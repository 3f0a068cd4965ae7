"""CI driver for the static-analysis gate (DESIGN.md §11).

    python -m repro.analysis.run --out analysis_report.json

Abstractly traces every step builder in train/steps.py on the smoke
config — train fwd/bwd (with the streamed-optimizer sweep when the plan
streams), zero1 train, prefill, static whole-batch decode, and the
slot-batched serve decode in model-width / int8 / int8+paged-arena
variants — runs every jaxpr-audit check on each, runs the repo lint
pass, and verifies the recompile sentinel (all slot-churn scenarios map
to ONE step signature: JXA006 if not). Exit 1 on any gating finding;
the JSON report is the artifact CI uploads.

Everything here is backend-free: no compile, no weights, runs on the
CPU-only CI runner in seconds.
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.jaxpr_audit import audit_step, aval_fingerprint
from repro.analysis.lint import default_paths, lint_paths
from repro.analysis.report import AnalysisReport, Finding
from repro.config.base import (DDLConfig, LMSConfig, MeshSpec, ShapeConfig,
                               TrainConfig)
from repro.configs import get_smoke_config
from repro.core.lms.planner import plan_memory, plan_serve_memory
from repro.launch.mesh import make_mesh
from repro.models import kvquant, paging
from repro.models.model import Model
from repro.models.paging import PageArena

S = jax.ShapeDtypeStruct


def _f32(s):
    return S(s.shape, jnp.float32)


def _allow_streams(plan) -> bool:
    """Per-layer device_puts inside the layer scan ARE the executor when
    the plan's SwapSchedule streams — JXA003 only bites un-planned ones."""
    sched = getattr(plan, "swap_schedule", None)
    return bool(sched is not None and getattr(sched, "stream", ()))


def _host_leaves(residency, **classes):
    """Flat avals of every leaf whose residency class the plan declares
    host — these must never be device_put whole back onto device."""
    out = []
    for cls, tree in classes.items():
        if tree is not None and residency.get(cls) == "host":
            out.extend(jax.tree_util.tree_leaves(tree))
    return out


def slot_decode_builder(model, cfg, mspec, mesh, *, slots, max_len, page,
                        kv_dtype="model", use_arena=False):
    """Build one slot-decode variant plus the abstract args to trace it
    with (reconstructing the cache avals exactly as the builder does)."""
    from repro.train.steps import build_slot_decode_step
    dshape = ShapeConfig("a_slots", "decode", max_len, slots)
    plan = plan_serve_memory(cfg, dshape, mspec, slots=slots,
                             page_size=page, kv_dtype=kv_dtype)
    arena = None
    if use_arena:
        kvp = plan.kv_paging
        device_pages = (kvp.device_pages if kvp is not None
                        and kvp.device_pages else slots * (max_len // page))
        arena = PageArena(page_size=page, device_pages=device_pages,
                          slots=slots, max_pages=max_len // page)
    from repro.train.steps import StepSpec
    fn, _, _, _ = build_slot_decode_step(
        model, dshape, mesh,
        spec=StepSpec(plan=plan, donate=True, kv_dtype=kv_dtype, arena=arena))
    cavals, cspecs = model.cache_abstract(dshape, mesh)
    if kvquant.is_int8(kv_dtype):
        cavals, cspecs = kvquant.quantize_cache_abstract(
            cavals, cspecs, dshape.seq_len)
    if arena is not None:
        cavals, cspecs = paging.page_cache_abstract(
            cavals, cspecs, dshape.seq_len, arena)
    pshapes, _ = model.abstract_params(mesh)
    batch = {"tokens": S((slots, 1), jnp.int32)}
    pos = S((slots,), jnp.int32)
    act = S((slots,), jnp.bool_)
    args = (pshapes, cavals, batch, pos, act)
    return fn, args, plan, cavals


def audit_all_steps(arch: str = "olmo-1b", *, seq: int = 32, batch: int = 2,
                    slots: int = 2, max_len: int = 16, page: int = 4):
    """StepAudit per builder (the tentpole sweep). Sizes mirror the smoke
    tests: big enough to exercise scans/pages, small enough to trace in
    seconds."""
    from repro.optim.adamw import AdamState
    from repro.train.steps import (StepSpec, TrainState, Zero1State,
                                   build_decode_step, build_prefill_step,
                                   build_train_step, build_zero1_train_step)
    cfg = get_smoke_config(arch)
    mspec = MeshSpec((1, 1), ("data", "model"))
    mesh = make_mesh(mspec)
    model = Model(cfg, attn_impl="naive")
    pshapes, _ = model.abstract_params(mesh)
    audits = []

    # --- train fwd/bwd (+ streamed optimizer sweep when the plan streams)
    tshape = ShapeConfig("a_train", "train", seq, batch)
    tplan = plan_memory(cfg, tshape, mspec, LMSConfig(enabled=True))
    tcfg = TrainConfig(model=cfg, shape=tshape, mesh=mspec,
                       ddl=DDLConfig(mode="allreduce"))
    fn, _, _ = build_train_step(model, tcfg, mesh,
                                spec=StepSpec(plan=tplan, donate=True))
    state_abs = TrainState(
        step=S((), jnp.int32), params=pshapes,
        opt=AdamState(step=S((), jnp.int32),
                      mu=jax.tree.map(_f32, pshapes),
                      nu=jax.tree.map(_f32, pshapes),
                      master=jax.tree.map(_f32, pshapes)))
    bspecs, _ = model.input_specs(tshape, mesh)
    audits.append(audit_step(
        "train_step", fn, (state_abs, bspecs), expect_donation=True,
        host_avals=_host_leaves(tplan.residency, params=pshapes,
                                optimizer=state_abs.opt),
        allow_scan_transfers=_allow_streams(tplan),
        plan_peak_bytes=tplan.peak_bytes))

    # --- zero1 train (flat packed optimizer shards)
    zplan = plan_memory(cfg, tshape, mspec, LMSConfig(enabled=True),
                        zero1=True)
    zcfg = TrainConfig(model=cfg, shape=tshape, mesh=mspec,
                       ddl=DDLConfig(mode="zero1"))
    zfn, _, _, packspec = build_zero1_train_step(
        model, zcfg, mesh, spec=StepSpec(plan=zplan, donate=True))
    flat = S((packspec.padded,), jnp.float32)
    zstate = Zero1State(step=S((), jnp.int32), params=pshapes,
                        mu=flat, nu=flat, master=flat)
    audits.append(audit_step(
        "zero1_train_step", zfn, (zstate, bspecs), expect_donation=True,
        host_avals=_host_leaves(zplan.residency, params=pshapes,
                                optimizer=[flat, flat, flat]),
        allow_scan_transfers=_allow_streams(zplan),
        plan_peak_bytes=zplan.peak_bytes))

    # --- prefill (no donation by design: the cache is born here)
    pshape = ShapeConfig("a_prefill", "prefill", max_len, slots)
    pplan = plan_memory(cfg, pshape, mspec, LMSConfig(enabled=True))
    pfn, _, _, _ = build_prefill_step(model, pshape, mesh,
                                      spec=StepSpec(plan=pplan))
    pb, _ = model.input_specs(pshape, mesh)
    pb = {k: v for k, v in pb.items() if k not in ("pos", "labels")}
    audits.append(audit_step(
        "prefill_step", pfn, (pshapes, pb),
        allow_scan_transfers=_allow_streams(pplan),
        plan_peak_bytes=pplan.peak_bytes))

    # --- static whole-batch decode (donates the cache)
    dshape = ShapeConfig("a_decode", "decode", max_len, slots)
    dplan = plan_memory(cfg, dshape, mspec, LMSConfig(enabled=True))
    dfn, _, _, _ = build_decode_step(model, dshape, mesh,
                                     spec=StepSpec(plan=dplan, donate=True))
    cavals, _ = model.cache_abstract(dshape, mesh)
    db, _ = model.input_specs(dshape, mesh)
    dpos = db.pop("pos")
    db.pop("labels", None)
    audits.append(audit_step(
        "decode_step", dfn, (pshapes, cavals, db, dpos),
        expect_donation=True,
        allow_scan_transfers=_allow_streams(dplan),
        plan_peak_bytes=dplan.peak_bytes))

    # --- slot-batched serve decode: model-width / int8 / int8+paged arena
    variants = [("slot_decode", "model", False),
                ("slot_decode_int8", "int8", False),
                ("slot_decode_int8_paged", "int8", True)]
    for name, kv_dtype, use_arena in variants:
        sfn, sargs, splan, scache = slot_decode_builder(
            model, cfg, mspec, mesh, slots=slots, max_len=max_len,
            page=page, kv_dtype=kv_dtype, use_arena=use_arena)
        tracked = [l for l in jax.tree_util.tree_leaves(scache)
                   if str(l.dtype) == "int8"]
        # NOTE: the plan's host kvcache class covers the spilled BACKLOG
        # the pool owns, not the active working set this step touches —
        # so the cache is deliberately NOT in host_avals here.
        audits.append(audit_step(
            name, sfn, sargs, expect_donation=True,
            tracked_quant_avals=tracked,
            host_avals=_host_leaves(splan.residency, params=pshapes),
            allow_scan_transfers=_allow_streams(splan),
            plan_peak_bytes=splan.peak_bytes))
    return audits


def sentinel_fingerprints(arch: str = "olmo-1b", *, slots: int = 2,
                          max_len: int = 16):
    """Fingerprint the slot-decode tick inputs under the churn scenarios
    the serve tests exercise (empty batch, single join, full slots,
    post-evict rejoin, staggered positions): shapes and dtypes must be
    invariant or the engine recompiles mid-serve."""
    from repro.serve.batching import decode_step_batch
    cfg = get_smoke_config(arch)
    scenarios = [
        ("all_idle", [0] * slots, [False] * slots),
        ("one_join", [3] + [0] * (slots - 1), [True] + [False] * (slots - 1)),
        ("full", [5] * slots, [True] * slots),
        ("staggered", list(range(1, slots + 1)), [True] * slots),
        ("post_evict", [max_len - 1] * slots,
         [i % 2 == 0 for i in range(slots)]),
    ]
    fps = {}
    for name, pos, act in scenarios:
        toks = jnp.zeros((slots, 1), jnp.int32)
        posd = jnp.asarray(pos, jnp.int32)
        batch = decode_step_batch(cfg, toks, posd)
        fps[name] = aval_fingerprint(
            (batch, posd, jnp.asarray(act, bool)),
            static=(slots, max_len))
    return fps


def build_report(arch: str = "olmo-1b", *, skip_lint: bool = False):
    report = AnalysisReport(meta={"arch": arch, "mesh": "1x1"})
    report.steps = audit_all_steps(arch)
    fps = sentinel_fingerprints(arch)
    report.meta["sentinel_fingerprints"] = fps
    if len(set(fps.values())) != 1:
        report.lint.append(Finding(
            "JXA006",
            "slot-decode churn scenarios map to MULTIPLE step signatures "
            f"({fps}); the fixed-shape contract is broken and the engine "
            "will recompile on join/evict",
            "slot_decode sentinel"))
    if not skip_lint:
        root, roots = default_paths()
        report.lint.extend(lint_paths(roots, root))
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="analysis_report.json")
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--skip-lint", action="store_true",
                    help="jaxpr audits + sentinel only (the lint pass has "
                    "its own entry point)")
    args = ap.parse_args(argv)
    report = build_report(args.arch, skip_lint=args.skip_lint)
    report.write(args.out)
    print(report.summary())
    print(f"wrote {args.out}")
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
