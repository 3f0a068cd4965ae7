"""Jaxpr auditor: abstract plan-conformance checks over jitted steps.

Every check here runs backend-free — `jax.make_jaxpr` on
`ShapeDtypeStruct` arguments traces the jitted function without
compiling, and tracing a jitted fn yields a single `jit` equation whose
params carry exactly the contract we audit: `donated_invars` (what the
builder promised to alias) and the closed inner jaxpr (what the program
actually does). Finding codes (DESIGN.md §11):

  JXA001 donation-dropped      a donated input has no aval-matching output
                               (XLA silently un-donates; the state's bytes
                               double at step boundaries)
  JXA002 host-leaf-on-device   a leaf the MemoryPlan declares host-resident
                               is device_put whole onto device memory
  JXA003 transfer-in-loop      device_put inside a scan/while body on the
                               hot path (per-iteration host sync) — allowed
                               only when the plan's SwapSchedule streams
  JXA004 quant-upcast          convert_element_type widens a whole tracked
                               int8/bf16 leaf to f32 outside the allowlist
                               (erases the quantization capacity win)
  JXA005 peak-over-budget      liveness peak estimate exceeds the planner's
                               priced budget (warning: the linear estimate
                               overcounts vs XLA; the delta is reported)

The liveness walk is a deliberate *over*-estimate: eqn-order liveness
with inner scan/jit peaks folded in at their call sites, no rematerial-
ization or scheduling freedom. It bounds what XLA can possibly hold live,
which is the number to reconcile the planner's static pricing against
(analysis_report.json carries the delta per step).
"""
from __future__ import annotations

import hashlib
import json
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.extend import core as jcore

from repro.analysis.report import Finding, StepAudit

# primitives whose body re-runs per iteration: a transfer inside one is a
# per-token / per-layer sync, not a one-off
LOOP_PRIMITIVES = ("scan", "while")
HOST_MEMORY_KINDS = ("pinned_host", "unpinned_host", "host")
WIDE_FLOATS = ("float32", "float64")
NARROW_SOURCES = ("int8", "bfloat16", "float16")

AvalKey = Tuple[Tuple[int, ...], str]


def aval_key(x) -> AvalKey:
    """(shape, dtype) key for abstract-value matching; accepts avals,
    ShapeDtypeStructs, and concrete arrays."""
    return (tuple(getattr(x, "shape", ())),
            str(np.dtype(getattr(x, "dtype", np.float32))))


def aval_bytes(x) -> int:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:   # tokens / abstract effects: free
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def leaf_keys(tree) -> List[AvalKey]:
    """Aval keys of every leaf of a pytree of avals/arrays."""
    return [aval_key(l) for l in jax.tree_util.tree_leaves(tree)]


# ---------------------------------------------------------------------------
# tracing

def trace_step(fn, args: Sequence, kwargs: Optional[Dict] = None):
    """Abstractly trace `fn(*args)` (args may be ShapeDtypeStructs).

    Returns (closed_jaxpr, inner_jaxpr, donated, in_avals, out_avals):
    for a jitted fn the outer trace is one jit eqn whose params hold the
    donation mask and the real program; for a plain fn donation is empty.
    """
    closed = jax.make_jaxpr(fn)(*args, **(kwargs or {}))
    jaxpr = closed.jaxpr
    inner = jaxpr
    donated: Tuple[bool, ...] = (False,) * len(jaxpr.invars)
    in_avals = [v.aval for v in jaxpr.invars]
    out_avals = list(closed.out_avals)
    if len(jaxpr.eqns) == 1 and jaxpr.eqns[0].primitive.name == "jit":
        eqn = jaxpr.eqns[0]
        sub = eqn.params.get("jaxpr")
        if sub is not None:
            inner = sub.jaxpr if isinstance(sub, jcore.ClosedJaxpr) else sub
        d = eqn.params.get("donated_invars")
        if d is not None:
            donated = tuple(d)
            in_avals = [v.aval for v in eqn.invars]
        out_avals = [v.aval for v in eqn.outvars]
    return closed, inner, donated, in_avals, out_avals


def _subjaxprs(eqn) -> Iterator[jcore.Jaxpr]:
    for val in eqn.params.values():
        for v in (val if isinstance(val, (list, tuple)) else (val,)):
            if isinstance(v, jcore.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jcore.Jaxpr):
                yield v


def iter_eqns(jaxpr: jcore.Jaxpr, in_loop: bool = False):
    """Yield (eqn, in_loop) over the whole program, descending into scan/
    while/cond/jit bodies; in_loop marks eqns under a loop body."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        child_loop = in_loop or eqn.primitive.name in LOOP_PRIMITIVES
        for sub in _subjaxprs(eqn):
            yield from iter_eqns(sub, child_loop)


# ---------------------------------------------------------------------------
# individual checks

def _put_targets(eqn) -> List:
    return list(eqn.params.get("devices", ()) or [None])


def _put_target_kinds(eqn) -> List[Optional[str]]:
    """Memory kinds a device_put targets (None = default placement). An
    in-jit transfer targets a `jax.memory.Space` ("host" / "device"); an
    eager one may target a Sharding carrying a memory kind."""
    kinds = []
    for d in _put_targets(eqn):
        if isinstance(d, jax.memory.Space):
            kinds.append(d.name.lower())
        else:
            kinds.append(getattr(d, "memory_kind", None))
    return kinds


def _put_is_targeted(eqn) -> bool:
    """True for a device_put with an explicit device / memory-kind target —
    an actual placement change. Targetless ALIAS puts (how jnp.asarray
    places closed-over constants, e.g. rope tables inside the layer scan)
    move nothing and are not transfers."""
    return any(d is not None for d in _put_targets(eqn))


def check_donation(name: str, donated: Sequence[bool],
                   in_avals: Sequence, out_avals: Sequence, *,
                   expect_donation: bool = False) -> List[Finding]:
    """JXA001: each donated input's aval must be consumable by some output
    (multiset match) or XLA drops the donation and the buffer doubles."""
    findings: List[Finding] = []
    pool: Dict[AvalKey, int] = {}
    for a in out_avals:
        k = aval_key(a)
        pool[k] = pool.get(k, 0) + 1
    n_donated = sum(bool(d) for d in donated)
    aliased = 0
    for d, a in zip(donated, in_avals):
        if not d:
            continue
        k = aval_key(a)
        if pool.get(k, 0) > 0:
            pool[k] -= 1
            aliased += 1
        else:
            findings.append(Finding(
                "JXA001",
                f"donated input {k[0]}:{k[1]} has no aval-matching output; "
                "XLA silently drops the donation and keeps both buffers "
                "live across the step boundary",
                name, data={"shape": list(k[0]), "dtype": k[1]}))
    if expect_donation and n_donated == 0:
        findings.append(Finding(
            "JXA001",
            "builder promises donation (donate=True) but the traced jaxpr "
            "declares no donated inputs at all",
            name))
    return findings


def check_transfers(name: str, jaxpr: jcore.Jaxpr, *,
                    host_avals: Iterable = (),
                    allow_scan_transfers: bool = False) -> List[Finding]:
    """JXA002 + JXA003 over every device_put in the program."""
    findings: List[Finding] = []
    host_keys = {aval_key(a) for a in host_avals}
    for eqn, in_loop in iter_eqns(jaxpr):
        if eqn.primitive.name != "device_put" or not _put_is_targeted(eqn):
            continue
        kinds = _put_target_kinds(eqn)
        to_host_only = kinds and all(k in HOST_MEMORY_KINDS for k in kinds)
        if not to_host_only:
            for v in eqn.outvars:
                k = aval_key(v.aval)
                if k in host_keys:
                    findings.append(Finding(
                        "JXA002",
                        f"leaf {k[0]}:{k[1]} is declared host-resident by "
                        "the MemoryPlan but the program device_puts it "
                        "whole onto device memory — the plan's peak "
                        "accounting no longer holds",
                        name, data={"shape": list(k[0]), "dtype": k[1],
                                    "target_kinds": [str(x) for x in kinds]}))
        if in_loop and not allow_scan_transfers:
            findings.append(Finding(
                "JXA003",
                "device_put inside a scan/while body on the hot path "
                f"(targets {kinds}); per-iteration transfers belong to a "
                "declared SwapSchedule stream, not an un-priced loop body",
                name, data={"target_kinds": [str(x) for x in kinds]}))
    return findings


def check_upcasts(name: str, jaxpr: jcore.Jaxpr, *,
                  tracked_avals: Iterable = (),
                  allow_upcast: Iterable = ()) -> List[Finding]:
    """JXA004: convert_element_type that widens a WHOLE tracked narrow leaf
    (exact aval match) to f32/f64. Per-slice dequantize inside a kernel or
    gather produces a different aval and is deliberately not flagged."""
    findings: List[Finding] = []
    tracked = {aval_key(a) for a in tracked_avals}
    allowed = {aval_key(a) for a in allow_upcast}
    tracked -= allowed
    if not tracked:
        return findings
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        new_dtype = str(np.dtype(eqn.params.get("new_dtype", np.float32)))
        if new_dtype not in WIDE_FLOATS:
            continue
        for v in eqn.invars:
            if isinstance(v, jcore.Literal):
                continue
            k = aval_key(v.aval)
            if k in tracked and k[1] in NARROW_SOURCES:
                findings.append(Finding(
                    "JXA004",
                    f"whole tracked leaf {k[0]}:{k[1]} widened to "
                    f"{new_dtype}; a full-width copy of a quantized/"
                    "half-width leaf erases its capacity saving",
                    name, data={"shape": list(k[0]), "from": k[1],
                                "to": new_dtype}))
    return findings


def peak_live_bytes(jaxpr: jcore.Jaxpr) -> int:
    """Upper-bound peak live bytes by eqn-order liveness. Inner call/loop
    bodies contribute max(0, inner_peak - inner_input_bytes) at their call
    site (their inputs alias operands already counted live out here)."""
    last_use: Dict[jcore.Var, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, jcore.Var):
                last_use[v] = i
    outset = {v for v in jaxpr.outvars if isinstance(v, jcore.Var)}
    live: Dict[jcore.Var, int] = {}
    cur = 0
    for v in (*jaxpr.constvars, *jaxpr.invars):
        if v not in live:
            live[v] = aval_bytes(v.aval)
            cur += live[v]
    peak = cur
    for i, eqn in enumerate(jaxpr.eqns):
        inner_extra = 0
        for sub in _subjaxprs(eqn):
            sub_in = sum(aval_bytes(v.aval)
                         for v in (*sub.constvars, *sub.invars))
            inner_extra = max(inner_extra, peak_live_bytes(sub) - sub_in)
        for v in eqn.outvars:
            if isinstance(v, jcore.Var) and v not in live:
                live[v] = aval_bytes(v.aval)
                cur += live[v]
        peak = max(peak, cur + max(inner_extra, 0))
        for v in eqn.invars:
            if (isinstance(v, jcore.Var) and last_use.get(v) == i
                    and v not in outset):
                cur -= live.pop(v, 0)
    return peak


# ---------------------------------------------------------------------------
# recompile sentinel

def aval_fingerprint(args_tree, static: Sequence = ()) -> str:
    """Stable signature of a step invocation: flattened (path, shape,
    dtype, sharding) of every leaf + treedef + static args. Two calls with
    the same fingerprint hit the same executable — churn scenarios (slot
    join/evict, value-only changes) MUST map to one fingerprint or the
    engine recompiles mid-serve."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(args_tree)
    rec = []
    for path, leaf in leaves:
        rec.append([jax.tree_util.keystr(path),
                    list(getattr(leaf, "shape", ())),
                    str(np.dtype(getattr(leaf, "dtype", np.float32))),
                    str(getattr(leaf, "sharding", None))])
    payload = json.dumps([rec, str(treedef), [repr(s) for s in static]],
                         sort_keys=True)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# driver

def audit_step(name: str, fn, args: Sequence,
               kwargs: Optional[Dict] = None, *,
               expect_donation: bool = False,
               host_avals: Iterable = (),
               tracked_quant_avals: Iterable = (),
               allow_upcast: Iterable = (),
               allow_scan_transfers: bool = False,
               plan_peak_bytes: Optional[int] = None,
               budget_bytes: Optional[int] = None) -> StepAudit:
    """Trace one jitted step abstractly and run every JXA check.

    host_avals / tracked_quant_avals are pytrees (or flat lists) of avals:
    the leaves the MemoryPlan declares host-resident, and the quantized/
    half-width leaves whose whole-leaf widening would erase the plan's
    capacity math. allow_scan_transfers reflects whether the plan's
    SwapSchedule actually streams (then per-layer device_puts inside the
    layer scan ARE the executor, not a bug)."""
    closed, inner, donated, in_avals, out_avals = trace_step(fn, args, kwargs)
    findings = check_donation(name, donated, in_avals, out_avals,
                              expect_donation=expect_donation)
    n_donated = sum(bool(d) for d in donated)
    n_dropped = sum(1 for f in findings if f.code == "JXA001"
                    and "no aval-matching output" in f.message)
    findings += check_transfers(
        name, inner,
        host_avals=jax.tree_util.tree_leaves(host_avals),
        allow_scan_transfers=allow_scan_transfers)
    findings += check_upcasts(
        name, inner,
        tracked_avals=jax.tree_util.tree_leaves(tracked_quant_avals),
        allow_upcast=jax.tree_util.tree_leaves(allow_upcast))
    peak = peak_live_bytes(inner)
    if budget_bytes is not None and peak > budget_bytes:
        findings.append(Finding(
            "JXA005",
            f"liveness peak estimate {peak / 2**20:.1f} MiB exceeds the "
            f"planner budget {budget_bytes / 2**20:.1f} MiB "
            f"(delta {(peak - budget_bytes) / 2**20:+.1f} MiB) — "
            "reconcile with MemoryPlan pricing",
            name, severity="warning",
            data={"peak_live_bytes": peak, "budget_bytes": budget_bytes}))
    n_eqns = sum(1 for _ in iter_eqns(inner))
    return StepAudit(
        name=name, findings=findings, n_eqns=n_eqns,
        in_bytes=sum(aval_bytes(a) for a in in_avals),
        out_bytes=sum(aval_bytes(a) for a in out_avals),
        donated_in=n_donated, donated_aliased=n_donated - n_dropped,
        peak_live_bytes=peak, plan_peak_bytes=plan_peak_bytes,
        budget_bytes=budget_bytes,
        fingerprint=aval_fingerprint(list(args)))
