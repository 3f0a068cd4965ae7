"""Operations and bytes of the held-experts grouped matmuls, and how their
executions are found in a device trace.

The MoE layer (models/moe.py) sorts a step's expert picks and runs the
held experts' SwiGLU as grouped matmuls (megablox `gmm`, and `tgmm` for
the weights' gradient): per layer and executed pass, w_gate and w_up
([G, d, f]) and w_down ([G, f, d]), G the experts held. Each op is one
`custom-call` in the trace that the TPU compiler names after the kernel
(`%gmm.55`, `%tgmm.13`) and whose HLO text carries a [G, d, f] or
[G, f, d] array (the weights it reads, or for `tgmm` the gradient it
writes). Other custom calls carry such arrays too (the copy that joins
the weights' slices), so the name is required.

Rows are reckoned, not read: tokens x k x G / E a pass (each token's k
picks spread evenly over the E experts), in bf16. An op of [G, a, b]
then does 2 rows a b FLOPs and moves 2 (rows (a + b) + G a b) bytes (the
rows in, the rows out, the weights once), forward and backward alike.
"""
from __future__ import annotations

import re
import sys

import devtrace
import hlo


def cell_model(ctx):
    """-> (model dict, microbatches) of the run: ctx.model where a caller
    sets it, else the cell named by the harness's --workload."""
    if getattr(ctx, "model", None) is not None:
        return ctx.model, getattr(ctx, "microbatches", 1)
    argv = sys.argv
    if "--workload" not in argv:
        return None, 1
    import harness
    cell = harness.load_cell(argv[argv.index("--workload") + 1])
    return cell["config"]["model"], cell["traffic"]["microbatches"]


def held(m: dict) -> int:
    return m.get("experts_held") or m["num_experts"]


def rows_per_pass(m: dict, tokens: float) -> float:
    return tokens * m["experts_per_token"] * held(m) / m["num_experts"]


def op_work(m: dict, tokens: float, a: int, b: int):
    """-> (FLOPs, bytes) of one grouped matmul over [G, a, b]."""
    rows = rows_per_pass(m, tokens)
    return 2.0 * rows * a * b, 2.0 * (rows * (a + b) + held(m) * a * b)


def gmm_dims(m: dict, text: str):
    """(a, b) of a grouped-matmul op's [G, a, b] array, else None."""
    name, _, op = hlo.split(text)
    if op != "custom-call" or not re.fullmatch(r"t?gmm(\.\d+)?", name):
        return None
    d, f = m["d_model"], m["d_ff"]
    for a, b in re.findall(rf"\[{held(m)},(\d+),(\d+)\]", text):
        if {int(a), int(b)} == {d, f}:
            return int(a), int(b)
    return None


def gmm_events(ctx, m: dict):
    """-> {plane: (steps, [(start, end, (a, b))])} of the step program's
    runs and the grouped-matmul executions on each chip."""
    module = devtrace.step_module(ctx.trace)
    if module is None:
        return {}
    out = {}
    for plane, dev in ctx.trace["devices"].items():
        steps = len(devtrace.step_runs(ctx.trace, plane, module))
        ev = [(s, e, dims) for n, s, e in dev["ops"]
              for dims in [gmm_dims(m, n)] if dims]
        if steps and ev:
            out[plane] = (steps, ev)
    return out
