"""Device traces: capture with `jax.profiler`, reduce to intervals.

A trace is kept in a compact form, so that the reduction can be checked on
a small recorded fixture:

    {"devices": {plane: {"ops": [[name, start_ns, end_ns], ...],
                         "modules": [[name, start_ns, end_ns], ...]}},
     "host": [[name, start_ns, end_ns], ...]}

`ops` are the events of a TPU plane's "XLA Ops" line (one per executed HLO
instruction, named by it), `modules` those of its "XLA Modules" line (one
per program execution, named by the program), and `host` the events of the
host threads (JAX's own dispatch events and the benchmark's
`TraceAnnotation`s), all on the profiler's one clock.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def options():
    """Profiler options: no Python function tracer (it would slow every
    host call of the loop), the host's own trace events on."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def load(log_dir: str) -> dict:
    """The newest `.xplane.pb` under `log_dir` -> the compact form."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    out: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.end_ns]
                                for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.end_ns]
                                   for e in line.events if e.duration_ns > 0)
    return out


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length covered by the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: List[Tuple[float, float]], lo: float, hi: float):
    """[(start, end)] of the stretches of [lo, hi) that no interval covers."""
    gaps, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi))
    return [g for g in gaps if g[1] > g[0]]


def step_module(trace: dict):
    """The program that takes most device time: the training step (None
    when the trace holds no program execution)."""
    tot: Dict[str, float] = {}
    for dev in trace["devices"].values():
        for name, s, e in dev["modules"]:
            tot[name] = tot.get(name, 0.0) + (e - s)
    return max(tot, key=tot.get) if tot else None


def step_runs(trace: dict, plane: str, module: str):
    """Sorted [(start, end)] of the step program's executions on a plane."""
    return sorted((s, e) for name, s, e in trace["devices"][plane]["modules"]
                  if name == module)


def host_label(trace: dict, start: float, end: float) -> str:
    """What the host was doing in [start, end): the shortest host event
    that covers the stretch's midpoint, else "nothing traced"."""
    mid = (start + end) / 2
    best = None
    for name, s, e in trace["host"]:
        if s <= mid < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "nothing traced"
