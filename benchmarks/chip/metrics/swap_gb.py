"""swap_gb: bytes moved between HBM and pinned host memory per step, in GB:
each host transfer that completes in the device trace (its done), at the
bytes its HLO instruction moves (hlo.py), over the step program's
executions, on the first chip. A count, to set beside the plan's
swap_bytes_per_step."""
import devtrace
import hlo


def read(ctx):
    module = devtrace.step_module(ctx.trace)
    if module is None:
        return None
    plane = sorted(ctx.trace["devices"])[0]
    steps = len(devtrace.step_runs(ctx.trace, plane, module))
    moved = 0
    for name, _, _ in ctx.trace["devices"][plane]["ops"]:
        t = hlo.transfer(name)
        if t is not None and t[0] == "done":
            moved += t[2]
    if not steps or not moved:
        return None
    return moved / steps / 1e9
