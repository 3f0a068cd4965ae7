"""host_gap_ms: device time between the end of one execution of the step
program and the start of the next, mean per step, averaged over the chips
(device trace, "XLA Modules" line). The trainer loop's host work between
steps (metrics sync, next batch, dispatch) shows here."""
import devtrace


def read(ctx):
    module = devtrace.step_module(ctx.trace)
    if module is None:
        return None
    per_chip = []
    for plane in ctx.trace["devices"]:
        runs = devtrace.step_runs(ctx.trace, plane, module)
        gaps = [b[0] - a[1] for a, b in zip(runs, runs[1:])]
        if gaps:
            per_chip.append(sum(gaps) / len(gaps) / 1e6)
    return sum(per_chip) / len(per_chip) if per_chip else None
