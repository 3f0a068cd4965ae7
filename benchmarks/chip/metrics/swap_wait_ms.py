"""swap_wait_ms: device time per step in the operations that move LMS
state between HBM and pinned host memory or wait for such a move: the
async transfers whose host side is in the host memory space (hlo.py),
their start and their done, summed from the device trace and averaged over
the chips."""
import devtrace
import hlo


def read(ctx):
    module = devtrace.step_module(ctx.trace)
    if module is None:
        return None
    per_chip = []
    for plane, dev in ctx.trace["devices"].items():
        steps = len(devtrace.step_runs(ctx.trace, plane, module))
        wait = [e - s for n, s, e in dev["ops"] if hlo.transfer(n)]
        if steps and wait:
            per_chip.append(sum(wait) / steps / 1e6)
    return sum(per_chip) / len(per_chip) if per_chip else None
