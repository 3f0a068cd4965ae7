"""feed_ms: mean host time of the program's `data.fetch` span (the data
loader making one batch's rows) in ms, over the spans that start between
the start of the first traced execution of the step program and the start
of the last, on the first chip (device trace: the span is a host event on
the profiler's clock). None where the program emits no such span."""
import devtrace

SITE = "data.fetch"


def read(ctx):
    module = devtrace.step_module(ctx.trace)
    if module is None:
        return None
    plane = sorted(ctx.trace["devices"])[0]
    runs = devtrace.step_runs(ctx.trace, plane, module)
    if len(runs) < 2:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    spans = [e - s for name, s, e in ctx.trace["host"]
             if name == SITE and lo <= s < hi]
    return sum(spans) / len(spans) / 1e6 if spans else None
