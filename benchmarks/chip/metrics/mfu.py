"""mfu: model FLOPs per token (flops.py: 6 N + 6 N_head + causal attention,
no recompute) times the tokens per second per chip that the device trace
shows (steps completed between the start of the first traced execution of
the step program and the end of the last), over the chip's peak bf16
FLOP/s (peaks.json, by device_kind)."""
import devtrace


def read(ctx):
    if ctx.peak is None:
        return None
    module = devtrace.step_module(ctx.trace)
    if module is None:
        return None
    plane = sorted(ctx.trace["devices"])[0]
    runs = devtrace.step_runs(ctx.trace, plane, module)
    if not runs:
        return None
    span_s = (runs[-1][1] - runs[0][0]) / 1e9
    tokens_per_s = len(runs) * ctx.tokens_per_step / span_s / ctx.chips
    return 100.0 * ctx.flops_per_token * tokens_per_s \
        / ctx.peak["bf16_flops_per_s"]
