"""idle_frac: the share of the traced window in which no operation ran on
the device, averaged over the chips (device trace: union of the "XLA Ops"
intervals)."""
import devtrace


def read(ctx):
    t = ctx.trace
    busy = [devtrace.union_ns((s, e) for _, s, e in dev["ops"]) / 1e9
            for dev in t["devices"].values()]
    if not busy:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / ctx.window_s)
