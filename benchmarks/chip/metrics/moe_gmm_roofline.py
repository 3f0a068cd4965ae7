"""moe_gmm_roofline: the roofline time of the grouped matmuls' reckoned
work, max(FLOPs / peak bf16 FLOP/s, bytes / HBM bytes/s) per op
(moe_flops.py, peaks.json), over their device time, in percent, on the
first chip with any. Above 100 the reckoning counts more rows than run."""
import moe_flops


def read(ctx):
    m, micro = moe_flops.cell_model(ctx)
    if m is None or not m.get("num_experts") or ctx.peak is None:
        return None
    found = moe_flops.gmm_events(ctx, m)
    if not found:
        return None
    steps, ev = found[sorted(found)[0]]
    tokens = ctx.tokens_per_step / ctx.chips / micro
    best, took = 0.0, 0.0
    for s, e, (a, b) in ev:
        flops, nbytes = moe_flops.op_work(m, tokens, a, b)
        best += max(flops / ctx.peak["bf16_flops_per_s"],
                    nbytes / ctx.peak["hbm_bytes_per_s"])
        took += (e - s) / 1e9
    return 100.0 * best / took if took else None
