"""moe_gmm_ms: device time per step of the held experts' grouped matmuls
(forward, rematerialized forward and backward; moe_flops.py finds them by
their HLO text), averaged over the chips."""
import moe_flops


def read(ctx):
    m, _ = moe_flops.cell_model(ctx)
    if m is None or not m.get("num_experts"):
        return None
    per_chip = [sum(e - s for s, e, _ in ev) / steps / 1e6
                for steps, ev in moe_flops.gmm_events(ctx, m).values()]
    return sum(per_chip) / len(per_chip) if per_chip else None
