"""Hardware model used by the LMS planner and the roofline analysis.

`attached()` is the spec of the chip the process runs on: chosen by the
TPU's `device_kind` (an unknown kind is an error), with the HBM the runtime
actually grants (`memory_stats()["bytes_limit"]`). Off TPU — CPU dry-runs
and tests — it is `DEFAULT`, the target the plans are made for. All
bandwidths are per-chip unless noted.
"""
import dataclasses
from dataclasses import dataclass


@dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops_bf16: float      # FLOP/s per chip
    hbm_bytes: int              # usable HBM per chip
    hbm_bw: float               # bytes/s per chip
    ici_link_bw: float          # bytes/s per ICI link (one direction)
    ici_links: int              # links per chip participating in a 2D torus
    dcn_bw: float               # bytes/s per chip across pods (data-center network)
    host_bw: float              # bytes/s host<->device DMA (the "NVLink" analogue)
    host_bytes: int             # host DRAM reachable per chip
    vmem_bytes: int             # per-core VMEM (Pallas tiling budget)


# TPU v5e (per problem statement: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI).
TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bytes=16 * 1024**3,
    hbm_bw=819e9,
    ici_link_bw=50e9,
    ici_links=4,
    dcn_bw=6.25e9,
    host_bw=32e9,
    host_bytes=256 * 1024**3,
    vmem_bytes=128 * 1024**2,
)

DEFAULT = TPU_V5E

# jax.Device.device_kind -> spec
BY_DEVICE_KIND = {"TPU v5 lite": TPU_V5E}


def attached() -> HardwareSpec:
    """Spec of the attached TPU, its HBM set to the runtime's bytes_limit;
    DEFAULT when the process runs on no TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return DEFAULT
    if dev.device_kind not in BY_DEVICE_KIND:
        raise KeyError(f"no HardwareSpec for device_kind {dev.device_kind!r}; "
                       f"known: {sorted(BY_DEVICE_KIND)}")
    limit = int(dev.memory_stats()["bytes_limit"])
    return dataclasses.replace(BY_DEVICE_KIND[dev.device_kind],
                               hbm_bytes=limit)
