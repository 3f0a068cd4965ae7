"""Host-residency helpers: the explicit swap side of LMS.

`effective_kind` gates memory-kind annotations on platform support;
`residency_shardings` applies a MemoryPlan's residency map to a param-spec
tree so jit in_shardings place each tensor in the right space;
`stream_layer_to_device` is the swap-in primitive the layer-streaming
executor (models/transformer.py) issues inside the decoder scans — XLA
lowers it to async copy-start/copy-done on TPU, overlappable with compute.
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro import compat
from repro.obs import get_obs

HOST = compat.HOST
DEVICE = compat.DEVICE


def _tree_bytes(tree) -> int:
    """Logical byte size of a tensor tree — works on tracers (aval shape/
    dtype), so the swap helpers can account bytes at JIT trace time."""
    total = 0
    for leaf in compat.tree.leaves(tree):
        total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def _record_swap(site: str, tree, cls: str) -> None:
    """Trace-time swap accounting (DESIGN.md §12): the stream helpers run
    inside jitted scan bodies, so this fires once per TRACE (one layer's
    tensors = the plan's swap unit), not once per execution — recorded as
    kind="trace" events plus per-residency-class byte counters."""
    obs = get_obs()
    nbytes = _tree_bytes(tree)
    obs.trace_event(site, bytes=nbytes, cls=cls)
    obs.registry.counter(f"{site}_bytes.{cls}").inc(nbytes)
    obs.registry.counter(f"{site}_events.{cls}").inc()


def effective_kind(kind):
    """`kind` where host residency is real (TPU), else None: XLA:CPU lists a
    host memory space but cannot lower an in-jit placement to it, so on the
    CPU test backend every residency annotation and swap is the identity —
    host residency there is proven by the planner's analytic model and the
    jaxpr auditor (compat.host_memory_kind decides, by platform)."""
    return kind if compat.host_memory_kind() is not None else None


def residency_shardings(spec_tree, mesh: Mesh, residency: dict, *,
                        group: str):
    """Param-spec tree -> NamedSharding tree honoring a MemoryPlan residency.

    group: which residency key governs this tree ("params", "optimizer",
    "kvcache", "grads").
    """
    kind = effective_kind(HOST) if residency.get(group, DEVICE) == "host" else None
    return compat.tree.map(
        lambda s: (NamedSharding(mesh, s, memory_kind=kind) if kind
                   else NamedSharding(mesh, s)), spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def stream_layer_to_device(layer_params, *, cls: str = "params"):
    """Swap-in one layer's tensor tree inside a scan body, preserving each
    leaf's sharding (host -> HBM, an async copy on TPU).
    Identity where the platform has one memory space, so the streamed graph
    stays numerically byte-identical to the resident graph.

    `cls`: the plan residency class being streamed ("params", "optimizer",
    "grads", "kvcache") — labels the trace-time swap accounting per
    class."""
    _record_swap("lms.swap_in", layer_params, cls)
    return compat.to_memory_kind(layer_params, effective_kind(DEVICE))


def stream_layer_to_host(layer_tree, *, cls: str = "params"):
    """Swap-OUT counterpart of `stream_layer_to_device`: place one layer's
    tensor tree back in pinned host memory inside a scan body (the streamed
    optimizer sweep's write-back, the backward hooks' gradient sink).
    Identity on single-memory-space platforms, like the swap-in."""
    _record_swap("lms.swap_out", layer_tree, cls)
    return compat.to_memory_kind(layer_tree, effective_kind(HOST))
