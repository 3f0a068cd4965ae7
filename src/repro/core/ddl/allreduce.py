"""DDL's topology-aware gradient reduction as explicit JAX collectives.

The paper's key mechanism: decompose one logical all-reduce into
reduce-scatter + all-gather phases per fabric tier. On a TPU mesh
("pod", "data", "model") with gradients computed per data-parallel shard
inside a shard_map manual over ("pod", "data"):

    1. reduce-scatter over `data`   (ICI, fast)         -> 1/data shard
    2. all-reduce over `pod`        (DCN, slow; shard only, optionally int8)
    3. all-gather over `data`       (ICI)               -> full gradient

Beyond-paper `zero1` mode stops after (2): each data rank keeps its shard,
updates its optimizer-state shard, and the all-gather moves *updated params*
instead of gradients (same volume, optimizer memory / |data|).

Gradients are flattened and packed into fixed-size buckets (paper: latency
minimization via fewer, larger, fabric-sized collectives).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro import compat
from repro.config.base import DDLConfig
from repro.core.ddl.compress import compressed_allreduce_pod


# ---------------------------------------------------------------------------
# Flat packing
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PackSpec:
    shapes: List[Tuple[int, ...]]
    dtypes: List
    sizes: List[int]
    treedef: object
    total: int
    pad_to: int

    @property
    def padded(self) -> int:
        n = self.total
        return n + ((-n) % self.pad_to)


def pack_spec(tree, pad_to: int) -> PackSpec:
    leaves, treedef = compat.tree.flatten(tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    return PackSpec(shapes, dtypes, sizes, treedef, int(sum(sizes)), pad_to)


def pack(tree, spec: PackSpec, dtype=jnp.float32) -> jnp.ndarray:
    leaves = compat.tree.leaves(tree)
    flat = jnp.concatenate([l.astype(dtype).reshape(-1) for l in leaves])
    return jnp.pad(flat, (0, spec.padded - spec.total))


def unpack(flat: jnp.ndarray, spec: PackSpec):
    out, off = [], 0
    for shape, dt, size in zip(spec.shapes, spec.dtypes, spec.sizes):
        out.append(flat[off:off + size].reshape(shape).astype(dt))
        off += size
    return compat.tree.unflatten(spec.treedef, out)


# ---------------------------------------------------------------------------
# Hierarchical reduction of one flat bucket (inside shard_map manual axes)
# ---------------------------------------------------------------------------

def hierarchical_allreduce_flat(x, *, data_axis: str = "data",
                                pod_axis: Optional[str] = None,
                                compress_dcn: bool = False,
                                error_feedback=None, mean_over: int = 1):
    """Full DDL schedule on a flat [N] tensor (N divisible by |data|).
    Returns (reduced_full [N], new_error_feedback)."""
    shard, ef = hierarchical_reduce_scatter_flat(
        x, data_axis=data_axis, pod_axis=pod_axis, compress_dcn=compress_dcn,
        error_feedback=error_feedback, mean_over=mean_over)
    full = jax.lax.all_gather(shard, data_axis, axis=0, tiled=True)
    return full, ef


def hierarchical_reduce_scatter_flat(x, *, data_axis: str = "data",
                                     pod_axis: Optional[str] = None,
                                     compress_dcn: bool = False,
                                     error_feedback=None, mean_over: int = 1):
    """Phases 1-2 of the DDL schedule: returns this rank's reduced shard
    [N/|data|] (the zero1 entry point)."""
    shard = jax.lax.psum_scatter(x, data_axis, scatter_dimension=0, tiled=True)
    ef = error_feedback
    if pod_axis is not None:
        if compress_dcn:
            shard, ef = compressed_allreduce_pod(shard, pod_axis,
                                                 error_feedback=error_feedback)
        else:
            shard = jax.lax.psum(shard, pod_axis)
    if mean_over > 1:
        shard = shard / mean_over
    return shard, ef


def flat_allreduce(x, axes: Tuple[str, ...], mean_over: int = 1):
    """The non-topology-aware baseline: one psum over every DP axis (what a
    flat NCCL ring would do)."""
    x = jax.lax.psum(x, axes)
    if mean_over > 1:
        x = x / mean_over
    return x


# ---------------------------------------------------------------------------
# Tree-level API (per-leaf, TP-sharding aware)
# ---------------------------------------------------------------------------
#
# The DDL schedule is applied PER LEAF, never across leaves: concatenating
# TP-sharded gradients into flat buckets would force GSPMD to all-gather the
# `model` axis (full-size gradients on every device — fatal for the 72B+
# models). Instead each leaf is reduce-scattered over a dimension that is
# (a) divisible by |data| and (b) not model-sharded (taken from its
# PartitionSpec); leaves with no such dimension (tiny, oddly-shaped) fall
# back to a plain hierarchical psum. The paper's bucketing-for-latency
# becomes XLA's job here: the per-leaf collectives are independent ops the
# latency-hiding scheduler can batch and overlap with backward compute.

def _choose_scatter_dim(shape, spec, data_size: int) -> Optional[int]:
    spec = tuple(spec) if spec is not None else ()
    spec = spec + (None,) * (len(shape) - len(spec))
    for i, (s, ax) in enumerate(zip(shape, spec)):
        if ax is None and s % data_size == 0 and s > 0:
            return i
    return None


def _leaf_is_replicated(spec) -> bool:
    return spec is None or all(a is None for a in tuple(spec))


def ddl_reduce_leaf(g, *, data_axis: str, pod_axis: Optional[str],
                    data_size: int, pod_size: int, compress_dcn: bool,
                    topology_aware: bool, spec=None, error_feedback=None):
    """DDL schedule on one gradient leaf. Returns (mean grad, new EF).

    Reductions run in f32: numerically standard for gradient averaging, and
    bf16 cross-replica collectives trip an XLA:CPU partitioner bug
    ("Invalid binary instruction opcode copy") in the dry-run environment.
    """
    g = g.astype(jnp.float32)
    mean_over = data_size * pod_size
    if not topology_aware:
        axes = (data_axis,) + ((pod_axis,) if pod_axis else ())
        return flat_allreduce(g, axes, mean_over=mean_over), error_feedback
    sdim = _choose_scatter_dim(g.shape, spec, data_size)
    if sdim is None:
        # fallback: plain hierarchical psum (no RS/AG decomposition)
        g = jax.lax.psum(g, data_axis)
        if pod_axis is not None:
            g = jax.lax.psum(g, pod_axis)
        return g / mean_over, error_feedback
    shard = jax.lax.psum_scatter(g, data_axis, scatter_dimension=sdim, tiled=True)
    ef = error_feedback
    if pod_axis is not None:
        if compress_dcn and _leaf_is_replicated(spec):
            orig_shape = shard.shape
            flat = shard.reshape(-1)
            red, ef = compressed_allreduce_pod(flat, pod_axis,
                                               error_feedback=error_feedback)
            shard = red.reshape(orig_shape)
        else:
            shard = jax.lax.psum(shard, pod_axis)
    full = jax.lax.all_gather(shard, data_axis, axis=sdim, tiled=True)
    return full / mean_over, ef


def ddl_reduce_tree(grads, cfg: DDLConfig, *, data_axis: str = "data",
                    pod_axis: Optional[str] = None, data_size: int,
                    pod_size: int = 1, param_specs=None, error_feedback=None):
    """DDL-reduce a gradient pytree. Returns (mean grads, new EF tree).

    param_specs: matching pytree of PartitionSpec (TP sharding of each leaf)
    so the reduce-scatter dimension avoids model-sharded dims.
    """
    if cfg.mode == "none":
        return grads, error_feedback
    leaves, treedef = compat.tree.flatten(grads)
    if param_specs is not None:
        specs = compat.tree.flatten(param_specs,
                                 is_leaf=lambda x: isinstance(x, PartitionSpec))[0]
    else:
        specs = [None] * len(leaves)
    efs = (error_feedback if error_feedback is not None else [None] * len(leaves))
    out, new_ef = [], []
    with jax.named_scope("ddl.reduce"):
        for g, sp, ef in zip(leaves, specs, efs):
            r, e = ddl_reduce_leaf(
                g, data_axis=data_axis, pod_axis=pod_axis,
                data_size=data_size, pod_size=pod_size,
                compress_dcn=cfg.compress_dcn,
                topology_aware=cfg.topology_aware, spec=sp,
                error_feedback=ef)
            out.append(r.astype(g.dtype))
            new_ef.append(e)
    ef_out = new_ef if error_feedback is not None else None
    return compat.tree.unflatten(treedef, out), ef_out


def init_error_feedback(grads_shapes, cfg: DDLConfig, data_size: int):
    """Zero per-leaf EF buffers (compressed replicated leaves only)."""
    if not (cfg.compress_dcn and cfg.topology_aware):
        return None
    leaves = compat.tree.leaves(grads_shapes)
    return [jnp.zeros(_ef_shape(l.shape, data_size), jnp.float32)
            for l in leaves]


def _ef_shape(shape, data_size):
    sdim = _choose_scatter_dim(shape, None, data_size)
    if sdim is None:
        return shape
    s = list(shape)
    s[sdim] //= data_size
    return tuple(s)


def make_buckets(spec_sizes: List[int], bucket_elems: int) -> List[List[int]]:
    """Group leaf indices into ~bucket_elems buckets (used by the pure-DP
    flat paths and the collective-latency benchmarks)."""
    buckets, cur, acc = [], [], 0
    for i, s in enumerate(spec_sizes):
        cur.append(i)
        acc += s
        if acc >= bucket_elems:
            buckets.append(cur)
            cur, acc = [], 0
    if cur:
        buckets.append(cur)
    return buckets
