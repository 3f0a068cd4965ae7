"""Overlapped backward: DDL gradient reduction issued *inside* the layer scan.

The post-hoc `ddl_reduce_tree` pass serializes every RS/AR/AG behind the
last layer's backward.  The paper's composition claim (LMS swap traffic AND
DDL reduction traffic both hide behind compute) needs the reduction to start
the moment a layer's gradients exist — the mirror image of the swap-in
double buffer.  This module provides that engine:

* ``make_grad_reduce_hook`` — a ``custom_vjp`` identity wrapper applied to a
  layer's params inside the decoder scan body.  Forward is the identity (the
  streamed/resident graphs are untouched); backward applies the DDL schedule
  to the layer's param *cotangents*, so the scan's backward sweep emits one
  per-layer reduction while earlier layers' backward is still computing, and
  — on host-resident plans — the reduced cotangent is what streams out to
  host as the next layer's params stream in.

  Small leaves coalesce into fixed-size buckets (``make_buckets``, sized by
  ``DDLConfig.bucket_mb``) so the fabric sees few large collectives instead
  of one per norm-scale vector.  Bucketing is per *scan-group iteration*:
  bucketing across layers would re-serialize the backward sweep the hook
  exists to overlap.  In ``"full"`` mode TP-sharded leaves are never
  flattened into buckets (concatenation would break the GSPMD model-axis
  layout — see the tree-level note in allreduce.py); they reduce per leaf
  via ``ddl_reduce_leaf``'s scatter-dim-aware path.  ``"shard"`` mode
  flattens everything, exactly like the legacy zero1 ``pack`` path it
  replaces: the flat shard-major optimizer state is inherently
  TP-oblivious, so zero1 remains a pure-DP/DP×pod technique here.

  Two keep modes:
    - ``"full"``  — RS(data) → AR(pod) → AG(data); the cotangent comes back
      as the fully reduced mean gradient (the paper's allreduce schedule).
    - ``"shard"`` — stop after AR(pod) and keep only this rank's 1/|data|
      shard, written back at its slot of a zero cotangent (shape rules of AD
      require the full shape; the zeros are never communicated).  The zero1
      step and the sharded microbatch accumulator slice the shard back out
      with ``collect_local_shards`` — no all-gather on the gradient path.
      A cotangent must match the primal (param) dtype, so the f32-reduced
      shard rounds through bf16 on its way out of the scan — one extra
      quantization of the reduced mean vs the legacy f32 pack path, the
      same magnitude as the bf16 noise each raw gradient already carries
      (DESIGN.md §5 "Numerics").

* ``ShardSpec`` — the shard-major flat layout those sliced-out shards live
  in: each leaf viewed as ``[rows, rowsize]`` (``rows`` = the scan's layer
  count for stacked leaves, else 1), rowsize padded to a multiple of |data|.
  Matching the hook's per-layer placement makes extraction a slice, not a
  collective, and gives zero1 optimizer state / microbatch accumulators a
  1/|data| footprint.

Error feedback is NOT threaded through the hooks: a ``custom_vjp`` backward
returns cotangents only, so compressed buckets quantize statelessly here.
EF remains a feature of the post-hoc ``ddl_reduce_tree`` path (DESIGN.md
§Overlapped backward).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.config.base import DDLConfig
from repro.core.ddl.allreduce import (_leaf_is_replicated, ddl_reduce_leaf,
                                      flat_allreduce,
                                      hierarchical_reduce_scatter_flat,
                                      make_buckets)
from repro.obs import get_obs


# executor default when DDLConfig.bucket_mb is None (auto)
DEFAULT_BUCKET_MB = 64


def _bucket_elems(cfg: DDLConfig) -> int:
    """DDLConfig.bucket_mb in f32 elements (reductions run in f32).
    bucket_mb=None means auto: the executor default."""
    mb = DEFAULT_BUCKET_MB if cfg.bucket_mb is None else int(cfg.bucket_mb)
    return max(mb * (1 << 20) // 4, 1)


def _flat_f32(x) -> jnp.ndarray:
    return jnp.reshape(x.astype(jnp.float32), (-1,))


# ---------------------------------------------------------------------------
# Flat bucket reduction (inside shard_map manual axes)
# ---------------------------------------------------------------------------

def _reduce_bucket_full(flat, *, data_axis, pod_axis, data_size, pod_size,
                        compress_dcn, topology_aware):
    """One flat f32 bucket -> fully reduced mean (RS/AR/AG or flat psum)."""
    mean_over = data_size * pod_size
    if not topology_aware:
        axes = (data_axis,) + ((pod_axis,) if pod_axis else ())
        return flat_allreduce(flat, axes, mean_over=mean_over)
    pad = (-flat.size) % max(data_size, 1)
    flatp = jnp.pad(flat, (0, pad))
    shard, _ = hierarchical_reduce_scatter_flat(
        flatp, data_axis=data_axis, pod_axis=pod_axis,
        compress_dcn=compress_dcn, mean_over=mean_over)
    full = jax.lax.all_gather(shard, data_axis, axis=0, tiled=True)
    return full[:flat.size]


def _reduce_bucket_shard(parts, *, data_axis, pod_axis, data_size, pod_size,
                         compress_dcn):
    """Reduce a bucket of leaves keeping only this rank's 1/|data| shard of
    EACH leaf, written back at its per-leaf slot of a zero cotangent (phases
    1-2 only; no all-gather).

    The layout must match ShardSpec — rank r owns columns [r*sl, (r+1)*sl)
    of every leaf's padded flat row — not rank r's chunk of the concatenated
    bucket, or `collect_local_shards`'s per-leaf slices would read zeros.
    Each leaf is reshaped to [d, sl] so row r stacks the per-leaf rank-r
    chunks side by side; one psum_scatter over the row dim then reduces the
    whole bucket and hands every rank exactly its per-leaf chunks."""
    d = max(data_size, 1)
    mean_over = data_size * pod_size
    cols, sls = [], []
    for g in parts:
        flat = _flat_f32(g)
        pr = flat.size + ((-flat.size) % d)
        sls.append(pr // d)
        cols.append(jnp.pad(flat, (0, pr - flat.size)).reshape(d, pr // d))
    mat = jnp.concatenate(cols, axis=1)                      # [d, bucket_sl]
    shard = jax.lax.psum_scatter(mat, data_axis, scatter_dimension=0,
                                 tiled=True)                 # [1, bucket_sl]
    if pod_axis is not None:
        if compress_dcn:
            from repro.core.ddl.compress import compressed_allreduce_pod
            shard, _ = compressed_allreduce_pod(shard, pod_axis)
        else:
            shard = jax.lax.psum(shard, pod_axis)
    shard = shard / mean_over
    rank = jax.lax.axis_index(data_axis)
    placed = jax.lax.dynamic_update_slice(jnp.zeros_like(mat), shard,
                                          (rank, 0))
    out, off = [], 0
    for g, sl in zip(parts, sls):
        x = placed[:, off:off + sl].reshape(-1)[:max(g.size, 1)]
        out.append(x.reshape(g.shape).astype(g.dtype))
        off += sl
    return out


def _split_bucket(flat, leaves):
    """Undo the concat of `leaves` (original shapes/dtypes) from flat f32."""
    out, off = [], 0
    for g in leaves:
        n = max(g.size, 1)
        out.append(flat[off:off + n].reshape(g.shape).astype(g.dtype))
        off += n
    return out


# ---------------------------------------------------------------------------
# The reduce-as-you-go hook
# ---------------------------------------------------------------------------

def _flatten_specs(param_specs, treedef, n):
    if param_specs is None:
        return [None] * n
    from jax.sharding import PartitionSpec
    specs = compat.tree.flatten(
        param_specs, is_leaf=lambda x: isinstance(x, PartitionSpec))[0]
    assert len(specs) == n, (len(specs), n)
    return specs


def reduce_tree_bucketed(ct, cfg: DDLConfig, *, data_axis: str,
                         pod_axis: Optional[str], data_size: int,
                         pod_size: int, keep: str, param_specs=None):
    """DDL-reduce one layer's cotangent pytree with fixed-size bucketing.
    This is the hook's backward, exposed for direct testing."""
    leaves, treedef = compat.tree.flatten(ct)
    specs = _flatten_specs(param_specs, treedef, len(leaves))
    out: List[Optional[jnp.ndarray]] = [None] * len(leaves)
    bucketable = []
    for i, (g, sp) in enumerate(zip(leaves, specs)):
        if keep == "full" and cfg.topology_aware and not _leaf_is_replicated(sp):
            r, _ = ddl_reduce_leaf(
                g, data_axis=data_axis, pod_axis=pod_axis,
                data_size=data_size, pod_size=pod_size,
                compress_dcn=cfg.compress_dcn,
                topology_aware=cfg.topology_aware, spec=sp)
            out[i] = r.astype(g.dtype)
        else:
            bucketable.append(i)
    sizes = [max(leaves[i].size, 1) for i in bucketable]
    buckets = make_buckets(sizes, _bucket_elems(cfg))
    if buckets:
        # trace-time accounting (fires once per layer-group trace, not per
        # execution): bucket count + f32 reduction bytes for this layer's
        # cotangent
        _obs = get_obs()
        _obs.trace_event("ddl.bucket", buckets=len(buckets),
                         bytes=4 * sum(sizes), keep=keep)
        _obs.registry.counter("ddl.buckets").inc(len(buckets))
        _obs.registry.counter("ddl.bucket_bytes").inc(4 * sum(sizes))
    for bucket in buckets:
        idxs = [bucketable[j] for j in bucket]
        parts = [leaves[i] for i in idxs]
        if keep == "full":
            flat = jnp.concatenate([_flat_f32(p) for p in parts])
            red = _reduce_bucket_full(
                flat, data_axis=data_axis, pod_axis=pod_axis,
                data_size=data_size, pod_size=pod_size,
                compress_dcn=cfg.compress_dcn,
                topology_aware=cfg.topology_aware)
            reduced = _split_bucket(red, parts)
        else:
            reduced = _reduce_bucket_shard(
                parts, data_axis=data_axis, pod_axis=pod_axis,
                data_size=data_size, pod_size=pod_size,
                compress_dcn=cfg.compress_dcn)
        for i, r in zip(idxs, reduced):
            out[i] = r
    return compat.tree.unflatten(treedef, out)


def make_grad_reduce_hook(cfg: DDLConfig, *, data_axis: str = "data",
                          pod_axis: Optional[str] = None, data_size: int = 1,
                          pod_size: int = 1, keep: str = "full",
                          param_specs=None,
                          sink: Optional[str] = None) -> Callable:
    """Identity-forward wrapper whose backward DDL-reduces the cotangent.

    Wrap a layer's param tree inside the scan body (`lp = hook(lp)`): the
    scan's backward then issues that layer's collectives as soon as its
    gradients exist, overlapping them with the remaining backward compute.
    `param_specs`: per-layer PartitionSpec tree (layer axis dropped) gating
    which leaves may be flattened into buckets.
    `sink`: optional memory kind (e.g. "pinned_host") the reduced cotangent
    is emitted to — the gradient host sink of a `residency["grads"]=="host"`
    plan. Each layer's reduced gradient leaves HBM as soon as it is
    produced, so only ~prefetch_depth layers of gradients are ever
    device-resident; the streamed optimizer sweep reads them back layer by
    layer. None (or an unsupported kind) keeps the cotangent where it is.
    """
    assert keep in ("full", "shard"), keep

    @jax.custom_vjp
    def hook(tree):
        return tree

    def fwd(tree):
        return tree, None

    def bwd(_, ct):
        with jax.named_scope("ddl.reduce"):
            red = reduce_tree_bucketed(
                ct, cfg, data_axis=data_axis, pod_axis=pod_axis,
                data_size=data_size, pod_size=pod_size, keep=keep,
                param_specs=param_specs)
        return (compat.to_memory_kind(red, sink),)

    hook.defvjp(fwd, bwd)
    return hook


def make_stack_hooks(stack_specs: Dict[str, object], cfg: DDLConfig, *,
                     data_axis: str = "data", pod_axis: Optional[str] = None,
                     data_size: int = 1, pod_size: int = 1,
                     keep: str = "full",
                     sink: Optional[str] = None) -> Dict[str, Callable]:
    """One hook per decoder scan group (the per-group param structures —
    and so the custom_vjp signatures — differ). `sink`: memory kind for the
    gradient host sink (see `make_grad_reduce_hook`)."""
    return {name: make_grad_reduce_hook(
                cfg, data_axis=data_axis, pod_axis=pod_axis,
                data_size=data_size, pod_size=pod_size, keep=keep,
                param_specs=spec, sink=sink)
            for name, spec in stack_specs.items()}


# ---------------------------------------------------------------------------
# Shard-major flat layout (zero1 state / sharded microbatch accumulator)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardSpec:
    """Layout of one rank's flat shard of a reduce-scattered pytree.

    Each leaf is a ``[rows, rowsize]`` matrix — ``rows`` is the scan trip
    count for stacked decoder leaves (the shard-mode hook reduces per layer
    ROW), 1 otherwise — with rowsize zero-padded to ``padded_row`` (a
    multiple of |data|).  Rank r's shard of a leaf is column block
    ``[:, r*sr:(r+1)*sr]`` (``sr = padded_row/|data|``); its flat local
    vector is those blocks flattened and concatenated in leaf order.
    """
    shapes: List[Tuple[int, ...]]
    dtypes: List
    rows: List[int]
    rowsizes: List[int]
    padded_rows: List[int]
    treedef: object
    data_size: int

    @property
    def local_size(self) -> int:
        d = max(self.data_size, 1)
        return sum(r * (p // d) for r, p in zip(self.rows, self.padded_rows))

    @property
    def padded(self) -> int:
        """Global flat length (the P("data")-sharded state vector)."""
        return max(self.data_size, 1) * self.local_size


def shard_spec(tree, data_size: int, stacked=None) -> ShardSpec:
    """Build the layout from a pytree of arrays/ShapeDtypeStructs.
    `stacked`: matching pytree of bools — True for leaves whose leading axis
    is a scan layer axis (decoder stack groups)."""
    leaves, treedef = compat.tree.flatten(tree)
    if stacked is None:
        flags = [False] * len(leaves)
    else:
        flags = compat.tree.leaves(stacked)
        assert len(flags) == len(leaves), (len(flags), len(leaves))
    d = max(data_size, 1)
    shapes, dtypes, rows, rowsizes, padded = [], [], [], [], []
    for l, st in zip(leaves, flags):
        shape = tuple(l.shape)
        n = int(np.prod(shape)) if shape else 1
        r = shape[0] if (st and shape) else 1
        rs = max(n // max(r, 1), 1)
        shapes.append(shape)
        dtypes.append(l.dtype)
        rows.append(r)
        rowsizes.append(rs)
        padded.append(rs + ((-rs) % d))
    return ShardSpec(shapes, dtypes, rows, rowsizes, padded, treedef, d)


def _leaf_rows(g, r, rs, pr):
    x = jnp.reshape(g.astype(jnp.float32), (r, rs))
    return jnp.pad(x, ((0, 0), (0, pr - rs)))


def collect_local_shards(tree, spec: ShardSpec, reduced, *, data_axis: str,
                         pod_axis: Optional[str], mean_over: int,
                         compress_dcn: bool = False) -> jnp.ndarray:
    """One rank's flat ``[local_size]`` f32 shard of the DDL-reduced tree.

    `reduced`: matching pytree of bools — True for leaves the shard-mode
    hook already reduced (zeros outside this rank's slot: sliced out, no
    collective), False for the rest (embedding, final norm, unscanned
    layers: reduce-scattered here)."""
    leaves, _ = compat.tree.flatten(tree)
    flags = compat.tree.leaves(reduced)
    assert len(flags) == len(leaves), (len(flags), len(leaves))
    d = spec.data_size
    rank = jax.lax.axis_index(data_axis)
    parts = []
    for g, was_reduced, r, rs, pr in zip(leaves, flags, spec.rows,
                                         spec.rowsizes, spec.padded_rows):
        x = _leaf_rows(g, r, rs, pr)
        sl = pr // d
        if was_reduced:
            loc = jax.lax.dynamic_slice(x, (0, rank * sl), (r, sl))
        else:
            loc = jax.lax.psum_scatter(x, data_axis, scatter_dimension=1,
                                       tiled=True)
            if pod_axis is not None:
                if compress_dcn:
                    from repro.core.ddl.compress import compressed_allreduce_pod
                    loc, _ = compressed_allreduce_pod(loc, pod_axis)
                else:
                    loc = jax.lax.psum(loc, pod_axis)
            loc = loc / mean_over
        parts.append(loc.reshape(-1))
    return jnp.concatenate(parts)


def allgather_local_shards(flat: jnp.ndarray, spec: ShardSpec, *,
                           data_axis: str):
    """Invert ``collect_local_shards``: all-gather each leaf's column blocks
    over `data`, unpad, reshape.  Leaves come back f32 (the accumulator /
    master-weight dtype); callers cast."""
    d = spec.data_size
    out, off = [], 0
    for shape, r, rs, pr in zip(spec.shapes, spec.rows, spec.rowsizes,
                                spec.padded_rows):
        sl = pr // d
        x = flat[off:off + r * sl].reshape(r, sl)
        full = jax.lax.all_gather(x, data_axis, axis=1, tiled=True)
        out.append(full[:, :rs].reshape(shape))
        off += r * sl
    return compat.tree.unflatten(spec.treedef, out)


def pack_global(tree, spec: ShardSpec) -> jnp.ndarray:
    """Full tree -> global flat ``[|data| * local_size]`` f32 in shard-major
    order (a P("data") sharding hands rank r exactly its local shard).
    Host-side state initialization; no collectives."""
    leaves, _ = compat.tree.flatten(tree)
    d = spec.data_size
    blocks = []
    for g, r, rs, pr in zip(leaves, spec.rows, spec.rowsizes,
                            spec.padded_rows):
        x = _leaf_rows(g, r, rs, pr)            # [r, pr]
        sl = pr // d
        x = x.reshape(r, d, sl).transpose(1, 0, 2)  # [d, r, sl]
        blocks.append(x.reshape(d, r * sl))
    return jnp.concatenate(blocks, axis=1).reshape(-1)


def unpack_global(flat: jnp.ndarray, spec: ShardSpec):
    """Inverse of ``pack_global`` (f32 leaves, original shapes)."""
    d = spec.data_size
    mat = flat.reshape(d, spec.local_size)
    out, off = [], 0
    for shape, r, rs, pr in zip(spec.shapes, spec.rows, spec.rowsizes,
                                spec.padded_rows):
        sl = pr // d
        x = mat[:, off:off + r * sl].reshape(d, r, sl)
        x = x.transpose(1, 0, 2).reshape(r, pr)[:, :rs]
        out.append(x.reshape(shape))
        off += r * sl
    return compat.tree.unflatten(spec.treedef, out)
