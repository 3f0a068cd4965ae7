"""jit'd dispatch: the megablox kernels on TPU (or under the CI
forced-interpret flag), the masked-dense oracle elsewhere."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.gates import resolve_interpret, use_pallas
from repro.kernels.grouped_matmul.kernel import TM, gmm_fwd
from repro.kernels.grouped_matmul.ref import gmm_ref
from repro.obs import get_obs


def grouped_matmul(lhs, rhs, group_sizes, *, expert_axes=(),
                   interpret: bool = False):
    """lhs [m, k] with rows sorted by group; rhs [G, k, n]; group_sizes
    int32 [>= G] -> [m, n], rows past the first G groups zero.
    Differentiable in lhs and rhs. `expert_axes`: the mesh axes rhs's group
    axis is split over (expert parallelism), () when it is whole.
    Trace-time counter `moe.gmm_calls`."""
    get_obs().registry.counter("moe.gmm_calls").inc()
    if not use_pallas(interpret):
        return gmm_ref(lhs, rhs, group_sizes)
    m = lhs.shape[0]
    pad = (-m) % min(TM, m)
    if pad:   # padding rows join the last group, which is never computed
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        group_sizes = group_sizes.at[-1].add(pad)
    kernel = functools.partial(gmm_fwd,
                               interpret=resolve_interpret(interpret))
    mesh = jax.sharding.get_abstract_mesh()
    auto = () if mesh.empty else tuple(mesh.auto_axes)
    expert_axes = tuple(expert_axes)
    if auto and expert_axes and set(expert_axes) <= set(auto):
        out = _expert_parallel(kernel, auto, expert_axes)(lhs, rhs,
                                                          group_sizes)
    elif auto:
        # a Mosaic kernel cannot be partitioned automatically: run it
        # manual over the mesh axes the caller left to the compiler
        out = jax.shard_map(kernel, in_specs=(P(), P(), P()),
                            out_specs=P(), axis_names=set(auto),
                            check_vma=False)(lhs, rhs, group_sizes)
    else:
        out = kernel(lhs, rhs, group_sizes)
    return out[:m] if pad else out


def _expert_parallel(kernel, auto, axes):
    """The kernel manual over `auto`, each shard computing the rows of its
    own groups (megablox's group offset) and zero elsewhere; the shards'
    [m, n] results are summed outside, where the compiler places the
    reduction and, in the backward, the broadcast of its cotangent."""
    def local(lhs, rhs, group_sizes, first):
        return kernel(lhs, rhs, group_sizes, group_offset=first[0])[None]

    run = jax.shard_map(local, in_specs=(P(), P(axes), P(), P(axes)),
                        out_specs=P(axes), axis_names=set(auto),
                        check_vma=False)

    def call(lhs, rhs, group_sizes):
        shards = math.prod(jax.sharding.get_abstract_mesh().shape[a]
                           for a in axes)
        first = jnp.arange(shards, dtype=jnp.int32) * (rhs.shape[0] // shards)
        return run(lhs, rhs, group_sizes, first).sum(0)
    return call
