"""The TPU kernels: megablox `gmm` (rows x each group's matrix) and its
custom VJP (`gmm` again for the rows' gradient, `tgmm` for the weights'),
as shipped with jax. Only the tiling is chosen here.

`group_sizes` may have more entries than `rhs` has groups: rows of the
trailing groups are not computed (no tile visits them) and come out zero.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.ops import gmm as _gmm

TM = 512          # row tile; the row count is padded to a multiple of it


def _tile(dim: int) -> int:
    """512 where it divides the dim, else the whole dim up to 2048 (the
    expert width 1408 is 11 x 128: a 128 tile would re-read the rows 11
    times), else 128 with the last tile masked."""
    if dim % 512 == 0:
        return 512
    return dim if dim <= 2048 else 128


def tiling(m: int, k: int, n: int):
    return (min(TM, m), _tile(k), _tile(n))


def gmm_fwd(lhs, rhs, group_sizes, *, group_offset=None,
            interpret: bool = False):
    """lhs [m, k] (rows sorted by group), rhs [G, k, n], group_sizes [>= G]
    int32 -> [m, n] in lhs's dtype (float32 accumulation). With
    `group_offset` (an int32 scalar) rhs holds groups [offset, offset + G)
    of group_sizes, and rows of the other groups come out zero."""
    return _gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype, tiling,
                group_offset, None, False, interpret)
