"""Oracle: each group's rows times its matrix, as masked dense products
(every row through every group's matrix; G x the work)."""
from __future__ import annotations

import jax.numpy as jnp


def gmm_ref(lhs, rhs, group_sizes):
    """lhs [m, k], rhs [G, k, n], group_sizes [>= G] -> [m, n]; rows past
    the first G groups are zero."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    row = jnp.arange(lhs.shape[0])[:, None]
    mask = (row >= starts[None, :rhs.shape[0]]) & (row < ends[None, :rhs.shape[0]])
    out = jnp.einsum("mk,gkn,mg->mn", lhs, rhs, mask.astype(lhs.dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)
