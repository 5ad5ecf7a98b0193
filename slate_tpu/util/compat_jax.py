"""The few JAX spellings the framework needs, one per function, for the
installed JAX (0.9)."""

from __future__ import annotations

import jax
from jax import lax


def tracing() -> bool:
    """True while a JAX transformation (jit, vmap, shard_map, eval_shape)
    is tracing on this thread."""
    return not jax.core.trace_ctx.is_top_level()


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """shard_map with the static replication checker disabled.

    Scan carries that start as constants and become psum-replicated inside
    the loop (dist_lu's perm/min-pivot trace, dist_chol's health trace) are
    genuinely replicated but not provably so to the checker — it requires
    exact carry-rep equality and has no join for constant reps."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pvary(x, axes):
    """Mark ``x`` device-varying over ``axes`` for the replication checker."""
    return lax.pcast(x, axes, to="varying")
