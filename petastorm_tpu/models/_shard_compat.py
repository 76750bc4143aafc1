"""shard_map vma helper shared by the parallel model families."""

from __future__ import annotations

import jax


def mark_varying(x, axis_names):
    """Mark ``x`` as varying over ``axis_names`` for shard_map's vma typing
    (constants mixed with per-shard data inside loop carries need this).
    Idempotent — axes ``x`` already varies over are skipped (pcast rejects
    re-casting)."""
    current = jax.typeof(x).vma
    missing = tuple(a for a in axis_names if a not in current)
    if not missing:
        return x
    return jax.lax.pcast(x, missing, to="varying")
