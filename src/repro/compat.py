"""``shard_map`` with replication checking off."""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma=False``: callers here produce
    replicated outputs by construction (e.g. the coreset solve repeated on
    every device), which the checker cannot see through."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
