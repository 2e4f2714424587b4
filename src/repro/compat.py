"""One import for ``jax.shard_map`` with replication checking off.

Keep this a leaf module (jax-only imports) so ``core/``, ``models/`` and
``launch/`` can all depend on it without cycles.
"""
from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma=False`` (``mesh`` is keyword-only)."""
    return _shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
