"""Multi-chip paths, written for `jax.shard_map` and its varying-manual-
axes typing (`jax.typeof(...).vma`, `jax.lax.pcast`)."""
from . import sharded  # noqa: F401
