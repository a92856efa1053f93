"""The one import home of ``shard_map`` and ``pcast``.

Both have moved between jax releases before.  Every module imports them
from here (lint rule TPU304 points here), so the next move is one edit.
"""

from jax import shard_map
from jax.lax import pcast

__all__ = ["shard_map", "pcast"]
