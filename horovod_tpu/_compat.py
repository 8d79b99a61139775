"""The two jax names the manual-SPMD code uses, imported from one place.

The installed jax (0.9) has ``jax.shard_map`` (with ``check_vma``) and
``jax.lax.axis_size``; every in-repo call site imports them from here.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map
axis_size = jax.lax.axis_size
