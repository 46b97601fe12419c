"""``shard_map`` for every user in this package (``jax.shard_map``)."""

from __future__ import annotations

import jax

shard_map = jax.shard_map
