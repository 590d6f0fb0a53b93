"""The one import site of `jax.shard_map` (top-level export, `check_vma=`
kwarg): every engine imports it from here."""

from __future__ import annotations

from jax import shard_map

__all__ = ["shard_map"]
