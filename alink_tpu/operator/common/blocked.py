"""What the blocked trainers share when they walk a worker's shard block by
block (``clustering/kmeans.py``, ``tree/hist.py``): reading a block, a sum
carried across the blocks with its compensation, and a row count that a
float32 psum keeps exact. All traceable."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def block_at(arr, i):
    """Block ``i`` of a shard laid out ``(blocks, ...)``."""
    return jax.lax.dynamic_index_in_dim(arr, i, 0, keepdims=False)


def kahan_add(acc, comp, x):
    """One compensated addition: the running sum and what it lost."""
    y = x - comp
    t = acc + y
    return t, (t - acc) - y


def split_count(rows):
    """An int32 row count as two halves that a float32 psum keeps exact
    (each under 2^16 a worker); ``join_count`` puts them back."""
    return rows // 65536, rows % 65536


def join_count(hi, lo):
    return hi.astype(jnp.int32) * 65536 + lo.astype(jnp.int32)
