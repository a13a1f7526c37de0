"""Criteo-shaped rows and weights, all from the seed.

One row is the Criteo Terabyte click log's: 13 integer counts and 26
categorical values, as the set is distributed for MLPerf DLRM (ids
assigned once, offline), hashed the way Alink's ``FeatureHasher`` does with
``field_aware``: every field owns one block of ``field_size`` slots; a
categorical value lands on ``hash(value) mod field_size`` of its block with
weight 1, an integer count on the one slot of its column with weight
``log1p(count)``. So a row is 39 (index, value) pairs, sorted, never two
on one index. Categorical values are drawn log-uniformly over the field's
published cardinality (Zipf with exponent 1: a few hot values, a long
tail). Clicks come from a hidden logistic model over (field, value), so
that the trainer has something to learn and scores are off the sigmoid's
flat ends.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer: uint64 -> well-mixed uint64."""
    x = x.astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def field_size(n_features: int, n_fields: int) -> int:
    """Slots per field: the feature space split evenly, down to a
    multiple of 16."""
    return n_features // n_fields // 16 * 16


def make_rows(seed: int, n_rows: int, shape: Dict, n_features: int
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(idx int32 (n, 39), val float32 (n, 39), click int64 (n,))``
    with feature indices in ``[0, n_features)``."""
    n_int = int(shape["int_fields"])
    cards = [int(c) for c in shape["cat_cardinalities"]]
    n_fields = n_int + len(cards)
    S = field_size(n_features, n_fields)
    rng = np.random.default_rng([int(seed), 1])
    idx = np.empty((n_rows, n_fields), np.int32)
    val = np.empty((n_rows, n_fields), np.float32)
    margin = np.full(n_rows, float(shape["click_bias"]))
    with np.errstate(over="ignore"):
        for k in range(n_int):
            slot = int(_mix(np.asarray([k + 1], np.uint64))[0] % np.uint64(S))
            idx[:, k] = k * S + slot
            v = np.log1p(np.floor(rng.exponential(20.0, n_rows)))
            val[:, k] = v
            margin += (v - 2.5) * 0.05 * (1 if k % 2 else -1)
        for j, card in enumerate(cards):
            k = n_int + j
            rank = np.floor(np.exp(rng.random(n_rows) * np.log(card))
                            ).astype(np.uint64) - np.uint64(1)
            h = _mix(rank + np.uint64((k + 1) << 40))
            idx[:, k] = k * S + (h % np.uint64(S)).astype(np.int64)
            val[:, k] = 1.0
            # the hidden effect of (field, value): uniform in +-0.6, and
            # nought for two values in three
            u = (_mix(h) >> np.uint64(11)).astype(np.float64) / float(1 << 53)
            margin += np.where(u < 1 / 3, (u * 3 - 0.5) * 1.2, 0.0)
    click = (rng.random(n_rows) < 1.0 / (1.0 + np.exp(-margin))
             ).astype(np.int64)
    return idx, val, click


def host_weights(seed: int, n: int, scale: float, stream: int) -> np.ndarray:
    """``n`` float32 weights, uniform in +-scale, from the seed: blocks of
    2^24, each from a generator of its own, filled by a few threads (NumPy
    draws without the interpreter's lock; one thread takes 8 s for 2^30)."""
    from concurrent.futures import ThreadPoolExecutor
    w = np.empty(n, np.float32)
    block = 1 << 24

    def fill(k: int) -> None:
        part = w[k * block:(k + 1) * block]
        rng = np.random.default_rng([int(seed), 2, int(stream), k])
        rng.random(out=part, dtype=np.float32)
        part -= np.float32(0.5)
        part *= np.float32(2.0 * scale)

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, range(-(-n // block))))
    return w


def device_weights(seed: int, n: int, scale: float, stream: int):
    """The same kind of vector made on the device in one jitted call, in
    the type it is served in (float32; float64 where a test session has
    x64 on, as the server then ships float64)."""
    import jax
    import jax.numpy as jnp
    dt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32

    @jax.jit
    def make(key):
        return jax.random.uniform(key, (n,), jnp.float32, -scale, scale
                                  ).astype(dt)

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    for part in (seed >> 31, int(stream)):
        key = jax.random.fold_in(key, part)
    return make(key)
