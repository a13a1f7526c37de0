"""A table of Gaussian blobs in the trainers' blocked layout, made on the
device from the seed.

What is known of HiBench's ``GenKMeansDataset`` (it wraps Mahout's sample
generator): ``num_of_clusters`` Gaussian clusters in ``dimensions``
dimensions, each with a centre and a spread of its own drawn once, and
``num_of_samples`` rows drawn around them. Here the centres are uniform in
a cube, the spreads and the shares of the clusters differ (the ranges are
the configuration's ``generator``), so a fit with more centres than there
are clusters has to cut through dense blobs.

The table never exists on the host: one program draws it block by block
into ``(row_blocks, d, S, 128)`` float32 (row ``r`` of block ``b`` at
``[b, :, r // 128, r % 128]``), rows past ``n_rows`` zero. The draw is
``jax.random`` with the ``rbg`` generator: the same seed gives the same
table on the same kind of device.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

LANES = 128


def mixture(seed: int, clusters: int, dim: int, spec: Dict) -> Dict[str, np.ndarray]:
    """Centres ``(c, d)``, spreads ``(c,)`` and shares ``(c,)`` of the
    clusters, from the seed."""
    rng = np.random.default_rng([int(seed), 5])
    half = float(spec["centre_half_width"])
    lo, hi = (float(v) for v in spec["spread_range"])
    s_lo, s_hi = (float(v) for v in spec["share_range"])
    share = rng.uniform(s_lo, s_hi, clusters)
    return {"centres": rng.uniform(-half, half, (clusters, dim)).astype(np.float32),
            "spreads": rng.uniform(lo, hi, clusters).astype(np.float32),
            "shares": (share / share.sum()).astype(np.float32)}


def _drawer(seed: int, n_rows: int, dim: int, block_rows: int,
            mix: Dict[str, np.ndarray]):
    """``block(b)`` draws block ``b`` of the table, inside a program."""
    import jax
    import jax.numpy as jnp
    if block_rows % (8 * LANES):
        raise ValueError("block_rows must be a multiple of 1,024")
    S = block_rows // LANES
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg"), int(seed) >> 31)
    cum = jnp.asarray(np.cumsum(mix["shares"])[:-1])
    centres, spreads = jnp.asarray(mix["centres"]), jnp.asarray(mix["spreads"])

    def block(b):
        ku, kz = jax.random.split(jax.random.fold_in(key, b))
        u = jax.random.uniform(ku, (S, LANES))
        cid = (u[..., None] >= cum).sum(-1)
        z = jax.random.normal(kz, (dim, S, LANES), jnp.float32)
        x = jnp.take(centres.T, cid, axis=1) + spreads[cid][None] * z
        at = b * block_rows + jnp.arange(block_rows).reshape(S, LANES)
        return jnp.where((at < n_rows)[None], x, 0.0)
    return block


def make_table(seed: int, n_rows: int, dim: int, block_rows: int,
               mix: Dict[str, np.ndarray]):
    """The table on the default device, ``(row_blocks, dim, S, 128)``."""
    import jax
    import jax.numpy as jnp
    block = _drawer(seed, n_rows, dim, block_rows, mix)
    nb = -(-n_rows // block_rows)
    return jax.jit(lambda: jax.lax.map(
        block, jnp.arange(nb, dtype=jnp.int32)))()


def make_block(seed: int, n_rows: int, dim: int, block_rows: int,
               mix: Dict[str, np.ndarray], b: int):
    """Block ``b`` alone of the table ``make_table`` gives: ``(dim, S,
    128)``."""
    import jax
    import jax.numpy as jnp
    block = _drawer(seed, n_rows, dim, block_rows, mix)
    return jax.jit(block)(jnp.asarray(b, jnp.int32))
