"""A table of (user, item, rating) triples at the shape of the Yahoo! Music
ratings of KDD-Cup 2011 track 1, in the trainers' blocked layout, made on
the device from the seed.

What is known of the table (Dror, Koenigstein, Koren, Weimer, "The Yahoo!
Music Dataset and KDD-Cup'11", JMLR W&CP 18; cuMF, arXiv:1603.03820, its
table of data sets): 1,000,990 users x 624,961 items, 252,800,275 training
ratings, whole numbers 0-100. The real file's degree distribution is not
known here; the generator's is ASSUMED and the configuration says so: the
user and the item of a rating are drawn independently, each from a power
law by a closed form of one uniform draw (no table lookup a rating):
``rank = floor(n * x ** skew)``, so rank ``k`` of ``n`` holds about
``ratings / (skew * n) * (k / n) ** (1 / skew - 1)`` ratings (skew 2: the
heaviest user ~126,000, the median ~180). The rank then goes through a
fixed bijection of the ids (``(rank * stride + n // 3) mod n``, the stride
coprime to ``n``), so that heavy rows lie anywhere among the ids, and the rows come
in the draw's random order: the program may assume no order of either.
A pair drawn twice is two observations.

The rating comes from a teacher fixed by the seed: a few product terms of
periodic functions of the two ids, plus noise, rounded and clipped to
0-100, so that a factor model has something to find.

Nothing exists on the host: one program draws the three columns block by
block into ``(row_blocks, S, 128)`` int32, int32 and float32 (row ``r`` of
block ``b`` at ``[b, r // 128, r % 128]``), rows past ``n_rows`` zero. The
draw is ``jax.random`` with the ``rbg`` generator: the same seed gives the
same table on the same kind of device.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

LANES = 128


def stride_for(n: int, want: int) -> int:
    """The least stride at or above ``want`` that is coprime to ``n`` and
    keeps ``rank * stride`` inside 32 bits."""
    s = int(want)
    while math.gcd(s, n) != 1:
        s += 1
    if (n - 1) * s + n // 3 >= 2 ** 32:
        raise ValueError(f"stride {s} overflows 32 bits at {n} ids")
    return s


def teacher(seed: int, spec: Dict) -> Dict[str, np.ndarray]:
    """The rating's teacher, from the seed: per term a frequency and a
    phase a side, and a weight."""
    rng = np.random.default_rng([int(seed), 23])
    T = int(spec["terms"])
    return {"fu": rng.uniform(0.5, 40.0, T).astype(np.float32),
            "fi": rng.uniform(0.5, 40.0, T).astype(np.float32),
            "pu": rng.uniform(0, 1, T).astype(np.float32),
            "pi": rng.uniform(0, 1, T).astype(np.float32),
            "w": (rng.choice([-1.0, 1.0], T)
                  * rng.uniform(0.6, 1.0, T)).astype(np.float32)}


def _drawer(seed: int, n_rows: int, block_rows: int, users: int, items: int,
            spec: Dict):
    """``block(b)`` draws block ``b`` of the three columns, inside a
    program."""
    import jax
    import jax.numpy as jnp
    if block_rows % (8 * LANES):
        raise ValueError("block_rows must be a multiple of 1,024")
    S = block_rows // LANES
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg"), int(seed) >> 31)
    t = {k: jnp.asarray(v) for k, v in teacher(seed, spec).items()}
    su = stride_for(users, int(spec["user_stride"]))
    si = stride_for(items, int(spec["item_stride"]))

    def ids(x, n, skew, stride):
        rank = jnp.minimum((x ** skew * n).astype(jnp.int32), n - 1)
        return ((rank.astype(jnp.uint32) * jnp.uint32(stride)
                 + jnp.uint32(n // 3)) % jnp.uint32(n)).astype(jnp.int32)

    def block(b):
        ku, kn = jax.random.split(jax.random.fold_in(key, b))
        x = jax.random.uniform(ku, (2, S, LANES), jnp.float32)
        z = jax.random.normal(kn, (S, LANES), jnp.float32)
        u = ids(x[0], users, float(spec["user_skew"]), su)
        i = ids(x[1], items, float(spec["item_skew"]), si)
        au = u.astype(jnp.float32) / users
        ai = i.astype(jnp.float32) / items
        two_pi = 2 * np.pi
        low_rank = sum(
            t["w"][k] * jnp.sin(two_pi * (t["fu"][k] * au + t["pu"][k]))
            * jnp.sin(two_pi * (t["fi"][k] * ai + t["pi"][k]))
            for k in range(int(spec["terms"])))
        r = jnp.round(float(spec["mean"]) + float(spec["scale"]) * low_rank
                      + float(spec["noise"]) * z).clip(0, 100)
        at = b * block_rows + jnp.arange(block_rows).reshape(S, LANES)
        here = at < n_rows
        return (jnp.where(here, u, 0), jnp.where(here, i, 0),
                jnp.where(here, r, 0.0).astype(jnp.float32))
    return block


def make_table(seed: int, n_rows: int, block_rows: int, users: int,
               items: int, spec: Dict):
    """``(users, items, ratings)``, each ``(row_blocks, S, 128)``, on the
    default device."""
    import jax
    import jax.numpy as jnp
    block = _drawer(seed, n_rows, block_rows, users, items, spec)
    nb = -(-n_rows // block_rows)
    return jax.jit(lambda: jax.lax.map(
        block, jnp.arange(nb, dtype=jnp.int32)))()
