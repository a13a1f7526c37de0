"""A table with the airline on-time data's columns, in the trainers' blocked
layout, made on the device from the seed, and its labels from a seeded
teacher.

What is known of the table (NVIDIA gbm-bench ``prepare_airline``; Mitchell
et al., arXiv:1806.11248, Table 2): 115 million flights x 13 features,
every column taken as a number (categorical columns as integer codes),
label ``ArrDelay > 0``. The 13 columns here have the table's kinds and
cardinalities (the configuration's ``generator`` gives them): year (22
values), month, day of month, day of week, two scheduled clock times
(hhmm, 0-2359), carrier (~29 codes), flight number (~8,000), elapsed
minutes, origin and destination (~340 codes each with a Zipf-like share),
distance, and a 0/1 flag. All are whole numbers held as float32.

The label is drawn from a teacher fixed by the seed: a sum of per-column
step functions and two pairwise terms through a logistic link, so that
every level of a tree finds a split and the loss falls.

Neither exists on the host: one program draws them block by block into
``(row_blocks, 13, S, 128)`` float32 and ``(row_blocks, S, 128)`` float32
(row ``r`` of block ``b`` at ``[b, :, r // 128, r % 128]``), rows past
``n_rows`` zero. The draw is ``jax.random`` with the ``rbg`` generator:
the same seed gives the same table on the same kind of device.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

LANES = 128
COLUMNS = ("year", "month", "day", "weekday", "dep_time", "arr_time",
           "carrier", "flight", "elapsed", "origin", "dest", "distance",
           "flag")
STEPS = 3          # steps of the teacher's function of one column


def teacher(seed: int, spec: Dict) -> Dict[str, np.ndarray]:
    """The label's teacher, from the seed: per column ``STEPS`` thresholds
    (at random ranks of the column's range) and step heights, two pairwise
    terms and a bias."""
    rng = np.random.default_rng([int(seed), 17])
    lo = np.asarray([spec["ranges"][c][0] for c in COLUMNS], np.float32)
    hi = np.asarray([spec["ranges"][c][1] for c in COLUMNS], np.float32)
    at = np.sort(rng.uniform(0.15, 0.85, (len(COLUMNS), STEPS)), 1)
    thr = np.floor(lo[:, None] + at * (hi - lo)[:, None]).astype(np.float32)
    height = rng.normal(0.0, float(spec["step_scale"]),
                        (len(COLUMNS), STEPS)).astype(np.float32)
    pairs = np.asarray([[COLUMNS.index(a), COLUMNS.index(b)]
                        for a, b in spec["pairs"]], np.int32)
    pair_thr = np.stack([thr[pairs[:, 0], 1], thr[pairs[:, 1], 1]], 1)
    pair_height = rng.normal(0.0, float(spec["pair_scale"]),
                             len(pairs)).astype(np.float32)
    # centred: a step at rank a of its column is passed by 1 - a of the
    # rows (of a uniform column), so the logit's mean stays near the bias
    # whatever heights the seed drew
    mean = float((height * (1 - at)).sum()
                 + (pair_height * (1 - at[pairs[:, 0], 1])
                    * (1 - at[pairs[:, 1], 1])).sum())
    return {"thr": thr, "height": height, "pairs": pairs,
            "pair_thr": pair_thr, "pair_height": pair_height,
            "bias": np.float32(float(spec["bias"]) - mean)}


def _drawer(seed: int, n_rows: int, block_rows: int, spec: Dict):
    """``block(b)`` draws block ``b`` of the table and its labels, inside
    a program."""
    import jax
    import jax.numpy as jnp
    if block_rows % (8 * LANES):
        raise ValueError("block_rows must be a multiple of 1,024")
    S = block_rows // LANES
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg"), int(seed) >> 31)
    t = {k: jnp.asarray(v) for k, v in teacher(seed, spec).items()}
    rng = spec["ranges"]
    skew = float(spec["zipf_power"])

    def whole(u, lo, hi, power=1.0):
        """Whole numbers in [lo, hi] from uniforms; ``power`` > 1 gives
        the low codes the larger share."""
        return jnp.floor(lo + (hi - lo + 1) * u ** power).clip(lo, hi)

    def clock(uh, um):
        return jnp.floor(uh * 24).clip(0, 23) * 100 \
            + jnp.floor(um * 60).clip(0, 59)

    def block(b):
        ku, kn, kl = jax.random.split(jax.random.fold_in(key, b), 3)
        u = jax.random.uniform(ku, (15, S, LANES))
        z = jax.random.normal(kn, (2, S, LANES), jnp.float32)
        distance = whole(u[11], *rng["distance"], power=2.0)
        cols = [
            whole(u[0], *rng["year"]), whole(u[1], *rng["month"]),
            whole(u[2], *rng["day"]), whole(u[3], *rng["weekday"]),
            clock(u[4], u[13]), clock(u[5], u[14]),
            whole(u[6], *rng["carrier"], power=skew),
            whole(u[7], *rng["flight"]),
            # minutes in the air follow the distance, with taxi and noise
            jnp.floor(30 + distance / 7.5 + 12 * z[0]).clip(*rng["elapsed"]),
            whole(u[9], *rng["origin"], power=skew),
            whole(u[10], *rng["dest"], power=skew),
            distance,
            (u[12] < float(spec["flag_share"])).astype(jnp.float32)]
        x = jnp.stack(cols).astype(jnp.float32)               # (13, S, 128)
        steps = (x[:, None] >= t["thr"][:, :, None, None])    # (13, 3, S, 128)
        logit = t["bias"] + (steps * t["height"][:, :, None, None]).sum((0, 1))
        for j in range(t["pairs"].shape[0]):
            a, c = t["pairs"][j, 0], t["pairs"][j, 1]
            logit = logit + t["pair_height"][j] * (
                (x[a] >= t["pair_thr"][j, 0]) & (x[c] >= t["pair_thr"][j, 1]))
        y = (jax.random.uniform(kl, (S, LANES)) < jax.nn.sigmoid(
            logit + float(spec["noise"]) * z[1])).astype(jnp.float32)
        at = b * block_rows + jnp.arange(block_rows).reshape(S, LANES)
        here = at < n_rows
        return jnp.where(here[None], x, 0.0), jnp.where(here, y, 0.0)
    return block


def make_table(seed: int, n_rows: int, block_rows: int, spec: Dict):
    """``(table (row_blocks, 13, S, 128), labels (row_blocks, S, 128))``
    on the default device."""
    import jax
    import jax.numpy as jnp
    block = _drawer(seed, n_rows, block_rows, spec)
    nb = -(-n_rows // block_rows)
    return jax.jit(lambda: jax.lax.map(
        block, jnp.arange(nb, dtype=jnp.int32)))()


def make_block(seed: int, n_rows: int, block_rows: int, spec: Dict, b: int):
    """Block ``b`` alone of what ``make_table`` gives."""
    import jax
    import jax.numpy as jnp
    return jax.jit(_drawer(seed, n_rows, block_rows, spec))(
        jnp.asarray(b, jnp.int32))
