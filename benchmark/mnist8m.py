"""A table with the shape of ``mnist8m`` in the trainers' blocked layout,
one byte a pixel, made on the device from the seed.

What is known of the set (Loosli, Canu, Bottou 2007, the infinite-MNIST
generator; the LIBSVM page lists it): 8,100,000 rows of 28 x 28 = 784
pixels, whole numbers 0-255, ten classes; ~19 % of a row's pixels are not
zero and the image's border is zero in every row. The file is not shipped.
Here a row draws a stroke class uniformly; a pixel is inked with the
probability that class's soft stroke mask gives it (ten seeded maps of a
few Gaussian bumps over the 28 x 28 grid, scaled so that ``ink_share`` of
all pixels are inked; a border of ``border`` columns is never inked, so
those columns are constant) and an inked pixel is uniform over 1-255. The
label comes from a seeded linear teacher over the pixels (the masks
centred, plus a random part) with Gumbel noise, so pixels correlate with
the label, no class is rare, and no linear model fits it exactly.

The table never exists on the host: one program draws it block by block
into ``(row_blocks, 784, S, 128)`` uint8 (row ``r`` of block ``b`` at
``[b, :, r // 128, r % 128]``) and the labels into ``(row_blocks, S, 128)``
int32, rows past ``n_rows`` zero. The draw is ``jax.random`` with the
``rbg`` generator: the same seed gives the same table on the same kind of
device.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

LANES = 128
SIDE = 28
CLASSES = 10


def border_columns(spec: Dict) -> np.ndarray:
    """``(784,)`` bool: the pixels no row inks. The top and bottom image
    rows, and the left and right pixels of the next ``border_side_rows``
    image rows."""
    r, c = np.divmod(np.arange(SIDE * SIDE), SIDE)
    side = int(spec["border_side_rows"])
    return (r == 0) | (r == SIDE - 1) | (
        ((c == 0) | (c == SIDE - 1)) & (r <= side))


def stroke_masks(seed: int, spec: Dict) -> np.ndarray:
    """``(10, 784)`` float64 inking probabilities, a class a row: a few
    Gaussian bumps a class, zero on the border, scaled so the mean over
    classes and pixels is ``ink_share``."""
    rng = np.random.default_rng([int(seed), 11])
    r, c = np.divmod(np.arange(SIDE * SIDE), SIDE)
    bumps = int(spec["bumps"])
    lo, hi = (float(v) for v in spec["bump_width_range"])
    raw = np.zeros((CLASSES, SIDE * SIDE))
    for k in range(CLASSES):
        for _ in range(bumps):
            cr, cc = rng.uniform(5, SIDE - 5, 2)
            w = rng.uniform(lo, hi)
            raw[k] += np.exp(-((r - cr) ** 2 + (c - cc) ** 2) / (2 * w * w))
    raw += float(spec["floor"])             # every class inks a little anywhere
    raw[:, border_columns(spec)] = 0.0
    want = float(spec["ink_share"])
    lo_a, hi_a = 0.0, 50.0
    for _ in range(60):                      # the amplitude that inks want
        a = 0.5 * (lo_a + hi_a)
        if np.minimum(a * raw, float(spec["ink_cap"])).mean() < want:
            lo_a = a
        else:
            hi_a = a
    return np.minimum(hi_a * raw, float(spec["ink_cap"]))


def teacher(seed: int, masks: np.ndarray, spec: Dict
            ) -> Tuple[np.ndarray, np.ndarray]:
    """``(T (10, 784), b (10,))`` float32: the label's linear teacher over
    pixels / 255. The masks centred over the classes, plus a seeded random
    part, scaled so a row's winning margin is a few noise widths, with
    biases that balance the classes."""
    rng = np.random.default_rng([int(seed), 13])
    centred = masks - masks.mean(0, keepdims=True)
    T = centred + float(spec["teacher_random"]) * centred.std() \
        * rng.standard_normal(masks.shape)
    T[:, masks.sum(0) == 0] = 0.0
    # the mean logit of class k on rows of stroke class k, less the mean of
    # the others', in units of the Gumbel noise's width
    mean_x = 0.5 * masks                     # E[pixel / 255 | stroke class]
    z = mean_x @ T.T                         # (stroke class, label class)
    margin = np.mean(np.diag(z) - (z.sum(1) - np.diag(z)) / (CLASSES - 1))
    T *= float(spec["teacher_margin"]) / max(margin, 1e-12)
    # the biases that give every class a tenth of a host sample drawn as
    # the table's rows are (the shares of the table then lie within a
    # tenth of that)
    n = int(spec["balance_sample_rows"])
    cls = rng.integers(0, CLASSES, n)
    x = (rng.random((n, masks.shape[1])) < masks[cls]) \
        * rng.integers(1, 256, (n, masks.shape[1])) / 255.0
    z = x @ T.T + float(spec["label_noise"]) * rng.gumbel(size=(n, CLASSES))
    b = -z.mean(0)
    for _ in range(40):
        share = np.bincount(np.argmax(z + b, 1), minlength=CLASSES) / n
        b -= 0.5 * np.log(np.maximum(share, 1e-4) * CLASSES)
    return T.astype(np.float32), b.astype(np.float32)


def _drawer(seed: int, n_rows: int, block_rows: int, spec: Dict):
    """``block(b)`` draws block ``b``: ``(pixels (784, S, 128) uint8,
    labels (S, 128) int32)``, inside a program."""
    import jax
    import jax.numpy as jnp
    if block_rows % (32 * LANES):
        raise ValueError("block_rows must be a multiple of 4,096: a block "
                         "of bytes is whole 8-bit register tiles")
    S = block_rows // LANES
    masks = stroke_masks(seed, spec)
    T, b0 = teacher(seed, masks, spec)
    # a pixel is inked where a random byte is under its threshold
    thr = jnp.asarray(np.clip(np.round(masks * 256.0), 0, 255)
                      .astype(np.int32).T)                   # (784, 10)
    T, b0 = jnp.asarray(T), jnp.asarray(b0)
    noise = float(spec["label_noise"])
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg"), int(seed) >> 31)

    def block(b):
        kc, ki, kv, kn = jax.random.split(jax.random.fold_in(key, b), 4)
        cls = jax.random.randint(kc, (S, LANES), 0, CLASSES, jnp.int32)
        ink = jax.random.bits(ki, (SIDE * SIDE, S, LANES), jnp.uint8)
        val = jax.random.bits(kv, (SIDE * SIDE, S, LANES), jnp.uint8)
        at = b * block_rows + jnp.arange(block_rows).reshape(S, LANES)
        live = at < n_rows
        # the row's class's thresholds by a chain of selects (a gather of
        # 51 million elements a block would go element by element)
        mine = jnp.zeros((SIDE * SIDE, S, LANES), jnp.int32)
        for k in range(CLASSES):
            mine = jnp.where(cls[None] == k, thr[:, k][:, None, None], mine)
        on = (ink.astype(jnp.int32) < mine) & live[None]
        x = jnp.where(on, jnp.maximum(val, 1), 0).astype(jnp.uint8)
        z = jnp.einsum("kd,dsl->ksl", T, x.astype(jnp.float32) / 255.0,
                       precision=jax.lax.Precision.HIGHEST)
        z = z + b0[:, None, None] \
            + noise * jax.random.gumbel(kn, (CLASSES, S, LANES), jnp.float32)
        y = jnp.where(live, jnp.argmax(z, 0).astype(jnp.int32), 0)
        return x, y
    return block


def make_table(seed: int, n_rows: int, block_rows: int, spec: Dict):
    """``(pixels (row_blocks, 784, S, 128) uint8, labels (row_blocks, S,
    128) int32)`` on the default device."""
    import jax
    import jax.numpy as jnp
    block = _drawer(seed, n_rows, block_rows, spec)
    nb = -(-n_rows // block_rows)
    return jax.jit(lambda: jax.lax.map(
        block, jnp.arange(nb, dtype=jnp.int32)))()
