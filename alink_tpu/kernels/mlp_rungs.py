"""The MLP line search's ladder as ONE Pallas kernel a block (device op
``mlp_rungs``).

Layer 1 is linear in the step, so the line pass takes a block's ``z1`` and
``zd1`` (``(h1, rows)`` float32, the two outputs of layer 1's product of
``[W1; D1]``) once, and each rung ``s`` of the ladder runs the rest of the
net from them: ``sigmoid(z1 - s zd1)``, the upper layers at ``W - s D`` and
``b - s db``, the softmax cross-entropy under the rows' weights. As XLA
compiles ``ann/mlp.py``'s ``lax.map`` of a rung, every rung reads
``z1`` and ``zd1`` from HBM again (262 MB a rung at the cell's 500 units
and 65,536 rows) and writes its activations back. Here a grid step reads
a tile of ``_TILE`` rows of both into VMEM once and runs all the rungs on
it in a ``fori_loop``, each rung's weights formed in VMEM from ``W`` and
``D``; a rung's loss over the tile leaves as 128 lane sums, and the
tiles' sums are added after the call.

Every product of two floats is the one the line pass hands in (``dot``,
``ann/mlp.dot_highest``: matmul precision ``highest``), taken at trace
time, so the arithmetic is the XLA rungs' and the two agree to float32's
grade, not bitwise (the order of the float32 sums differs). Which path
the ladder takes is read from the input (:func:`rung_path`), never set.
"""

from __future__ import annotations

import math

from .mlp import _jitted
from .runtime import interpret_mode, pallas_available

__all__ = ["rung_path", "losses"]

_LANES = 128
#: rows a grid step takes (at most). On a v5e at the cell's shapes the
#: ladder of a block ran 4.72 / 4.22 / 4.33 / 4.43 / 4.43 ms at 256 /
#: 512 / 1,024 / 2,048 / 4,096 rows, and its kernel compiled in 0.9 s
#: at 512, 3.8 s at 2,048
_TILE = 512
#: VMEM the kernel may ask for (:func:`_vmem_bytes`)
_VMEM = 64 << 20


def _tile(rows: int) -> int:
    """Rows a grid step takes of a block of ``rows``: ``_TILE``, or the
    most rows under it that divide the block and are a power of two of
    lane rows (:func:`_fold_lanes` halves them)."""
    return _LANES * math.gcd(rows // _LANES, _TILE // _LANES)


def _up(n: int, to: int) -> int:
    return -(-n // to) * to


def _vmem_bytes(sizes, tile: int) -> int:
    """VMEM the kernel takes for a net whose layers above the input are
    ``sizes`` (``h1``, …, classes) on a tile of ``tile`` rows: two buffers
    of the tile's ``z1``, ``zd1``, labels and weights; two buffers of
    every upper layer's ``W``, ``D``, ``b``, ``db`` and a rung's weights
    with their three bfloat16 parts; a rung's activations of every layer
    in float32 with their three parts, twice for ``h1`` (the argument of
    the sigmoid); 4 MB for the rest."""
    f32 = 4
    need = 2 * 2 * sizes[0] * tile * f32 + 2 * 2 * 8 * tile * f32
    for i, o in zip(sizes[:-1], sizes[1:]):
        w = _up(o, 8) * _up(i, _LANES) * f32
        need += 2 * 2 * w + 2 * 2 * _up(o, 8) * _LANES * f32 + w + 3 * w // 2
    need += sizes[0] * tile * (2 * f32 + 3 * 2)
    need += sum(_up(o, 8) * tile * (f32 + 3 * 2) for o in sizes[1:])
    return need + (4 << 20)


def rung_path(layer_sizes, rows: int, dtype) -> str:
    """``"kernel"`` where :func:`losses` can run the ladder of a net of
    ``layer_sizes`` (input, hidden…, classes) over blocks of ``rows``
    rows in ``dtype`` — a backend that executes Pallas (a TPU, or the
    interpreter the tier-1 rig turns on), float32, one hidden layer or
    more, blocks of whole lane rows and a need of VMEM within ``_VMEM`` —
    else ``"xla"``: ``ann/mlp.py``'s ``lax.map`` of a rung.
    ``tests/test_tpu_compile.py`` compiles the kernel at the envelope's
    corners for a described v5e."""
    import numpy as np
    ok = pallas_available() and np.dtype(dtype) == np.float32 \
        and len(layer_sizes) >= 3 and rows % _LANES == 0 \
        and _vmem_bytes(list(layer_sizes[1:]), _tile(rows)) <= _VMEM
    if ok:
        # loaded here, before the engine traces the step (kernels/linear.py)
        import jax.experimental.pallas.tpu  # noqa: F401
    return "kernel" if ok else "xla"


def losses(z1, zd1, y, w, layers, dirs, steps, dot):
    """The ladder's losses over one block: ``(num_steps,)``, entry ``j``
    the summed cross-entropy of the block's rows at ``coef - steps[j] ·
    direction``. ``z1``, ``zd1`` ``(h1, rows)`` layer 1's pre-activations
    and their change along the direction; ``y`` ``(rows,)`` labels, ``w``
    ``(rows,)`` weights; ``layers`` / ``dirs`` the upper layers' ``(W
    (in, out), b (out,))`` of the coefficients and of the direction;
    ``dot(spec, a, b)`` the product of two floats."""
    uppers = tuple((W.T, b[:, None], D.T, db[:, None])
                   for (W, b), (D, db) in zip(layers, dirs))
    rows = z1.shape[1]
    return _jitted(_losses, ("dot", "interpret"))(
        z1, zd1, y.reshape(1, rows), w.reshape(1, rows), uppers, steps,
        dot=dot, interpret=interpret_mode()).sum((0, 2))[:steps.shape[0]]


def _moved(v, s, dv):
    """``v - s · dv``: a coefficient, or layer 1's pre-activation, at step
    ``s`` along the direction."""
    return v - s * dv


def _fold_lanes(v):
    """``(1, n)`` summed to ``(1, 128)``, halves added (``n / 128`` a power
    of two)."""
    while v.shape[1] > _LANES:
        half = v.shape[1] // 2
        v = v[:, :half] + v[:, half:]
    return v


def _losses(z1, zd1, y, w, uppers, steps, dot, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h1, rows = z1.shape
    n = steps.shape[0]
    tile = _tile(rows)
    k = uppers[-1][0].shape[0]
    out_rows = _up(n, 8)
    f32 = jnp.float32
    flat = [a.astype(f32) for layer in uppers for a in layer]

    def kernel(s_ref, z_ref, zd_ref, y_ref, w_ref, *refs):
        o_ref, wrefs = refs[-1], refs[:-1]
        hit = y_ref[...] == lax.broadcasted_iota(jnp.int32, (k, tile), 0)
        which = lax.broadcasted_iota(jnp.int32, (out_rows, _LANES), 0)

        def rung(j, acc):
            s = s_ref[j]
            h = jax.nn.sigmoid(_moved(z_ref[...], s, zd_ref[...]))
            for q in range(0, len(wrefs), 4):
                W, b, D, db = (r[...] for r in wrefs[q:q + 4])
                z = dot("oi,ir->or", _moved(W, s, D), h) + _moved(b, s, db)
                h = jax.nn.sigmoid(z) if q + 4 < len(wrefs) else z
            m = h.max(0, keepdims=True)
            den = jnp.exp(h - m).sum(0, keepdims=True)
            zy = jnp.where(hit, h, 0.0).sum(0, keepdims=True)
            part = _fold_lanes(w_ref[...] * (m + jnp.log(den) - zy))
            return jnp.where(which == j, part, acc)

        o_ref[0] = lax.fori_loop(0, n, rung,
                                 jnp.zeros((out_rows, _LANES), f32))

    rows_spec = pl.BlockSpec((1, tile), lambda t: (0, t))
    return pl.pallas_call(
        kernel,
        grid=(rows // tile,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((h1, tile), lambda t: (0, t)),
                  pl.BlockSpec((h1, tile), lambda t: (0, t)),
                  rows_spec, rows_spec]
        + [pl.BlockSpec(a.shape, lambda t: (0, 0)) for a in flat],
        out_specs=pl.BlockSpec((1, out_rows, _LANES), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows // tile, out_rows, _LANES), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_bytes(
                [h1] + [u[0].shape[0] for u in uppers], tile) + (16 << 20)),
        interpret=interpret,
        name="mlp_rungs",
    )(steps.astype(f32), z1.astype(f32), zd1.astype(f32), y.astype(jnp.int32),
      w.astype(f32), *flat)
