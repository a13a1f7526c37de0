"""The batched SPD solve as one Pallas kernel (``ops/smallsolve.py`` says
when it runs): 128 systems a grid step, batch on the lanes, their
augmented matrices ``(n, n + 1, 128)`` copied into VMEM once and
eliminated there, so a system crosses HBM once instead of once an
elimination step.

A step ``k`` of the forward elimination (traced once a ``k``: the column
range it still has to touch is static) normalises row ``k`` and takes it
out of every row below, one row a trip: a row is ``ceil((n + 1 - k) / 8)``
registers, the factor a register spread over their sublanes. The back
substitution reads row ``k`` (unit upper triangular) against the solved
``x`` as one multiply and a sublane reduction a row. No pivoting: the
systems are SPD.
"""

from __future__ import annotations

import functools

from .runtime import interpret_mode, pallas_available

_LANES = 128
_TILE = 8
#: the widest system whose 128 augmented matrices fit a grid step's VMEM
#: three times over (the fetched block twice, the working copy once)
MAX_N = 127


def kernel_available(dtype, n: int) -> bool:
    import numpy as np
    ok = pallas_available() and np.dtype(dtype) == np.float32 and n <= MAX_N
    if ok:
        import jax.experimental.pallas.tpu  # noqa: F401  (paid once, here)
    return ok


def solve_augmented_kernel(M):
    """``M`` (n, J, B) float32, ``J > n`` (column ``n`` the right-hand
    side, any further columns ignored; ``J`` a multiple of 8 and ``B`` of
    128 save a padding copy) -> ``x`` (n, B); see the module docstring."""
    return _solve_call()(M, interpret=interpret_mode())


@functools.lru_cache(maxsize=None)
def _solve_call():
    import jax
    return jax.jit(_solve, static_argnames="interpret")


def _solve(M, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, J, B = M.shape
    f32 = jnp.float32
    Jp = -(-J // _TILE) * _TILE
    Bp = -(-B // _LANES) * _LANES
    # lanes past the batch hold the identity: nothing divides by zero
    Mp = jnp.pad(M, ((0, 0), (0, Jp - J), (0, Bp - B)))
    if Bp != B:
        Mp = Mp + jnp.eye(n, Jp, dtype=f32)[:, :, None] * (
            lax.broadcasted_iota(jnp.int32, (1, 1, Bp), 2) >= B)

    def kernel(m_ref, x_ref, w_ref):
        w_ref[...] = m_ref[...]
        for k in range(n):
            j0 = k // _TILE * _TILE
            rowk = w_ref[k, j0:, :] / w_ref[k, k:k + 1, :]
            w_ref[k, j0:, :] = rowk

            def below(i, k=k, j0=j0, rowk=rowk):
                w_ref[i, j0:, :] = (w_ref[i, j0:, :]
                                    - w_ref[i, k:k + 1, :] * rowk)

            def two(t, _, k=k, below=below):       # two rows a trip
                below(k + 1 + 2 * t)
                below(k + 2 + 2 * t)

            left = n - k - 1
            if left >= 2:
                lax.fori_loop(0, left // 2, two, None)
            if left % 2:
                below(n - 1)
        x_ref[...] = jnp.zeros(x_ref.shape, f32)
        for k in range(n - 1, -1, -1):
            # columns k + 1 .. n - 1 of row k against what is solved; x is
            # zero from row k up and past n, so the whole row may enter
            s = jnp.sum(w_ref[k] * x_ref[...], axis=0, keepdims=True)
            x_ref[k:k + 1, :] = w_ref[k, n:n + 1, :] - s

    block = n * Jp * _LANES * 4
    x = pl.pallas_call(
        kernel,
        grid=(Bp // _LANES,),
        in_specs=[pl.BlockSpec((n, Jp, _LANES), lambda s: (0, 0, s))],
        out_specs=pl.BlockSpec((Jp, _LANES), lambda s: (0, s)),
        out_shape=jax.ShapeDtypeStruct((Jp, Bp), f32),
        scratch_shapes=[pltpu.VMEM((n, Jp, _LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=3 * block + (8 << 20)),
        interpret=interpret,
        name="als_solve",
    )(Mp)
    return x[:n, :B]
