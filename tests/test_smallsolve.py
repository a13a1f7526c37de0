"""The batched SPD solve (``ops/smallsolve.py``) against
``numpy.linalg.solve`` in float64, on ridge-weighted Gram matrices with
the condition numbers an ALS half-sweep meets at rank 10 and rank 100, by
both of its paths: the XLA elimination and the Pallas kernel (interpreted
here; compiled for a described chip in ``tests/test_tpu_compile.py``)."""

import numpy as np
import pytest


def _systems(n, batch, seed, lam=1.4):
    """Gram + lambda * count * I of ``count`` rows with a common mean (what
    factors of positive ratings look like): the largest eigenvalue is the
    mean's, ~count * n, the ridge's lambda * count the smallest."""
    rng = np.random.default_rng(seed)
    count = rng.integers(1, 400, batch)
    A = np.empty((batch, n, n))
    for s, c in enumerate(count):
        X = 1.0 + 0.5 * rng.standard_normal((c, n))
        A[s] = X.T @ X + lam * c * np.eye(n)
    b = rng.standard_normal((batch, n)) * count[:, None]
    return A, b


@pytest.mark.parametrize("path", ["xla", "kernel"])
@pytest.mark.parametrize("n", [10, 100])
def test_batched_spd_solve_against_float64(monkeypatch, n, path):
    import jax.numpy as jnp
    from alink_tpu.ops import smallsolve
    if path == "kernel":
        monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")
    A, b = _systems(n, 40 if n == 100 else 200, seed=n)
    cond = np.linalg.cond(A)
    assert cond.max() > (50 if n == 100 else 5)     # not the identity
    assert smallsolve.solve_path(jnp.float32, n) == path
    x = np.asarray(smallsolve.batched_spd_solve(
        jnp.asarray(A, jnp.float32), jnp.asarray(b, jnp.float32)))
    want = np.linalg.solve(A, b[..., None])[..., 0]
    err = np.abs(x - want).max(1) / np.abs(want).max(1)
    # float32's rounding times the condition number, with room
    assert err.max() < 2e-7 * cond.max() + 2e-6, (err.max(), cond.max())


def test_the_solve_keeps_its_leading_axes_and_other_dtypes_go_by_xla():
    import jax.numpy as jnp
    from alink_tpu.ops import smallsolve
    A, b = _systems(6, 12, seed=3)
    x = smallsolve.batched_spd_solve(jnp.asarray(A.reshape(3, 4, 6, 6)),
                                     jnp.asarray(b.reshape(3, 4, 6)))
    assert x.shape == (3, 4, 6)
    assert smallsolve.solve_path(jnp.float64, 6) == "xla"
    np.testing.assert_allclose(
        np.asarray(x).reshape(12, 6),
        np.linalg.solve(A, b[..., None])[..., 0], rtol=1e-9)
