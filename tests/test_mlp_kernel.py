"""Layer 1 of the MLP trainer's blocked passes as Pallas kernels
(``alink_tpu/kernels/mlp.py``), on the CPU rig under
``ALINK_TPU_PALLAS_INTERPRET=1``:

* the forward ``A . x + b`` and the backward ``C . x^T`` over a block of a
  byte table against the XLA walk's ``block_forward`` / ``block_backward``,
  and planted faults that a parity test at that tolerance must see;
* which walk runs is read from the input (``walk_path``);
* a fit on the kernel walk gives the XLA walk's gradients, losses and
  ladders, and says which walk ran (``paths["walk"]``, the ``mlp.optimize``
  span, ``alink_mlp_pass_blocks_total``).

**The pinned tolerance.** Both walks take the same products (bytes exact
in bfloat16 against three bfloat16 parts split by bit mask, float32
accumulation, the parts joined low to high); only the order of the
float32 sums inside a product differs: ``2e-6`` of the largest entry. A
part dropped reads ~1e-5 (the lowest) to ~4e-3 (the middle) of it."""

import numpy as np
import pytest

from alink_tpu.common.mlenv import MLEnvironmentFactory
from alink_tpu.kernels import linear
from alink_tpu.kernels import mlp as K
from alink_tpu.operator.common.ann import mlp as M
from alink_tpu.operator.common.optim.objfunc import (block_backward,
                                                     block_forward)

TOL = 2e-6


@pytest.fixture(autouse=True)
def _keep_the_sessions_env_and_programs():
    from alink_tpu.engine.comqueue import clear_program_cache
    before = MLEnvironmentFactory.get_default()
    clear_program_cache()
    yield
    clear_program_cache()
    MLEnvironmentFactory.set_default(before)


def _interp(monkeypatch, on=True):
    monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1" if on else "0")


def _block(dtype, d, S, m, nbl=2, seed=5):
    """A byte table of ``nbl`` blocks at 30 % ink over the whole range of
    ``dtype`` (half a signed table's ink negative), ``m`` coefficient rows
    and intercepts, and ``m`` values a row."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    span = np.iinfo(dtype)
    X = (rng.integers(span.min, span.max + 1, (nbl, d, S, 128))
         * (rng.random((nbl, d, S, 128)) < 0.3)).astype(dtype)
    A = rng.standard_normal((m, d)) * 0.05
    return (jnp.asarray(X), jnp.asarray(A), jnp.asarray(rng.standard_normal(m)),
            jnp.asarray(rng.standard_normal((m, S * 128))))


def _gaps(X, A, b, C, cuts=1):
    """The kernels' widest gap to the XLA walk over every block, over the
    XLA walk's largest entry: ``(forward, backward)``; the forward's
    product cut into ``cuts`` outputs and put together again."""
    import jax.numpy as jnp
    m = A.shape[0]
    fwd = bwd = 0.0
    for i in range(X.shape[0]):
        z = jnp.concatenate(K._forward(X, jnp.int32(i), A, b, cuts,
                                       interpret=True))
        ref = block_forward(X[i], A).reshape(m, -1) + b[:, None]
        fwd = max(fwd, float(jnp.abs(z - ref).max() / jnp.abs(ref).max()))
        g = K._backward(X, jnp.int32(i), C, interpret=True)
        ref = block_backward(X[i], C.reshape((m,) + X.shape[2:]))
        bwd = max(bwd, float(jnp.abs(g - ref).max() / jnp.abs(ref).max()))
    return fwd, bwd


@pytest.mark.parametrize("dtype, d, S, m, cuts", [
    (np.uint8, 40, 64, 12, 1),
    (np.int8, 129, 32, 20, 1),
    (np.uint8, 300, 32, 2 * 11, 2),
    (np.uint8, 784, 32, 1, 1),
    (np.int8, 784, 32, 2 * 5, 2),
], ids=["two_tiles_a_block_12_rows", "signed_ragged_run_20_rows",
        "stacked_W_and_D_three_runs", "one_row_at_the_cells_width",
        "signed_stacked_W_and_D_at_the_cells_width"])
def test_the_kernels_agree_with_the_xla_walk(dtype, d, S, m, cuts):
    """Unsigned and signed bytes, ``m`` not a multiple of 8 (the edge rows
    of a part's registers are padding), a stacked ``[W; D]`` cut into its
    two outputs as the line pass asks, a block of two byte tiles (16 grid
    steps) and blocks other than the first: both products to the
    tolerance. Signed bytes take the whole range, −128 … 127, in a ragged
    last run and in the unrolled loop over whole runs (784: 7 × 112)."""
    X, A, b, C = _block(dtype, d, S, m)
    if dtype == np.int8:
        assert np.asarray(X).min() == -128 and np.asarray(X).max() == 127
        assert all((np.asarray(X)[:, :, j::4] < 0).any() for j in range(4))
    fwd, bwd = _gaps(X, A, b, C, cuts)
    assert fwd <= TOL and bwd <= TOL, (fwd, bwd)


@pytest.mark.parametrize("fault, over", [
    ("lowest_part_dropped", True),
    ("middle_part_dropped", True),
    ("parts_joined_high_to_low", False),
])
def test_a_planted_fault_against_the_tolerance(monkeypatch, fault, over):
    """A part of the split dropped reads over the tolerance in both
    kernels: the grade rests on all three parts. The parts joined high to
    low does NOT: each part is summed over the whole contraction before
    the join, so the join's order moves a sum by an ulp of it (9.6e-8 of
    the largest entry here, against 4.2e-8 joined low to high)."""
    if fault == "parts_joined_high_to_low":
        monkeypatch.setattr(K, "_join", lambda lo, mid, hi: (hi + mid) + lo)
    else:
        part = 2 if fault == "lowest_part_dropped" else 1

        def split3(a):
            p = linear.split3(a)
            n = p.shape[0] // 3
            return p.at[part * n:(part + 1) * n].set(0)
        monkeypatch.setattr(K, "split3", split3)
    fwd, bwd = _gaps(*_block(np.uint8, 40, 32, 12))
    assert (fwd > 3 * TOL and bwd > 3 * TOL) if over \
        else (fwd <= TOL and bwd <= TOL), (fwd, bwd)


@pytest.mark.parametrize("case, dtype, d, S, m, pallas, want", [
    ("the cell's table under the interpreter", np.uint8, 784, 512, 1000,
     True, "kernel"),
    ("signed bytes", np.int8, 784, 512, 1000, True, "kernel"),
    ("without Pallas", np.uint8, 784, 512, 1000, False, "xla"),
    ("a table of floats", np.float32, 784, 512, 1000, True, "xla"),
    ("wider integers", np.int32, 784, 512, 1000, True, "xla"),
    ("blocks that are not whole byte tiles", np.uint8, 784, 40, 1000, True,
     "xla"),
    ("the most rows within the budget", np.uint8, 784, 512, 1712, True,
     "kernel"),
    ("rows over the budget", np.uint8, 784, 512, 1713, True, "xla"),
    ("one feature, which Mosaic refuses", np.uint8, 1, 512, 10, True, "xla"),
], ids=lambda v: v.replace(" ", "_").replace("'", "")
    if isinstance(v, str) and " " in v else None)
def test_walk_path_is_read_from_the_input(monkeypatch, case, dtype, d, S, m,
                                          pallas, want):
    _interp(monkeypatch, on=pallas)
    assert K.walk_path(dtype, d, S, m) == want, case


# -- a whole fit through the operator ---------------------------------------

LAYERS = [784, 24, 12, 10]


@pytest.fixture(scope="module")
def fits():
    """The same tiny fit (two blocks of 4,096 rows, 3 supersteps) on each
    walk: its train info, the ``mlp.optimize`` span's args and what the
    fit added to ``alink_mlp_pass_blocks_total``, by (pass, walk)."""
    import test_mlp_blocked as T
    from test_linear_blocked import _events_since, _ring_mark
    from alink_tpu.common.metrics import get_registry
    from alink_tpu.engine.comqueue import clear_program_cache

    def count():
        return {(r["labels"]["pass"], r["labels"]["walk"]): float(r["value"])
                for r in get_registry().snapshot()
                if r["name"] == "alink_mlp_pass_blocks_total"}
    n = 2 * 4096 - 300
    x, y = T._pixels(n, seed=8)
    out = {}
    for walk in ("xla", "kernel"):
        with pytest.MonkeyPatch.context() as mp:
            _interp(mp, on=walk == "kernel")
            clear_program_cache()
            before, mark = count(), _ring_mark()
            info = dict(T._mlp(T._source(x, y, n), hidden=LAYERS[1:-1],
                               max_iter=3).get_train_info())
            span = next(e for e in _events_since(mark)
                        if e.get("name") == "mlp.optimize")
            after = count()
        out[walk] = (info, span["args"], {
            k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)})
    out["n"] = n
    return out


def test_a_fit_gives_one_answer_on_both_walks(fits):
    """Gradients, losses and the 11 ladder losses of every superstep to
    float32's grade; the same rungs; every row counted by every pass."""
    xla, kernel = fits["xla"][0], fits["kernel"][0]
    assert (kernel["rows_trace"] == fits["n"]).all()
    assert list(kernel["rung_trace"]) == list(xla["rung_trace"])
    for t in range(3):
        g, g0 = kernel["grad_trace"][t], xla["grad_trace"][t]
        assert np.linalg.norm(g - g0) <= 1e-5 * np.linalg.norm(g0), t
    np.testing.assert_allclose(kernel["loss_curve"], xla["loss_curve"],
                               rtol=1e-6)
    np.testing.assert_allclose(kernel["line_trace"], xla["line_trace"],
                               rtol=1e-6)


def test_a_fit_says_which_walk_ran(fits):
    """``paths["walk"]``, the ``mlp.optimize`` span's ``walk`` and
    ``alink_mlp_pass_blocks_total{pass, walk}`` (supersteps x the table's
    blocks, a kind of pass) name the walk; the arithmetic is the same."""
    for walk in ("xla", "kernel"):
        info, args, added = fits[walk]
        assert info["paths"] == {"design": "blocks:uint8",
                                 "moments": "linear_moments",
                                 "pass": "blocked:bf16x3", "walk": walk,
                                 "rungs": "xla"}
        assert args["walk"] == walk
        assert added == {("grad", walk): 3 * 2, ("line", walk): 3 * 2}


def test_a_float_table_keeps_its_walk(monkeypatch):
    """Where Pallas runs, a table of floats still takes the XLA walk (at
    ``highest``): the layer-1 walk is for bytes."""
    from alink_tpu.common.columnar import as_block_column
    _interp(monkeypatch)
    col = as_block_column(np.zeros((4096, 20), np.float32))
    assert M.walk_path(col.blocks, [20, 16, 4]) == "xla"
    assert M.walk_path(np.zeros((2, 20, 32, 128), np.uint8),
                       [20, 16, 4]) == "kernel"
