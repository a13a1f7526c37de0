"""The MLP line search's ladder as one Pallas kernel a block
(``alink_tpu/kernels/mlp_rungs.py``), on the CPU rig under
``ALINK_TPU_PALLAS_INTERPRET=1`` and in float32, as on the chip:

* the kernel's losses against the XLA rungs (``ann/mlp.py``'s ``lax.map``
  of a rung) at the tiny net's and the cell's widths, with rows of zero
  weight, and planted faults that the tolerance must see;
* the line pass on each path, and its rung at a step of 0 against the
  gradient pass's loss;
* which path runs is read from the input (``rung_path``), and outside the
  envelope the XLA rungs run;
* a fit says which path its rungs took (``paths["rungs"]``, the
  ``mlp.optimize`` span, ``alink_mlp_rung_blocks_total``), and the
  benchmark's planted faults of the ladder still make the cell incorrect
  with the kernel engaged.

**The pinned tolerance.** Both paths take the same products (matmul
precision ``highest``) and the same elementwise steps; the float32 sums
of a rung's loss are added in another order: ``1e-6`` relative, the
readings 1e-7 to 3e-7."""

import os
import sys

import numpy as np
import pytest

from alink_tpu.common.mlenv import MLEnvironmentFactory
from alink_tpu.kernels import linear
from alink_tpu.kernels import mlp_rungs as R
from alink_tpu.operator.common.ann import mlp as M
from alink_tpu.operator.common.optim.optimizers import LINE_LADDER

TOL = 1e-6
LAYERS = [784, 24, 12, 10]


@pytest.fixture(autouse=True)
def _keep_the_sessions_env_and_programs():
    from alink_tpu.engine.comqueue import clear_program_cache
    before = MLEnvironmentFactory.get_default()
    clear_program_cache()
    yield
    clear_program_cache()
    MLEnvironmentFactory.set_default(before)


@pytest.fixture
def f32(monkeypatch):
    """Float32 as on the chip, the kernels in the interpreter."""
    import jax
    monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")
    with jax.enable_x64(False):
        yield


def _ladder(sizes, rows, pad=0, seed=3):
    """A block's ``z1``, ``zd1`` ``(h1, rows)``, labels and weights (the
    last ``pad`` rows of weight 0, as a table's padding), the upper
    layers' weights and direction for ``sizes`` (``h1``, …, classes), and
    the step program's 11 steps; float32."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)

    def a(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)
    w = np.ones(rows, np.float32)
    w[rows - pad:] = 0
    y = rng.integers(0, sizes[-1], rows).astype(np.int32)
    y[rows - pad:] = 0
    pairs = list(zip(sizes[:-1], sizes[1:]))
    layers = [(a(i, o, scale=1 / np.sqrt(i)), a(o, scale=0.5))
              for i, o in pairs]
    dirs = [(a(i, o, scale=0.3 / np.sqrt(i)), a(o, scale=0.2))
            for i, o in pairs]
    return (a(sizes[0], rows, scale=2.0), a(sizes[0], rows),
            jnp.asarray(y), jnp.asarray(w), layers, dirs,
            jnp.asarray(0.5 * LINE_LADDER, jnp.float32))


def _xla_rungs(z1, zd1, y, w, layers, dirs, steps):
    """``ann/mlp.py``'s XLA rungs, as ``line_pass`` runs them outside the
    kernel's envelope."""
    import jax

    def rung(s):
        upper = [(None, None)] + [(W - s * D, b - s * db) for (W, b), (D, db)
                                  in zip(layers, dirs)]
        return M._softmax_loss(M._upper(upper, z1 - s * zd1)[1], y, w)[0]
    return jax.lax.map(rung, steps)


def _row_losses(z1, zd1, y, w, layers, dirs, steps, dot=None):
    """Each row's loss at each rung ``(num_steps, rows)``: from the kernel
    (traced anew, so that a planted fault is seen) on a block whose tiles
    are one lane row each, so that a lane of its output is one row; and,
    with ``dot`` False, from the XLA rungs' statements."""
    import jax
    import jax.numpy as jnp
    n = steps.shape[0]
    if dot is False:
        def rung(s):
            upper = [(None, None)] + [(W - s * D, b - s * db)
                                      for (W, b), (D, db) in zip(layers, dirs)]
            z = M._upper(upper, z1 - s * zd1)[1]
            m = z.max(0)
            hit = y[None] == jnp.arange(z.shape[0], dtype=y.dtype)[:, None]
            return w * (m + jnp.log(jnp.exp(z - m).sum(0))
                        - jnp.where(hit, z, 0.0).sum(0))
        return jax.lax.map(rung, steps)
    assert R._tile(z1.shape[1]) == R._LANES
    uppers = tuple((W.T, b[:, None], D.T, db[:, None])
                   for (W, b), (D, db) in zip(layers, dirs))
    out = R._losses(z1, zd1, y.reshape(1, -1), w.reshape(1, -1), uppers,
                    steps, dot=dot or M.dot_highest, interpret=True)
    return out[:, :n].transpose(1, 0, 2).reshape(n, -1)


def _gap(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref) / np.abs(ref)))


@pytest.mark.parametrize("sizes, rows, pad", [
    ([24, 12, 10], 4096, 0),
    ([24, 12, 10], 4096, 300),
    ([500, 150, 10], 384, 0),
    ([500, 150, 10], 512, 100),
    ([40, 10], 256, 0),
    ([30, 20, 16, 3], 640, 17),
], ids=["tiny_net_a_block", "tiny_net_padded_rows", "cell_widths_384_rows",
        "cell_widths_padded_rows", "one_hidden_layer", "three_hidden_layers"])
def test_the_kernel_agrees_with_the_xla_rungs(f32, sizes, rows, pad):
    """The 11 losses of the ladder, through the kernel's entry point, to
    the tolerance: the tiny net over a block of eight tiles, the cell's
    500-150-10 over a few hundred rows (tiles of 128), rows of zero weight
    (a loss of 0 whatever their activations), one and three hidden
    layers, a block that is not a whole number of ``_TILE`` rows."""
    assert R.rung_path([784] + sizes, rows, np.float32) == "kernel"
    z1, zd1, y, w, layers, dirs, steps = _ladder(sizes, rows, pad)
    got = R.losses(z1, zd1, y, w, layers, dirs, steps, M.dot_highest)
    assert got.shape == (11,) and got.dtype == np.float32
    assert _gap(got, _xla_rungs(z1, zd1, y, w, layers, dirs, steps)) <= TOL


def _lowest_part_dropped(spec, a, b):
    """The product with the activations' lowest bfloat16 part dropped."""
    import jax.numpy as jnp
    parts = linear.split3(b).reshape((3,) + b.shape).astype(jnp.float32)
    return M.dot_highest(spec, a, parts[0] + parts[1])


@pytest.mark.parametrize("sizes", [[24, 12, 10], [500, 150, 10]],
                         ids=["tiny_net", "cell_widths"])
@pytest.mark.parametrize("fault", ["layer_2_not_moved",
                                   "activation_lowest_part_dropped"])
def test_a_planted_fault_against_the_tolerance(f32, monkeypatch, sizes,
                                               fault):
    """Each row's loss at each rung, over the largest of them: the clean
    kernel within the tolerance; a rung whose layer-2 weights are not
    moved by its step, and the activations' lowest bfloat16 part dropped
    from the products (16 bits where ``highest`` keeps 24), over 3 x the
    tolerance (the latter reads 4.9e-6 to 9.8e-6 on four seeds, the clean
    kernel 0 to 2.2e-7). Summed over a block's rows the dropped part
    averages out to 3e-7 to 1.8e-6 of the loss: it is a row's loss that
    shows a grade."""
    args = _ladder(sizes, 384, pad=24, seed=8)
    ref = np.asarray(_row_losses(*args, dot=False))

    def gap(got):
        return float(np.abs(np.asarray(got) - ref).max() / np.abs(ref).max())
    assert gap(_row_losses(*args)) <= TOL
    dot = None
    if fault == "layer_2_not_moved":
        moved = R._moved
        monkeypatch.setattr(R, "_moved", lambda v, s, dv: v if v.shape == (
            sizes[1], sizes[0]) else moved(v, s, dv))
    else:
        dot = _lowest_part_dropped
    assert gap(_row_losses(*args, dot=dot)) > 3 * TOL


# -- the line pass ----------------------------------------------------------------

@pytest.fixture(scope="module")
def passes():
    """The line pass over a byte table of two blocks (the second padded)
    on each rung path, and the gradient pass, at float32 and with the
    kernels interpreted: ``{path: (losses, rows)}``, ``"grad"``: loss."""
    import jax
    import jax.numpy as jnp
    import test_mlp_blocked as T
    from alink_tpu.common.columnar import DenseBlockColumn
    with pytest.MonkeyPatch.context() as mp, jax.enable_x64(False):
        mp.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")
        n = 2 * 4096 - 300
        x, y = T._pixels(n, seed=12)
        col = DenseBlockColumn(x, n)
        obj = M.MlpObjFunc(LAYERS)
        X = x.transpose(0, 2, 3, 1).reshape(-1, 784)[:n].astype(np.float64)
        std = X.std(0)
        shard = T._shard(obj, col, y, X.mean(0), np.where(std < 1e-12, 1.0,
                                                          std))
        rng = np.random.RandomState(13)
        coef = jnp.asarray(rng.randn(obj.dim) * 0.5 / 28, jnp.float32)
        direction = jnp.asarray(rng.randn(obj.dim) * 0.05, jnp.float32)
        steps = jnp.asarray(0.5 * LINE_LADDER, jnp.float32)
        out = {"path": M.rung_path(shard["X"], LAYERS, np.float32), "n": n}
        for path in ("kernel", "xla"):
            mp.setattr(R, "rung_path", lambda *a, p=path: p)
            jaxpr = str(jax.make_jaxpr(obj.line_pass)(shard, coef, direction,
                                                      steps))
            losses, rows = jax.jit(obj.line_pass)(shard, coef, direction,
                                                  steps)
            out[path] = (np.asarray(losses), int(rows),
                         jaxpr.count("name=mlp_rungs"))
        out["grad"] = float(jax.jit(obj.grad_pass)(shard, coef)[1])
    return out


def test_the_line_pass_gives_the_xla_ladder(passes):
    """The table's rows take the kernel (``rung_path`` reads it from the
    shard), one kernel call a block in the walk's loop body, none on the
    XLA path; the two ladders to the tolerance, every row counted."""
    assert passes["path"] == "kernel"
    (kern, rows, calls), (xla, xrows, xcalls) = passes["kernel"], passes["xla"]
    assert (calls, xcalls) == (1, 0)
    assert rows == xrows == passes["n"]
    assert _gap(kern, xla) <= TOL


def test_a_step_of_0_is_the_gradient_pass_loss(passes):
    """The ladder's first rung is the coefficients themselves: its loss is
    the gradient pass's loss over the same rows, to float32's grade."""
    assert _gap(passes["kernel"][0][0], passes["grad"]) <= TOL


# -- the path is read from the input ------------------------------------------------

@pytest.mark.parametrize("case, layers, rows, dtype, pallas, want", [
    ("the cell", [784, 500, 150, 10], 65536, np.float32, True, "kernel"),
    ("the tiny net", LAYERS, 4096, np.float32, True, "kernel"),
    ("a block of 3 lane rows", LAYERS, 384, np.float32, True, "kernel"),
    ("without Pallas", [784, 500, 150, 10], 65536, np.float32, False, "xla"),
    ("float64", [784, 500, 150, 10], 65536, np.float64, True, "xla"),
    ("no hidden layer", [784, 10], 65536, np.float32, True, "xla"),
    ("rows not whole lane rows", LAYERS, 4000, np.float32, True, "xla"),
    ("the widest layer 2 within the budget", [784, 500, 2632, 10], 65536,
     np.float32, True, "kernel"),
    ("layer 2 over the budget", [784, 500, 2640, 10], 65536, np.float32,
     True, "xla"),
    ("the widest layer 1 within the budget", [784, 3183, 150, 10], 65536,
     np.float32, True, "kernel"),
    ("layer 1 over the budget", [784, 3184, 150, 10], 65536, np.float32,
     True, "xla"),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) and " " in v
    else None)
def test_rung_path_is_read_from_the_input(monkeypatch, case, layers, rows,
                                          dtype, pallas, want):
    monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1" if pallas else "0")
    assert R.rung_path(layers, rows, dtype) == want, case


def test_outside_the_envelope_the_xla_rungs_run(f32):
    """A net with no hidden layer reads ``xla``: the line pass lowers no
    rung kernel, and its ladder is the XLA rungs' bit for bit."""
    import jax
    import jax.numpy as jnp
    sizes = [40, 10]
    obj = M.MlpObjFunc(sizes)
    rng = np.random.RandomState(3)
    blocks = jnp.asarray(rng.randn(2, 40, 8, 128), jnp.float32)
    shard = {"X": blocks, "y": jnp.asarray(rng.randint(0, 10, (2, 8, 128))),
             "w": jnp.ones((2, 8, 128), jnp.float32)}
    assert M.rung_path(blocks, sizes, np.float32) == "xla"
    coef = jnp.asarray(rng.randn(obj.dim) * 0.1, jnp.float32)
    direction = jnp.asarray(rng.randn(obj.dim) * 0.1, jnp.float32)
    steps = jnp.asarray(LINE_LADDER, jnp.float32)
    assert "mlp_rungs" not in str(jax.make_jaxpr(obj.line_pass)(
        shard, coef, direction, steps))
    got = jax.jit(obj.line_pass)(shard, coef, direction, steps)[0]
    layers = M.unstack(coef, sizes)
    (W1, b1), (D1, db1) = layers[0], M.unstack(direction, sizes)[0]
    rows = [b.reshape(40, -1) for b in blocks]
    want = sum(_xla_rungs(W1.T @ x + b1[:, None], D1.T @ x + db1[:, None],
                          y.reshape(-1), w.reshape(-1), [], [], steps)
               for x, y, w in zip(rows, shard["y"], shard["w"]))
    assert _gap(got, want) <= TOL


# -- a whole fit through the operator ----------------------------------------------

@pytest.fixture(scope="module")
def fits():
    """The same tiny fit (two blocks of 4,096 rows, 3 supersteps) in
    float32 with the kernels interpreted, on each rung path: its train
    info, the ``mlp.optimize`` span's args, what the fit added to
    ``alink_mlp_rung_blocks_total`` by walk, and the kernel's calls."""
    import jax
    import test_mlp_blocked as T
    from test_linear_blocked import _events_since, _ring_mark
    from alink_tpu.common.metrics import get_registry
    from alink_tpu.engine.comqueue import clear_program_cache

    def count():
        return {r["labels"]["walk"]: float(r["value"])
                for r in get_registry().snapshot()
                if r["name"] == "alink_mlp_rung_blocks_total"}
    n = 2 * 4096 - 300
    x, y = T._pixels(n, seed=8)
    out = {"n": n}
    for path in ("kernel", "xla"):
        calls = []
        with pytest.MonkeyPatch.context() as mp, jax.enable_x64(False):
            mp.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")
            if path == "xla":
                mp.setattr(R, "rung_path", lambda *a: "xla")
            losses = R.losses
            mp.setattr(R, "losses", lambda *a: calls.append(1) or losses(*a))
            clear_program_cache()
            before, mark = count(), _ring_mark()
            info = dict(T._mlp(T._source(x, y, n), hidden=LAYERS[1:-1],
                               max_iter=3).get_train_info())
            span = next(e for e in _events_since(mark)
                        if e.get("name") == "mlp.optimize")
            after = count()
        out[path] = (info, span["args"], {
            k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}, len(calls))
    return out


def test_a_fit_says_which_path_its_rungs_took(fits):
    """``paths["rungs"]``, the ``mlp.optimize`` span's ``rungs`` and
    ``alink_mlp_rung_blocks_total{walk}`` (supersteps x the table's
    blocks) name the path; the kernel is traced for the first superstep
    and for the loop's body, and not at all on the XLA path."""
    for path, traced in (("kernel", 2), ("xla", 0)):
        info, args, added, calls = fits[path]
        assert info["paths"]["rungs"] == path and args["rungs"] == path
        assert added == {path: 3 * 2}
        assert calls == traced


def test_a_fit_gives_one_answer_on_both_paths(fits):
    """Losses, ladders and gradients of every superstep to float32's
    grade; the same rungs; every row counted by every pass."""
    kernel, xla = fits["kernel"][0], fits["xla"][0]
    assert (kernel["rows_trace"] == fits["n"]).all()
    assert list(kernel["rung_trace"]) == list(xla["rung_trace"])
    np.testing.assert_allclose(kernel["line_trace"], xla["line_trace"],
                               rtol=TOL)
    np.testing.assert_allclose(kernel["loss_curve"], xla["loss_curve"],
                               rtol=TOL)
    for g, g0 in zip(kernel["grad_trace"], xla["grad_trace"]):
        assert np.linalg.norm(g - g0) <= 1e-5 * np.linalg.norm(g0)


@pytest.mark.parametrize("kind", ["ladder_plain_bfloat16",
                                  "ladder_zd1_halved"])
def test_the_benchmarks_ladder_faults_keep_their_teeth(f32, monkeypatch,
                                                       kind):
    """The benchmark's planted faults of the ladder (the line pass traced
    with its float products in plain bfloat16; ``zd1`` from half the
    direction's layer 1), planted with the kernel engaged, make the cell
    incorrect by ``line_gap``: the kernel takes its product from
    ``ann/mlp.py`` when the line pass is traced."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "benchmark_suite"))
    try:
        import test_bench_mlp as B
    finally:
        sys.path.pop(0)
    calls = []
    losses = R.losses
    monkeypatch.setattr(R, "losses", lambda *a: calls.append(1) or losses(*a))
    B._planted(monkeypatch, kind)
    out = B._run()
    assert calls
    assert out["correct"] is False
    over = [c["name"] for c in out["compared"] if c["value"] > c["limit"]]
    assert "line_gap" in over, out["compared"]
