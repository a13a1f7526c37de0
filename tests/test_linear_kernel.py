"""ISSUE 38 — the quasi-Newton passes over a table of bytes as one streamed
Pallas kernel each (``alink_tpu/kernels/linear.py``), on the CPU rig under
``ALINK_TPU_PALLAS_INTERPRET=1``:

* the kernel walk against the XLA walk of ``optim/objfunc.py`` on a small
  byte table: every sum to float32's grade, the rows counted exactly;
* which walk a pass takes is read from its input (``pass_path``), and where
  it reads ``xla`` the passes lower to the block loop's statements and
  nothing of the kernel's;
* a fit says what ran (``paths["walk"]``, the ``linear.optimize`` span,
  ``alink_linear_pass_blocks_total``), and gives one answer on 1 and 4
  virtual devices.

**The pinned tolerance.** Both walks take the same products (bytes exact
in bfloat16 against three bfloat16 parts, float32 accumulation), so a
logit differs only by the order in which the MXU's float32 partial sums
over the features are added: ``2e-6`` of the largest logit. A block's
sums over its rows are added in another order too (the kernel: a lane at
a time down the block, then over the lanes, a grid step Kahan-joined to
the pass; XLA: its own reduction tree, a block Kahan-joined to the pass)
and on the rig the XLA walk's softmax is float64 where the kernel's is
float32: ``2e-6`` of the largest entry for the gradient, ``1e-6``
relative for the loss, the weight and the ladder (sums of ~10,000
positive float32 terms)."""

import collections
import re

import numpy as np
import pytest

from alink_tpu.common.mlenv import MLEnvironment, MLEnvironmentFactory
from alink_tpu.kernels import linear as K
from alink_tpu.operator.common.optim import objfunc as F
from alink_tpu.operator.common.optim import optimizers as O

LOGIT_TOL = GRAD_TOL = 2e-6
SUM_TOL = 1e-6


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


def _shard(S, k, folded, d=40, nbl=3, seed=5, dtype=np.uint8):
    """A worker's shard of a byte table: ``nbl`` blocks, 30 % ink (over
    the whole range of ``dtype``, so half a signed table's ink is
    negative), random weights, the last block's second half zero-weight
    padding rows."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    span = np.iinfo(dtype)
    X = (rng.integers(span.min, span.max + 1, (nbl, d, S, 128))
         * (rng.random((nbl, d, S, 128)) < 0.3)).astype(dtype)
    y = rng.integers(0, k, (nbl, S, 128)).astype(np.int32)
    w = rng.random((nbl, S, 128)) + 0.5
    w[-1, S // 2:] = 0.0
    data = {"X": jnp.asarray(X), "y": jnp.asarray(y), "w": jnp.asarray(w)}
    if folded:
        data["scale"] = jnp.asarray(1.0 / (1.0 + rng.random(d) * 50))
        data["shift"] = jnp.asarray(rng.random(d))
    dim = d + int(folded)                       # the intercept rides along
    obj = F.SoftmaxObjFunc(k, dim)
    coef = jnp.asarray(rng.standard_normal(obj.dim) * 0.02)
    direction = jnp.asarray(rng.standard_normal(obj.dim) * 0.02)
    return obj, data, coef, direction, int((w != 0).sum())


def _both_passes(obj, data, coef, direction):
    import jax
    import jax.numpy as jnp
    steps = jnp.asarray(O.LINE_LADDER, coef.dtype)
    grad = jax.jit(lambda d, c: obj.grad_pass(d, c))(data, coef)
    line = jax.jit(lambda d, c, dr, st, e: obj.line_pass(d, c, dr, st, e))(
        data, coef, direction, steps, grad[3])
    return [np.asarray(a) for a in (*grad, *line)]


@pytest.mark.parametrize("folded", [True, False],
                         ids=["standardized+intercept", "raw"])
@pytest.mark.parametrize("k", [3, 10])
@pytest.mark.parametrize("S", [32, 64])
def test_kernel_walk_agrees_with_the_xla_walk(monkeypatch, S, k, folded):
    """Gradient, loss, weight, kept logits and the ladder's losses of the
    two walks to the tolerance above; rows counted EXACTLY equal, and
    equal to the rows of weight other than 0."""
    obj, data, coef, direction, live = _shard(S, k, folded)
    _interp(monkeypatch, on=False)
    assert F.walk_path(data["X"], k - 1) == "xla"
    ref = _both_passes(obj, data, coef, direction)
    _interp(monkeypatch)
    assert F.walk_path(data["X"], k - 1) == "kernel"
    got = _both_passes(obj, data, coef, direction)
    g, loss, wsum, logits, rows, ladder, line_rows = got
    g0, loss0, wsum0, logits0, rows0, ladder0, line_rows0 = ref
    assert rows == rows0 == line_rows == line_rows0 == live
    assert np.abs(logits - logits0).max() <= LOGIT_TOL * np.abs(logits0).max()
    assert np.abs(g - g0).max() <= GRAD_TOL * np.abs(g0).max()
    np.testing.assert_allclose(loss, loss0, rtol=SUM_TOL)
    np.testing.assert_allclose(wsum, wsum0, rtol=SUM_TOL)
    np.testing.assert_allclose(ladder, ladder0, rtol=SUM_TOL)
    assert ladder[0] == pytest.approx(loss, rel=SUM_TOL)   # rung 0: step 0


@pytest.mark.parametrize("d", [300, 129], ids=["3_runs", "ragged_run"])
def test_kernel_walk_over_several_runs_of_features(monkeypatch, d):
    """A table wider than one run of features (``_FEATURES``) is widened a
    run at a time, the last run shorter: the same sums."""
    obj, data, coef, direction, live = _shard(32, 4, True, d=d, nbl=2)
    assert K._chunks(d)[0] > 1 and K._chunks(d)[2] != K._chunks(d)[1]
    _interp(monkeypatch, on=False)
    ref = _both_passes(obj, data, coef, direction)
    _interp(monkeypatch)
    got = _both_passes(obj, data, coef, direction)
    assert got[4] == ref[4] == got[6] == live
    assert np.abs(got[0] - ref[0]).max() <= GRAD_TOL * np.abs(ref[0]).max()
    np.testing.assert_allclose(got[5], ref[5], rtol=SUM_TOL)


@pytest.mark.parametrize("d", [40, 129], ids=["one_run", "ragged_run"])
@pytest.mark.parametrize("k", [3, 10])
def test_kernel_walk_reads_signed_bytes_signed(monkeypatch, k, d):
    """An ``int8`` table takes the kernel walk too, and a byte of the top
    bit set is the NEGATIVE number the XLA walk's cast reads (−1, not
    255), in each of a word's four places: the same sums."""
    obj, data, coef, direction, live = _shard(32, k, True, d=d, nbl=2,
                                              dtype=np.int8)
    X = np.asarray(data["X"])
    assert X.dtype == np.int8 and X.min() == -128 and X.max() == 127
    # every row group of a word (row s of a block is byte s % 4) holds
    # negative bytes
    assert all((X[:, :, j::4] < 0).any() for j in range(4))
    _interp(monkeypatch, on=False)
    ref = _both_passes(obj, data, coef, direction)
    _interp(monkeypatch)
    assert F.walk_path(data["X"], k - 1) == "kernel"
    got = _both_passes(obj, data, coef, direction)
    assert got[4] == ref[4] == got[6] == live
    assert np.abs(got[3] - ref[3]).max() <= LOGIT_TOL * np.abs(ref[3]).max()
    assert np.abs(got[0] - ref[0]).max() <= GRAD_TOL * np.abs(ref[0]).max()
    np.testing.assert_allclose(got[1], ref[1], rtol=SUM_TOL)
    np.testing.assert_allclose(got[5], ref[5], rtol=SUM_TOL)


@pytest.mark.parametrize("lanes, total", [
    ([2 ** 17 + 1] * 127 + [2 ** 17], 2 ** 24 + 127),
    ([2 ** 24 - 1] * 128, 2 ** 31 - 128),
    ([3] * 128, 384),
], ids=["just_over_2^24", "the_most_a_shard_holds", "small"])
def test_rows_are_added_over_the_lanes_as_whole_numbers(lanes, total):
    """A pass leaves the kernel with its rows counted a lane each, whole
    numbers in float32 (exact under 2^24 a lane); over the lanes they are
    added as int32, so a shard of more than 2^24 rows is still counted
    exactly (a float32 sum of the first case reads 2^24 + 128)."""
    import jax.numpy as jnp
    t = np.zeros((8, 128), np.float32)
    t[5] = lanes
    t[0] = 0.25
    if total == 2 ** 24 + 127:
        assert float(np.float32(lanes).sum(dtype=np.float32)) != total
    sums, rows = K._lane_sums(jnp.asarray(t), 5)
    assert rows.dtype == jnp.int32 and int(rows) == total
    assert float(sums[0]) == 32.0


@pytest.mark.parametrize("case, dtype, d, S, m, pallas, want", [
    ("a byte table under the interpreter", np.uint8, 784, 512, 9, True,
     "kernel"),
    ("signed bytes", np.int8, 784, 512, 9, True, "kernel"),
    ("without Pallas", np.uint8, 784, 512, 9, False, "xla"),
    ("a table of floats", np.float32, 784, 512, 9, True, "xla"),
    ("wider integers", np.int32, 784, 512, 9, True, "xla"),
    ("blocks that are not whole byte tiles", np.uint8, 784, 40, 9, True,
     "xla"),
    ("parts that overflow the MXU's columns", np.uint8, 784, 512, 41, True,
     "xla"),
    ("the most classes that fit", np.uint8, 784, 512, 40, True, "kernel"),
    ("a table too wide for a step", np.uint8, 9000, 512, 9, True, "xla"),
    ("the widest table", np.uint8, 8192, 512, 9, True, "kernel"),
    ("one feature, which Mosaic refuses", np.uint8, 1, 512, 9, True, "xla"),
    ("two features", np.uint8, 2, 512, 9, True, "kernel"),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) and " " in v
    else None)
def test_pass_path_is_read_from_the_input(monkeypatch, case, dtype, d, S, m,
                                          pallas, want):
    _interp(monkeypatch, on=pallas)
    assert K.pass_path(dtype, d, S, m) == want, case


def _ops(text):
    return collections.Counter(re.findall(r"stablehlo\.(\w+)", text))


def test_the_xla_walk_holds_nothing_of_the_kernel(monkeypatch):
    """Without Pallas the two passes are the block loop's statements (at
    PR 38 their lowered text was byte for byte that of the commit before
    the kernel, cbf81d4: CHANGES.md): ONE loop over the blocks, in it a
    block's slices (table, labels, weights; pass 2 the kept logits too)
    and the MXU products of the stacked parts against the block as
    bfloat16 (pass 1 two, pass 2 one), no call out of XLA. And nothing
    but ``walk_path`` stands between the walks: with Pallas there and the
    path held to ``xla`` the passes lower to the same text."""
    import jax
    import jax.numpy as jnp
    from alink_tpu.common.compat import lowered_text
    nbl, d, S, k = 3, 40, 32, 10
    f, sd = jnp.zeros(()).dtype, jax.ShapeDtypeStruct
    data = {"X": sd((nbl, d, S, 128), jnp.uint8),
            "y": sd((nbl, S, 128), jnp.int32), "w": sd((nbl, S, 128), f),
            "scale": sd((d,), f), "shift": sd((d,), f)}
    obj = F.SoftmaxObjFunc(k, d + 1)
    coef = sd((obj.dim,), f)

    def lowered():
        return {
            "grad": lowered_text(jax.jit(
                lambda dd, c: obj.grad_pass(dd, c)).lower(data, coef)),
            "line": lowered_text(jax.jit(
                lambda dd, c, dr, st, e: obj.line_pass(dd, c, dr, st, e))
                .lower(data, coef, coef, sd((11,), f),
                       sd((nbl, k - 1, S, 128), f)))}
    _interp(monkeypatch, on=False)
    text = lowered()
    stack, block = f"tensor<{3 * (k - 1)}x{d}xbf16>", \
        f"tensor<{d}x{S * 128}xbf16>"
    for name, slices, products in (("grad", 3, 2), ("line", 4, 1)):
        t, ops = text[name], _ops(text[name])
        assert "pallas" not in t and "custom_call" not in t, name
        assert ops["while"] == 1 and ops["dynamic_slice"] == slices, name
        assert ops["dot_general"] == products, name
        assert len(re.findall(
            rf"dot_general.*\({re.escape(stack)}, {re.escape(block)}\)",
            t)) == 1, name                       # the forward product
    _interp(monkeypatch)
    kernel = lowered()
    for name in text:
        assert _ops(kernel[name]) != _ops(text[name]), name
    monkeypatch.setattr(F, "walk_path", lambda X, m: "xla")
    assert lowered() == text


# -- a whole fit through the operator ---------------------------------------

def _fit(n, x, y, workers=1, max_iter=4):
    import jax
    import test_linear_blocked as T
    env = MLEnvironment(parallelism=workers, devices=jax.devices()[:workers])
    MLEnvironmentFactory.set_default(env)
    return dict(T._softmax(T._byte_source(x, y, n), max_iter=max_iter)
                .get_train_info())


@pytest.fixture(scope="module")
def pixels():
    import test_linear_blocked as T
    n = 8 * 4096 - 500
    x, y = T._pixels(n, seed=9)
    return n, x, y


@pytest.mark.parametrize("walk", ["kernel", "xla"])
def test_a_fit_says_which_walk_ran(monkeypatch, pixels, walk):
    """``get_train_info()["paths"]["walk"]``, the ``linear.optimize``
    span and ``alink_linear_pass_blocks_total{pass, walk}`` (supersteps x
    the table's blocks, a kind of pass) name the walk the step program
    took; ``paths["pass"]`` names the arithmetic, which is the same."""
    import test_linear_blocked as T
    from alink_tpu.common.metrics import get_registry
    n, x, y = pixels
    _interp(monkeypatch, on=walk == "kernel")

    def count(kind, by):
        return sum(float(r["value"]) for r in get_registry().snapshot()
                   if r["name"] == "alink_linear_pass_blocks_total"
                   and r.get("labels") == {"pass": kind, "walk": by})
    before = {(kind, by): count(kind, by) for kind in ("grad", "line")
              for by in ("kernel", "xla")}
    mark = T._ring_mark()
    info = _fit(n, x, y)
    assert info["paths"] == {"design": "blocks:uint8",
                             "moments": "linear_moments",
                             "pass": "blocked:bf16x3", "walk": walk}
    opt = next(e for e in T._events_since(mark)
               if e.get("name") == "linear.optimize")
    assert opt["args"]["walk"] == walk
    other = "xla" if walk == "kernel" else "kernel"
    for kind in ("grad", "line"):
        assert count(kind, walk) - before[kind, walk] == 4 * 8
        assert count(kind, other) == before[kind, other]
    assert (info["rows_trace"] == n).all()


@pytest.mark.parametrize("workers", [1, 4])
def test_a_fit_on_the_kernel_walk_gives_one_answer(monkeypatch, pixels,
                                                   workers):
    """``SoftmaxTrainBatchOp`` on the kernel walk over 1 and 4 virtual
    devices against the one-device XLA walk: the same rungs, every row
    counted by both passes, the loss curve and the coefficients to
    float32's rounding of the products."""
    n, x, y = pixels
    _interp(monkeypatch, on=False)
    one = _fit(n, x, y)
    assert one["paths"]["walk"] == "xla"
    _interp(monkeypatch)
    got = _fit(n, x, y, workers=workers)
    assert got["paths"]["walk"] == "kernel"
    assert (got["rows_trace"] == n).all()
    assert list(got["rung_trace"]) == list(one["rung_trace"])
    np.testing.assert_allclose(got["loss_curve"], one["loss_curve"],
                               rtol=1e-6)
    scale = np.abs(one["coef"]).max()
    assert np.abs(got["coef"] - one["coef"]).max() < 1e-4 * scale
