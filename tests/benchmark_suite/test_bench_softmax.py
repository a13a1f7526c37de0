"""The L-BFGS-loop cell's own pieces at a size a test can hold: the table
from the seed, the operations count against a hand count, the readers
against hand counts, the window's cut, the controls read not correct
against the cell's limits, and ``correct`` coming out false with each
fault planted in the program under the whole run."""

import importlib
import os
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
AS = "TPU v5 lite"
CELL = "softmax-fit"


@pytest.fixture(autouse=True)
def _keep_the_sessions_env_and_programs():
    """``run_cell`` opens a session over the cell's chips, and a planted
    fault must not be served a program compiled without it (or leave one
    behind)."""
    from alink_tpu.common.mlenv import MLEnvironmentFactory
    from alink_tpu.engine.comqueue import clear_program_cache
    before = MLEnvironmentFactory.get_default()
    clear_program_cache()
    yield
    clear_program_cache()
    MLEnvironmentFactory.set_default(before)


def _tiny():
    from benchmark import run as R
    found = R.load_cell(CELL)
    return R.tiny(found["config"]), R.tiny(found["traffic"])


def _run(seed=20261004, seconds=0.3, trace=False):
    from benchmark.run import run_cell
    return run_cell(CELL, seed, seconds, trace, tiny_size=True,
                    require_tpu=False, device_kind_as=AS)


# -- data and arithmetic --------------------------------------------------------

def test_same_seed_same_table_with_the_sets_shape():
    from benchmark import mnist8m
    config, _ = _tiny()
    n, spec = config["rows"], config["generator"]
    seed = 2 ** 31 + 5
    a = [np.asarray(v) for v in mnist8m.make_table(seed, n, 4096, spec)]
    b = [np.asarray(v) for v in mnist8m.make_table(seed, n, 4096, spec)]
    c = [np.asarray(v) for v in mnist8m.make_table(seed + 1, n, 4096, spec)]
    assert [v.dtype for v in a] == [np.uint8, np.int32]
    assert a[0].shape == (3, 784, 32, 128) and a[1].shape == (3, 32, 128)
    assert all((x == y).all() for x, y in zip(a, b))
    assert (a[0] != c[0]).any() and (a[1] != c[1]).any()
    X = a[0].transpose(0, 2, 3, 1).reshape(-1, 784)
    Y = a[1].reshape(-1)
    assert not X[n:].any() and not Y[n:].any()          # the padding is zero
    X, Y = X[:n], Y[:n]
    assert 0.17 < (X > 0).mean() < 0.21                 # ~19 % inked
    assert X.max() == 255 and X[X > 0].min() == 1
    constant = X.std(0) == 0
    assert constant.sum() == 64 == mnist8m.border_columns(spec).sum()
    assert (constant == mnist8m.border_columns(spec)).all()
    share = np.bincount(Y, minlength=10) / n
    assert share.min() > 0.07 and share.max() < 0.13    # no class is rare
    # pixels correlate with the label: class means differ
    means = np.stack([X[Y == k].mean(0) for k in range(10)])
    assert np.abs(means - means.mean(0)).max() > 20


def test_superstep_counts_match_a_hand_count():
    from benchmark import opcount, opcount_linear
    ops, byt = opcount_linear.softmax_superstep(1000, 20, 4)
    assert ops == 3 * 2 * 1000 * 21 * 3 and byt == 2 * 1000 * 20
    assert opcount_linear.moments_pass(1000, 20) == (80000, 20000)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops, byt = opcount_linear.softmax_superstep(8_100_000, 784, 10)
    assert byt == 12_700_800_000 and ops == 343_359_000_000
    assert opcount.bound_by(ops, byt, peak) == "memory"
    assert opcount.least_seconds(ops, byt, peak) == pytest.approx(
        0.015508, rel=1e-4)
    assert ops / peak["flops_per_s"] == pytest.approx(0.001743, rel=1e-3)
    assert opcount.least_seconds(*opcount_linear.moments_pass(
        8_100_000, 784), peak) == pytest.approx(0.007754, rel=1e-4)


NEW_READERS = ("qn_step_dev", "qn_step_roofline", "linear_fit_mfu",
               "moments_share")


@pytest.mark.parametrize("base", NEW_READERS)
def test_new_reader_returns_none_with_nothing_to_read(base, monkeypatch):
    """A program without the spans, the counters or the step program (the
    parent's) gives each reader nothing, and none raises."""
    from benchmark import program_spans
    monkeypatch.setattr(program_spans, "window_events", lambda: [])
    ctx = types.SimpleNamespace(reduced=None, facts={}, config={
        "step_program": "jit_linear_qn"})
    reader = importlib.import_module("benchmark.readers." + base)
    assert reader.read(ctx) is None
    ctx.reduced = {"module_s": {}, "module_calls": {}, "window_s": 1.0,
                   "busy_s": 0.5}
    assert reader.read(ctx) is None


def test_device_readers_against_a_hand_count(monkeypatch):
    from benchmark import program_spans
    from benchmark.readers import (linear_fit_mfu, moments_share,
                                   qn_step_dev, qn_step_roofline)
    ctx = types.SimpleNamespace(
        config={"step_program": "jit_linear_qn"},
        facts={"supersteps": 40, "fits": 2, "step_least_s": 0.0155,
               "moments_least_s": 0.00775},
        reduced={"module_s": {"jit_linear_qn(77)": 5.2,
                              "jit_linear_moments(3)": 0.1,
                              "jit_linear_qn_other": 9.0},
                 "module_calls": {"jit_linear_qn(77)": 2,
                                  "jit_linear_moments(3)": 2,
                                  "jit_linear_qn_other": 1},
                 "window_s": 5.5, "busy_s": 5.3})
    assert qn_step_dev.read(ctx) == pytest.approx(130.0)
    assert qn_step_roofline.read(ctx) == pytest.approx(100 * 0.0155 / 0.130)
    assert linear_fit_mfu.read(ctx) == pytest.approx(
        100 * (40 * 0.0155 + 2 * 0.00775) / 5.5)
    events = [{"name": n, "dur": d * 1e6, "ph": "X", "profiled": True}
              for n, d in (("linear.fit", 2.0), ("linear.extract", 0.01),
                           ("linear.moments", 0.09), ("linear.optimize", 1.8),
                           ("linear.fit", 2.0), ("linear.moments", 0.1))]
    monkeypatch.setattr(program_spans, "window_events", lambda: events)
    assert moments_share.read(ctx) == pytest.approx(100 * 0.2 / 4.0)


# -- the window -------------------------------------------------------------------

def test_the_window_holds_whole_fits_over_the_l2_ladder(monkeypatch):
    """At least ``min_fits`` fits, fit ``i`` at ``l2_ladder[i mod 4]``,
    nothing compiled in the window, every fit running all its supersteps,
    and every pass of every fit counting every row."""
    from alink_tpu.operator.batch.classification.linear import (
        SoftmaxTrainBatchOp)
    seen = []
    set_l2 = SoftmaxTrainBatchOp.set_l2
    monkeypatch.setattr(SoftmaxTrainBatchOp, "set_l2",
                        lambda self, v: seen.append(v) or set_l2(self, v))
    config, traffic = _tiny()
    out = _run(seconds=0.0)
    f = out["facts"]
    assert out["correct"] is True and out["failed"] == 0
    assert f["fits"] == traffic["min_fits"] == out["attempted"]
    ladder = config["l2_ladder"]
    assert seen == [ladder[i % 4] for i in range(1 + f["fits"])]
    assert f["compiles_in_window"] == 0
    assert f["supersteps_min"] == config["max_iter"] == f["max_iter"]
    assert f["supersteps"] == f["fits"] * config["max_iter"]
    assert f["passes"] == f["fits"] * (1 + 2 * config["max_iter"])
    assert f["rows_counted"] == config["rows"] * f["passes"]
    assert out["metrics"]["train_rate"]["value"] == pytest.approx(
        config["rows"] * f["fits"] / f["window_s"])
    assert (f["design_path"], f["pass_path"]) == ("blocks:uint8",
                                                  "blocked:bf16x3")


# -- the controls -----------------------------------------------------------------

@pytest.fixture(scope="module")
def readings():
    from benchmark import controls_softmax
    config, _ = _tiny()
    return controls_softmax.readings(31, config, supersteps=4), \
        config["limits"]


def _over(got, limits):
    return [k for k, v in got.items()
            if v > (limits[k] if k in limits else 0.0)]


def test_the_clean_stand_in_reads_correct(readings):
    got, limits = readings
    assert _over(got["float32_again"], limits) == []


@pytest.mark.parametrize("control,number", [
    ("bfloat16", "grad_gap"), ("block_left_out", "moments_gap"),
    ("block_left_out", "rows_gap"), ("no_standardization", "grad_gap"),
    ("rung_off_by_one", "step_gap"), ("pair_dropped", "dir_gap"),
    ("pair_dropped", "coef_gap")])
def test_a_control_reads_not_correct_by_its_number(readings, control, number):
    got, limits = readings
    assert number in _over(got[control], limits), got[control]


def test_counts_added_in_float32_are_caught_where_they_round():
    """At this table's sizes every partial sum of the passes' counts is a
    whole multiple of 32 under 2^29, which float32 holds: the control
    reads 0 here, and the exact limit stands for the sizes where it does
    not."""
    from benchmark.reference import softmax as ref
    assert ref.float32_count([8_100_000] * 41) == 8_100_000 * 41
    assert ref.float32_count([8_100_001] * 41) != 8_100_001 * 41


# -- faults planted in the program, under the whole run ---------------------------

def _planted(monkeypatch, kind):
    import jax
    import jax.numpy as jnp
    from alink_tpu.operator.common.optim import objfunc as F
    from alink_tpu.operator.common.optim import optimizers as O
    if kind == "plain_bfloat16":
        split3 = F.split3

        def top_part_only(a):
            hi = split3(a)[:a.shape[0]]
            return jnp.concatenate([hi, jnp.zeros_like(hi),
                                    jnp.zeros_like(hi)], 0)
        monkeypatch.setattr(F, "split3", top_part_only)
    elif kind == "block_left_out":
        block_at = F.block_at
        # every pass reads block 0 where it should read the last block
        monkeypatch.setattr(F, "block_at", lambda arr, i: block_at(
            arr, jnp.where(i == arr.shape[0] - 1, 0, i)))
    elif kind == "standardization_left_out":
        fold = F.fold_coef
        monkeypatch.setattr(F, "fold_coef", lambda data, Wm: fold(
            {k: v for k, v in data.items() if k not in ("scale", "shift")},
            Wm))
    elif kind == "argmin_off_by_one":
        argmin = jnp.argmin
        monkeypatch.setattr(O.jnp, "argmin", lambda a, *k, **kw: jnp.minimum(
            argmin(a, *k, **kw) + 1, a.shape[0] - 1))
    else:
        raise AssertionError(kind)
    del jax


@pytest.mark.parametrize("kind,number", [
    ("plain_bfloat16", "grad_gap"), ("block_left_out", "grad_gap"),
    ("standardization_left_out", "loss_gap"),
    ("argmin_off_by_one", "step_gap")])
def test_a_planted_fault_makes_the_cell_incorrect(monkeypatch, kind, number):
    _planted(monkeypatch, kind)
    out = _run()
    assert out["correct"] is False
    over = [c["name"] for c in out["compared"] if c["value"] > c["limit"]]
    assert number in over, out["compared"]
