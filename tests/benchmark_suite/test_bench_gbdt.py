"""The boost-loop cell's own pieces at a size a test can hold: the table
and its labels from the seed, the operations count against a hand count,
the readers against hand counts, the window's cut, the controls read not
correct against the cell's limits, and ``correct`` coming out false with
each fault planted in the program under the whole run."""

import os
import re
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
AS = "TPU v5 lite"
CELL = "gbdt-fit"
GAPS = ("split_gain_gap", "leaf_gap", "loss_gap", "count_gap")


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


def _run(seed=20261002, seconds=0.3, trace=False):
    from benchmark.run import run_cell
    return run_cell(CELL, seed, seconds, trace, tiny_size=True,
                    require_tpu=False, device_kind_as=AS)


# -- data and arithmetic --------------------------------------------------------

def test_same_seed_same_table_and_the_last_block_is_padded():
    from benchmark import airline
    config, _ = _tiny()
    args = (config["rows"], config["block_rows"], config["generator"])
    seed = 2 ** 31 + 5
    a, la = (np.asarray(v) for v in airline.make_table(seed, *args))
    b, lb = (np.asarray(v) for v in airline.make_table(seed, *args))
    c, _ = (np.asarray(v) for v in airline.make_table(seed + 1, *args))
    assert a.dtype == np.float32 and a.shape == (2, 13, 32, 128)
    assert la.dtype == np.float32 and la.shape == (2, 32, 128)
    assert (a == b).all() and (la == lb).all() and (a != c).any()
    rows = a.transpose(0, 2, 3, 1).reshape(-1, 13)
    pad = config["rows"]
    assert (rows[pad:] == 0).all() and (la.reshape(-1)[pad:] == 0).all()
    # whole numbers inside the columns' ranges, both labels present
    assert (rows == np.round(rows)).all()
    for j, name in enumerate(airline.COLUMNS):
        lo, hi = config["generator"]["ranges"][name]
        assert rows[:pad, j].min() >= lo and rows[:pad, j].max() <= hi, name
    assert 0.2 < la.reshape(-1)[:pad].mean() < 0.8
    one = np.asarray(airline.make_block(seed, *args, 1)[0])
    assert (one == a[1]).all()


def test_gbdt_counts_match_a_hand_count():
    from benchmark import opcount, opcount_gbdt
    ops, byt = opcount_gbdt.gbdt_tree(1000, 13, 6)
    # a level: 13 bins (1 B), g and h (8 B), the node (1 B) a row; the
    # margins read and written and the label read once a tree
    assert byt == 1000 * (6 * (13 + 8 + 1) + 12) == 144_000
    assert ops == 3 * 13 * 1000 * 6
    ops_b, byt_b = opcount_gbdt.gbdt_binning(1000, 13)
    assert byt_b == 1000 * 13 * 9 and ops_b == 2 * 13 * 1000
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert opcount.bound_by(ops, byt, peak) == "memory"
    # the cell's floor: 16.56 GB a tree over 819 GB/s
    least = opcount.least_seconds(
        *opcount_gbdt.gbdt_tree(115_000_000, 13, 6), peak)
    assert least == pytest.approx(115e6 * 144 / 819e9)


def test_new_readers_return_none_with_nothing_to_read():
    import importlib
    ctx = types.SimpleNamespace(reduced=None, facts={}, config={
        "step_program": "jit_gbdt_grow", "bin_program": "jit_gbdt_bin",
        "edge_program": "jit_gbdt_edges"})
    for base in ("gbdt_tree_dev", "gbdt_tree_roofline", "gbdt_fit_mfu",
                 "gbdt_bin_dev"):
        assert importlib.import_module(
            "benchmark.readers." + base).read(ctx) is None, base


def test_device_readers_against_a_hand_count():
    from benchmark.readers import (gbdt_bin_dev, gbdt_fit_mfu, gbdt_tree_dev,
                                   gbdt_tree_roofline)
    reduced = {"window_s": 16.0,
               "module_s": {"jit_gbdt_grow(123)": 12.0, "jit_gbdt_bin(9)": 0.5,
                            "jit_gbdt_edges(7)": 0.7, "jit_other(1)": 3.0},
               "module_calls": {"jit_gbdt_grow(123)": 2, "jit_gbdt_bin(9)": 2,
                                "jit_gbdt_edges(7)": 2, "jit_other(1)": 5}}
    ctx = types.SimpleNamespace(
        reduced=reduced,
        facts={"trees": 8, "fits": 2, "tree_least_s": 0.02,
               "bin_least_s": 0.004},
        config={"step_program": "jit_gbdt_grow",
                "bin_program": "jit_gbdt_bin",
                "edge_program": "jit_gbdt_edges"})
    assert gbdt_tree_dev.read(ctx) == pytest.approx(1500.0)
    assert gbdt_tree_roofline.read(ctx) == pytest.approx(100 * 0.02 / 1.5)
    assert gbdt_bin_dev.read(ctx) == pytest.approx(600.0)
    assert gbdt_fit_mfu.read(ctx) == pytest.approx(
        100 * (8 * 0.02 + 2 * 0.004) / 16.0)


def test_span_readers_read_the_programs_spans(quiet_tracer):
    from benchmark.readers import bin_share, engine_host_ms
    ctx = types.SimpleNamespace(reduced=None, facts={}, config={})
    assert bin_share.read(ctx) is None

    def span(name, ms):
        quiet_tracer._record(ph="X", name=name, cat="t", ts_ns=0,
                             dur_ns=int(ms * 1e6), tid=1, id=1, parent=None,
                             args=None, profiled=True)
    for name, ms in (("gbdt.fit", 400), ("gbdt.fit", 600), ("gbdt.bin", 30),
                     ("gbdt.bin", 50), ("gbdt.grow", 900),
                     ("comqueue.exec", 5), ("comqueue.exec", 5),
                     ("comqueue.prepare", 2), ("comqueue.fetch", 4)):
        span(name, ms)
    assert bin_share.read(ctx) == pytest.approx(8.0)
    assert engine_host_ms.read(ctx) == pytest.approx(3.0)


def test_the_generator_asks_for_the_row_block_column_first():
    """A program without the column (the parent of the PR that brought the
    cell) fails at the generator's import, before any table is built."""
    with open(os.path.join(ROOT, "benchmark", "generators",
                           "boost_loop.py")) as f:
        src = f.read()
    imports = re.findall(r"^(?:from|import) .*$", src, re.M)
    assert imports[0] == "from __future__ import annotations"
    assert imports[1] == ("from alink_tpu.common.columnar import "
                          "RowBlockColumn")


def test_importing_the_new_modules_touches_no_jax():
    import subprocess
    import sys
    mods = ["benchmark.airline", "benchmark.opcount_gbdt",
            "benchmark.reference.gbdt", "benchmark.controls_gbdt"]
    code = ("import sys\n" + "".join(f"import {m}\n" for m in mods)
            + "bad = [m for m in sys.modules if m.split('.')[0] in "
              "('jax', 'jaxlib', 'libtpu', 'alink_tpu')]\n"
              "assert not bad, bad\n")
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=dict(os.environ, PYTHONPATH=ROOT),
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference", "gbdt.py")) as f:
        src = f.read()
    assert not re.findall(r"^\s*(?:from|import) .*alink_tpu", src, re.M)


# -- the reference by hand ----------------------------------------------------------

def test_exact_edges_are_the_inverted_cdf_quantiles():
    from benchmark.reference import gbdt as ref
    col = np.asarray([[1., 1., 1., 2., 2., 3., 4., 4., 4., 4., 5., 9.]])
    # shares at or below: 1 -> 3/12, 2 -> 5/12, 3 -> 6/12, 4 -> 10/12
    e = ref.exact_edges(col, 4)                    # targets 1/4, 1/2, 3/4
    assert e[0].tolist() == [1.0, 3.0, 4.0]
    e = ref.exact_edges(col, 3)                    # targets 1/3, 2/3
    assert e[0].tolist() == [2.0, 4.0]


def test_level_gains_and_the_gain_gap_by_hand():
    from benchmark.reference import gbdt as ref
    # one node, one feature, three bins of (G, H, count)
    h = np.asarray([[[[-4., 2., 10.], [1., 2., 10.], [3., 2., 10.]]]])
    gain, ok = ref.level_gains(h, lam=1.0, min_leaf=10)
    s = lambda g, hh: g * g / (hh + 1.0)
    assert gain[0, 0, 0] == pytest.approx(0.5 * (s(-4, 2) + s(4, 4) - s(0, 6)))
    assert gain[0, 0, 1] == pytest.approx(0.5 * (s(-3, 4) + s(3, 2) - s(0, 6)))
    assert ok.all()
    _, ok = ref.level_gains(h, lam=1.0, min_leaf=11)
    assert not ok[0, 0, 0] and ok[0, 0, 1] is not None
    params = {"max_depth": 1, "reg_lambda": 1.0, "min_samples_per_leaf": 10}
    best = ref.split_gain_gap(h, np.asarray([0]), np.asarray([0]), params)
    worse = ref.split_gain_gap(h, np.asarray([0]), np.asarray([1]), params)
    assert best == 0.0
    assert worse == pytest.approx(1 - gain[0, 0, 1] / gain[0, 0, 0])
    # a node left unsplit though a split would gain
    assert ref.split_gain_gap(h, np.asarray([-1]), np.asarray([0]),
                              params) == 1.0


def test_a_float32_count_past_2_to_24_is_not_exact_and_int64_is():
    """The ``float32_counts`` control, on a synthetic count (no such table
    is built): 400 blocks of 65,535 rows."""
    from benchmark.reference import gbdt as ref
    blocks = [np.asarray([65535, 1])] * 400
    assert ref.sum_counts(blocks).tolist() == [400 * 65535, 400]
    low = ref.sum_counts(blocks, np.float32)
    assert low[0] != 400 * 65535 and low[1] == 400


# -- the window -------------------------------------------------------------------

def test_the_window_is_cut_at_fit_boundaries_and_holds_two_fits():
    from benchmark import run as R
    from benchmark.generators import boost_loop
    found = R.load_cell(CELL)
    config, traffic = R.tiny(found["config"]), R.tiny(found["traffic"])
    peaks = R.load_json(os.path.join(R.HERE, "peaks.json"))[AS]
    ctx = R.Ctx(found["cell"], config, traffic, 13, 0.01, False, peaks, 0.0)
    from alink_tpu.common.mlenv import use_local_env
    use_local_env(parallelism=1)
    gen = boost_loop.Generator(ctx)
    gen.run()
    fits = ctx.facts["fits"]
    # whole fits only, and at least two however short --seconds is
    assert fits == len(gen.fit_s) == ctx.attempted >= 2 and ctx.failed == 0
    assert ctx.facts["window_s"] == pytest.approx(sum(gen.fit_s))
    assert ctx.e2e["train_rate"] == pytest.approx(
        config["rows"] * fits / ctx.facts["window_s"])
    assert ctx.facts["trees"] == fits * config["num_trees"]
    assert ctx.facts["rows_counted"] == config["rows"] * ctx.facts["trees"]
    assert ctx.facts["fits_counted"] == fits
    assert ctx.facts["hist_path"] == "scatter"          # off the TPU
    # the first warm fit is the one compared, whole
    assert gen.first["features"].shape == (config["num_trees"], 7)
    assert gen.first["counts"].shape == (config["num_trees"], 15)
    assert (gen.first["counts"][:, 0] == config["rows"]).all()
    assert (np.diff(gen.first["loss_curve"]) < 0).all(), "the loss falls"
    gen.release()
    gen.verify()
    assert ctx.correct, ctx.compared


def test_the_traced_run_reports_every_per_layer_metric_it_can_off_a_chip():
    out = _run(seconds=0.3, trace=True)
    assert out["correct"] is True
    # device-trace metrics need a device plane; the spans and counters do not
    assert {"bin_share.boost", "engine_host_ms.boost",
            "compiles_in_window.boost", "gbdt_fit_mfu"} <= set(out["metrics"])
    assert out["metrics"]["compiles_in_window.boost"]["value"] == 0
    assert 0 < out["metrics"]["bin_share.boost"]["value"] < 100


# -- the controls -----------------------------------------------------------------

def _fails(readings, limits):
    return {k for k, v in readings.items() if v > float(limits[k])}


@pytest.fixture(scope="module")
def control_readings():
    from benchmark import controls_gbdt
    config, _ = _tiny()
    return {seed: controls_gbdt.readings(seed, config)
            for seed in (3, 2 ** 31 + 9)}


@pytest.mark.parametrize("control,bad", [
    ("bfloat16", {"leaf_gap"}),
    ("block_left_out", {"count_gap", "leaf_gap"}),
    ("stale_margins", {"loss_gap", "leaf_gap"}),
    ("no_descent", {"count_gap"}),
    ("sampled_edges", {"edge_rank_gap"}),
    ("uniform_edges", {"edge_rank_gap"}),
    ("half_the_edges", {"edge_rank_gap"})])
def test_each_control_fails_the_limit_it_is_there_for(control_readings,
                                                      control, bad):
    config, _ = _tiny()
    lim = config["limits"]
    for seed, got in control_readings.items():
        assert set(got["float32_again"]) == set(GAPS)
        assert not _fails(got["float32_again"], lim), seed
        # the whole table's exact quantiles stand where they should
        assert got["exact_edges"] == {"edge_rank_gap": 0.0}, seed
        assert bad <= _fails(got[control], lim), (seed, got[control])
        # by a decade or more
        for name in bad - {"count_gap"}:
            assert got[control][name] > 10 * float(lim[name]), (seed, name)


# -- faults planted in the program, under the whole run -------------------------------

def _plant(monkeypatch, kind):
    import jax.numpy as jnp
    from alink_tpu.operator.common.tree import hist as H
    from alink_tpu.operator.common.tree import trainers as T
    if kind == "bfloat16_stats":
        real = T.build_tree_blocked
        monkeypatch.setattr(
            T, "build_tree_blocked", lambda bins, node, stats_at, *a, **k:
            real(bins, node, lambda i: stats_at(i).astype(
                jnp.bfloat16).astype(jnp.float32), *a, **k))
    elif kind == "block_left_out":
        real = T.build_tree_blocked
        monkeypatch.setattr(
            T, "build_tree_blocked", lambda bins, node, stats_at, *a, **k:
            real(bins, node, lambda i: stats_at(i) * (i != 0), *a, **k))

    elif kind == "margins_never_folded":
        monkeypatch.setattr(T, "lookup",
                            lambda table, ids: jnp.zeros(ids.shape,
                                                         table.dtype))
    elif kind == "no_descent":
        monkeypatch.setattr(H, "descend_block",
                            lambda bins_b, node_b, *a: node_b * 2)
    elif kind == "edges_of_a_sample":
        from alink_tpu.common.columnar import DenseBlockColumn
        real = T.make_bin_edges
        monkeypatch.setattr(
            T, "make_bin_edges", lambda col, *a, **k: real(
                DenseBlockColumn(col.blocks[:1], col.block_rows), *a, **k))
    else:                               # bins against shifted edges
        real = T.bin_blocks
        monkeypatch.setattr(T, "bin_blocks",
                            lambda Xs, edges: real(Xs, edges * 1.5))


@pytest.mark.parametrize("kind,bad", [
    ("bfloat16_stats", {"leaf_gap"}),
    ("block_left_out", {"count_gap", "rows_gap"}),
    ("margins_never_folded", {"loss_gap"}),
    ("no_descent", {"count_gap"}),
    ("edges_of_a_sample", {"edge_rank_gap"}),
    ("bins_off", {"count_gap"})])
def test_a_fault_in_the_program_makes_the_fit_incorrect(monkeypatch, kind, bad):
    _plant(monkeypatch, kind)
    out = _run(seed=41)
    assert out["correct"] is False
    failed = {c["name"] for c in out["compared"] if c["value"] > c["limit"]}
    assert bad <= failed, out["compared"]
    assert out["failed"] == 0, "wrong, not crashed"
