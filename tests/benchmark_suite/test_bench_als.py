"""The ALS-loop cell's own pieces at a size a test can hold: the table
from the seed, the operations count against a hand count, the readers
against hand counts, the window's cut, the controls read not correct
against the cell's limits, and ``correct`` coming out false with each
fault planted in the program under the whole run."""

import os
import re
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
AS = "TPU v5 lite"
CELL = "als-fit"
GAPS = ("user_solve_gap", "item_solve_gap", "count_gap", "rmse_gap")


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


def _run(seed=20261003, seconds=0.3, trace=False):
    from benchmark.run import run_cell
    return run_cell(CELL, seed, seconds, trace, tiny_size=True,
                    require_tpu=False, device_kind_as=AS)


# -- data and arithmetic --------------------------------------------------------

def test_same_seed_same_table_a_power_law_and_a_padded_last_block():
    from benchmark import yahoo
    config, _ = _tiny()
    args = (config["ratings"], config["block_rows"], config["users"],
            config["items"], config["generator"])
    seed = 2 ** 31 + 5
    a = [np.asarray(v) for v in yahoo.make_table(seed, *args)]
    b = [np.asarray(v) for v in yahoo.make_table(seed, *args)]
    c = [np.asarray(v) for v in yahoo.make_table(seed + 1, *args)]
    assert [v.dtype for v in a] == [np.int32, np.int32, np.float32]
    assert all(v.shape == (3, 32, 128) for v in a)
    assert all((x == y).all() for x, y in zip(a, b))
    assert (a[0] != c[0]).any() and (a[2] != c[2]).any()
    n = config["ratings"]
    u, i, r = (v.reshape(-1) for v in a)
    assert not u[n:].any() and not i[n:].any() and not r[n:].any()
    assert 0 <= u[:n].min() and u[:n].max() < config["users"]
    assert 0 <= i[:n].min() and i[:n].max() < config["items"]
    assert (r == np.round(r)).all() and r.min() >= 0 and r.max() <= 100
    assert 10 < r[:n].std() < 35                 # not all clipped, not flat
    # a power law: the heaviest user far above the median, and (the ids
    # went through the bijection) not user 0
    cnt = np.bincount(u[:n], minlength=config["users"])
    assert cnt.max() > 8 * np.median(cnt[cnt > 0])
    assert cnt.argmax() != 0
    assert (cnt == 0).any(), "some users have no rating at this size"


def test_the_id_bijection_is_one():
    from benchmark import yahoo
    for n, want in ((1000990, 3643), (624961, 2741), (700, 3643)):
        s = yahoo.stride_for(n, want)
        assert len({k * s % n for k in range(0, n, max(n // 5000, 1))}) \
            == len(range(0, n, max(n // 5000, 1)))
        assert np.gcd(s, n) == 1 and (n - 1) * s + n // 3 < 2 ** 32


def test_als_counts_match_a_hand_count():
    from benchmark import opcount, opcount_als
    ops, byt = opcount_als.als_half_sweep(1000, 10, 20, 4)
    # a rating: 4 * 5 for the symmetric sums, 2 * 4 for the right-hand
    # side; a row: 4^3 / 3 + 2 * 16
    assert ops == 1000 * (20 + 8) + 10 * (21 + 32)
    assert byt == 1000 * 8 + 4 * 4 * (10 + 20)
    assert opcount_als.als_grouping(1000) == (2000, 2 * 2 * 1000 * 20)
    assert opcount_als.als_solves(10, 4) == (10 * (21 + 32), 10 * 4 * 24)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # the cell's shapes: compute-bound, ~15 ms a half-sweep of operations
    # against ~3 ms of bytes; the grouping's floor is the bytes'
    ops, byt = opcount_als.als_iteration(252_800_275, 1_000_990, 624_961, 100)
    assert opcount.bound_by(ops, byt, peak) == "compute"
    assert ops == 2 * 252_800_275 * 10_300 + 1_625_951 * 353_333
    assert opcount.least_seconds(ops, byt, peak) / 2 == pytest.approx(
        0.01468, rel=2e-3)
    assert byt / 2 / 819e9 == pytest.approx(0.00327, rel=2e-3)
    assert opcount.least_seconds(*opcount_als.als_grouping(252_800_275),
                                 peak) == pytest.approx(0.02469, rel=2e-3)


def test_new_readers_return_none_with_nothing_to_read():
    import importlib
    ctx = types.SimpleNamespace(reduced=None, facts={}, config={
        "step_program": "jit_als_sweep", "group_program": "jit_als_group"})
    for base in ("als_sweep_dev", "als_sweep_roofline", "als_fit_mfu",
                 "als_group_dev", "als_solve_roofline"):
        assert importlib.import_module(
            "benchmark.readers." + base).read(ctx) is None, base


def test_device_readers_against_a_hand_count():
    from benchmark.readers import (als_fit_mfu, als_group_dev,
                                   als_solve_roofline, als_sweep_dev,
                                   als_sweep_roofline)
    reduced = {"window_s": 40.0,
               "op_s": {"als_solve.41": 0.5, "als_solve.43": 0.3,
                        "fusion.9": 7.0},
               "module_s": {"jit_als_sweep(123)": 24.0,
                            "jit_als_group(9)": 8.0, "jit_other(1)": 3.0},
               "module_calls": {"jit_als_sweep(123)": 2,
                                "jit_als_group(9)": 2, "jit_other(1)": 5}}
    ctx = types.SimpleNamespace(
        reduced=reduced,
        facts={"half_sweeps": 4, "fits": 2, "sweep_least_s": 0.015,
               "group_least_s": 0.025},
        config={"step_program": "jit_als_sweep",
                "group_program": "jit_als_group", "users": 1000,
                "items": 600, "rank": 100},
        peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert als_sweep_dev.read(ctx) == pytest.approx(6000.0)
    # 4 half-sweeps solve every user and item twice: 3,200 systems, each
    # 40,800 bytes across memory once (0.16 ms at 819 GB/s; their 353,333
    # operations 5.7 us at the peak: the solves' floor is the bytes')
    assert als_solve_roofline.read(ctx) == pytest.approx(
        100 * 3200 * 40_800 / 819e9 / 0.8)
    ctx.reduced["op_s"] = {"fusion.9": 7.0}        # another solve: silent
    assert als_solve_roofline.read(ctx) is None
    assert als_sweep_roofline.read(ctx) == pytest.approx(100 * 0.015 / 6.0)
    assert als_group_dev.read(ctx) == pytest.approx(4000.0)
    assert als_fit_mfu.read(ctx) == pytest.approx(
        100 * (4 * 0.015 + 2 * 0.025) / 40.0)


def test_span_readers_read_the_programs_spans(quiet_tracer):
    from benchmark.readers import engine_host_ms, group_share
    ctx = types.SimpleNamespace(reduced=None, facts={}, config={})
    assert group_share.read(ctx) is None

    def span(name, ms):
        quiet_tracer._record(ph="X", name=name, cat="t", ts_ns=0,
                             dur_ns=int(ms * 1e6), tid=1, id=1, parent=None,
                             args=None, profiled=True)
    for name, ms in (("als.fit", 400), ("als.fit", 600), ("als.group", 90),
                     ("als.group", 110), ("als.sweep", 700),
                     ("comqueue.exec", 5), ("comqueue.exec", 5),
                     ("comqueue.prepare", 2), ("comqueue.fetch", 4)):
        span(name, ms)
    assert group_share.read(ctx) == pytest.approx(20.0)
    assert engine_host_ms.read(ctx) == pytest.approx(3.0)


def test_the_generator_asks_for_the_blocked_fits_names_first():
    """A program without the blocked ALS fit (the parent of the PR that
    brought the cell) fails at the generator's import, before any table is
    drawn: left to itself its fit would walk 252.8 million device values in
    Python."""
    with open(os.path.join(ROOT, "benchmark", "generators",
                           "als_loop.py")) as f:
        src = f.read()
    imports = re.findall(r"^(?:from|import) [^\n(]*(?:\([^)]*\))?", src, re.M)
    assert imports[0] == "from __future__ import annotations"
    assert imports[1].startswith(
        "from alink_tpu.operator.common.recommendation.als import (")
    assert "GROUP_PROGRAM" in imports[1] and "SWEEP_PROGRAM" in imports[1]


def test_importing_the_new_modules_touches_no_jax():
    import subprocess
    import sys
    mods = ["benchmark.yahoo", "benchmark.opcount_als",
            "benchmark.reference.als", "benchmark.controls_als"]
    code = ("import sys\n" + "".join(f"import {m}\n" for m in mods)
            + "bad = [m for m in sys.modules if m.split('.')[0] in "
              "('jax', 'jaxlib', 'libtpu', 'alink_tpu')]\n"
              "assert not bad, bad\n")
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=dict(os.environ, PYTHONPATH=ROOT),
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference", "als.py")) as f:
        src = f.read()
    assert not re.findall(r"^\s*(?:from|import) .*alink_tpu", src, re.M)


# -- the reference by hand ----------------------------------------------------------

def test_the_reference_solve_and_sample_by_hand():
    from benchmark.reference import als as ref
    params = {"rank": 2, "lambda": 0.5, "implicit": False, "alpha": 40.0,
              "nonnegative": False, "sample_rows": 2}
    keys = np.asarray([2, 0, 2, 2, 1])
    others = np.asarray([0, 1, 1, 2, 0])
    ratings = np.asarray([1.0, 2.0, 3.0, 4.0, 5.0])
    other = np.asarray([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]])
    cnt = np.bincount(keys, minlength=4)
    rows = np.asarray([0, 2, 3])
    found = ref.ratings_of(keys, rows, 4)
    assert found[0].tolist() == [1, 0, 2, 3]
    x = ref.solve_rows(rows, found, others, ratings, other, cnt, params)
    # row 0: one rating of 2 on item 1: (e1 e1^T + 0.5 I) x = 2 e1
    np.testing.assert_allclose(x[0], [0.0, 2 / 1.5])
    # row 2: items 0, 1, 2 rated 1, 3, 4; ridge 0.5 * 3
    X = other[[0, 1, 2]]
    np.testing.assert_allclose(x[1], np.linalg.solve(
        X.T @ X + 1.5 * np.eye(2), X.T @ [1.0, 3.0, 4.0]))
    assert not x[2].any()                          # no rating: zeros
    plain = ref.solve_rows(rows, found, others, ratings, other, cnt, params,
                           weighted=False)
    np.testing.assert_allclose(plain[1], np.linalg.solve(
        X.T @ X + 0.5 * np.eye(2), X.T @ [1.0, 3.0, 4.0]))
    # the sample always holds the heaviest, the lightest and the empty
    cnt = np.asarray([5, 0, 900, 1, 40, 0, 7] + [10] * 500)
    rows = ref.sample_rows(cnt, seed=3, size=20, side=0)
    assert {2, 3, 1, 5} <= set(rows.tolist()) and len(rows) >= 200


def test_a_float32_offset_past_2_to_24_is_not_exact():
    """The ``float32_counts`` control, on synthetic counts (no such table
    is built in a test): the offsets of 300 rows of 65,537 ratings pass
    2^24, where float32 steps by 2."""
    from benchmark.reference import als as ref
    cnt = np.full(300, 65537)
    low = ref.float32_counts(cnt)
    assert (ref.float32_counts(cnt[:200]) == 65537).all()
    assert (low != cnt).any() and abs(low - cnt).max() <= 2


# -- the window -------------------------------------------------------------------

def test_the_window_is_cut_at_fit_boundaries_and_holds_two_fits():
    from benchmark import run as R
    from benchmark.generators import als_loop
    found = R.load_cell(CELL)
    config, traffic = R.tiny(found["config"]), R.tiny(found["traffic"])
    peaks = R.load_json(os.path.join(R.HERE, "peaks.json"))[AS]
    ctx = R.Ctx(found["cell"], config, traffic, 13, 0.01, False, peaks, 0.0)
    from alink_tpu.common.mlenv import use_local_env
    use_local_env(parallelism=1)
    gen = als_loop.Generator(ctx)
    gen.run()
    fits = ctx.facts["fits"]
    # whole fits only, and at least two however short --seconds is
    assert fits == len(gen.fit_s) == ctx.attempted >= 2 and ctx.failed == 0
    assert ctx.facts["window_s"] == pytest.approx(sum(gen.fit_s))
    assert ctx.e2e["train_rate"] == pytest.approx(
        config["ratings"] * fits / ctx.facts["window_s"])
    assert ctx.facts["half_sweeps"] == 2 * fits * config["num_iter"]
    assert ctx.facts["ratings_counted"] == (config["ratings"]
                                            * ctx.facts["half_sweeps"])
    assert ctx.facts["fits_counted"] == fits
    assert ctx.facts["solve_path"] == "xla"             # off the TPU
    assert ctx.facts["group_path"] == "sort"
    # the first warm fit is the one compared, whole
    first = gen.first
    assert first["user_factors"].shape == (config["users"], 128)
    assert first["items_read"].shape == (config["items"], 128)
    assert int(np.asarray(first["user_counts"]).sum()) == config["ratings"]
    assert first["ratings"] == 2 * config["ratings"] * config["num_iter"]
    gen.release()
    gen.verify()
    assert ctx.correct, ctx.compared
    assert [c["name"] for c in ctx.compared] == list(GAPS) + ["rows_gap"]


def test_the_result_line_has_the_contracts_keys():
    out = _run()
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device",
                        "facts", "compared"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2
    assert set(out["metrics"]) == {"train_rate", "setup_s"}
    assert out["metrics"]["train_rate"]["unit"] == "rows/s"
    assert out["metrics"]["train_rate"]["value"] > 0


def test_the_traced_run_reports_every_per_layer_metric_it_can_off_a_chip():
    out = _run(seconds=0.3, trace=True)
    assert out["correct"] is True
    # device-trace metrics need a device plane; the spans and counters do not
    assert {"group_share.als", "engine_host_ms.als",
            "compiles_in_window.als", "als_fit_mfu"} <= set(out["metrics"])
    assert out["metrics"]["compiles_in_window.als"]["value"] == 0
    assert 0 < out["metrics"]["group_share.als"]["value"] < 100
    assert 0 < out["metrics"]["als_fit_mfu"]["value"] < 100


# -- the controls -----------------------------------------------------------------

def _fails(readings, limits):
    return {k for k, v in readings.items() if v > float(limits[k])}


@pytest.fixture(scope="module")
def control_readings():
    from benchmark import controls_als
    config, _ = _tiny()
    return {seed: controls_als.readings(seed, config)
            for seed in (3, 2 ** 31 + 9)}


@pytest.mark.parametrize("control,bad", [
    ("bfloat16", {"user_solve_gap", "item_solve_gap"}),
    ("block_left_out", {"count_gap", "user_solve_gap", "item_solve_gap",
                        "rmse_gap"}),
    ("stale_factors", {"item_solve_gap"}),
    ("plain_lambda", {"user_solve_gap", "item_solve_gap"})])
def test_each_control_fails_the_limit_it_is_there_for(control_readings,
                                                      control, bad):
    config, _ = _tiny()
    lim = config["limits"]
    for seed, got in control_readings.items():
        assert set(got["float64_again"]) == set(GAPS)
        assert not _fails(got["float64_again"], lim), seed
        assert not _fails(got["float32"], lim), seed
        # offsets under 2^24: float32 carries them exactly at this size
        # (test_a_float32_offset_past_2_to_24_is_not_exact has the rest)
        assert not _fails(got["float32_counts"], lim), seed
        assert bad <= _fails(got[control], lim), (seed, got[control])
        # by a decade or more
        for name in bad - {"count_gap"}:
            assert got[control][name] > 10 * float(lim[name]), (seed, name)


# -- faults planted in the program, under the whole run -------------------------------

def _plant(monkeypatch, kind):
    import jax
    import jax.numpy as jnp
    from alink_tpu.operator.common.recommendation import als as A
    if kind == "bfloat16_gram":
        monkeypatch.setattr(A, "HIGHEST", jax.lax.Precision.DEFAULT)
        real = jnp.einsum

        def low(spec, *ops, **kw):
            if spec == "bkf,bkg->bfg":
                ops = [o.astype(jnp.bfloat16).astype(jnp.float32)
                       for o in ops]
            return real(spec, *ops, **kw)
        monkeypatch.setattr(A.jnp, "einsum", low)
    elif kind == "slab_left_out":
        # a row's ratings 17 to 32 never folded (nor counted)
        real = A._owned
        monkeypatch.setattr(A, "_owned", lambda at, st, en: real(at, st, en)
                            & ~real(at, st + 16, st + 32))
    elif kind == "stale_factors":
        real = A._half_sweep

        def stale(other, *a, old=None, **k):
            # the item half-sweep reads user factors that were never solved
            return real(other * 0.5 if old is not None else other, *a,
                        old=old, **k)
        monkeypatch.setattr(A, "_half_sweep", stale)
    elif kind == "plain_lambda":
        monkeypatch.setattr(A, "_ridge_weight",
                            lambda n: jnp.ones(n.shape, jnp.float32))
    else:                               # counts: one rating lost a row
        real = A._run_lengths
        monkeypatch.setattr(A, "_run_lengths", lambda off, n:
                            jnp.maximum(real(off, n) - 1, 0))


@pytest.mark.parametrize("kind,bad", [
    ("bfloat16_gram", {"user_solve_gap"}),
    ("slab_left_out", {"user_solve_gap", "rows_gap"}),
    ("stale_factors", {"item_solve_gap"}),
    ("plain_lambda", {"user_solve_gap", "item_solve_gap"}),
    ("counts_off", {"count_gap"})])
def test_a_fault_in_the_program_makes_the_fit_incorrect(monkeypatch, kind, bad):
    _plant(monkeypatch, kind)
    out = _run(seed=41)
    assert out["correct"] is False
    failed = {c["name"] for c in out["compared"] if c["value"] > c["limit"]}
    assert bad <= failed, out["compared"]
    assert out["failed"] == 0, "wrong, not crashed"
