"""The fit-loop cell's own pieces at a size a test can hold: the table from
the seed, the operations count against a hand count, the window's cut, the
controls read not correct against the cell's limits, and ``correct``
coming out false with each fault planted in the program under the whole
run."""

import os
import re
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
AS = "TPU v5 lite"
CELL = "kmeans-fit"
GAPS = ("centroid_gap", "weight_gap", "inertia_gap", "init_weight_gap",
        "init_member_gap")


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


def _run(seed=20261001, seconds=0.3, trace=False):
    from benchmark.run import run_cell
    return run_cell(CELL, seed, seconds, trace, tiny_size=True,
                    require_tpu=False, device_kind_as=AS)


# -- data and arithmetic --------------------------------------------------------

def test_same_seed_same_table_and_the_last_block_is_padded():
    from benchmark import blobs
    config, _ = _tiny()
    args = (config["rows"], config["dimensions"], config["block_rows"])
    seed = 2 ** 31 + 5
    mix = blobs.mixture(seed, config["num_of_clusters"], args[1],
                        config["generator"])
    a = np.asarray(blobs.make_table(seed, *args, mix))
    b = np.asarray(blobs.make_table(seed, *args, mix))
    c = np.asarray(blobs.make_table(seed + 1, *args, blobs.mixture(
        seed + 1, config["num_of_clusters"], args[1], config["generator"])))
    assert a.dtype == np.float32 and a.shape == (5, 20, 8, 128)
    assert np.array_equal(a, b) and not np.array_equal(a[0], c[0])
    flat = a.transpose(0, 2, 3, 1).reshape(-1, 20)
    assert np.all(flat[5000:] == 0) and np.all(np.abs(flat[:5000]).sum(1) > 0)
    assert np.array_equal(np.asarray(blobs.make_block(seed, *args, mix, 3)), a[3])
    # clusters of unlike size and spread
    assert mix["shares"].sum() == pytest.approx(1.0)
    assert mix["shares"].max() / mix["shares"].min() > 1.1
    assert mix["spreads"].max() / mix["spreads"].min() > 1.1


def test_kmeans_superstep_counts_match_a_hand_count():
    from benchmark import opcount, opcount_kmeans
    # one row, one feature, one centre: subtract, multiply, add into the
    # distance, add into the centre's sum; the feature and the weight read
    assert opcount_kmeans.kmeans_superstep(1, 1, 1) == (4, 8)
    ops, byt = opcount_kmeans.kmeans_superstep(100_000_000, 20, 10)
    assert ops == 4 * 100_000_000 * 10 * 20 == 80_000_000_000
    assert byt == 100_000_000 * 21 * 4 == 8_400_000_000
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert opcount.bound_by(ops, byt, peak) == "memory"
    assert opcount.least_seconds(ops, byt, peak) == pytest.approx(0.0102564, rel=1e-4)


def test_fit_seeds_come_from_the_runs_seed_and_the_fits_number():
    from benchmark.generators.fit_loop import fit_seed
    big = 2 ** 31 + 11
    seeds = [fit_seed(big, i) for i in range(6)]
    assert seeds == [fit_seed(big, i) for i in range(6)]
    assert len(set(seeds)) == 6 and all(0 <= s < 2 ** 31 - 1 for s in seeds)
    assert fit_seed(big + 1, 0) != seeds[0]


def test_new_readers_return_none_with_nothing_to_read():
    import importlib
    ctx = types.SimpleNamespace(reduced=None, facts={}, config={
        "step_program": "jit_kmeans_lloyd", "init_program": "jit_kmeans_init",
        "init_rounds": 5})
    for base in ("lloyd_step_dev", "lloyd_step_roofline", "kmeans_fit_mfu"):
        assert importlib.import_module(
            "benchmark.readers." + base).read(ctx) is None, base


def test_span_readers_read_the_programs_spans(quiet_tracer):
    from benchmark.readers import engine_host_ms, init_share
    ctx = types.SimpleNamespace(reduced=None, facts={}, config={})
    assert init_share.read(ctx) is None and engine_host_ms.read(ctx) is None

    def span(name, ms):
        quiet_tracer._record(ph="X", name=name, cat="t", ts_ns=0,
                             dur_ns=int(ms * 1e6), tid=1, id=1, parent=None,
                             args=None, profiled=True)
    for name, ms in (("kmeans.fit", 100), ("kmeans.init", 60),
                     ("kmeans.recluster", 5), ("kmeans.lloyd", 30),
                     ("comqueue.exec", 58), ("comqueue.exec", 29),
                     ("comqueue.prepare", 1), ("comqueue.prepare", 2),
                     ("comqueue.fetch", 3), ("comqueue.fetch", 4)):
        span(name, ms)
    assert init_share.read(ctx) == pytest.approx(65.0)
    assert engine_host_ms.read(ctx) == pytest.approx(5.0)


def test_the_generator_asks_for_the_dense_block_column_first():
    """A program without the column (the parent of the PR that brought the
    cell) fails at the generator's import, before any table is built."""
    with open(os.path.join(ROOT, "benchmark", "generators", "fit_loop.py")) as f:
        src = f.read()
    imports = re.findall(r"^(?:from|import) .*$", src, re.M)
    assert imports[0] == "from __future__ import annotations"
    assert imports[1] == ("from alink_tpu.common.columnar import "
                          "DenseBlockColumn")


def test_importing_the_new_modules_touches_no_jax():
    import subprocess
    import sys
    mods = ["benchmark.blobs", "benchmark.opcount_kmeans",
            "benchmark.reference.kmeans"]
    code = ("import sys\n" + "".join(f"import {m}\n" for m in mods)
            + "bad = [m for m in sys.modules if m.split('.')[0] in "
              "('jax', 'jaxlib', 'libtpu', 'alink_tpu')]\n"
              "assert not bad, bad\n")
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=dict(os.environ, PYTHONPATH=ROOT),
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]


# -- the window -------------------------------------------------------------------

def test_the_window_is_cut_at_fit_boundaries():
    from benchmark import run as R
    from benchmark.generators import fit_loop
    found = R.load_cell(CELL)
    config, traffic = R.tiny(found["config"]), R.tiny(found["traffic"])
    peaks = R.load_json(os.path.join(R.HERE, "peaks.json"))[AS]
    ctx = R.Ctx(found["cell"], config, traffic, 13, 0.25, False, peaks, 0.0)
    from alink_tpu.common.mlenv import use_local_env
    use_local_env(parallelism=1)
    gen = fit_loop.Generator(ctx)
    gen.run()
    fits = ctx.facts["fits"]
    # whole fits only: the window closes on the first fit boundary at or
    # after --seconds, and every fit before it ended inside
    assert fits == len(gen.fit_s) == ctx.attempted and ctx.failed == 0
    assert ctx.facts["window_s"] == pytest.approx(sum(gen.fit_s))
    assert ctx.facts["window_s"] >= 0.25
    assert sum(gen.fit_s[:-1]) < 0.25
    assert ctx.e2e["train_rate"] == pytest.approx(
        config["rows"] * fits / ctx.facts["window_s"])
    per_fit = config["init_rounds"] + config["max_iter"]
    assert ctx.facts["supersteps"] == fits * per_fit
    assert ctx.facts["rows_counted"] == config["rows"] * fits * per_fit
    assert ctx.facts["fits_counted"] == fits
    # the first warm fit is the one compared, whole
    assert gen.first["steps"] == config["max_iter"]
    assert gen.first["centroids"].shape == (config["max_iter"], config["k"],
                                            config["dimensions"])
    gen.release()
    gen.verify()
    assert ctx.correct, ctx.compared


def test_the_traced_run_reports_every_per_layer_metric_it_can_off_a_chip():
    out = _run(seconds=0.3, trace=True)
    assert out["correct"] is True
    # device-trace metrics need a device plane; the spans and counters do not
    assert {"init_share.fit", "engine_host_ms.fit", "compiles_in_window.fit",
            "kmeans_fit_mfu"} <= set(out["metrics"])
    assert out["metrics"]["compiles_in_window.fit"]["value"] == 0
    assert 0 < out["metrics"]["init_share.fit"]["value"] < 100


# -- the controls -----------------------------------------------------------------

def _fails(readings, limits):
    return {k for k, v in readings.items() if v > float(limits[k])}


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3, 77])
def test_controls_and_faults_fail_and_float32_passes(seed):
    from benchmark import controls_kmeans
    config, traffic = _tiny()
    got = controls_kmeans.readings(seed, config, traffic)
    lim = config["limits"]
    assert set(got["float32_again"]) == set(GAPS)
    assert not _fails(got["float32_again"], lim)
    assert {"weight_gap", "init_weight_gap"} <= _fails(got["bfloat16"], lim)
    assert {"weight_gap", "inertia_gap"} <= _fails(got["block_left_out"], lim)
    assert "centroid_gap" in _fails(got["centroids_unchanged"], lim)
    assert {"weight_gap", "init_member_gap"} <= _fails(got["stale_last_block"], lim)
    # each by a decade or more
    assert got["bfloat16"]["weight_gap"] > 10 * float(lim["weight_gap"])
    assert got["block_left_out"]["weight_gap"] > 10 * float(lim["weight_gap"])
    assert got["centroids_unchanged"]["centroid_gap"] > 10 * float(lim["centroid_gap"])
    assert got["stale_last_block"]["weight_gap"] > 10 * float(lim["weight_gap"])


# -- faults planted in the program, under the whole run -------------------------------

def _plant(monkeypatch, kind):
    import jax.numpy as jnp
    from alink_tpu.operator.common.clustering import kmeans as K
    if kind == "bfloat16_distances":
        real = K.block_distances

        def low(xb, C, distance_type="EUCLIDEAN"):
            return real(xb.astype(jnp.bfloat16), C.astype(jnp.bfloat16),
                        distance_type).astype(xb.dtype)
        monkeypatch.setattr(K, "block_distances", low)
    elif kind == "block_left_out":
        real = K._lloyd_pass
        monkeypatch.setattr(K, "_lloyd_pass", lambda Xs, Ws, C, dist:
                            real(Xs[1:], Ws[1:], C, dist))
    elif kind == "centroids_unchanged":
        real = K._lloyd_update

        def frozen(buf, C):
            _, cnts, inertia, _, rows = real(buf, C)
            return C, cnts, inertia, jnp.asarray(jnp.inf, C.dtype), rows
        monkeypatch.setattr(K, "_lloyd_update", frozen)
    else:                               # a stale last block
        real = K._lloyd_pass
        monkeypatch.setattr(K, "_lloyd_pass", lambda Xs, Ws, C, dist:
                            real(Xs.at[-1].set(jnp.roll(Xs[0], 1, axis=0)),
                                 Ws, C, dist))


@pytest.mark.parametrize("kind,bad", [
    ("bfloat16_distances", {"weight_gap"}),
    ("block_left_out", {"weight_gap", "rows_gap"}),
    ("centroids_unchanged", {"centroid_gap"}),
    ("stale_last_block", {"weight_gap"})])
def test_a_fault_in_the_program_makes_the_fit_incorrect(monkeypatch, kind, bad):
    _plant(monkeypatch, kind)
    out = _run(seed=41)
    assert out["correct"] is False
    failed = {c["name"] for c in out["compared"] if c["value"] > c["limit"]}
    assert bad <= failed, out["compared"]
    assert out["failed"] == 0, "wrong, not crashed"
