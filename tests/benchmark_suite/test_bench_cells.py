"""Every cell end to end at a tiny size on the CPU, through the harness's
own ``run_cell`` with its look for a chip skipped; the window's cut; and
``correct`` coming out false with the timed path broken underneath."""

import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
AS = "TPU v5 lite"      # whose peaks a CPU run counts against; never printed


@pytest.fixture(autouse=True)
def _keep_the_sessions_env():
    """``run_cell`` opens a session over the cell's chips; the other test
    files of this worker expect the 8-device one back."""
    from alink_tpu.common.mlenv import MLEnvironmentFactory
    before = MLEnvironmentFactory.get_default()
    yield
    MLEnvironmentFactory.set_default(before)


def _run(cell, seed=20260930, seconds=0.6, trace=False):
    from benchmark.run import run_cell
    return run_cell(cell, seed, seconds, trace, tiny_size=True,
                    require_tpu=False, device_kind_as=AS, parked=True)


def _bench():
    """BENCHMARK.json and the parked cells beside it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "parked.json")) as f:
        more = json.load(f)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] += more[key]
    return bench


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_cell_runs_end_to_end_and_is_correct(cell):
    bench = _bench()
    out = _run(cell, seed=2 ** 31 + 77)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "compared"
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == want and "setup_s" in want
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for c in out["compared"]:
        assert c["value"] <= c["limit"], c
    assert out["facts"]["compiles_in_window"] == 0
    json.dumps(out)


def test_same_seed_same_inputs_and_the_drain_does_the_same_work():
    from benchmark import data
    shape = {"int_fields": 13, "cat_cardinalities": [1000, 7, 40_000_000],
             "click_bias": -3.0}
    a = data.make_rows(2 ** 31 + 5, 512, shape, 4095)
    b = data.make_rows(2 ** 31 + 5, 512, shape, 4095)
    c = data.make_rows(2 ** 31 + 6, 512, shape, 4095)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    idx, val, click = a
    assert idx.shape == (512, 16) and idx.min() >= 0 and idx.max() < 4095
    assert np.all(np.diff(idx, axis=1) > 0), "one index per field, sorted"
    assert set(np.unique(click)) <= {0, 1} and 0 < click.mean() < 0.5
    w = data.host_weights(9, 1000, 0.05, 0)
    assert np.array_equal(w, data.host_weights(9, 1000, 0.05, 0))
    assert np.abs(w).max() <= 0.05 and w.std() > 0.02


def test_the_drains_window_is_cut_at_snapshot_boundaries():
    from benchmark import run as R
    from benchmark.generators import closed_drain
    found = R.load_cell("ftrl-drain")
    config, traffic = R.tiny(found["config"]), R.tiny(found["traffic"])
    peaks = R.load_json(os.path.join(R.HERE, "peaks.json"))[AS]
    ctx = R.Ctx(found["cell"], config, traffic, 11, 0.3, False, peaks, 0.0)
    from alink_tpu.common.mlenv import use_local_env
    use_local_env(parallelism=1)
    gen = closed_drain.Generator(ctx)
    gen.run()
    every, B = traffic["snapshot_every"], config["batch_rows"]
    # the window opens on the boundary that ends warm-up and closes on the
    # first boundary at or after --seconds
    assert gen.t0 >= gen.snap_t[traffic["warm_cycles"] - 1]
    assert gen.t1 in gen.snap_t and gen.t1 - gen.t0 >= 0.3
    before = [t for t in gen.snap_t if gen.t0 < t < gen.t1]
    assert all(t - gen.t0 < 0.3 for t in before)
    assert (gen.b1 - gen.b0) % every == 0
    assert ctx.facts["rows"] == (gen.b1 - gen.b0) * B
    assert ctx.facts["snapshots"] == (gen.b1 - gen.b0) // every
    assert ctx.facts["rows_counted"] == ctx.facts["rows"]
    assert ctx.e2e["train_rate"] == pytest.approx(
        ctx.facts["rows"] / (gen.t1 - gen.t0))
    # event times 0..every: the first cycle holds every + 1 micro-batches
    assert gen.snap_batch[0] == every + 1 and gen.snap_batch[1] == 2 * every + 1


# -- faults planted in the program, under the whole run -----------------------

def _break_ftrl_step(monkeypatch, kind):
    import jax
    import jax.numpy as jnp
    from alink_tpu.operator.stream.onlinelearning import ftrl as F
    real = F._ftrl_sparse_step_factory

    @functools.lru_cache(maxsize=4)
    def factory(mesh, alpha, beta, l1, l2, donate=False, kernel="off"):
        step = real(mesh, alpha, beta, l1, l2, donate=False, kernel=kernel)
        if kind == "state_unchanged":
            def broken(idx, val, y, z, n):
                return z, n, step(idx, val, y, z, n)[2]
        else:                       # half of the batch left out
            def broken(idx, val, y, z, n):
                keep = (jnp.arange(val.shape[0]) < val.shape[0] // 2)[:, None]
                return step(idx, val * keep, y, z, n)
        return jax.jit(broken)

    monkeypatch.setattr(F, "_ftrl_sparse_step_factory", factory)


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch_left_out"])
def test_a_broken_ftrl_step_makes_the_drain_incorrect(monkeypatch, kind):
    _break_ftrl_step(monkeypatch, kind)
    out = _run("ftrl-drain", seed=31)
    assert out["correct"] is False
    bad = {c["name"] for c in out["compared"] if c["value"] > c["limit"]}
    assert "w_worst_gap" in bad, out["compared"]
    if kind == "state_unchanged":
        got = {c["name"]: c["value"] for c in out["compared"]}
        assert got["dw_norm_gap"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", ["serve-steady", "serve-flood"])
def test_an_altered_answer_makes_serving_incorrect(monkeypatch, cell):
    from alink_tpu.operator.common.linear.mapper import LinearModelMapper
    real = LinearModelMapper._finish

    def altered(self, scores, data):
        scores = np.array(scores, copy=True)
        scores[::2] += 0.01            # every other margin, where produced
        return real(self, scores, data)

    monkeypatch.setattr(LinearModelMapper, "_finish", altered)
    out = _run(cell, seed=32)
    assert out["correct"] is False
    bad = {c["name"] for c in out["compared"] if c["value"] > c["limit"]}
    assert {"prob_gap", "prob_gap_before_swap"} <= bad, out["compared"]


# -- the command itself ---------------------------------------------------------

def test_a_parked_cell_is_not_in_the_drivers_benchmark():
    from benchmark.run import load_cell
    with pytest.raises(SystemExit, match="no workload"):
        load_cell("serve-steady")
    assert load_cell("serve-steady", parked=True)["cell"]["chips"] == 1


def _command(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if not k.startswith("XLA_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=cwd, BENCH_RUN="7")
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "ftrl-drain",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_off_a_tpu_the_command_exits_nonzero_with_no_result_line():
    res = _command(ROOT, "--tiny", "1")
    assert res.returncode != 0
    assert res.stdout.strip() == "", res.stdout[-500:]
    assert "TPU" in res.stderr


def test_alone_with_benchmark_json_the_command_exits_nonzero(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _command(str(tmp_path))
    assert res.returncode != 0 and res.stdout.strip() == ""
