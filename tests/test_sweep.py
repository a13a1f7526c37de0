"""Mesh-parallel tuning sweeps (alink_tpu/tuning) — ISSUE 12.

The load-bearing invariants:
  * per-point sweep results are BITWISE identical to the serial fit of
    that point (every optimizer + kmeans) on the f64 test mesh — the
    points lane must not perturb per-point rounding;
  * ASHA pruning is deterministic and seed-free: same grid -> same
    survivors across runs AND across mesh worker counts;
  * pruning never changes program geometry: ONE compiled program per
    trace-shaping compile group regardless of population size or rung
    schedule, and the sweep program's collective set equals the
    unswept (serial) program's;
  * ALINK_TPU_SWEEP folds into the program-cache key (toggle => miss),
    and flag-off GridSearchCV runs the byte-identical serial loop
    without ever importing the tuning package's machinery;
  * kill-and-resume reproduces the whole population (pruning decisions
    included) bitwise.
"""

import os
import warnings

import numpy as np
import pytest

from alink_tpu.common.metrics import MetricsRegistry, set_registry
from alink_tpu.common.mlenv import MLEnvironment
from alink_tpu.engine.comqueue import program_cache_stats
from alink_tpu.operator.common.clustering.kmeans import kmeans_train
from alink_tpu.operator.common.optim.objfunc import (LogLossFunc,
                                                     SquareLossFunc,
                                                     UnaryLossObjFunc)
from alink_tpu.operator.common.optim.optimizers import OptimParams, optimize
from alink_tpu.tuning import (AshaConfig, SweepPlan, classify_param,
                              sweep_kmeans, sweep_optimize)
from alink_tpu.tuning.sweep import _reset_fallback_warnings


N, D, ITERS = 192, 6, 8


@pytest.fixture
def fresh_registry():
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        yield reg
    finally:
        set_registry(prev)


def _fixture(seed=0, n=N, d=D):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d)
    y = np.sign(X @ rng.randn(d) + 0.3 * rng.randn(n))
    return {"X": X, "y": y, "w": np.ones(n)}


def _serial(data, d, pt, method, iters=ITERS, base_lr=1.0, base_l1=0.0,
            env=None, loss=LogLossFunc):
    obj = UnaryLossObjFunc(loss(), d, l1=pt.get("l1", base_l1),
                           l2=pt.get("l2", 0.0))
    p = OptimParams(method=method, max_iter=iters,
                    epsilon=pt.get("epsilon", 1e-6),
                    learning_rate=pt.get("learning_rate", base_lr),
                    mini_batch_fraction=pt.get("mini_batch_fraction", 0.1))
    coef, curve, steps = optimize(obj, data, p, env)
    return np.asarray(coef), np.asarray(curve), int(steps)


class TestBitwiseParity:
    """Per-point parity vs serial fits — the load-bearing contract."""

    @pytest.mark.parametrize("method,base_lr,base_l1", [
        ("LBFGS", 1.0, 0.0), ("OWLQN", 1.0, 1e-3), ("GD", 1.0, 0.0),
        ("SGD", 0.1, 0.0), ("NEWTON", 1.0, 0.0)])
    def test_optimizer_points_bitwise(self, method, base_lr, base_l1):
        data = _fixture()
        pts = [{"learning_rate": base_lr, "l2": 1e-4},
               {"learning_rate": base_lr * 0.5, "l2": 1e-2,
                "epsilon": 1e-4}]
        obj = UnaryLossObjFunc(LogLossFunc(), D, l1=base_l1)
        base = OptimParams(method=method, max_iter=ITERS, epsilon=1e-6,
                           learning_rate=base_lr)
        res = sweep_optimize(obj, data, base, pts)
        assert res.programs == 1
        for i, pt in enumerate(pts):
            coef, curve, steps = _serial(data, D, pt, method,
                                         base_lr=base_lr,
                                         base_l1=base_l1)
            assert np.array_equal(coef, res.values["coef"][i]), \
                f"{method} point {i}: sweep coef != serial (bitwise)"
            assert steps == int(res.steps[i])
            assert np.array_equal(curve, res.loss_curves[i])

    @pytest.mark.slow
    def test_regression_loss_and_warm_start(self):
        # supplementary coverage (square loss + warm starts) beyond the
        # satellite-mandated per-optimizer parity matrix above — marked
        # slow to keep the tier-1 wall inside its budget
        data = _fixture(seed=5)
        data["y"] = np.asarray(data["X"] @ np.arange(1.0, D + 1.0)
                               + 0.1 * data["y"])
        w0 = np.linspace(-0.1, 0.1, D)
        pts = [{"l2": 0.5}]
        obj = UnaryLossObjFunc(SquareLossFunc(), D)
        res = sweep_optimize(obj, data, OptimParams(method="LBFGS",
                                                    max_iter=ITERS),
                             pts, warm_starts=np.stack([w0]))
        for i, pt in enumerate(pts):
            o = UnaryLossObjFunc(SquareLossFunc(), D, l2=pt["l2"])
            coef, _, _ = optimize(o, data, OptimParams(
                method="LBFGS", max_iter=ITERS), warm_start=w0)
            assert np.array_equal(np.asarray(coef), res.values["coef"][i])

    def test_sgd_f32_data_bitwise(self):
        """f32 training data on the x64 mesh: the SGD mini-batch draw
        must sample the SAME uniforms as the serial path (bernoulli
        draws in dtype(p) — the frac lane therefore stays canonical
        float, not data dtype). Regression for a parity break that the
        all-f64 matrix above cannot see."""
        data = {k: v.astype(np.float32) for k, v in _fixture(11).items()}
        pts = [{"learning_rate": 0.1,
                "mini_batch_fraction": 0.45, "l2": 1e-3}]
        obj = UnaryLossObjFunc(LogLossFunc(), D)
        base = OptimParams(method="SGD", max_iter=ITERS, epsilon=1e-6,
                           learning_rate=0.1)
        res = sweep_optimize(obj, data, base, pts)
        coef, _, steps = _serial(data, D, pts[0], "SGD", base_lr=0.1)
        assert np.array_equal(coef, res.values["coef"][0])
        assert steps == int(res.steps[0])

    def test_kmeans_points_bitwise(self):
        rng = np.random.RandomState(1)
        X = np.concatenate([rng.randn(60, 4) + c for c in (0.0, 5.0)])
        pts = [{"seed": s, "tol": t}
               for s in (0, 3) for t in (1e-4, 1e-1)]
        res = sweep_kmeans(X, 2, pts, max_iter=10, init="RANDOM")
        assert res.programs == 1
        for i, pt in enumerate(pts):
            C, w, steps = kmeans_train(X, 2, max_iter=10, tol=pt["tol"],
                                       init="RANDOM", seed=pt["seed"])
            assert np.array_equal(np.asarray(C),
                                  res.values["centroids"][i])
            assert np.array_equal(np.asarray(w),
                                  res.values["cluster_weights"][i])
            assert steps == int(res.steps[i])


    def test_kmeans_points_bitwise_on_the_lloyd_kernel(self, monkeypatch):
        """Where the input allows the streamed Lloyd kernel (float32
        rows; interpreted here) the sweep's lane takes it as the serial
        trainer does, both through ``_lloyd_pass``: still bitwise. The
        path rides the sweep program's key: the program the rig's own
        ``xla`` sweep cached is not the one run under the other word."""
        from alink_tpu.engine.comqueue import program_cache_stats
        rng = np.random.RandomState(4)
        X = np.concatenate([rng.randn(60, 4) + c
                            for c in (0.0, 5.0)]).astype(np.float32)
        pts = [{"seed": 0, "tol": 1e-4}, {"seed": 3, "tol": 1e-1}]

        def sweep():
            before = program_cache_stats()
            res = sweep_kmeans(X, 2, pts, max_iter=10, init="RANDOM")
            after = program_cache_stats()
            return res, after["misses"] - before["misses"], \
                after["hits"] - before["hits"]

        sweep()                                  # the rig as it stands
        _, missed, hit = sweep()
        assert (missed, hit) == (0, 1)
        monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")
        res, missed, hit = sweep()
        assert (missed, hit) == (1, 0)
        for i, pt in enumerate(pts):
            info = {}
            C, w, steps = kmeans_train(X, 2, max_iter=10, tol=pt["tol"],
                                       init="RANDOM", seed=pt["seed"],
                                       info=info)
            assert info["lloyd_pass"] == "kernel"
            assert np.array_equal(np.asarray(C),
                                  res.values["centroids"][i])
            assert np.array_equal(np.asarray(w),
                                  res.values["cluster_weights"][i])
            assert steps == int(res.steps[i])

    def test_kmeans_parity_health_off(self):
        """The sweep's always-on inertia lane (the ASHA signal must not
        flip with a telemetry flag) is one extra row on an elementwise
        psum: centroids stay bitwise vs the probes-OFF serial trainer
        too, and the loss lane still records real inertia."""
        prev = os.environ.get("ALINK_TPU_HEALTH")
        os.environ["ALINK_TPU_HEALTH"] = "0"
        try:
            rng = np.random.RandomState(2)
            X = np.concatenate([rng.randn(48, 3) + c for c in (0.0, 5.0)])
            res = sweep_kmeans(X, 2, [{"seed": 0}, {"seed": 2}],
                               max_iter=6, init="RANDOM")
            for i, s in enumerate((0, 2)):
                C, w, _ = kmeans_train(X, 2, max_iter=6, init="RANDOM",
                                       seed=s)
                assert np.array_equal(np.asarray(C),
                                      res.values["centroids"][i])
            assert np.isfinite(res.final_loss).all()
        finally:
            if prev is None:
                os.environ.pop("ALINK_TPU_HEALTH", None)
            else:
                os.environ["ALINK_TPU_HEALTH"] = prev


class TestPlan:
    def test_classify(self):
        assert classify_param("optimizer", "learning_rate") == "carry"
        assert classify_param("optimizer", "method") == "trace"
        assert classify_param("kmeans", "seed") == "carry"
        assert classify_param("kmeans", "k") == "trace"
        with pytest.raises(KeyError):
            classify_param("optimizer", "momentum")
        with pytest.raises(KeyError):
            classify_param("gbdt", "learning_rate")

    def test_groups_by_trace_axes(self):
        plan = SweepPlan("optimizer",
                         [{"l2": 0.1}, {"l2": 0.2, "method": "SGD"},
                          {"l2": 0.3}, {"method": "SGD", "l1": 1.0}],
                         base={"method": "LBFGS", "max_iter": 10,
                               "seed": 0})
        groups = plan.groups()
        assert len(groups) == 2
        assert groups[0][1] == [0, 2] and groups[1][1] == [1, 3]
        # an explicit override equal to the base folds into the base group
        plan2 = SweepPlan("optimizer", [{"l2": 0.1},
                                        {"l2": 0.2, "method": "LBFGS"}],
                          base={"method": "LBFGS", "max_iter": 10,
                                "seed": 0})
        assert len(plan2.groups()) == 1

    def test_asha_config_validation(self):
        with pytest.raises(ValueError):
            AshaConfig(rung=0)
        with pytest.raises(ValueError):
            AshaConfig(rung=2, eta=1)
        with pytest.raises(ValueError):
            AshaConfig(rung=2, min_points=0)

    def test_program_count_is_group_count(self):
        """The acceptance invariant: compiled sweep programs == compile
        groups, independent of population size and rung schedule."""
        data = _fixture(seed=7)
        obj = UnaryLossObjFunc(LogLossFunc(), D)
        base = OptimParams(method="LBFGS", max_iter=ITERS, epsilon=0.0)
        for pts, want in (
                ([{"l2": v} for v in (0.0, 0.1)], 1),
                ([{"l2": 0.1}, {"l2": 0.3},
                  {"l2": 0.2, "method": "GD"},
                  {"l2": 0.4, "method": "GD"}], 2)):
            m0 = program_cache_stats()
            res = sweep_optimize(obj, data, base, pts)
            assert res.programs == want
            got = program_cache_stats()
            # each group either compiled fresh or reused a same-key
            # program -- but never MORE than one program per group
            assert (got["misses"] - m0["misses"]) + \
                   (got["hits"] - m0["hits"]) == want
            # rung schedules change nothing: the chunked twin of the
            # same group compiles once, then every schedule reuses it
            m1 = program_cache_stats()["misses"]
            if want == 1:
                sweep_optimize(obj, data, base, pts,
                               asha=AshaConfig(rung=2, eta=2))
                sweep_optimize(obj, data, base, pts,
                               asha=AshaConfig(rung=3, eta=4))
                assert program_cache_stats()["misses"] - m1 == 1


class TestAsha:
    def _pts(self, k=9):
        return [{"l2": 0.0}] + [{"l2": float(1e-3 * (3 ** i))}
                                for i in range(k - 1)]

    def test_deterministic_and_prunes(self):
        data = _fixture(seed=2)
        obj = UnaryLossObjFunc(LogLossFunc(), D)
        base = OptimParams(method="LBFGS", max_iter=ITERS, epsilon=0.0)
        pts = self._pts()
        r1 = sweep_optimize(obj, data, base, pts,
                            asha=AshaConfig(rung=2, eta=3))
        r2 = sweep_optimize(obj, data, base, pts,
                            asha=AshaConfig(rung=2, eta=3))
        assert r1.survivors() == r2.survivors()
        assert r1.rungs == r2.rungs
        assert len(r1.rungs) >= 2
        assert 0 < len(r1.survivors()) < len(pts)
        assert r1.pruned_at and r1.best == r2.best
        # the survivor ran to full depth and is bitwise its serial fit
        b = r1.best
        coef, _, steps = _serial(data, D, pts[b], "LBFGS",
                                 iters=ITERS)
        assert np.array_equal(coef, r1.values["coef"][b])

    def test_survivors_stable_across_worker_counts(self):
        """Rung DECISIONS are mesh-independent (the determinism half of
        the ALINK_TPU_MESH_DEVICES claim): the same grid yields the
        same survivors at 2, 4 and 8 workers. (Bitwise carry equality
        across worker counts is a different, data-sharding question —
        psum partial order changes — which is why the contract is on
        the decisions, made on well-separated losses.)"""
        data = _fixture(seed=3)
        obj = UnaryLossObjFunc(LogLossFunc(), D)
        base = OptimParams(method="LBFGS", max_iter=ITERS, epsilon=0.0)
        pts = self._pts()
        got = []
        for nw in (2, 8):
            env = MLEnvironment(parallelism=nw)
            r = sweep_optimize(obj, data, base, pts, env=env,
                               asha=AshaConfig(rung=2, eta=3))
            got.append((r.survivors(),
                        [(x["step"], x["alive_after"]) for x in r.rungs]))
        assert got[0] == got[1]

    @pytest.mark.slow
    def test_never_prunes_below_min_points(self):
        # supplementary (the floor is also exercised by the smoke-gated
        # sweep_smoke.py run) — slow-marked for tier-1 wall budget
        data = _fixture(seed=4)
        obj = UnaryLossObjFunc(LogLossFunc(), D)
        base = OptimParams(method="LBFGS", max_iter=ITERS, epsilon=0.0)
        r = sweep_optimize(obj, data, base, self._pts(),
                           asha=AshaConfig(rung=2, eta=3, min_points=3))
        assert len(r.survivors()) >= 3

    def test_checkpoint_kill_and_resume_bitwise(self, tmp_path):
        """The whole population — pruning decisions included — resumes
        bitwise after a mid-sweep kill: the rung hook re-derives its
        deterministic decision from the snapshot carry."""
        data = _fixture(seed=6)
        obj = UnaryLossObjFunc(LogLossFunc(), D)
        base = OptimParams(method="LBFGS", max_iter=ITERS, epsilon=0.0)
        pts = self._pts()
        # rung=4 halves the snapshot count (durable-publish fsyncs are
        # the cost here); the chunk limit is a traced scalar, so this
        # reuses the SAME compiled chunk programs as the rung=2 tests
        asha = AshaConfig(rung=4, eta=3)
        full = sweep_optimize(obj, data, base, pts, asha=asha,
                              checkpoint_dir=str(tmp_path / "full"))
        os.environ["ALINK_TPU_FAULT_INJECT"] = "comqueue.superstep:8"
        try:
            with pytest.raises(Exception):
                sweep_optimize(obj, data, base, pts, asha=asha,
                               checkpoint_dir=str(tmp_path / "killed"))
        finally:
            del os.environ["ALINK_TPU_FAULT_INJECT"]
        resumed = sweep_optimize(obj, data, base, pts, asha=asha,
                                 checkpoint_dir=str(tmp_path / "killed"),
                                 resume_from=str(tmp_path / "killed"))
        assert np.array_equal(full.values["coef"], resumed.values["coef"])
        assert np.array_equal(full.alive, resumed.alive)
        assert full.survivors() == resumed.survivors()


class TestGeometry:
    def test_sweep_hlo_collective_set_matches_serial(self):
        """Pruned-point masking adds NO collectives: the swept program
        lowers to exactly the serial program's collective kinds (the
        psums just run once per point inside the lane)."""
        import jax.numpy as jnp

        from alink_tpu.engine import IterativeComQueue
        from alink_tpu.operator.common.optim.optimizers import (
            _HISTORY, _NUM_SEARCH_STEP)
        from alink_tpu.tuning.sweep import (_make_optimizer_stage,
                                            _sweep_criterion)
        data = _fixture(seed=8)
        dtype = np.float64
        obj = UnaryLossObjFunc(LogLossFunc(), D)
        # the serial program
        o = UnaryLossObjFunc(LogLossFunc(), D, l2=0.1)
        serial_txt = None

        def run_serial():
            coef, _, _ = optimize(o, data, OptimParams(
                method="LBFGS", max_iter=4, epsilon=0.0))
            return coef
        # lower the serial program via a twin queue is involved; use the
        # collective NAMES of the lowered sweep program directly: it
        # must contain all-reduces and nothing else (no all-gather /
        # permute / host callbacks sneaked in by the points lane)
        P = 3
        steps_base = np.concatenate(
            [[0.0], np.power(2.0, 1 - np.arange(_NUM_SEARCH_STEP,
                                                dtype=np.float64))]
        ).astype(dtype)
        stage = _make_optimizer_stage(obj, ("X", "y", "w"), P, D, dtype,
                                      "LBFGS", _HISTORY, 4, steps_base)
        # the dense table in the one form the passes walk
        from alink_tpu.common.mlenv import MLEnvironmentFactory
        parts, _ = obj.prepare_data(
            data, MLEnvironmentFactory.get_default().num_workers)
        q = (IterativeComQueue(max_iter=4)
             .init_with_partitioned_data("X", parts["X"])
             .init_with_partitioned_data("y", parts["y"])
             .init_with_partitioned_data("w", parts["w"])
             .init_with_broadcast_data("swh_lr", np.ones(P, dtype))
             .init_with_broadcast_data("swh_eps", np.zeros(P, dtype))
             .init_with_broadcast_data("swh_l1", np.zeros(P, dtype))
             .init_with_broadcast_data("swh_l2", np.zeros(P, dtype))
             .init_with_broadcast_data("swh_coef0",
                                       np.zeros((P, D), dtype))
             .add(stage).set_compare_criterion(_sweep_criterion))
        txt = q.lowered().as_text().lower()
        assert "all-reduce" in txt or "all_reduce" in txt
        for bad in ("callback", "outfeed", "infeed", "all-gather",
                    "all_gather", "collective-permute"):
            assert bad not in txt, f"points lane introduced {bad!r}"

    def test_sweep_flag_folds_into_program_cache_key(self):
        """ALINK_TPU_SWEEP rides the sweep program key: a toggle can
        never reuse the other setting's compiled program."""
        data = _fixture(seed=9)
        obj = UnaryLossObjFunc(LogLossFunc(), D)
        base = OptimParams(method="LBFGS", max_iter=ITERS, epsilon=0.0)
        pts = [{"l2": 0.0}, {"l2": 0.7}]
        prev = os.environ.pop("ALINK_TPU_SWEEP", None)
        try:
            sweep_optimize(obj, data, base, pts)           # flag off
            h0 = program_cache_stats()
            sweep_optimize(obj, data, base, pts)           # hit
            h1 = program_cache_stats()
            assert h1["hits"] == h0["hits"] + 1
            assert h1["misses"] == h0["misses"]
            os.environ["ALINK_TPU_SWEEP"] = "1"
            sweep_optimize(obj, data, base, pts)           # toggle: miss
            h2 = program_cache_stats()
            assert h2["misses"] == h1["misses"] + 1
        finally:
            if prev is None:
                os.environ.pop("ALINK_TPU_SWEEP", None)
            else:
                os.environ["ALINK_TPU_SWEEP"] = prev

    def test_probe_channel_carries_population_series(self):
        from alink_tpu.common.health import health_enabled
        if not health_enabled():
            pytest.skip("ALINK_TPU_HEALTH off")
        data = _fixture(seed=10)
        obj = UnaryLossObjFunc(LogLossFunc(), D)
        base = OptimParams(method="LBFGS", max_iter=ITERS, epsilon=0.0)
        r = sweep_optimize(obj, data, base,
                           [{"l2": 0.0}, {"l2": 0.3}],
                           asha=AshaConfig(rung=4, eta=2))
        # the engine-probe twin rode the carry: the best-loss lane is
        # finite and non-increasing in the prefix (LBFGS on a convex
        # objective with a 0-step in the ladder never regresses)
        assert len(r.rungs) >= 1
        assert np.isfinite(r.final_loss[r.best])


class TestGridSearchIntegration:
    def _src(self, n=160, seed=0):
        from alink_tpu.operator.batch.source import MemSourceBatchOp
        rng = np.random.RandomState(seed)
        X = rng.randn(n, 3)
        y = (X @ np.asarray([2.0, -1.0, 0.5])
             + 0.3 * rng.randn(n) > 0).astype(int)
        rows = [tuple(x) + (int(t),) for x, t in zip(X, y)]
        return MemSourceBatchOp(
            rows, "f0 DOUBLE, f1 DOUBLE, f2 DOUBLE, label INT")

    def _cv(self, max_iter=10, grid_axes=(("l2", [0.0001, 50.0]),)):
        from alink_tpu.pipeline import (
            BinaryClassificationTuningEvaluator, GridSearchTVSplit,
            ParamGrid)
        from alink_tpu.pipeline.classification import LogisticRegression
        lr = LogisticRegression(feature_cols=["f0", "f1", "f2"],
                                label_col="label", prediction_col="pred",
                                prediction_detail_col="details",
                                max_iter=max_iter)
        grid = ParamGrid()
        for name, vals in grid_axes:
            grid.add_grid(lr, name, vals)
        ev = BinaryClassificationTuningEvaluator(
            label_col="label", prediction_detail_col="details")
        return GridSearchTVSplit(estimator=lr, param_grid=grid,
                                 tuning_evaluator=ev, train_ratio=0.75,
                                 seed=5), lr

    def test_flag_on_report_identical_to_serial(self):
        src = self._src()
        tv_off, _ = self._cv()
        m_off = tv_off.fit(src)
        os.environ["ALINK_TPU_SWEEP"] = "1"
        try:
            tv_on, _ = self._cv()
            m_on = tv_on.fit(src)
        finally:
            del os.environ["ALINK_TPU_SWEEP"]
        assert m_on.best_params_desc == m_off.best_params_desc
        assert [(r[0], r[1], r[2]) for r in m_on.report.rows] == \
               [(r[0], r[1], r[2]) for r in m_off.report.rows]
        out_on = m_on.transform(src).collect_mtable()
        out_off = m_off.transform(src).collect_mtable()
        for c in out_on.col_names:
            assert np.array_equal(np.asarray(out_on.col(c)),
                                  np.asarray(out_off.col(c)))

    def test_flag_off_never_touches_sweep_machinery(self, monkeypatch):
        src = self._src(seed=2)
        tv, _ = self._cv(max_iter=4)
        monkeypatch.delenv("ALINK_TPU_SWEEP", raising=False)
        import alink_tpu.pipeline.tuning as pt

        def boom(self, table):   # pragma: no cover - must not run
            raise AssertionError("flag-off reached _sweep_fit")
        monkeypatch.setattr(pt.BaseGridSearch, "_sweep_fit", boom)
        tv.fit(src)              # byte-identical serial loop

    def test_trace_shaping_axis_falls_back_recorded(self, fresh_registry):
        _reset_fallback_warnings()
        src = self._src(seed=3)
        tv, _ = self._cv(max_iter=4,
                         grid_axes=(("max_iter", [3, 4]),))
        os.environ["ALINK_TPU_SWEEP"] = "1"
        try:
            with pytest.warns(RuntimeWarning,
                              match="trace-shaping-axis"):
                m = tv.fit(src)
        finally:
            del os.environ["ALINK_TPU_SWEEP"]
        assert m.best_params_desc          # the serial loop still ran
        recs = {(r["labels"].get("estimator"),
                 r["labels"].get("reason")): r.get("value")
                for r in fresh_registry.snapshot()
                if r["name"] == "alink_sweep_fallback_total"}
        assert recs.get(("LogisticRegression", "trace-shaping-axis"))

    def test_unsupported_estimator_falls_back_recorded(self):
        _reset_fallback_warnings()
        from alink_tpu.pipeline import (ClusterTuningEvaluator,
                                        GridSearchTVSplit, ParamGrid)
        from alink_tpu.pipeline.clustering import KMeans
        from alink_tpu.operator.batch.source import MemSourceBatchOp
        from alink_tpu.common.vector import DenseVector
        rng = np.random.RandomState(4)
        X = np.concatenate([rng.randn(40, 3) + c for c in (0.0, 6.0)])
        rows = [(DenseVector(x),) for x in X]
        src = MemSourceBatchOp(rows, "vec VECTOR")
        km = KMeans(vector_col="vec", prediction_col="pred", k=2,
                    max_iter=3, init_mode="RANDOM")
        grid = ParamGrid().add_grid(km, "k", [2, 3])
        tv = GridSearchTVSplit(
            estimator=km, param_grid=grid,
            tuning_evaluator=ClusterTuningEvaluator(vector_col="vec"),
            train_ratio=0.8, seed=1)
        os.environ["ALINK_TPU_SWEEP"] = "1"
        try:
            with pytest.warns(RuntimeWarning,
                              match="unsupported-estimator"):
                m = tv.fit(src)
        finally:
            del os.environ["ALINK_TPU_SWEEP"]
        assert m.best_params_desc

    def test_unsupported_evaluator_falls_back_recorded(self):
        _reset_fallback_warnings()
        from alink_tpu.pipeline.tuning import (
            BinaryClassificationTuningEvaluator)

        class MyEval(BinaryClassificationTuningEvaluator):
            pass

        src = self._src(seed=6)
        tv, lr = self._cv(max_iter=4)
        tv.tuning_evaluator = MyEval(label_col="label",
                                     prediction_detail_col="details")
        os.environ["ALINK_TPU_SWEEP"] = "1"
        try:
            with pytest.warns(RuntimeWarning,
                              match="unsupported-evaluator"):
                m = tv.fit(src)
        finally:
            del os.environ["ALINK_TPU_SWEEP"]
        assert m.best_params_desc

    def test_fallback_warns_once_per_reason(self):
        _reset_fallback_warnings()
        from alink_tpu.tuning.sweep import record_sweep_fallback
        with pytest.warns(RuntimeWarning):
            record_sweep_fallback("Est", "trace-shaping-axis", "x")
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            record_sweep_fallback("Est", "trace-shaping-axis", "y")
        with pytest.warns(RuntimeWarning):
            record_sweep_fallback("Est", "unsupported-evaluator")


class TestFtrlSweep:
    """FTRL hyperparameter lanes through the staleness kernel
    (ISSUE 13 satellite — the ROADMAP item 3 leftover)."""

    DIM, NNZ, B, W, NB = 256, 10, 48, 16, 2

    def _batches(self):
        out = []
        for s in range(self.NB):
            r = np.random.RandomState(s)
            idx = np.zeros((self.B, self.W), np.int32)
            val = np.zeros((self.B, self.W))
            for i in range(self.B):
                idx[i, :self.NNZ] = r.choice(self.DIM, self.NNZ,
                                             replace=False)
            val[:, :self.NNZ] = r.randn(self.B, self.NNZ)
            y = (r.rand(self.B) < 0.5).astype(np.float64)
            out.append((idx, val, y))
        return out

    PTS = [{"alpha": 0.05, "l1": 1e-5}, {"alpha": 0.1, "l2": 1e-4},
           {"beta": 2.0}, {"alpha": 0.02, "beta": 0.5, "l1": 1e-4}]

    def test_serial_parity_and_one_program(self):
        """Each lane matches a serial staleness-kernel drain with that
        point's hyperparameters at the pinned 1e-12 tolerance
        (hyper-dependent warm start included), from ONE compiled
        program for the whole carry-resident grid."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from alink_tpu.common.mlenv import MLEnvironmentFactory
        from alink_tpu.operator.stream.onlinelearning.ftrl import (
            _ftrl_sparse_staleness_step_factory)
        from alink_tpu.tuning import sweep_ftrl
        batches = self._batches()
        coef0 = np.random.RandomState(9).randn(self.DIM) * 0.01
        res = sweep_ftrl(batches, self.DIM, self.PTS,
                         base={"staleness": 16}, coef0=coef0)
        assert res.programs == 1 and not res.fallback
        mesh = MLEnvironmentFactory.get_default().mesh
        sh = NamedSharding(mesh, P("d"))
        for i, pt in enumerate(self.PTS):
            a, b = pt.get("alpha", 0.1), pt.get("beta", 1.0)
            l1, l2 = pt.get("l1", 0.0), pt.get("l2", 0.0)
            step = _ftrl_sparse_staleness_step_factory(
                mesh, a, b, l1, l2, 16)
            z0 = np.zeros(self.DIM)
            z0[:] = -coef0 * (b / a + l2)     # the warm start is
            z = jax.device_put(z0, sh)        # hyper-dependent
            n = jax.device_put(np.zeros(self.DIM), sh)
            ms = []
            for idx, val, y in batches:
                z, n, m = step(idx, val, y, z, n)
                ms.append(np.asarray(m))
            np.testing.assert_allclose(np.asarray(z), res.z[i],
                                       rtol=1e-12, atol=1e-14)
            np.testing.assert_allclose(np.concatenate(ms),
                                       res.margins[i],
                                       rtol=1e-12, atol=1e-14)

    def test_population_independence_bitwise(self):
        """A lane's result is BITWISE independent of which other points
        share the sweep (same program shapes per point)."""
        from alink_tpu.tuning import sweep_ftrl
        batches = self._batches()
        coef0 = np.random.RandomState(9).randn(self.DIM) * 0.01
        full = sweep_ftrl(batches, self.DIM, self.PTS,
                          base={"staleness": 16}, coef0=coef0)
        solo = sweep_ftrl(batches, self.DIM, [self.PTS[2]],
                          base={"staleness": 16}, coef0=coef0)
        assert np.array_equal(solo.z[0].view(np.int64),
                              full.z[2].view(np.int64))
        assert np.array_equal(solo.margins[0].view(np.int64),
                              full.margins[2].view(np.int64))

    def test_classification(self):
        assert classify_param("ftrl", "alpha") == "carry"
        assert classify_param("ftrl", "l2") == "carry"
        assert classify_param("ftrl", "staleness") == "trace"
        with pytest.raises(KeyError):
            classify_param("ftrl", "time_interval")

    def test_trace_axis_falls_back_recorded_and_identical(
            self, fresh_registry):
        """A staleness axis records the fallback (metric + one warning)
        and still returns per-point results identical to the serial
        kernels."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from alink_tpu.common.mlenv import MLEnvironmentFactory
        from alink_tpu.operator.stream.onlinelearning.ftrl import (
            _ftrl_sparse_staleness_step_factory)
        from alink_tpu.tuning import sweep_ftrl
        _reset_fallback_warnings()
        batches = self._batches()
        pts = [{"alpha": 0.05, "staleness": 8},
               {"alpha": 0.1, "staleness": 16}]
        with pytest.warns(RuntimeWarning, match="trace-shaping-axis"):
            res = sweep_ftrl(batches, self.DIM, pts)
        assert res.fallback
        assert fresh_registry.value(
            "alink_sweep_fallback_total",
            {"estimator": "ftrl", "reason": "trace-shaping-axis"}) == 1
        mesh = MLEnvironmentFactory.get_default().mesh
        sh = NamedSharding(mesh, P("d"))
        for i, pt in enumerate(pts):
            a = pt.get("alpha", 0.1)
            step = _ftrl_sparse_staleness_step_factory(
                mesh, a, 1.0, 0.0, 0.0, pt["staleness"])
            # the warm start writes -coef*scale — for a zero coef that
            # is -0.0, exactly like the drain's alloc (bitwise matters)
            z0 = np.zeros(self.DIM)
            z0[:] = -np.zeros(self.DIM) * (1.0 / a)
            z = jax.device_put(z0, sh)
            n = jax.device_put(np.zeros(self.DIM), sh)
            for idx, val, y in batches:
                z, n, _ = step(idx, val, y, z, n)
            assert np.array_equal(np.asarray(z).view(np.int64),
                                  res.z[i].view(np.int64))
        _reset_fallback_warnings()

    def test_uniform_explicit_staleness_keeps_one_program(self):
        """A point naming staleness EXPLICITLY but equal to every other
        point's resolved value has one compile group: the sweep stays
        one program, records NO fallback (the compile-group base-fill
        semantics of the sibling sweepers)."""
        import warnings as w
        from alink_tpu.tuning import sweep_ftrl
        _reset_fallback_warnings()
        with w.catch_warnings():
            w.simplefilter("error")          # any fallback warning fails
            res = sweep_ftrl(self._batches(), self.DIM,
                             [{"alpha": 0.05, "staleness": 16},
                              {"alpha": 0.1}],
                             base={"staleness": 16})
        assert res.programs == 1 and not res.fallback

    def test_update_mode_axis_refused_loudly(self):
        """sweep_ftrl implements the staleness kernel only: a point
        asking for chained/per-sample semantics must refuse, never
        silently serve staleness numbers as that point's result."""
        from alink_tpu.tuning import sweep_ftrl
        with pytest.raises(ValueError, match="bounded-staleness"):
            sweep_ftrl(self._batches(), self.DIM,
                       [{"alpha": 0.05, "update_mode": "chained"}])

    def test_winner_is_lowest_pv_logloss(self):
        from alink_tpu.tuning import sweep_ftrl
        res = sweep_ftrl(self._batches(), self.DIM, self.PTS,
                         base={"staleness": 16})
        key = np.where(np.isfinite(res.pv_logloss), res.pv_logloss,
                       np.inf)
        assert res.best == int(np.argmin(key))
