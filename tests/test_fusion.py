"""Trace-time collective fusion (ALINK_TPU_FUSE_COLLECTIVES) + measured
multi-device mesh plumbing — ISSUE 9.

Covers:
  * deferred-reduction accumulator semantics (single-payload passthrough,
    multi-payload flatten/offset-slice, pmin-on-the-max-lane negation,
    fused-group manifest records);
  * engine integration: compiled all-reduce counts actually DROP
    (Newton 2 -> 1 per superstep, ALS normal equations 3 -> 1 per side,
    FM 2 -> 1) while training results stay bitwise-identical for
    logreg/kmeans/ALS/FTRL; dependency-forced programs (L-BFGS line
    search) provably keep their collectives;
  * flag-off lowered HLO byte-identity + program-cache key fold +
    checkpoint-signature fold;
  * fusion observability: alink_collective_fused_total /
    alink_collective_payload_fused_bytes + manifest membership, surfaced
    in tools/run_report.py;
  * io/sharding partition rules (match_partition_rules / state_sharding /
    device_put_state) and the ALINK_TPU_MESH_DEVICES session flag.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from alink_tpu.common.compat import shard_map
from alink_tpu.common.mlenv import MLEnvironment, MLEnvironmentFactory
from alink_tpu.engine import communication as comm
from alink_tpu.engine.comqueue import clear_program_cache, program_cache_stats
from alink_tpu.engine.recovery import program_signature


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("d",))


def _count_allreduce(hlo: str) -> int:
    return hlo.count("all-reduce(") + hlo.count("all-reduce-start(")


def _count_allgather(hlo: str) -> int:
    return hlo.count("all-gather(") + hlo.count("all-gather-start(")


@pytest.fixture
def fused_env(monkeypatch):
    """Arm the fusion flag for one test and isolate the program cache."""
    monkeypatch.setenv("ALINK_TPU_FUSE_COLLECTIVES", "1")
    clear_program_cache()
    yield
    clear_program_cache()


def _with_flag(monkeypatch, value):
    if value is None:
        monkeypatch.delenv("ALINK_TPU_FUSE_COLLECTIVES", raising=False)
    else:
        monkeypatch.setenv("ALINK_TPU_FUSE_COLLECTIVES", value)
    clear_program_cache()


# ---------------------------------------------------------------------------
# accumulator unit semantics
# ---------------------------------------------------------------------------

class TestDeferredAccumulator:
    def test_two_psums_fuse_to_one_op_bitwise(self):
        mesh = _mesh()

        def unfused(a, b):
            return jax.lax.psum(a, "d"), jax.lax.psum(b, "d")

        def fused(a, b):
            with comm.fusing(True):
                x = comm.manifest_psum(a, "d", name="a", num_workers=4)
                y = comm.manifest_psum(b, "d", name="b", num_workers=4)
                return jnp.asarray(x), jnp.asarray(y)

        specs = dict(mesh=mesh, in_specs=(P("d"), P("d")),
                     out_specs=(P(), P()), check_vma=False)
        f0 = jax.jit(shard_map(unfused, **specs))
        f1 = jax.jit(shard_map(fused, **specs))
        r = np.random.RandomState(0)
        a = r.randn(8, 3).astype(np.float32)
        b = r.randn(8, 5).astype(np.float32)
        for u, v in zip(f0(a, b), f1(a, b)):
            assert (np.asarray(u) == np.asarray(v)).all()
        h0 = f0.lower(a, b).compile().as_text()
        h1 = f1.lower(a, b).compile().as_text()
        assert _count_allreduce(h0) == 2
        assert _count_allreduce(h1) == 1

    def test_single_payload_passthrough_is_plain_psum(self):
        """A 1-member lane lowers the ORIGINAL payload through the raw op
        — same compiled collective set as the eager wrapper."""
        mesh = _mesh()

        def one(a, armed):
            if armed:
                with comm.fusing(True):
                    return jnp.asarray(
                        comm.manifest_psum(a, "d", name="x", num_workers=4))
            return comm.manifest_psum(a, "d", name="x", num_workers=4)

        specs = dict(mesh=mesh, in_specs=(P("d"),), out_specs=P(),
                     check_vma=False)
        a = np.ones((8, 3), np.float32)
        h0 = jax.jit(shard_map(lambda a: one(a, False), **specs)).lower(
            a).compile().as_text()
        h1 = jax.jit(shard_map(lambda a: one(a, True), **specs)).lower(
            a).compile().as_text()
        assert _count_allreduce(h0) == _count_allreduce(h1) == 1

    def test_pmin_rides_max_lane_negated_bitwise(self):
        mesh = _mesh()

        def unfused(a, b):
            return (comm.manifest_pmax(a, "d", name="mx", num_workers=4),
                    comm.manifest_pmin(b, "d", name="mn", num_workers=4))

        def fused(a, b):
            with comm.fusing(True):
                mx = comm.manifest_pmax(a, "d", name="mx", num_workers=4)
                mn = comm.manifest_pmin(b, "d", name="mn", num_workers=4)
                return jnp.asarray(mx), jnp.asarray(mn)

        specs = dict(mesh=mesh, in_specs=(P("d"), P("d")),
                     out_specs=(P(), P()), check_vma=False)
        f0 = jax.jit(shard_map(unfused, **specs))
        f1 = jax.jit(shard_map(fused, **specs))
        r = np.random.RandomState(1)
        a = r.randn(8, 4).astype(np.float64)
        b = r.randn(8, 4).astype(np.float64)
        for u, v in zip(f0(a, b), f1(a, b)):
            assert (np.asarray(u) == np.asarray(v)).all()
        assert _count_allreduce(f1.lower(a, b).compile().as_text()) == 1

    def test_gather_pair_fuses_bitwise(self):
        mesh = _mesh()

        def fused(a, b):
            with comm.fusing(True):
                ga = comm.manifest_all_gather(a, "d", name="ga",
                                              num_workers=4)
                gb = comm.manifest_all_gather(b, "d", name="gb",
                                              num_workers=4)
                return jnp.asarray(ga), jnp.asarray(gb)

        def unfused(a, b):
            return (comm.manifest_all_gather(a, "d", name="ga",
                                             num_workers=4),
                    comm.manifest_all_gather(b, "d", name="gb",
                                             num_workers=4))

        specs = dict(mesh=mesh, in_specs=(P("d"), P("d")),
                     out_specs=(P(), P()), check_vma=False)
        f0 = jax.jit(shard_map(unfused, **specs))
        f1 = jax.jit(shard_map(fused, **specs))
        r = np.random.RandomState(2)
        a = r.randn(8, 3).astype(np.float32)
        b = r.randn(8, 2).astype(np.float32)
        for u, v in zip(f0(a, b), f1(a, b)):
            assert (np.asarray(u) == np.asarray(v)).all()
        assert _count_allgather(f1.lower(a, b).compile().as_text()) == 1
        assert _count_allgather(f0.lower(a, b).compile().as_text()) == 2

    def test_dependent_psums_flush_separately(self):
        """A psum whose input uses an earlier psum's OUTPUT cannot fuse
        with it — the flush-on-use rule is the dependency proof."""
        mesh = _mesh()

        def dep(a):
            with comm.fusing(True):
                s = comm.manifest_psum(a, "d", name="s", num_workers=4)
                s2 = comm.manifest_psum(jnp.asarray(s) * 2, "d", name="s2",
                                        num_workers=4)
                return jnp.asarray(s2)

        f = jax.jit(shard_map(dep, mesh=mesh, in_specs=(P("d"),),
                              out_specs=P(), check_vma=False))
        a = np.ones((8, 3), np.float32)
        assert _count_allreduce(f.lower(a).compile().as_text()) == 2
        # s = psum(ones) = 4 per element; s2 = psum(4 * 2) = 32
        assert (np.asarray(f(a)) == 32.0).all()

    def test_fused_record_carries_membership(self):
        mesh = _mesh()
        manifest = []

        def fn(a, b):
            with comm.collecting(manifest):
                with comm.fusing(True):
                    x = comm.manifest_psum(a, "d", name="glw",
                                           num_workers=4)
                    y = comm.manifest_psum(b, "d", name="H", num_workers=4)
                    return jnp.asarray(x), jnp.asarray(y)

        jax.jit(shard_map(fn, mesh=mesh, in_specs=(P("d"), P("d")),
                          out_specs=(P(), P()), check_vma=False)).lower(
            np.ones((8, 2), np.float32), np.ones((8, 3), np.float32))
        fused = [rec for rec in manifest if len(rec) > 3]
        assert len(fused) == 1
        kind, name, nbytes, members = fused[0]
        assert kind == "AllReduce"
        assert members == ("glw", "H")
        assert "fused(glw+H)" == name
        # per-worker shard bytes (2,2)+(2,3) f32 = 40, x 4 workers logical
        assert nbytes == 40 * 4

    def test_record_manifest_charges_fused_metrics(self):
        from alink_tpu.common.metrics import get_registry
        reg = get_registry()
        base = reg.value("alink_collective_fused_total",
                         {"collective": "AllReduce"})
        comm.record_manifest(
            [("AllReduce", "fused(a+b)", 128, ("a", "b")),
             ("AllReduce", "solo", 64)], times=3)
        assert reg.value("alink_collective_fused_total",
                         {"collective": "AllReduce"}) == base + 3
        assert reg.value("alink_collective_payload_fused_bytes",
                         {"collective": "AllReduce"}) >= 3 * 128


# ---------------------------------------------------------------------------
# engine integration: real trainers fused vs unfused
# ---------------------------------------------------------------------------

def _newton_artifacts(env):
    import alink_tpu.operator.common.optim.optimizers as O
    from alink_tpu.operator.common.optim.objfunc import (LogLossFunc,
                                                         UnaryLossObjFunc)
    r = np.random.RandomState(0)
    n, d = 48, 5
    X = r.randn(n, d)
    y = np.where(X[:, 0] > 0, 1.0, -1.0)
    data = {"X": X, "y": y, "w": np.ones(n)}

    def run():
        obj = UnaryLossObjFunc(LogLossFunc(), d, l2=1e-3)
        return O.optimize(obj, data, O.OptimParams(
            method="Newton", max_iter=3, epsilon=0.0), env)[0]

    def hlo():
        import alink_tpu.engine.comqueue as cq
        cap = {}
        orig = cq.IterativeComQueue.exec

        def spy(q):
            cap["hlo"] = q.lowered().compile().as_text()
            raise _Stop()
        cq.IterativeComQueue.exec = spy
        try:
            run()
        except _Stop:
            pass
        finally:
            cq.IterativeComQueue.exec = orig
        return cap["hlo"]

    return run, hlo


class _Stop(Exception):
    pass


class TestEngineFusion:
    def test_newton_two_to_one_bitwise(self, monkeypatch):
        env = MLEnvironmentFactory.get_default()
        run, hlo = _newton_artifacts(env)
        _with_flag(monkeypatch, None)
        h0, c0 = hlo(), run()
        _with_flag(monkeypatch, "1")
        h1, c1 = hlo(), run()
        # module = init-pass + loop-body copies: 2/superstep -> 1
        assert _count_allreduce(h0) == 4
        assert _count_allreduce(h1) == 2
        assert (np.asarray(c0) == np.asarray(c1)).all()

    def test_lbfgs_line_search_is_dependency_forced(self, monkeypatch):
        """L-BFGS's 2 all-reduces per superstep are separated by real
        data flow (the line-loss psum needs the direction built from the
        psummed gradient): fusion must NOT change the count, and results
        stay bitwise-identical."""
        import alink_tpu.operator.common.optim.optimizers as O
        import alink_tpu.engine.comqueue as cq
        from alink_tpu.operator.common.optim.objfunc import (
            LogLossFunc, UnaryLossObjFunc)
        env = MLEnvironmentFactory.get_default()
        r = np.random.RandomState(0)
        n, d = 48, 4
        data = {"X": r.randn(n, d),
                "y": np.where(r.randn(n) > 0, 1.0, -1.0),
                "w": np.ones(n)}

        def run():
            obj = UnaryLossObjFunc(LogLossFunc(), d, l2=1e-3)
            return O.optimize(obj, data, O.OptimParams(
                method="LBFGS", max_iter=3, epsilon=0.0), env)[0]

        def hlo():
            cap = {}
            orig = cq.IterativeComQueue.exec

            def spy(q):
                cap["hlo"] = q.lowered().compile().as_text()
                raise _Stop()
            cq.IterativeComQueue.exec = spy
            try:
                run()
            except _Stop:
                pass
            finally:
                cq.IterativeComQueue.exec = orig
            return cap["hlo"]

        _with_flag(monkeypatch, None)
        h0, c0 = hlo(), run()
        _with_flag(monkeypatch, "1")
        h1, c1 = hlo(), run()
        assert _count_allreduce(h0) == _count_allreduce(h1) == 4
        assert (np.asarray(c0) == np.asarray(c1)).all()

    def test_als_three_to_one_bitwise(self, monkeypatch):
        from alink_tpu.operator.common.recommendation import als as A
        import alink_tpu.engine.comqueue as cq
        env = MLEnvironmentFactory.get_default()
        r = np.random.RandomState(0)
        users = r.randint(0, 24, 300)
        items = r.randint(0, 16, 300)
        ratings = (r.rand(300) * 5).astype(np.float32)
        params = A.AlsTrainParams(rank=3, num_iter=3, lambda_reg=0.1)

        def run():
            return A.als_train(users, items, ratings, params, env=env)

        def hlo():
            cap = {}
            orig = cq.IterativeComQueue.exec

            def spy(q):
                cap["hlo"] = q.lowered().compile().as_text()
                raise _Stop()
            cq.IterativeComQueue.exec = spy
            try:
                run()
            except _Stop:
                pass
            finally:
                cq.IterativeComQueue.exec = orig
            return cap["hlo"]

        _with_flag(monkeypatch, None)
        h0 = hlo()
        r0 = run()
        _with_flag(monkeypatch, "1")
        h1 = hlo()
        r1 = run()
        n0, n1 = _count_allreduce(h0), _count_allreduce(h1)
        # per superstep: two half-sweeps x (A, b, cnt) + rmse = 7 psums
        # unfused; each half-sweep's normal equations fuse 3 -> 1, the
        # rmse psum is dependency-separated -> 3 (x2 module copies)
        assert n0 == 14, n0
        assert n1 == 6, n1
        assert (np.asarray(r0[0]) == np.asarray(r1[0])).all()
        assert (np.asarray(r0[1]) == np.asarray(r1[1])).all()

    def test_kmeans_and_quantile_bitwise(self, monkeypatch):
        from alink_tpu.operator.common.clustering.kmeans import kmeans_train
        from alink_tpu.operator.common.dataproc.quantile import (
            distributed_quantiles)
        env = MLEnvironmentFactory.get_default()
        r = np.random.RandomState(0)
        Xk = r.randn(64, 3)
        Xq = r.randn(128, 3)
        probs = np.array([0.25, 0.5, 0.75])
        _with_flag(monkeypatch, None)
        k0 = np.asarray(kmeans_train(Xk, k=3, max_iter=4, env=env)[0])
        q0 = distributed_quantiles(Xq, probs, env=env)
        _with_flag(monkeypatch, "1")
        k1 = np.asarray(kmeans_train(Xk, k=3, max_iter=4, env=env)[0])
        q1 = distributed_quantiles(Xq, probs, env=env)
        assert (k0 == k1).all()
        assert (q0 == q1).all()

    def test_ftrl_staleness_step_bitwise_across_flag(self, monkeypatch):
        """FTRL margin psums are dependency-forced singles: the compiled
        step program is byte-identical under the flag, so (z, n) match
        bitwise."""
        from alink_tpu.operator.stream.onlinelearning.ftrl import (
            _ftrl_sparse_staleness_step_factory)
        mesh = Mesh(np.array(jax.devices()), ("d",))
        r = np.random.RandomState(0)
        dim = 64
        idx = r.randint(0, dim, (32, 6)).astype(np.int32)
        val = r.rand(32, 6)
        y = r.randint(0, 2, 32).astype(np.float64)
        z0 = np.zeros(dim)
        n0 = np.zeros(dim)

        def run():
            step = _ftrl_sparse_staleness_step_factory(
                mesh, 0.1, 1.0, 1e-3, 1e-3, 8)
            z, n, m = step(idx, val, y, jnp.asarray(z0), jnp.asarray(n0))
            return np.asarray(z), np.asarray(n), np.asarray(m)

        _with_flag(monkeypatch, None)
        z_a, n_a, m_a = run()
        _with_flag(monkeypatch, "1")
        z_b, n_b, m_b = run()
        assert (z_a == z_b).all() and (n_a == n_b).all() \
            and (m_a == m_b).all()

    def test_flag_off_hlo_byte_identical(self, monkeypatch):
        """Unset vs explicit '0' lower byte-identically (the registry
        falsy contract)."""
        env = MLEnvironmentFactory.get_default()
        _, hlo = _newton_artifacts(env)
        texts = []
        for flag in (None, "0"):
            _with_flag(monkeypatch, flag)
            # ONE call line for both: the compiled text embeds the
            # source locations of its call stack, so two call lines
            # differ byte-wise whatever the flag (this passed only while
            # an earlier test had left the persistent compile cache
            # armed, which hands back the first executable for both)
            texts.append(hlo())
        assert texts[0] == texts[1]

    def test_flag_folds_into_program_cache_key(self, monkeypatch):
        env = MLEnvironmentFactory.get_default()
        run, _ = _newton_artifacts(env)
        _with_flag(monkeypatch, None)
        run()
        before = program_cache_stats()
        monkeypatch.setenv("ALINK_TPU_FUSE_COLLECTIVES", "1")  # NO cache
        run()                                                  # clear here
        after = program_cache_stats()
        assert after["misses"] == before["misses"] + 1, \
            "toggling ALINK_TPU_FUSE_COLLECTIVES must MISS, not serve a " \
            "structurally different cached program"

    def test_flag_folds_into_checkpoint_signature(self):
        kw = dict(num_workers=8, max_iter=4, seed=0,
                  part_sig=(("X", (4, 2), "float64"),), bcast_names=("b",),
                  stages_digest=("s",))
        off = program_signature(**kw)
        on = program_signature(fuse_collectives=True, **kw)
        assert "fuse_collectives" not in off       # old snapshots resume
        assert on["fuse_collectives"] is True
        assert off != on

    def test_fused_metrics_after_engine_exec(self, monkeypatch):
        from alink_tpu.common.metrics import get_registry
        env = MLEnvironmentFactory.get_default()
        run, _ = _newton_artifacts(env)
        reg = get_registry()
        base = reg.value("alink_collective_fused_total",
                         {"collective": "AllReduce"})
        _with_flag(monkeypatch, "1")
        run()
        assert reg.value("alink_collective_fused_total",
                         {"collective": "AllReduce"}) > base

    def test_run_report_renders_fused_column(self):
        from alink_tpu.common.metrics import MetricsRegistry
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "rr_fusion_test", os.path.join(
                os.path.dirname(__file__), "..", "tools", "run_report.py"))
        rr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(rr)
        reg = MetricsRegistry()
        lbl = {"collective": "AllReduce"}
        reg.inc("alink_collective_calls_total", 5, lbl)
        reg.inc("alink_collective_logical_bytes_total", 4096, lbl)
        reg.inc("alink_collective_fused_total", 2, lbl)
        reg.inc("alink_collective_payload_fused_bytes", 1024, lbl)
        text = rr.render(reg)
        assert "fused calls" in text
        assert "2 collectives were FUSED" in text


# ---------------------------------------------------------------------------
# partition rules + mesh flag (measured multi-device plumbing)
# ---------------------------------------------------------------------------

class TestPartitionRules:
    def test_match_rules_by_path(self):
        from alink_tpu.io.sharding import match_partition_rules
        tree = {"z": np.zeros(8), "n": np.zeros(8),
                "coef": np.zeros((4, 2)), "lr": np.float64(0.1)}
        specs = match_partition_rules(
            ((r"^(z|n)$", P("d")),), tree, default=P())
        assert specs["z"] == P("d") and specs["n"] == P("d")
        assert specs["coef"] == P()
        assert specs["lr"] == P()          # scalars never partition

    def test_unmatched_leaf_raises_without_default(self):
        from alink_tpu.io.sharding import match_partition_rules
        with pytest.raises(ValueError, match="no rule matches"):
            match_partition_rules(((r"^z$", P("d")),),
                                  {"mystery": np.zeros(4)})

    def test_nested_paths_join_with_slash(self):
        from alink_tpu.io.sharding import match_partition_rules
        tree = {"emb": {"in": np.zeros((8, 2)), "out": np.zeros((8, 2))}}
        specs = match_partition_rules(
            ((r"^emb/in$", P("d")), (r".*", P())), tree)
        assert specs["emb"]["in"] == P("d")
        assert specs["emb"]["out"] == P()

    def test_device_put_state_places_on_mesh(self):
        from alink_tpu.io.sharding import device_put_state
        from alink_tpu.operator.stream.onlinelearning.ftrl import (
            ftrl_state_rules)
        mesh = Mesh(np.array(jax.devices()), ("d",))
        tree = {"z": np.zeros(16), "n": np.zeros(16)}
        placed = device_put_state(tree, mesh, ftrl_state_rules(),
                                  default=P())
        assert placed["z"].sharding.spec == P("d")
        assert placed["n"].sharding.spec == P("d")
        assert (np.asarray(placed["z"]) == 0).all()


class TestMeshDevicesFlag:
    def test_default_is_all_devices(self, monkeypatch):
        monkeypatch.delenv("ALINK_TPU_MESH_DEVICES", raising=False)
        env = MLEnvironment()
        assert env.num_workers == len(jax.devices())

    def test_flag_caps_device_count(self, monkeypatch):
        monkeypatch.setenv("ALINK_TPU_MESH_DEVICES", "4")
        env = MLEnvironment()
        assert env.num_workers == 4
        assert env.mesh.devices.size == 4

    def test_flag_beyond_available_raises(self, monkeypatch):
        monkeypatch.setenv("ALINK_TPU_MESH_DEVICES", "64")
        with pytest.raises(ValueError, match="ALINK_TPU_MESH_DEVICES"):
            MLEnvironment()

    def test_explicit_devices_bypass_flag(self, monkeypatch):
        monkeypatch.setenv("ALINK_TPU_MESH_DEVICES", "2")
        env = MLEnvironment(devices=jax.devices()[:3], parallelism=3)
        assert env.num_workers == 3
