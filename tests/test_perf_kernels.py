"""ISSUE 6 (perf_opt) kernel/measurement contracts.

Tentpole (a) — chained-correction strict FTRL (`update_mode="chained"`):
  * bitwise equal to the per-sample strict scan program (staleness K=1)
    on collision-free chunks;
  * documented-tolerance equal on colliding chunks (association-only
    rounding: fl(base + fl(d1 + d2)) vs fl(fl(base + d1) + d2));
  * the chunk length rides the factory/jit cache key and the
    checkpoint signature (chained mode only).

Tentpole (b) — fused tree-histogram kernel (`ALINK_TPU_FUSED_HIST`):
  * numeric parity of the "xla" and "pallas" formulations with the
    default kernel;
  * flag OFF lowers byte-identically to pre-flag programs;
  * the collective set (one psum per level) is identical in every mode;
  * the mode is folded into the engine program-cache key.

Tentpole (c) — pinned compiled baseline:
  * the native single-slot loop matches the interpreted per-sample loop;
  * the pin is measured once and REUSED (no re-measure) on the same rig;
  * `bench_compare --baseline-provenance` refuses cross-fingerprint
    diffs.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))


# ---------------------------------------------------------------------------
# fixtures (shared shapes with tests/test_stream.py)
# ---------------------------------------------------------------------------

def _mesh():
    from alink_tpu.common.mlenv import MLEnvironmentFactory
    return MLEnvironmentFactory.get_default().mesh


def _coo_batch(B, dim, nnz, width, seed, disjoint=False, chunk=8):
    """Padded COO batch; ``disjoint=True`` gives every row inside each
    ``chunk``-row window its own contiguous feature block (collision-free
    chunks)."""
    rng = np.random.RandomState(seed)
    idx = np.zeros((B, width), np.int32)
    val = np.zeros((B, width))
    if disjoint:
        block = dim // chunk
        for i in range(B):
            base = (i % chunk) * block
            idx[i, :nnz] = np.sort(
                rng.choice(block, nnz, replace=False)) + base
    else:
        for i in range(B):
            idx[i, :nnz] = rng.choice(dim, nnz, replace=False)
    val[:, :nnz] = rng.randn(B, nnz)
    y = (rng.rand(B) < 0.5).astype(np.float64)
    return idx, val, y


def _state(dim, seed=3):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    rng = np.random.RandomState(seed)
    shard = NamedSharding(_mesh(), P("d"))
    return (jax.device_put(rng.randn(dim) * 0.1, shard),
            jax.device_put(np.abs(rng.randn(dim)) * 0.1, shard))


# ---------------------------------------------------------------------------
# (a) chained-correction strict FTRL
# ---------------------------------------------------------------------------

class TestChainedCorrection:
    def test_bitwise_parity_on_collision_free_chunks(self):
        """Collision-free chunks: every correction matvec adds an exact
        0.0, so the chained kernel is BIT-IDENTICAL to the per-sample
        strict scan program (the staleness factory at K=1, which
        degenerates to per-sample — test_ftrl_staleness_one_equals_strict
        pins that identity)."""
        from alink_tpu.operator.stream.onlinelearning.ftrl import (
            _ftrl_sparse_chained_step_factory,
            _ftrl_sparse_staleness_step_factory)
        dim, nnz, B, w, K = 256, 4, 64, 8, 8
        idx, val, y = _coo_batch(B, dim, nnz, w, seed=7, disjoint=True,
                                 chunk=K)
        z0, n0 = _state(dim)
        strict = _ftrl_sparse_staleness_step_factory(
            _mesh(), 0.05, 1.0, 1e-5, 1e-5, K=1)
        chain = _ftrl_sparse_chained_step_factory(
            _mesh(), 0.05, 1.0, 1e-5, 1e-5, K=K)
        zs, ns, ms = strict(idx, val, y, z0, n0)
        zc, nc, mc = chain(idx, val, y, z0, n0)
        assert (np.asarray(zc) == np.asarray(zs)).all()
        assert (np.asarray(nc) == np.asarray(ns)).all()
        assert (np.asarray(mc) == np.asarray(ms)).all()

    def test_tolerance_parity_on_colliding_chunks(self):
        """Colliding chunks differ only in fp ASSOCIATION (the chunk sums
        deltas before adding the base). Documented tolerance: rtol 1e-12
        on the f64 test mesh (f32 production: ~1e-4 on trajectories)."""
        from alink_tpu.operator.stream.onlinelearning.ftrl import (
            _ftrl_sparse_chained_step_factory,
            _ftrl_sparse_staleness_step_factory)
        dim, nnz, B, w = 64, 6, 128, 8      # dense collisions: 128*6 >> 64
        idx, val, y = _coo_batch(B, dim, nnz, w, seed=11)
        z0, n0 = _state(dim)
        strict = _ftrl_sparse_staleness_step_factory(
            _mesh(), 0.05, 1.0, 1e-5, 1e-5, K=1)
        chain = _ftrl_sparse_chained_step_factory(
            _mesh(), 0.05, 1.0, 1e-5, 1e-5, K=16)
        zs, ns, ms = strict(idx, val, y, z0, n0)
        zc, nc, mc = chain(idx, val, y, z0, n0)
        np.testing.assert_allclose(np.asarray(zc), np.asarray(zs),
                                   rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(nc), np.asarray(ns),
                                   rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(mc), np.asarray(ms),
                                   rtol=1e-9, atol=1e-12)

    def test_stream_op_chained_mode(self):
        """update_mode="chained" through the production stream op: equal
        to the per-sample scan within the documented tolerance, bitwise
        vs the staleness-1 program on disjoint chunks."""
        from test_stream import (_disjoint_sparse_fixture,
                                 _sparse_lr_fixture, _ftrl_final_coef)
        from alink_tpu.operator.batch.classification.linear import (
            LogisticRegressionTrainBatchOp)
        from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
        dim = 64
        table = _disjoint_sparse_fixture(n=128, dim=dim, nnz=3, seed=7)
        warm = LogisticRegressionTrainBatchOp(
            vector_col="vec", label_col="label", max_iter=3,
            with_intercept=False).link_from(
            MemSourceBatchOp(_sparse_lr_fixture(64, dim, 4, 1)))
        c_s1 = _ftrl_final_coef(table, warm, 8, "staleness", staleness=1)
        c_chain = _ftrl_final_coef(table, warm, 8, "chained", chunk_size=8)
        assert (np.asarray(c_chain) == np.asarray(c_s1)).all()
        c_sample = _ftrl_final_coef(table, warm, 8, "sample")
        np.testing.assert_allclose(c_chain, c_sample, rtol=1e-9, atol=1e-12)

    def test_chunk_size_rides_cache_key(self):
        """Different chunk lengths are different programs (the lru key
        carries K); identical args hit the cached callable."""
        from alink_tpu.operator.stream.onlinelearning.ftrl import (
            _ftrl_sparse_chained_step_factory)
        a = _ftrl_sparse_chained_step_factory(_mesh(), 0.05, 1.0, 1e-5,
                                              1e-5, K=8)
        b = _ftrl_sparse_chained_step_factory(_mesh(), 0.05, 1.0, 1e-5,
                                              1e-5, K=16)
        a2 = _ftrl_sparse_chained_step_factory(_mesh(), 0.05, 1.0, 1e-5,
                                               1e-5, K=8)
        assert a is a2
        assert a is not b

    def test_chunk_size_in_checkpoint_signature(self, tmp_path):
        """A chained-mode snapshot refuses to resume under a different
        chunk_size (the association rounding differs); the other modes'
        signatures are unchanged, so their pre-existing snapshots stay
        resumable."""
        from test_stream import _sparse_lr_fixture
        from alink_tpu.common.checkpoint import CheckpointError
        from alink_tpu.operator.batch.classification.linear import (
            LogisticRegressionTrainBatchOp)
        from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
        from alink_tpu.operator.stream.onlinelearning.ftrl import (
            FtrlTrainStreamOp)
        from alink_tpu.operator.stream.source.sources import (
            MemSourceStreamOp)
        table = _sparse_lr_fixture(n=64, dim=64, nnz=3, seed=5)
        warm = LogisticRegressionTrainBatchOp(
            vector_col="vec", label_col="label", max_iter=2).link_from(
            MemSourceBatchOp(table.first_n(16)))

        def drain(chunk_size):
            op = FtrlTrainStreamOp(
                warm, vector_col="vec", label_col="label",
                update_mode="chained", chunk_size=chunk_size,
                checkpoint_dir=str(tmp_path), checkpoint_every_batches=2,
                time_interval=1e9).link_from(
                MemSourceStreamOp(table, batch_size=16))
            for _ in op.micro_batches():
                pass

        drain(chunk_size=8)
        with pytest.raises(CheckpointError):
            drain(chunk_size=16)
        drain(chunk_size=8)                  # same chunk: resumes cleanly


# ---------------------------------------------------------------------------
# (b) fused tree-histogram kernel
# ---------------------------------------------------------------------------

def _gbdt_fixture(n=1500, F=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    return X, y


def _train_with_mode(mode, X, y, monkeypatch, interpret=False):
    """A fit through ``hist.build_tree``, whose per-level histogram the
    flag selects: a histogram-parallel forest since PR 31 (``gbdt_train``
    grows on the blocked builder, which the flag does not reach)."""
    from alink_tpu.operator.common.tree.trainers import (TreeTrainParams,
                                                         forest_train)
    if mode is None:
        monkeypatch.delenv("ALINK_TPU_FUSED_HIST", raising=False)
    else:
        monkeypatch.setenv("ALINK_TPU_FUSED_HIST", mode)
    if interpret:
        monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")
    p = TreeTrainParams(num_trees=3, max_depth=4, n_bins=16,
                        subsample_ratio=0.8)
    stats = np.stack([y, y * y, np.ones_like(y)], 1)
    tf, tb, tm, tv, edges, imp = forest_train(X, stats, p, "variance",
                                              ensemble=False)
    return (np.asarray(tf), np.asarray(tb), np.asarray(tv),
            np.asarray(imp))


class TestFusedHistogram:
    def test_xla_and_pallas_parity_with_default(self, monkeypatch):
        """Identical split structure and matching loss curves across
        off/xla/pallas — the fused kernels change the lowering, not the
        trees."""
        X, y = _gbdt_fixture()
        off = _train_with_mode(None, X, y, monkeypatch)
        xla = _train_with_mode("xla", X, y, monkeypatch)
        pls = _train_with_mode("pallas", X, y, monkeypatch, interpret=True)
        for got, name in ((xla, "xla"), (pls, "pallas")):
            assert (got[0] == off[0]).all(), name     # features
            assert (got[1] == off[1]).all(), name     # split bins
            np.testing.assert_allclose(got[3], off[3], rtol=1e-4,
                                       err_msg=name)  # importances

    def test_mode_resolution_and_gating(self, monkeypatch):
        from alink_tpu.operator.common.tree.hist import fused_hist_mode
        import jax
        monkeypatch.delenv("ALINK_TPU_FUSED_HIST", raising=False)
        assert fused_hist_mode() == "off"
        monkeypatch.setenv("ALINK_TPU_FUSED_HIST", "0")
        assert fused_hist_mode() == "off"
        monkeypatch.setenv("ALINK_TPU_FUSED_HIST", "1")
        assert fused_hist_mode() == "xla"
        monkeypatch.setenv("ALINK_TPU_FUSED_HIST", "pallas")
        monkeypatch.delenv("ALINK_TPU_PALLAS_INTERPRET", raising=False)
        expect = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert fused_hist_mode() == expect   # gated on backend
        monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")
        assert fused_hist_mode() == "pallas"

    def test_pallas_compile_failure_demotes_to_xla(self, monkeypatch):
        """When the Pallas kernel cannot compile (the eager probe fails),
        the dispatch demotes to the fused XLA formulation with a one-time
        warning — training completes with identical trees instead of
        crashing at queue.exec()'s compile."""
        import warnings as w
        from alink_tpu.operator.common.tree import hist

        def boom(*a, **k):
            raise RuntimeError("mosaic says no")

        monkeypatch.setattr(hist, "_pallas_level_hist", boom)
        monkeypatch.setattr(hist, "_PALLAS_PROBED", {})
        monkeypatch.setattr(hist, "_PALLAS_WARNED", [False])
        X, y = _gbdt_fixture(n=500, F=4, seed=3)
        off = _train_with_mode(None, X, y, monkeypatch)
        with w.catch_warnings(record=True) as caught:
            w.simplefilter("always")
            pls = _train_with_mode("pallas", X, y, monkeypatch,
                                   interpret=True)
        assert (pls[0] == off[0]).all()      # demoted path: same trees
        msgs = [str(c.message) for c in caught
                if "demoting to the fused XLA" in str(c.message)]
        assert len(msgs) == 1                # warned exactly once

    def _lowered_text(self, mode, monkeypatch):
        """Lower ONE shard_map'd level program (hist + psum + argmax) —
        the build_tree superstep fragment whose lowering the flag
        selects."""
        import jax
        import jax.numpy as jnp
        from alink_tpu.common.compat import shard_map
        from jax.sharding import PartitionSpec as P
        from alink_tpu.operator.common.tree.hist import build_tree, \
            make_xgb_gain, make_xgb_leaf
        if mode is None:
            monkeypatch.delenv("ALINK_TPU_FUSED_HIST", raising=False)
        else:
            monkeypatch.setenv("ALINK_TPU_FUSED_HIST", mode)
        mesh = _mesh()
        n_dev = mesh.devices.size
        n, F, n_bins = 8 * n_dev, 3, 8

        def fn(binned, stats):
            out = build_tree(binned, stats, 2, n_bins, make_xgb_gain(1.0),
                             make_xgb_leaf(1.0), axis_name="d")
            return out[0], out[3]

        sm = shard_map(fn, mesh=mesh, in_specs=(P("d"), P("d")),
                       out_specs=(P(), P()))
        low = jax.jit(sm).lower(
            jax.ShapeDtypeStruct((n, F), jnp.int32),
            jax.ShapeDtypeStruct((n, 3), jnp.float32))
        from alink_tpu.common.compat import lowered_text
        return lowered_text(low)

    @staticmethod
    def _collectives(txt):
        # HLO spells collectives "all-reduce", StableHLO "all_reduce" —
        # normalize so the set is representation-independent
        t = txt.replace("_", "-")
        return {op for op in ("all-reduce", "all-gather",
                              "collective-permute", "all-to-all",
                              "reduce-scatter") if op in t}

    def test_flag_off_hlo_byte_identical_and_collective_set(self,
                                                            monkeypatch):
        """Flag off (unset or "0") lowers byte-identically — the fused
        code contributes ZERO ops to pre-flag programs; flag on changes
        the lowering (the cache key must fold it) but the collective set
        (the per-level psum) is unchanged."""
        unset = self._lowered_text(None, monkeypatch)
        off = self._lowered_text("0", monkeypatch)
        xla = self._lowered_text("xla", monkeypatch)
        assert unset == off
        assert xla != off
        assert self._collectives(off) == self._collectives(xla)
        assert "all-reduce" in self._collectives(off)

    def test_mode_folds_into_program_cache_key(self, monkeypatch):
        """Toggling the flag recompiles: a fresh program-cache entry per
        mode (never a stale program served across a toggle)."""
        from alink_tpu.engine import comqueue as cq

        def gbdt_keys():
            # cache keys are (user_key, stages_digest, mesh, ...): the
            # trainers' tuple leads the composite (the forest's: see
            # ``_train_with_mode``)
            return {k[0] for k in cq._PROGRAM_CACHE
                    if isinstance(k[0], tuple) and k[0]
                    and k[0][0] == "forest"}

        X, y = _gbdt_fixture(n=400, F=4, seed=2)
        _train_with_mode(None, X, y, monkeypatch)
        keys_off = gbdt_keys()
        assert any("off" in k for k in keys_off)
        _train_with_mode("xla", X, y, monkeypatch)
        new = gbdt_keys() - keys_off
        assert len(new) == 1
        assert "xla" in next(iter(new))


# ---------------------------------------------------------------------------
# (c) pinned compiled baseline + provenance gate
# ---------------------------------------------------------------------------

class TestPinnedBaseline:
    def test_native_matches_interpreted_loop(self):
        """The compiled single-slot loop IS the interpreted per-sample
        loop on distinct-slot rows — and the canonical baseline batch
        GUARANTEES distinct slots (make_batch_criteo resamples intra-row
        collisions), because duplicate-slot semantics differ between
        numpy fancy-assignment, the sequential C loop and the device
        scatter-add."""
        from alink_tpu.native import ftrl_slot_run, get_lib
        if get_lib() is None:
            pytest.skip("native library unavailable")
        rng = np.random.RandomState(0)
        B, w, dim = 256, 8, 1024
        idx = np.zeros((B, w), np.int32)
        val = np.zeros((B, w))
        for i in range(B):
            idx[i] = rng.choice(dim, w, replace=False)
        val[:, :5] = rng.randn(B, 5)        # cols 5.. are val-0 padding
        y = (rng.rand(B) < 0.5).astype(np.float64)
        z = rng.randn(dim) * 0.1
        n = np.abs(rng.randn(dim)) * 0.1
        zc, nc = z.copy(), n.copy()
        assert ftrl_slot_run(idx, val, y, zc, nc, 0.05, 1.0, 1e-5, 1e-5)
        zn, nn = z.copy(), n.copy()
        for i in range(B):
            ii, vv, yy = idx[i], val[i], y[i]
            zi, ni = zn[ii], nn[ii]
            decay = (1.0 + np.sqrt(ni)) / 0.05 + 1e-5
            wi = np.where(np.abs(zi) <= 1e-5, 0.0,
                          -(zi - np.sign(zi) * 1e-5) / decay)
            p = 1.0 / (1.0 + np.exp(-np.clip(wi @ vv, -35, 35)))
            g = (p - yy) * vv
            sigma = (np.sqrt(ni + g * g) - np.sqrt(ni)) / 0.05
            zn[ii] = zi + g - sigma * wi
            nn[ii] = ni + g * g
        np.testing.assert_allclose(zc, zn, rtol=0, atol=1e-12)
        np.testing.assert_allclose(nc, nn, rtol=0, atol=1e-12)

    def test_pin_once_then_reuse(self, tmp_path, monkeypatch):
        """First call measures and writes the rig entry; later calls on
        the same rig REUSE it (zero re-measures — the drift that made
        r05's vs_baseline meaningless is structurally gone)."""
        import bench
        calls = []
        monkeypatch.setattr(
            bench, "_measure_compiled_ftrl_baseline",
            lambda *a, **k: calls.append(1) or (123456.0, 120000.0,
                                                "native-c"))
        path = str(tmp_path / "BASELINE_compiled.json")
        r1 = bench.pinned_ftrl_baseline(path)
        r2 = bench.pinned_ftrl_baseline(path)
        assert len(calls) == 1
        assert r1["sps_best"] == r2["sps_best"] == 123456.0
        doc = json.load(open(path))
        fp, info = bench.rig_fingerprint()
        assert fp in doc["rigs"]
        assert doc["rigs"][fp]["impl"] == "native-c"
        assert doc["rigs"][fp]["provenance"]["kernel"].endswith(
            "ftrl_slot_run")
        # a DIFFERENT rig's entry is untouched by this rig's pin
        doc["rigs"]["deadbeef0000"] = dict(doc["rigs"][fp], sps_best=1.0)
        with open(path, "w") as f:
            json.dump(doc, f)
        r3 = bench.pinned_ftrl_baseline(path)
        assert r3["sps_best"] == 123456.0
        assert json.load(open(path))["rigs"]["deadbeef0000"][
            "sps_best"] == 1.0

    def test_repin_requires_explicit_env_and_changes_provenance(
            self, tmp_path, monkeypatch):
        """An explicit re-pin re-measures AND changes the provenance
        fingerprint (it digests the pinned record, not just the rig),
        so --baseline-provenance refuses same-rig re-pinned diffs too."""
        import bench
        rates = iter([(99.0, 98.0, "native-c"), (77.0, 76.0, "native-c")])
        calls = []
        monkeypatch.setattr(
            bench, "_measure_compiled_ftrl_baseline",
            lambda *a, **k: calls.append(1) or next(rates))
        path = str(tmp_path / "b.json")
        r1 = bench.pinned_ftrl_baseline(path)
        monkeypatch.setenv("ALINK_TPU_REPIN_BASELINE", "1")
        r2 = bench.pinned_ftrl_baseline(path)
        assert len(calls) == 2               # explicit re-pin re-measures
        assert r1["provenance_fp"] != r2["provenance_fp"]
        assert r1["fp"] == r2["fp"]          # same rig, different pin

    def test_corrupt_pin_file_never_rewritten(self, tmp_path, monkeypatch,
                                              capsys):
        """A truncated/corrupt BASELINE_compiled.json (carrying OTHER
        rigs' committed pins) is never clobbered: the run warns, uses an
        in-memory measurement, and leaves the file byte-identical."""
        import bench
        monkeypatch.setattr(
            bench, "_measure_compiled_ftrl_baseline",
            lambda *a, **k: (99.0, 98.0, "native-c"))
        path = tmp_path / "b.json"
        path.write_text('{"version": 1, "rigs": {"other')   # truncated
        before = path.read_text()
        rec = bench.pinned_ftrl_baseline(str(path))
        assert rec["sps_best"] == 99.0       # in-memory record still usable
        assert path.read_text() == before    # file untouched
        assert "REFUSING to rewrite" in capsys.readouterr().err

    def test_interpreted_pin_upgrades_when_native_appears(self, tmp_path,
                                                          monkeypatch,
                                                          capsys):
        """A numpy-interpreted pin (no C toolchain at pin time) must not
        be reused once the compiled kernel is available — dividing by the
        ~30x-slower interpreted loop would inflate vs_baseline in a way
        the (rig-hash-identical) provenance gate cannot catch."""
        import bench
        monkeypatch.setattr(
            bench, "_measure_compiled_ftrl_baseline",
            lambda *a, **k: (50_000.0, 49_000.0, "numpy-interpreted"))
        path = str(tmp_path / "b.json")
        r1 = bench.pinned_ftrl_baseline(path)
        assert r1["impl"] == "numpy-interpreted"
        monkeypatch.setattr(
            bench, "_measure_compiled_ftrl_baseline",
            lambda *a, **k: (1_500_000.0, 1_400_000.0, "native-c"))
        monkeypatch.setattr(bench, "_native_available", lambda: True)
        r2 = bench.pinned_ftrl_baseline(path)
        assert r2["impl"] == "native-c"
        assert r2["provenance_fp"] != r1["provenance_fp"]
        assert "numpy-interpreted" in capsys.readouterr().err
        # and a native pin stays reused (no churn)
        r3 = bench.pinned_ftrl_baseline(path)
        assert r3["pinned_at"] == r2["pinned_at"]

    def test_canonical_batch_rows_have_distinct_slots(self):
        """Every row of the canonical baseline batch addresses distinct
        state slots — the precondition for the C / numpy / scatter-add
        implementations to agree exactly."""
        import bench
        idx, val, y = bench.make_batch_criteo(0, dim=2048, nnz=24, B=512)
        nnz_cols = idx[:, :25]               # intercept + 24 features
        srt = np.sort(nnz_cols, axis=1)
        assert not (srt[:, 1:] == srt[:, :-1]).any()


class TestBaselineProvenanceGate:
    def _dump(self, path, sps, fp=None, mode=None):
        doc = {"workloads_sps_vs": {"ftrl_criteo": [sps, 10.0, 0.1]}}
        if fp is not None:
            doc["baseline_fp"] = fp
        if mode:
            doc["mode"] = mode
        with open(path, "w") as f:
            json.dump(doc, f)
        return str(path)

    def test_refuses_cross_fingerprint(self, tmp_path, capsys):
        import bench_compare as cli
        a = self._dump(tmp_path / "a.json", 100.0, fp="aaaa")
        b = self._dump(tmp_path / "b.json", 200.0, fp="bbbb")
        rc = cli.main([a, b, "--baseline-provenance"])
        assert rc == 3
        assert "REFUSING" in capsys.readouterr().err

    def test_same_fingerprint_compares(self, tmp_path, capsys):
        import bench_compare as cli
        a = self._dump(tmp_path / "a.json", 100.0, fp="aaaa")
        b = self._dump(tmp_path / "b.json", 200.0, fp="aaaa")
        assert cli.main([a, b, "--baseline-provenance",
                         "--threshold", "10"]) == 0

    def test_missing_fingerprint_warns_not_refuses(self, tmp_path, capsys):
        import bench_compare as cli
        a = self._dump(tmp_path / "a.json", 100.0)          # pre-r06 dump
        b = self._dump(tmp_path / "b.json", 101.0, fp="aaaa")
        assert cli.main([a, b, "--baseline-provenance"]) == 0
        err = capsys.readouterr().err
        assert "WARNING" in err and "fingerprint" in err

    def test_without_flag_behavior_unchanged(self, tmp_path):
        import bench_compare as cli
        a = self._dump(tmp_path / "a.json", 100.0, fp="aaaa")
        b = self._dump(tmp_path / "b.json", 200.0, fp="bbbb")
        assert cli.main([a, b]) == 0         # no flag: plain report
