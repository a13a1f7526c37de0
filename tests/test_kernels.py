"""ISSUE 13 — the Pallas kernel tier (alink_tpu/kernels/).

The load-bearing contracts, all runnable on the CPU tier-1 rig via
``ALINK_TPU_PALLAS_INTERPRET=1``:

* **fused serving score kernel** — bitwise vs the ``seq_chunk_sum``
  XLA programs at every bucket, dense AND sparse; sharded mesh 1/4/8
  parity survives the flag (fused demotes to the sharded path,
  recorded);
* **bf16/int8 score path** — label-exact + pinned-tolerance vs the f32
  host mapper; fused and XLA low-precision twins bitwise-equal;
* **flag-off byte-identity + key folds** — every new flag's off-path
  lowers byte-identically, and every toggle is a program/serving
  cache MISS, never a stale hit;
* **demotion is never silent** — one RuntimeWarning per (kernel,
  reason) + the alink_kernel_demotions_total / serve-fallback
  counters.
"""

import warnings

import numpy as np
import pytest

from alink_tpu.common.metrics import MetricsRegistry, set_registry
from alink_tpu.kernels import runtime as kr
from alink_tpu.kernels.serve import (lowp_model_arrays, quantize_int8,
                                     serve_dtype)


def _interp(monkeypatch):
    monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int64) if a.dtype == np.float64 else a.view(np.int32)


# ---------------------------------------------------------------------------
# runtime: availability / demotion / probe
# ---------------------------------------------------------------------------

class TestRuntime:
    def test_availability_gating(self, monkeypatch):
        import jax
        monkeypatch.delenv("ALINK_TPU_PALLAS_INTERPRET", raising=False)
        assert kr.pallas_available() == (jax.default_backend() == "tpu")
        monkeypatch.setenv("ALINK_TPU_PALLAS_INTERPRET", "1")
        assert kr.pallas_available()
        assert kr.interpret_mode() == (jax.default_backend() != "tpu")

    def test_demote_once_warns_once_and_counts(self, monkeypatch):
        reg = MetricsRegistry()
        old = set_registry(reg)
        kr.reset_demotions()
        try:
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                kr.demote_once("k1", "r1", "detail")
                kr.demote_once("k1", "r1")          # deduped
                kr.demote_once("k1", "r2")          # new reason: warns
            msgs = [str(c.message) for c in caught]
            assert sum("'k1'" in m and "r1" in m for m in msgs) == 1
            assert sum("r2" in m for m in msgs) == 1
            assert reg.value("alink_kernel_demotions_total",
                             {"kernel": "k1", "reason": "r1"}) == 2
            assert reg.value("alink_kernel_demotions_total",
                             {"kernel": "k1", "reason": "r2"}) == 1
        finally:
            set_registry(old)
            kr.reset_demotions()

    def test_eager_probe_memoizes_failure_and_demotes(self):
        calls = []

        def boom():
            calls.append(1)
            raise RuntimeError("mosaic says no")

        kr.reset_demotions()
        kr._PROBED.pop(("t-kernel", "shape"), None)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            assert kr.eager_probe("t-kernel", ("shape",), boom) is False
            assert kr.eager_probe("t-kernel", ("shape",), boom) is False
        assert len(calls) == 1                      # memoized
        assert sum("probe-failed" in str(c.message) for c in caught) == 1
        kr._PROBED.pop(("t-kernel", "shape"), None)
        kr.reset_demotions()


# ---------------------------------------------------------------------------
# the fused serving score kernel + bf16/int8
# ---------------------------------------------------------------------------

def _serve_fixture(seed=0, n=96, d=20, detail=False):
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.params import Params
    from alink_tpu.common.vector import DenseVector
    from alink_tpu.operator.batch.classification.linear import (
        LogisticRegressionTrainBatchOp)
    from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
    from alink_tpu.operator.common.linear.mapper import LinearModelMapper
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d)
    y = (X @ rng.randn(d) > 0).astype(np.int64)
    vecs = np.empty(n, object)
    vecs[:] = [DenseVector(X[i]) for i in range(n)]
    tbl = MTable({"vec": vecs, "label": y}, "vec VECTOR, label LONG")
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=3).link_from(
        MemSourceBatchOp(tbl))
    pp = {"prediction_col": "pred", "vector_col": "vec"}
    if detail:
        pp["prediction_detail_col"] = "det"
    mapper = LinearModelMapper(warm.get_output_table().schema,
                               tbl.select(["vec"]).schema, Params(pp))
    mapper.load_model(warm.get_output_table())
    return tbl, mapper


@pytest.fixture(scope="module")
def linear_fix():
    return _serve_fixture(seed=4, n=128)


def _tables_equal(a, b):
    if a.col_names != b.col_names or a.num_rows != b.num_rows:
        return False
    return all(str(x) == str(y)
               for c in a.col_names for x, y in zip(a.col(c), b.col(c)))


class TestFusedServeKernel:
    def test_dense_bitwise_every_bucket(self, monkeypatch, linear_fix):
        from alink_tpu.serving import CompiledPredictor
        tbl, mapper = linear_fix
        req = tbl.select(["vec"]).first_n(13)
        monkeypatch.delenv("ALINK_TPU_SERVE_FUSED", raising=False)
        base = CompiledPredictor(mapper, buckets=(1, 4, 16))
        _interp(monkeypatch)
        monkeypatch.setenv("ALINK_TPU_SERVE_FUSED", "1")
        fused = CompiledPredictor(mapper, buckets=(1, 4, 16))
        # per-bucket: pad the same rows to every bucket size
        for k in (1, 3, 13):
            sub = req.first_n(k)
            assert _tables_equal(base.predict_table(sub),
                                 fused.predict_table(sub))
        # scores bitwise, not just labels: compare the device outputs
        import jax.numpy as jnp
        ko, kf = base._active.kernel, fused._active.kernel
        kind, arrs = ko.encode(req, 16)
        so = ko.device_fns[kind](
            tuple(jnp.asarray(a) for a in ko.model_arrays), *arrs)
        sf = kf.device_fns[kind](
            tuple(jnp.asarray(a) for a in kf.model_arrays), *arrs)
        assert np.array_equal(_bits(so), _bits(sf))

    def test_sparse_bitwise(self, monkeypatch):
        from alink_tpu.common.mtable import MTable
        from alink_tpu.common.params import Params
        from alink_tpu.common.vector import SparseVector
        from alink_tpu.operator.batch.classification.linear import (
            LogisticRegressionTrainBatchOp)
        from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
        from alink_tpu.operator.common.linear.mapper import LinearModelMapper
        from alink_tpu.serving import CompiledPredictor
        rng = np.random.RandomState(3)
        n, dim, nnz = 48, 256, 9
        rows = np.empty(n, object)
        rows[:] = [SparseVector(dim,
                                np.sort(rng.choice(dim, nnz, False)),
                                rng.randn(nnz)) for _ in range(n)]
        y = np.asarray([1 if sum(v.values) > 0 else 0 for v in rows])
        tbl = MTable({"vec": rows, "label": y}, "vec VECTOR, label LONG")
        warm = LogisticRegressionTrainBatchOp(
            vector_col="vec", label_col="label", max_iter=2).link_from(
            MemSourceBatchOp(tbl))
        mapper = LinearModelMapper(
            warm.get_output_table().schema, tbl.select(["vec"]).schema,
            Params({"prediction_col": "pred", "vector_col": "vec"}))
        mapper.load_model(warm.get_output_table())
        req = tbl.select(["vec"])
        monkeypatch.delenv("ALINK_TPU_SERVE_FUSED", raising=False)
        base = CompiledPredictor(mapper, buckets=(16, 64)).predict_table(req)
        _interp(monkeypatch)
        monkeypatch.setenv("ALINK_TPU_SERVE_FUSED", "1")
        fused = CompiledPredictor(mapper, buckets=(16, 64)).predict_table(req)
        assert _tables_equal(base, fused)

    def test_sharded_mesh_1_4_8_with_flag_on(self, monkeypatch,
                                              linear_fix):
        """SERVE_FUSED on a SHARDED predictor: the fused kernel has no
        sharded twin, so the predictor records the standard fallback
        and the mesh-size-invariance contract survives bitwise."""
        import jax
        from alink_tpu.serving import CompiledPredictor
        from alink_tpu.serving.predictor import _reset_fallback_warnings
        from alink_tpu.serving.sharded import serving_mesh
        tbl, mapper = linear_fix
        req = tbl.select(["vec"]).first_n(11)
        _interp(monkeypatch)
        monkeypatch.setenv("ALINK_TPU_SERVE_FUSED", "1")
        _reset_fallback_warnings()
        outs = {}
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for s in (1, 4, 8):
                mesh = serving_mesh(jax.devices()[:s])
                pred = CompiledPredictor(mapper, buckets=(4, 16),
                                         sharded=True, mesh=mesh)
                outs[s] = pred.predict_table(req)
        assert _tables_equal(outs[1], outs[4])
        assert _tables_equal(outs[1], outs[8])
        assert any("no-sharded-kernel" in str(c.message) for c in caught)

    def test_fused_demotes_without_backend(self, monkeypatch,
                                           linear_fix):
        import jax
        if jax.default_backend() == "tpu":
            pytest.skip("TPU backend: fused is genuinely available")
        from alink_tpu.serving import CompiledPredictor
        from alink_tpu.serving.predictor import _reset_fallback_warnings
        tbl, mapper = linear_fix
        req = tbl.select(["vec"]).first_n(5)
        monkeypatch.delenv("ALINK_TPU_SERVE_FUSED", raising=False)
        monkeypatch.delenv("ALINK_TPU_PALLAS_INTERPRET", raising=False)
        base = CompiledPredictor(mapper, buckets=(8,)).predict_table(req)
        monkeypatch.setenv("ALINK_TPU_SERVE_FUSED", "1")
        _reset_fallback_warnings()
        reg = MetricsRegistry()
        old = set_registry(reg)
        try:
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                demoted = CompiledPredictor(mapper, buckets=(8,))
            out = demoted.predict_table(req)
            assert _tables_equal(base, out)
            assert any("pallas-unavailable" in str(c.message)
                       for c in caught)
            assert reg.value(
                "alink_serve_fallback_total",
                {"mapper": "LinearModelMapper",
                 "reason": "pallas-unavailable"}) >= 1
            # the demoted kernel resolves fused=False: its signature
            # equals the flag-off one, so hot paths share programs
            assert demoted._active.kernel.signature[-1] is False
        finally:
            set_registry(old)
            _reset_fallback_warnings()


class TestLowPrecisionServing:
    def test_quantize_int8_roundtrip(self):
        w = np.asarray([-2.0, -0.5, 0.0, 0.7, 1.99])
        q, scale = quantize_int8(w)
        assert q.dtype == np.int8 and q.max() <= 127 and q.min() >= -127
        np.testing.assert_allclose(q * float(scale), w,
                                   atol=float(scale) / 2 + 1e-12)
        qz, sz = quantize_int8(np.zeros(4))
        assert float(sz) == 1.0 and (qz == 0).all()

    def test_dtype_parse(self, monkeypatch):
        monkeypatch.delenv("ALINK_TPU_SERVE_DTYPE", raising=False)
        assert serve_dtype() == "f32"
        for raw, want in (("bf16", "bf16"), ("BFLOAT16", "bf16"),
                          ("int8", "int8"), ("fp32", "f32"), ("0", "f32")):
            monkeypatch.setenv("ALINK_TPU_SERVE_DTYPE", raw)
            assert serve_dtype() == want
        monkeypatch.setenv("ALINK_TPU_SERVE_DTYPE", "int4")
        with pytest.raises(ValueError):
            serve_dtype()

    @pytest.mark.parametrize("dt", ["bf16", "int8"])
    def test_label_exact_and_pinned_tolerance(self, monkeypatch, dt,
                                              linear_fix):
        """The low-precision parity gate: labels EXACT vs the f32 host
        mapper, scores inside the pinned tolerance. The fixture keeps
        every |score| above the quantization error bound — the
        documented 'when is int8 safe' condition (docs/serving.md)."""
        from alink_tpu.serving import CompiledPredictor
        tbl, mapper = linear_fix
        req = tbl.select(["vec"])
        host = mapper.map_table(req)
        host_scores = mapper.predict_scores(req)
        monkeypatch.setenv("ALINK_TPU_SERVE_DTYPE", dt)
        pred = CompiledPredictor(mapper, buckets=(128,))
        kern = pred._active.kernel
        assert kern.signature[-2] == dt         # the key fold
        import jax.numpy as jnp
        kind, arrs = kern.encode(req, 128)
        scores = np.asarray(kern.device_fns[kind](
            tuple(jnp.asarray(a) for a in kern.model_arrays),
            *arrs))[:req.num_rows]
        # pinned tolerance: bf16 terms carry ~2^-9 relative error per
        # term; int8 weights ~scale/2 per weight — 2% of the score
        # scale bounds both on this fixture
        tol = 0.02 * max(1.0, float(np.abs(host_scores).max()))
        np.testing.assert_allclose(scores, host_scores, atol=tol)
        safe = np.abs(host_scores) > tol        # away from the boundary
        out = pred.predict_table(req)
        got = np.asarray([str(v) for v in out.col("pred")])
        want = np.asarray([str(v) for v in host.col("pred")])
        assert safe.sum() > req.num_rows * 0.8  # the fixture is usable
        assert (got[safe] == want[safe]).all()  # label-exact

    @pytest.mark.parametrize("dt", ["bf16", "int8"])
    def test_fused_equals_xla_low_precision(self, monkeypatch, dt):
        """The fused kernel and the XLA twin produce BITWISE-equal
        low-precision scores (same term rounding, same strict
        reduction)."""
        _interp(monkeypatch)
        import jax
        import jax.numpy as jnp
        from alink_tpu.kernels.serve import (make_fused_score_fns,
                                             make_xla_score_fns)
        rng = np.random.RandomState(0)
        dim8, n, width = 128, 16, 8
        mdl = tuple(jnp.asarray(a) for a in
                    lowp_model_arrays(rng.randn(dim8), 0.25, dt))
        X = jnp.asarray(rng.randn(n, dim8))
        idx = jnp.asarray(rng.randint(0, dim8, (n, width)), jnp.int32)
        val = jnp.asarray(rng.randn(n, width))
        for kind, args in (("dense", (X,)), ("sparse", (idx, val))):
            sx = jax.jit(make_xla_score_fns(dt, np.float64)[kind])(
                mdl, *args)
            sf = jax.jit(make_fused_score_fns(dt, np.float64)[kind])(
                mdl, *args)
            assert np.array_equal(_bits(sx), _bits(sf)), kind

    def test_serving_key_fold_toggle_is_miss(self, monkeypatch,
                                             linear_fix):
        """Toggling SERVE_DTYPE or SERVE_FUSED changes the kernel
        signature, so the serving program cache MISSES — three
        predictors, three disjoint program-key sets."""
        from alink_tpu.serving import CompiledPredictor
        tbl, mapper = linear_fix
        req = tbl.select(["vec"]).first_n(4)
        keys = {}
        _interp(monkeypatch)
        for name, env in (("off", {}),
                          ("bf16", {"ALINK_TPU_SERVE_DTYPE": "bf16"}),
                          ("fused", {"ALINK_TPU_SERVE_FUSED": "1"})):
            monkeypatch.delenv("ALINK_TPU_SERVE_DTYPE", raising=False)
            monkeypatch.delenv("ALINK_TPU_SERVE_FUSED", raising=False)
            for k, v in env.items():
                monkeypatch.setenv(k, v)
            pred = CompiledPredictor(mapper, buckets=(8,))
            pred.predict_table(req)
            keys[name] = set(pred._programs)
        assert not (keys["off"] & keys["bf16"])
        assert not (keys["off"] & keys["fused"])
        assert not (keys["bf16"] & keys["fused"])

    def test_flag_off_signature_and_hlo_stable(self, monkeypatch,
                                               linear_fix):
        """Unset and explicitly-falsy flags resolve identically: same
        signature, same (byte-identical) lowered score program."""
        import jax
        from alink_tpu.common.compat import lowered_text
        tbl, mapper = linear_fix

        def lowered():
            k = mapper.serving_kernel()
            import jax.numpy as jnp
            mdl = tuple(jnp.asarray(a) for a in k.model_arrays)
            kind, arrs = k.encode(tbl.select(["vec"]).first_n(4), 8)
            low = jax.jit(k.device_fns[kind]).lower(mdl, *arrs)
            return k.signature, lowered_text(low)

        monkeypatch.delenv("ALINK_TPU_SERVE_DTYPE", raising=False)
        monkeypatch.delenv("ALINK_TPU_SERVE_FUSED", raising=False)
        sig_unset, hlo_unset = lowered()
        monkeypatch.setenv("ALINK_TPU_SERVE_DTYPE", "f32")
        monkeypatch.setenv("ALINK_TPU_SERVE_FUSED", "0")
        sig_off, hlo_off = lowered()
        assert sig_unset == sig_off
        assert hlo_unset == hlo_off
        assert sig_unset[-2:] == ("f32", False)


# ---------------------------------------------------------------------------
# flag registration hygiene
# ---------------------------------------------------------------------------

class TestFlagRegistration:
    def test_new_flags_declared(self):
        from alink_tpu.common.flags import FLAGS
        for name in ("ALINK_TPU_SERVE_FUSED", "ALINK_TPU_SERVE_DTYPE",
                     "ALINK_TPU_PALLAS_INTERPRET"):
            fl = FLAGS.get(name)
            assert fl is not None and fl.key_neutral

