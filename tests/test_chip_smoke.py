"""chip_smoke.py must not rot between chip runs, and the compile cache is
placed by one rule.

* the smoke's three legs run here at a tiny width on the 8-device CPU
  mesh — everything but the device check, which must refuse this rig;
* ``JAX_COMPILATION_CACHE_DIR`` set: nothing in the repo moves jax's
  persistent compile cache; unset: every session shares one fixed
  directory inside the checkout; no source pairs a temp name with it.
"""

import os
import re

import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 8 fields x 16 slots = dim 128: same legs, same checks, seconds on CPU
TINY = chip_smoke.SmokeConfig(n_fields=8, field_size=16, batch=64,
                              train_rows=256, lbfgs_iters=4,
                              stream_batches=6, requests=16, vocab=50)


@pytest.fixture
def _restore_session():
    """The smoke and the bench Harness install their own registry and
    default session; put the test session's back."""
    from alink_tpu.common.metrics import get_registry, set_registry
    from alink_tpu.common.mlenv import MLEnvironmentFactory
    reg, env = get_registry(), MLEnvironmentFactory.get_default()
    yield
    set_registry(reg)
    MLEnvironmentFactory.set_default(env)


class TestSmokeLegs:
    def test_three_legs_at_tiny_width(self, _restore_session):
        out = chip_smoke.run_legs(TINY)
        assert out["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": 8}
        assert out["dim"] == 128 and out["claim"] is None
        assert list(out)[-1] == "claim"
        legs = out["legs"]
        assert list(legs) == ["batch_trainer", "stream_trainer", "server"]
        assert legs["batch_trainer"]["psums_per_superstep"] == 2
        assert legs["stream_trainer"]["snapshots"] >= 3
        assert legs["stream_trainer"]["state_devices"] == 8
        srv = legs["server"]
        assert srv["programs"] == 5 and srv["requests"] == TINY.requests
        # the mesh-sharded dispatch compiled (its manifest capture did
        # not fail) and spans the mesh
        assert srv["sharded"]["devices"] == 8
        assert all(v == 0 for v in out["counters"].values())

    def test_refuses_a_rig_without_a_tpu(self, monkeypatch):
        monkeypatch.setattr(
            chip_smoke, "run_legs",
            lambda cfg: pytest.fail("a leg ran without a TPU"))
        with pytest.raises(SystemExit) as exc:
            chip_smoke.main()
        assert exc.value.code not in (0, None)
        assert "no TPU" in str(exc.value.code)

    def test_a_hidden_fallback_fails_the_run(self, _restore_session,
                                             monkeypatch):
        """A serve fallback recorded anywhere on the path must turn the
        run red even though every answer was right."""
        real = chip_smoke.leg_server

        def leg_with_fallback(cfg, warm, snaps):
            from alink_tpu.serving.predictor import (
                _reset_fallback_warnings, record_serve_fallback)
            _reset_fallback_warnings()
            record_serve_fallback("LinearModelMapper", "no-serving-kernel")
            return real(cfg, warm, snaps)

        monkeypatch.setattr(chip_smoke, "leg_server", leg_with_fallback)
        with pytest.raises(chip_smoke.SmokeFailure, match="fallback"):
            chip_smoke.run_legs(TINY)


class TestCompileCacheRule:
    @pytest.fixture
    def _restore_cache_dir(self):
        import jax
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def _touch_every_entry_point(self, tmp_path, monkeypatch):
        """Construct a session, the bench Harness, and run an armed
        aotcache store + load."""
        import jax
        import jax.numpy as jnp

        import bench
        from alink_tpu.common import aotcache
        from alink_tpu.common.mlenv import MLEnvironment
        from alink_tpu.common.plan import ExecutionPlan
        MLEnvironment()
        bench.Harness()
        monkeypatch.setenv("ALINK_TPU_AOT_CACHE_DIR", str(tmp_path / "aot"))
        assert aotcache.active()
        plan = ExecutionPlan("cache_rule_test", (("k", 1),))
        fn = jax.jit(lambda x: x + 1.0)
        assert aotcache.store(plan, fn, (jnp.zeros(4),), cache="rule")
        assert aotcache.load(plan, cache="rule", record=False) is not None

    def test_env_var_places_it_and_no_code_moves_it(
            self, tmp_path, monkeypatch, _restore_session,
            _restore_cache_dir):
        import jax
        placed = str(tmp_path / "placed-from-outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        # what jax itself does at import when the variable is set
        jax.config.update("jax_compilation_cache_dir", placed)
        self._touch_every_entry_point(tmp_path, monkeypatch)
        assert jax.config.jax_compilation_cache_dir == placed
        from alink_tpu.common.mlenv import place_compile_cache
        assert place_compile_cache() == placed

    def test_unset_is_one_fixed_dir_in_the_checkout(
            self, tmp_path, monkeypatch, _restore_session,
            _restore_cache_dir):
        import jax
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        self._touch_every_entry_point(tmp_path, monkeypatch)
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(ROOT, ".jax_cache")

    def test_no_source_pairs_a_temp_name_with_the_cache(self):
        setter = re.compile(
            r"""update\(\s*["']jax_compilation_cache_dir["']""")
        setters, paired = [], []
        for base, dirs, files in os.walk(ROOT):
            dirs[:] = [d for d in dirs
                       if not d.startswith(".")
                       and d not in ("tests", "chiprun_out", "__pycache__")]
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(base, name)
                with open(path, encoding="utf-8") as f:
                    src = f.read()
                if "jax_compilation_cache_dir" not in src:
                    continue
                rel = os.path.relpath(path, ROOT)
                if setter.search(src):
                    setters.append(rel)
                if "tempfile" in src or "mkdtemp" in src:
                    paired.append(rel)
        assert setters == [os.path.join("alink_tpu", "common", "mlenv.py")]
        assert paired == []
