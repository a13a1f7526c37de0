"""ExecutionPlan contracts (ISSUE 19 tentpole): byte-identity of every
migrated cache key against the hand-threaded legacy tuples, canonical
cross-process digest stability, and named single-dimension diffs.

The migration discipline is the PR-7 one: the plan must be a pure
REFACTOR of key derivation — ``legacy_key()`` reproduces the exact
historical tuples, the checkpoint signatures are content-identical
dicts, lowered HLO is byte-identical with the ledger on or off, and
hit/miss behavior never moves.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from alink_tpu.common import compileledger
from alink_tpu.common import plan as planlib
from alink_tpu.common.plan import ExecutionPlan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# digest: canonical + cross-process stable
# ---------------------------------------------------------------------------

_DIGEST_DIMS = (
    ("ALINK_TPU_SERVE_DTYPE", "f32"),
    ("bucket", 128),
    ("buckets", (1, 4, 128)),
    ("flags", {"donate": True, "fuse": False}),
    ("seed", 7),
    ("nothing", None),
)

_CHILD = """
import sys
sys.path.insert(0, {root!r})
from alink_tpu.common.plan import ExecutionPlan
p = ExecutionPlan("test", {dims!r})
print(p.digest())
"""


class TestDigest:
    def test_stable_within_process(self):
        a = ExecutionPlan("test", _DIGEST_DIMS)
        b = ExecutionPlan("test", _DIGEST_DIMS)
        assert a.digest() == b.digest()
        assert a == b
        # hashability holds for the tuple-of-primitives dims real cache
        # keys are built from (the dict dim above exercises _canon only)
        h = ExecutionPlan("test", _DIGEST_DIMS[:3])
        assert hash(h) == hash(ExecutionPlan("test", _DIGEST_DIMS[:3]))

    def test_stable_across_processes(self):
        """Python's builtin hash() is salted per process; the plan
        digest must NOT be — a fresh interpreter building the same
        flags+buckets plan prints the same digest (the AOT-persistent-
        cache precondition, ROADMAP item 3)."""
        here = ExecutionPlan("test", _DIGEST_DIMS).digest()
        src = _CHILD.format(root=ROOT, dims=_DIGEST_DIMS)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        outs = {subprocess.run([sys.executable, "-c", src], env=env,
                               capture_output=True, text=True,
                               timeout=120, check=True).stdout.strip()
                for _ in range(2)}
        assert outs == {here}

    def test_single_dimension_change_moves_digest_and_names_diff(self):
        base = ExecutionPlan("test", _DIGEST_DIMS)
        for i, (name, old) in enumerate(_DIGEST_DIMS):
            changed = list(_DIGEST_DIMS)
            changed[i] = (name, "CHANGED" if old != "CHANGED" else "X")
            other = ExecutionPlan("test", tuple(changed))
            assert other.digest() != base.digest(), name
            d = other.diff(base)
            assert [e["dim"] for e in d] == [name]
            assert d[0]["old"] == repr(old)

    def test_type_sensitive_diff(self):
        """1 vs True must diff (they key differently in some legacy
        tuples even though == holds)."""
        a = ExecutionPlan("t", (("x", 1),))
        b = ExecutionPlan("t", (("x", True),))
        assert a.diff(b) and a.diff(b)[0]["dim"] == "x"
        assert a.digest() != b.digest()

    def test_cold_start_diff(self):
        p = ExecutionPlan("t", (("x", 1),))
        assert p.diff(None) == [{"dim": "cold-start",
                                 "old": "-", "new": "-"}]

    def test_mesh_digest_uses_fingerprint(self):
        """A live jax Mesh dim digests by fingerprint (axis names +
        shape + device strings), not repr — two Mesh objects over the
        same devices digest identically."""
        import jax
        from jax.sharding import Mesh
        devs = np.array(jax.devices()[:1])
        m1 = Mesh(devs, ("w",))
        m2 = Mesh(devs, ("w",))
        assert ExecutionPlan("t", (("mesh", m1),)).digest() \
            == ExecutionPlan("t", (("mesh", m2),)).digest()


# ---------------------------------------------------------------------------
# engine: legacy ckey byte-identity + checkpoint signature content
# ---------------------------------------------------------------------------

class TestEnginePlan:
    def test_legacy_key_reproduces_historical_13_tuple(self):
        """The pre-ISSUE-19 ckey shape, order and values, less the
        collective-fusion slot ISSUE 29 took out (12 positions now):

            (program_key, stages_dig, mesh, nw, max_iter, seed,
             criterion?, step_log, probes, donate,
             sorted(parts), sorted(bcast))
        """
        flags = (("ALINK_TPU_STEP_LOG", False),
                 ("ALINK_TPU_HEALTH", True),
                 ("ALINK_TPU_DONATE", True))
        mesh = object()   # identity-keyed, exactly like the legacy tuple
        p = planlib.engine_plan(
            program_key=("lr", 5), stages_digest="digest123", mesh=mesh,
            num_workers=4, max_iter=10, seed=7, has_criterion=True,
            flags=flags, part_names=("a", "train"), bcast_names=("b0",))
        assert p.legacy_key() == (
            ("lr", 5), "digest123", mesh, 4, 10, 7,
            True, False, True, True, ("a", "train"), ("b0",))

    def test_live_flags_match_accessors(self):
        from alink_tpu.common.health import health_enabled
        from alink_tpu.common.profiling import step_log_enabled
        from alink_tpu.engine.comqueue import donation_enabled
        flags = dict(planlib.engine_flags())
        assert flags == {
            "ALINK_TPU_STEP_LOG": step_log_enabled(),
            "ALINK_TPU_HEALTH": health_enabled(),
            "ALINK_TPU_DONATE": donation_enabled(),
        }

    def test_checkpoint_signature_content_identical(self):
        from alink_tpu.engine import recovery
        flags = (("ALINK_TPU_STEP_LOG", False),
                 ("ALINK_TPU_HEALTH", True),
                 ("ALINK_TPU_DONATE", False))
        p = planlib.engine_plan(
            program_key=None, stages_digest="sd", mesh=None,
            num_workers=2, max_iter=3, seed=9, has_criterion=False,
            flags=flags, part_names=("train",), bcast_names=("w",))
        got = planlib.engine_checkpoint_signature(
            p, part_sig=(("train", (8, 2)),), data_token="tok")
        want = recovery.program_signature(
            num_workers=2, max_iter=3, seed=9,
            part_sig=(("train", (8, 2)),), bcast_names=("w",),
            stages_digest="sd", data_token="tok",
            probes_on=True)
        assert got == want
        # what the commit before ISSUE 29 wrote into a snapshot for this
        # plan (recorded there): a run checkpointed then resumes now
        assert got == {
            "kind": "comqueue_carry", "num_workers": 2, "max_iter": 3,
            "seed": 9, "parts": [["train", "(8, 2)"]], "bcast": ["w"],
            "stages_blake2b": "0d981ed3936c5a4633a22848",
            "health_probes": True,
            "data_blake2b": "62d0ff5ca76d04a362551c5e"}


# ---------------------------------------------------------------------------
# FTRL: checkpoint-signature content identity (incl. conditional keys)
# ---------------------------------------------------------------------------

def _legacy_ftrl_signature(*, alpha, beta, l1, l2, dim, dim_pad,
                           update_mode, staleness, chunk_size,
                           has_icpt, warm_fp, kern_resolved_pallas):
    """The pre-ISSUE-19 hand-built ck_signature, verbatim."""
    sig = {"kind": "ftrl_state", "alpha": alpha, "beta": beta,
           "l1": l1, "l2": l2, "dim": dim, "dim_pad": dim_pad,
           "update_mode": update_mode,
           "staleness": (staleness
                         if update_mode == "staleness" else None),
           "has_intercept": bool(has_icpt),
           "warm_coef_blake2b": warm_fp}
    if update_mode == "chained":
        sig["chunk_size"] = chunk_size
        if kern_resolved_pallas:
            sig["ftrl_kernel"] = "pallas"
    return sig


class TestFtrlPlan:
    @pytest.mark.parametrize("mode", ["dense", "staleness", "chained"])
    def test_signature_content_identical(self, mode, monkeypatch):
        monkeypatch.delenv("ALINK_TPU_FTRL_KERNEL", raising=False)
        kw = dict(alpha=0.1, beta=1.0, l1=0.01, l2=0.05, dim=33,
                  dim_pad=64, update_mode=mode, staleness=4,
                  chunk_size=128)
        p = planlib.ftrl_plan(mesh=None, has_intercept=True,
                              warm_fp="abc123", **kw)
        want = _legacy_ftrl_signature(
            has_icpt=True, warm_fp="abc123",
            kern_resolved_pallas=False, **kw)
        assert planlib.ftrl_checkpoint_signature(p) == want


# ---------------------------------------------------------------------------
# sweep + serving views
# ---------------------------------------------------------------------------

class TestSweepPlan:
    def test_legacy_program_key_byte_identity(self, monkeypatch):
        monkeypatch.delenv("ALINK_TPU_SWEEP", raising=False)
        p = planlib.sweep_plan("ftrl", ("a", 1))
        assert planlib.legacy_sweep_program_key(p) == \
            ("sweep", "ftrl", False, "a", 1)
        monkeypatch.setenv("ALINK_TPU_SWEEP", "1")
        p2 = planlib.sweep_plan("ftrl", ("a", 1))
        assert planlib.legacy_sweep_program_key(p2) == \
            ("sweep", "ftrl", True, "a", 1)
        d = p2.diff(p)
        assert [e["dim"] for e in d] == ["ALINK_TPU_SWEEP"]


class TestServingEventPlan:
    def _splan(self, sig):
        from alink_tpu.serving.plan import ServingPlan
        return ServingPlan(signature=tuple(sig), buckets=(1, 4, 16),
                           sharded=False, mesh_fp=None)

    def test_signature_tail_decomposes_into_flag_dims(self):
        sp = self._splan(("linear", 8, "f32", False))
        p = planlib.serving_event_plan(sp, kind="dense", bucket=16,
                                       trailing=((8,),))
        assert p.get("ALINK_TPU_SERVE_DTYPE") == "f32"
        assert p.get("ALINK_TPU_SERVE_FUSED") is False
        assert p.get("geometry") == ("linear", 8)
        assert p.get("bucket") == 16

    def test_dtype_flip_diffs_exactly_the_flag(self):
        a = planlib.serving_event_plan(
            self._splan(("linear", 8, "f32", False)), kind="dense",
            bucket=16, trailing=((8,),))
        b = planlib.serving_event_plan(
            self._splan(("linear", 8, "int8", False)), kind="dense",
            bucket=16, trailing=((8,),))
        d = b.diff(a)
        assert [e["dim"] for e in d] == ["ALINK_TPU_SERVE_DTYPE"]
        assert d[0]["old"] == "'f32'" and d[0]["new"] == "'int8'"

    def test_bucket_change_diffs_bucket(self):
        a = planlib.serving_event_plan(
            self._splan(("linear", 8, "f32", False)), kind="dense",
            bucket=128, trailing=())
        b = planlib.serving_event_plan(
            self._splan(("linear", 8, "f32", False)), kind="dense",
            bucket=512, trailing=())
        assert [e["dim"] for e in b.diff(a)] == ["bucket"]


# ---------------------------------------------------------------------------
# the no-op proof: ledger on/off — identical keys, hit/miss, HLO
# ---------------------------------------------------------------------------

class TestLedgerIsKeyNeutral:
    def _run_queue(self, seed):
        import jax.numpy as jnp
        from alink_tpu.engine import AllReduce, IterativeComQueue

        def stage(ctx):
            if ctx.is_init_step:
                ctx.put_obj("acc", jnp.zeros(()))
            ctx.put_obj("local", jnp.ones(()))

        def fold(ctx):
            ctx.put_obj("acc", ctx.get_obj("acc") + ctx.get_obj("local"))

        q = (IterativeComQueue(max_iter=3, seed=seed)
             .add(stage).add(AllReduce("local")).add(fold))
        q.set_program_key(("plan_test", seed))
        return q.exec()

    def test_engine_cache_keys_and_hits_identical(self, monkeypatch):
        """Same program run twice under ledger ON and ledger OFF: the
        program-cache key set and the hit/miss deltas are identical —
        the ledger observes the cache, it is not part of the key."""
        from alink_tpu.engine import comqueue

        def deltas():
            comqueue.clear_program_cache()
            compileledger.reset()
            h0 = dict(comqueue._PROGRAM_CACHE_STATS)
            self._run_queue(3)
            self._run_queue(3)
            h1 = comqueue._PROGRAM_CACHE_STATS
            return (set(comqueue._PROGRAM_CACHE),
                    h1["hits"] - h0["hits"],
                    h1["misses"] - h0["misses"])

        monkeypatch.setenv("ALINK_TPU_COMPILE_LEDGER", "0")
        keys_off, hits_off, miss_off = deltas()
        assert not compileledger.compilez_doc()["caches"]
        monkeypatch.setenv("ALINK_TPU_COMPILE_LEDGER", "1")
        keys_on, hits_on, miss_on = deltas()
        assert keys_on == keys_off
        assert (hits_on, miss_on) == (hits_off, miss_off)
        row = compileledger.compilez_doc()["caches"]["engine.program"]
        assert row["misses"] == miss_on and row["hits"] == hits_on

    def test_serving_lowered_hlo_byte_identical(self, monkeypatch):
        """The serving score program lowers to byte-identical text with
        the ledger on or off (the ledger records AROUND the compile; it
        must never reach the traced computation)."""
        import jax
        import jax.numpy as jnp
        from alink_tpu.common.compat import lowered_text
        from alink_tpu.common.mtable import MTable
        from alink_tpu.common.params import Params
        from alink_tpu.common.vector import DenseVector
        from alink_tpu.operator.batch.classification.linear import (
            LogisticRegressionTrainBatchOp)
        from alink_tpu.operator.batch.source.sources import (
            MemSourceBatchOp)
        from alink_tpu.operator.common.linear.mapper import (
            LinearModelMapper)

        rng = np.random.RandomState(3)
        X = rng.randn(32, 6)
        y = (X @ rng.randn(6) > 0).astype(np.int64)
        vecs = np.empty(32, object)
        vecs[:] = [DenseVector(X[i]) for i in range(32)]
        tbl = MTable({"vec": vecs, "label": y},
                     "vec VECTOR, label LONG")
        warm = LogisticRegressionTrainBatchOp(
            vector_col="vec", label_col="label", max_iter=2).link_from(
            MemSourceBatchOp(tbl))
        mapper = LinearModelMapper(
            warm.get_output_table().schema,
            tbl.select(["vec"]).schema,
            Params({"prediction_col": "pred", "vector_col": "vec"}))
        mapper.load_model(warm.get_output_table())

        def lowered():
            k = mapper.serving_kernel()
            mdl = tuple(jnp.asarray(a) for a in k.model_arrays)
            kind, arrs = k.encode(tbl.select(["vec"]).first_n(4), 8)
            return k.signature, lowered_text(
                jax.jit(k.device_fns[kind]).lower(mdl, *arrs))

        monkeypatch.setenv("ALINK_TPU_COMPILE_LEDGER", "0")
        sig_off, hlo_off = lowered()
        monkeypatch.setenv("ALINK_TPU_COMPILE_LEDGER", "1")
        sig_on, hlo_on = lowered()
        assert sig_on == sig_off
        assert hlo_on == hlo_off

    def test_ledger_flags_registered_key_neutral(self):
        from alink_tpu.common.flags import FLAGS
        for name in ("ALINK_TPU_COMPILE_LEDGER", "ALINK_TPU_COMPILE_RING"):
            f = FLAGS.get(name)
            assert f is not None and f.key_neutral, name
            assert not f.folds_into
