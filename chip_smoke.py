#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the online-learning main path once, through the entry
points a user calls, at the full width of the model the repo is built
around: hashed-CTR logistic regression at dim 65,536 (32 fields x 2,048
slots), micro-batches of 4,096 rows. Depth is cut (a few supersteps, a few
micro-batches, a few dozen requests); the data is random, made from a seed.

Three legs, one after the other, each ending in a host fetch of its result:

* batch trainer  — MemSourceBatchOp -> FeatureHasherBatchOp(field_aware) ->
  LogisticRegressionTrainBatchOp: L-BFGS supersteps through
  IterativeComQueue (one ``lax.while_loop`` + psum program; on TPU the bf16
  field-block branch);
* stream trainer — FtrlTrainStreamOp warm-started from the batch model over
  a few micro-batches with several snapshot emissions, donation on;
* server         — a snapshot loaded into LinearModelMapper,
  CompiledPredictor compiled at every default bucket, PredictServer
  answering requests with one ``swap_model`` in the middle; every score is
  compared with the host mapper under the written f32 tolerance below.

Nothing on the path may hide the device: the smoke fails unless the circuit
breaker never opened, no batch was served by the host fallback, no serve
fallback or kernel demotion was recorded, no serving loop respawned and no
fallback warning fired. With more than one device it also checks that the
training data and the FTRL (z, n) state live on every device and that one
mesh-sharded serving dispatch agrees with the single-device scores.

It spawns no process that touches JAX (one process owns the chip), needs no
network, and exits non-zero — printing no result line — when JAX finds no
TPU. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

    python chip_smoke.py          # on the chip; x64 off; the compile cache
                                  # goes where JAX_COMPILATION_CACHE_DIR
                                  # says, else to <repo>/.jax_cache
"""

from __future__ import annotations

import json
import os
import sys
import time
import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

_F32_EPS = float(np.finfo(np.float32).eps)

#: warnings that mean some layer quietly left the compiled/device path
_FALLBACK_WARNING_MARKS = (
    "AOT lower failed", "demoted to its XLA path", "demoting to",
    "falls back to the host mapper", "refusing artifact",
    "AOT admission warming failed", "failed its first dispatch")


class SmokeFailure(RuntimeError):
    """A phase of the smoke produced a wrong or hidden result."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(f"chip_smoke: {message}", flush=True)


@dataclass(frozen=True)
class SmokeConfig:
    """Sizes of one smoke run. ``FULL`` is what the chip runs; the tier-1
    test runs the same legs at a tiny width on the CPU mesh."""
    n_fields: int = 32
    field_size: int = 2048          # dim = n_fields * field_size = 65,536
    batch: int = 4096               # micro-batch rows
    train_rows: int = 16384         # batch-trainer rows (4 micro-batches)
    lbfgs_iters: int = 4
    stream_batches: int = 6         # event times 0..5
    emit_every: float = 2.0         # -> emissions at t=2, t=4 and the final
    requests: int = 48              # served requests, half before the swap
    vocab: int = 200                # distinct raw tokens per field: each is
                                    # seen often enough that the trainers
                                    # generalize instead of memorizing
    seed: int = 20260926

    @property
    def dim(self) -> int:
        return self.n_fields * self.field_size


FULL = SmokeConfig()


# ---------------------------------------------------------------------------
# data: raw string rows, made from a seed
# ---------------------------------------------------------------------------

def _schema(cfg: SmokeConfig) -> str:
    return ", ".join([f"c{k} STRING" for k in range(cfg.n_fields)]
                     + ["click LONG"])


def make_rows(cfg: SmokeConfig, n_rows: int, seed: int):
    """``n_rows`` raw CTR rows: one categorical token per field, and a
    click drawn from a logistic model over per-(field, token) effects so
    the trainers have something to learn."""
    from alink_tpu.common.mtable import MTable
    rng = np.random.RandomState(cfg.seed)            # the effects are fixed
    effect = rng.randn(cfg.n_fields, cfg.vocab) \
        * (rng.rand(cfg.n_fields, cfg.vocab) < 0.2)
    rng = np.random.RandomState(seed)
    tok = rng.randint(0, cfg.vocab, size=(n_rows, cfg.n_fields))
    margin = effect[np.arange(cfg.n_fields)[None, :], tok].sum(1)
    click = (rng.rand(n_rows) < 1.0 / (1.0 + np.exp(-margin))).astype(np.int64)
    cols = {f"c{k}": np.char.add("v", tok[:, k].astype("U8")).astype(object)
            for k in range(cfg.n_fields)}
    cols["click"] = click
    return MTable(cols, _schema(cfg))


def _hasher_kw(cfg: SmokeConfig) -> dict:
    cols = [f"c{k}" for k in range(cfg.n_fields)]
    return dict(selected_cols=cols, categorical_cols=cols, output_col="vec",
                num_features=cfg.dim, field_aware=True,
                reserved_cols=["click"])


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _metric_total(name: str, labels: Optional[Dict[str, str]] = None) -> float:
    """Sum of one metric's series (optionally those matching ``labels``)."""
    from alink_tpu.common.metrics import get_registry
    total = 0.0
    for rec in get_registry().snapshot():
        if rec["name"] != name or "value" not in rec:
            continue
        if labels and any(rec["labels"].get(k) != v
                          for k, v in labels.items()):
            continue
        total += float(rec["value"])
    return total


def _allreduce_calls() -> float:
    """psums charged so far (the manifest records them as AllReduce)."""
    return _metric_total("alink_collective_calls_total",
                         {"collective": "AllReduce"})


def _memory_per_device() -> List[Optional[dict]]:
    """``bytes_in_use`` / ``peak_bytes_in_use`` per device (None where the
    backend reports no memory statistics, e.g. the CPU)."""
    import jax
    out = []
    for d in jax.devices():
        st = d.memory_stats()
        out.append(None if not st else
                   {"bytes_in_use": int(st.get("bytes_in_use", 0)),
                    "peak_bytes_in_use": int(st.get("peak_bytes_in_use", 0))})
    return out


# ---------------------------------------------------------------------------
# leg 1: batch trainer
# ---------------------------------------------------------------------------

def leg_batch_trainer(cfg: SmokeConfig):
    """Hash + L-BFGS through the operators; returns (warm_op, info)."""
    import jax
    from alink_tpu.operator.batch.classification.linear import (
        LogisticRegressionTrainBatchOp)
    from alink_tpu.operator.batch.feature.feature_ops import (
        FeatureHasherBatchOp)
    from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
    from alink_tpu.operator.common.linear.base import (
        LinearModelDataConverter)

    n_dev = len(jax.devices())
    rows = make_rows(cfg, cfg.train_rows, cfg.seed + 1)
    psum_before = _allreduce_calls()
    steps_before = _metric_total("alink_comqueue_supersteps_total")

    def train():
        feat = FeatureHasherBatchOp(**_hasher_kw(cfg)).link_from(
            MemSourceBatchOp(rows))
        op = LogisticRegressionTrainBatchOp(
            vector_col="vec", label_col="click",
            max_iter=cfg.lbfgs_iters).link_from(feat)
        op.get_output_table()                # the host fetch of the model
        return op

    warm, first_s = _timed(train)
    _, second_s = _timed(train)
    model = LinearModelDataConverter.load_table(warm.get_output_table())
    coef = np.asarray(model.coef)
    check(coef.shape == (cfg.dim + 1,),
          f"batch model has {coef.shape} coefficients, want {cfg.dim + 1} "
          f"(intercept + {cfg.dim})")
    check(bool(np.isfinite(coef).all()), "batch model has non-finite weights")
    check(float(np.abs(coef).max()) > 0.0, "batch model is all zeros")
    curve = np.asarray(
        warm.get_side_output(0).get_output_table().col("loss"), np.float64)
    check(len(curve) >= 2 and bool(np.isfinite(curve).all()),
          f"L-BFGS loss curve is {curve}")
    check(curve[-1] < curve[0],
          f"L-BFGS did not reduce the loss: {curve[0]} -> {curve[-1]}")
    # the BSP program's psums, as the engine charged its manifest: two per
    # superstep (the gradient, then the line-search losses)
    psums = _allreduce_calls() - psum_before
    steps = _metric_total("alink_comqueue_supersteps_total") - steps_before
    check(steps >= 2 * 2 and psums == 2 * steps,
          f"the L-BFGS program's manifest charged {psums} psums over "
          f"{steps} supersteps in two trainings; want 2 per superstep")
    mem = _memory_per_device()
    if n_dev > 1 and all(m is not None for m in mem):
        # every device held at least its row shard of the hashed indices
        shard_bytes = (cfg.train_rows // n_dev) * (cfg.n_fields + 1) * 4
        for i, m in enumerate(mem):
            check(m["peak_bytes_in_use"] >= shard_bytes,
                  f"device {i} peaked at {m['peak_bytes_in_use']} bytes "
                  f"during batch training; its row shard alone is "
                  f"{shard_bytes} bytes — the training data did not reach it")
    info = {"first_s": round(first_s, 3), "second_s": round(second_s, 3),
            "supersteps": int(len(curve)),
            "loss_first": float(curve[0]), "loss_last": float(curve[-1]),
            "psums_per_superstep": int(psums // steps), "memory": mem}
    return warm, info


# ---------------------------------------------------------------------------
# leg 2: stream trainer
# ---------------------------------------------------------------------------

def leg_stream_trainer(cfg: SmokeConfig, warm):
    """FTRL over a few micro-batches; returns (snapshot tables, info)."""
    import jax
    from alink_tpu.engine.comqueue import donation_enabled
    from alink_tpu.operator.stream.batch_twins import FeatureHasherStreamOp
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        FtrlTrainStreamOp)
    from alink_tpu.operator.stream.source.sources import MemSourceStreamOp

    check(donation_enabled(), "ALINK_TPU_DONATE is off; the smoke runs the "
                              "default (donating) step programs")
    n_dev = len(jax.devices())
    dim_pad = -(-(cfg.dim + 1) // n_dev) * n_dev
    rows = make_rows(cfg, cfg.batch * cfg.stream_batches, cfg.seed + 2)
    state_seen: List[dict] = []

    def watch_state(w_device, info):
        # the live (z, n) — and the weights derived from them — are the
        # (dim_pad,) arrays alive at an emission boundary
        live = [a for a in jax.live_arrays()
                if a.shape == (dim_pad,) and not a.is_deleted()]
        state_seen.append({
            "arrays": len(live),
            "device_sets": sorted({len(a.sharding.device_set)
                                   for a in live}),
            "shard_devices": sorted({len({s.device
                                          for s in a.addressable_shards})
                                     for a in live}),
            "memory": _memory_per_device()})
        return False                         # take the host snapshot too

    def drain():
        src = MemSourceStreamOp(rows, batch_size=cfg.batch,
                                time_per_batch=1.0)
        feat = FeatureHasherStreamOp(**_hasher_kw(cfg)).link_from(src)
        ftrl = FtrlTrainStreamOp(
            warm, vector_col="vec", label_col="click", alpha=0.05,
            beta=1.0, l1=1e-5, l2=1e-5,
            time_interval=cfg.emit_every).link_from(feat)
        ftrl.set_device_snapshot_consumer(watch_state)
        return [mt for _t, mt in ftrl.timed_batches()]

    snaps, first_s = _timed(drain)
    seen_first = list(state_seen)
    snaps2, second_s = _timed(drain)
    check(len(snaps) >= 3,
          f"stream trainer emitted {len(snaps)} snapshots, want >= 3 "
          f"(two interval emissions and the final one)")
    from alink_tpu.operator.common.linear.base import (
        LinearModelDataConverter)
    coefs = [np.asarray(LinearModelDataConverter.load_table(s).coef)
             for s in snaps]
    for c in coefs:
        check(c.shape == (cfg.dim + 1,) and bool(np.isfinite(c).all()),
              "an FTRL snapshot is mis-shaped or non-finite")
    check(not np.array_equal(coefs[0], coefs[-1]),
          "the FTRL weights did not move between the first and the last "
          "snapshot")
    coefs2 = [np.asarray(LinearModelDataConverter.load_table(s).coef)
              for s in snaps2]
    replay_equal = len(coefs) == len(coefs2) and all(
        np.array_equal(a, b) for a, b in zip(coefs, coefs2))
    check(replay_equal, "a second drain of the same stream gave different "
                        "snapshots (donated state reused, or a stale step "
                        "program)")
    for seen in seen_first:
        check(seen["arrays"] >= 2, f"no live (z, n) found: {seen}")
        check(seen["device_sets"] == [n_dev]
              and seen["shard_devices"] == [n_dev],
              f"FTRL (z, n) is not sharded over all {n_dev} devices: {seen}")
        for i, m in enumerate(seen["memory"]):
            check(m is None or m["bytes_in_use"] > 0,
                  f"device {i} holds no bytes while the FTRL state is live")
    info = {"first_s": round(first_s, 3), "second_s": round(second_s, 3),
            "micro_batches": cfg.stream_batches, "snapshots": len(snaps),
            "state_devices": n_dev, "replay_bitwise": bool(replay_equal),
            "memory": _memory_per_device()}
    return snaps, info


# ---------------------------------------------------------------------------
# leg 3: server
# ---------------------------------------------------------------------------

def _pos_probs(table, pos_label: str) -> np.ndarray:
    """P(positive) per row from an output table's detail column."""
    col = table.col("detail")
    probs = getattr(col, "probs", None)
    if probs is not None:
        return np.asarray(probs)[:, 0].astype(np.float64)
    return np.asarray([json.loads(s)[pos_label] for s in col], np.float64)


def _tolerance(vec_col, coef: np.ndarray) -> np.ndarray:
    """The written f32 tolerance on P(positive), per request row.

    The server rounds each product ``val_j * w[idx_j]`` to float32, sums
    the n products strictly left to right and adds the bias, all in
    float32 (unit roundoff ``u = eps/2``); the host mapper does the same
    in float64. The standard running-sum bound puts the f32 margin within
    ``(n + 1) * u * S`` of the exact one, ``S = |b| + sum_j |val_j * w_j|``;
    the sigmoid's slope is at most 1/4, and evaluating it in float32 adds
    a few ulps of a number <= 1:

        |dp| <= eps_f32 * ((n + 1) / 8 * S + 8)

    A worst-case bound, so it cannot flake; at this model's S a bf16
    score path (``ALINK_TPU_SERVE_DTYPE=bf16``) breaks it several-fold."""
    idx, val = np.asarray(vec_col.idx), np.asarray(vec_col.val, np.float64)
    S = np.abs(coef[0]) + np.abs(val * coef[1:][idx]).sum(1)
    n = idx.shape[1]
    return _F32_EPS * ((n + 1) / 8.0 * S + 8.0)


def _compare(got: np.ndarray, want: np.ndarray, tol: np.ndarray,
             what: str) -> dict:
    diff = np.abs(got - want)
    worst = int(np.argmax(diff - tol))
    check(bool((diff <= tol).all()),
          f"{what}: P(positive) differs from the host mapper by "
          f"{diff[worst]:.3e} at row {worst} (tolerance {tol[worst]:.3e})")
    return {"max_abs_diff": float(diff.max()),
            "bitwise": bool(np.array_equal(got, want))}


def leg_server(cfg: SmokeConfig, warm, snaps):
    """Compiled serving at every default bucket + a served swap."""
    import jax
    from alink_tpu.common.params import Params
    from alink_tpu.operator.batch.feature.feature_ops import (
        FeatureHasherBatchOp)
    from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
    from alink_tpu.operator.common.linear.base import (
        LinearModelDataConverter)
    from alink_tpu.operator.common.linear.mapper import LinearModelMapper
    from alink_tpu.serving.predictor import (DEFAULT_BUCKETS,
                                             CompiledPredictor)
    from alink_tpu.serving.server import PredictServer

    n_dev = len(jax.devices())
    n_req = max(cfg.requests, max(DEFAULT_BUCKETS))
    feat = FeatureHasherBatchOp(**_hasher_kw(cfg)).link_from(
        MemSourceBatchOp(make_rows(cfg, n_req, cfg.seed + 3)))
    req = feat.get_output_table().select(["vec"])
    model_a, model_b = snaps[0], snaps[-1]

    def mapper_for(model_table):
        m = LinearModelMapper(model_table.schema, req.schema,
                              Params({"vector_col": "vec",
                                      "prediction_col": "pred",
                                      "prediction_detail_col": "detail"}))
        m.load_model(model_table)
        return m

    def model_of(model_table):
        md = LinearModelDataConverter.load_table(model_table)
        return np.asarray(md.coef, np.float64), str(md.label_values[0])

    mapper_a, mapper_b = mapper_for(model_a), mapper_for(model_b)
    coef_a, pos = model_of(model_a)
    coef_b, _ = model_of(model_b)
    vec = req.col("vec")
    tol_a, tol_b = _tolerance(vec, coef_a), _tolerance(vec, coef_b)
    host_a = _pos_probs(mapper_a.map_table(req), pos)
    host_b = _pos_probs(mapper_b.map_table(req), pos)
    check(float(np.abs(host_a - host_b).max()) > float(tol_a.max()),
          "the two served models are indistinguishable; the swap check "
          "would prove nothing")
    unsaturated = float(np.mean((host_a > 0.02) & (host_a < 0.98)))
    check(unsaturated >= 0.5,
          f"only {unsaturated:.0%} of the reference probabilities are off "
          f"the sigmoid's flat ends; the tolerance check would have no "
          f"teeth")

    predictor = CompiledPredictor(mapper_a, name="chip_smoke")
    check(predictor.buckets == tuple(DEFAULT_BUCKETS),
          f"predictor buckets {predictor.buckets} are not the defaults")
    per_bucket = {}
    parity = {"max_abs_diff": 0.0, "bitwise": True}
    first_s = second_s = 0.0
    for b in predictor.buckets:
        part = req.first_n(b)
        out, t1 = _timed(lambda: predictor.predict_table(part))
        out2, t2 = _timed(lambda: predictor.predict_table(part))
        got = _pos_probs(out, pos)
        check(np.array_equal(got, _pos_probs(out2, pos)),
              f"bucket {b}: two dispatches of the same rows differ")
        res = _compare(got, host_a[:b], tol_a[:b], f"bucket {b}")
        parity["max_abs_diff"] = max(parity["max_abs_diff"],
                                     res["max_abs_diff"])
        parity["bitwise"] = parity["bitwise"] and res["bitwise"]
        per_bucket[str(b)] = {"first_s": round(t1, 4),
                              "second_s": round(t2, 4)}
        first_s += t1
        second_s += t2
    programs = predictor.cache_stats()["programs"]
    check(programs == len(predictor.buckets),
          f"{programs} serving programs compiled for "
          f"{len(predictor.buckets)} buckets")

    rows = [req.row(i) for i in range(cfg.requests)]
    half = cfg.requests // 2
    with PredictServer(predictor, name="chip_smoke") as server:
        def serve(lo, hi):
            futs = [server.submit(rows[i]) for i in range(lo, hi)]
            out = [f.result(timeout=120) for f in futs]
            # response row = (vec, pred, detail): detail is last
            return np.asarray([json.loads(r[-1])[pos] for r in out])

        got_a = serve(0, half)
        version = server.swap_model(model_b)
        got_b = serve(half, cfg.requests)
        stats = server.stats()
    _compare(got_a, host_a[:half], tol_a[:half], "served before the swap")
    _compare(got_b, host_b[half:cfg.requests], tol_b[half:cfg.requests],
             "served after the swap")
    check(stats["requests"] == cfg.requests and stats["failed"] == 0,
          f"server answered {stats['requests']} of {cfg.requests} "
          f"requests, {stats['failed']} failed")
    check(stats["model_version"] == version and version >= 2,
          f"swap_model did not install a new version: {stats}")
    # nothing on the serving path hid the device
    check(stats["fallback_batches"] == 0,
          f"{stats['fallback_batches']} batches were served by the HOST "
          f"fallback")
    brk = stats["breaker"]
    check(brk["opens"] == 0 and brk["state"] == "closed",
          f"the serving circuit breaker engaged: {brk}")
    check(stats["loop_respawns"] == 0 and stats["quarantined"] == 0,
          f"a serving loop crashed and was respawned: {stats}")
    check(stats["shed"] == 0, f"{stats['shed']} requests were shed")
    served_on = sorted(str(d) for d in predictor.devices)

    sharded = None
    if n_dev > 1:
        # one mesh-sharded dispatch must agree with the single-device
        # scores under the same tolerance
        psum_before = _allreduce_calls()
        # (the swap left model B active in the single-device predictor)
        sp = CompiledPredictor(mapper_b, sharded=True,
                               name="chip_smoke_sharded")
        check(sp.sharded, f"sharded serving was refused on {n_dev} devices")
        b = 32
        part = req.first_n(b)
        single = _pos_probs(predictor.predict_table(part), pos)
        got = _pos_probs(sp.predict_table(part), pos)
        res = _compare(got, host_b[:b], tol_b[:b], "sharded dispatch")
        check(bool((np.abs(got - single) <= tol_b[:b]).all()),
              "sharded and single-device scores disagree")
        check(len(sp.devices) == n_dev,
              f"the sharded model sits on {len(sp.devices)} devices, "
              f"want {n_dev}")
        check(_allreduce_calls() > psum_before,
              "the sharded dispatch charged no psum to the manifest")
        sharded = {"devices": len(sp.devices),
                   "bitwise_vs_single": bool(np.array_equal(got, single)),
                   **res}
    info = {"first_s": round(first_s, 3), "second_s": round(second_s, 3),
            "buckets": per_bucket, "programs": programs,
            "requests": stats["requests"], "batches": stats["batches"],
            "swaps": 1, "model_version": stats["model_version"],
            "parity_vs_host": parity, "unsaturated_share": unsaturated,
            "served_on": served_on,
            "sharded": sharded,
            "fallback_batches": stats["fallback_batches"],
            "breaker_opens": brk["opens"],
            "loop_respawns": stats["loop_respawns"],
            "memory": _memory_per_device()}
    return info


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def device_report() -> dict:
    """The device as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run_legs(cfg: SmokeConfig) -> dict:
    """The three legs plus the no-hidden-fallback checks, on whatever
    devices JAX has. ``main`` refuses to get here without a TPU; the
    tier-1 test calls this directly at a tiny width on the CPU mesh."""
    import jax
    import jaxlib
    from alink_tpu import native
    from alink_tpu.common.metrics import MetricsRegistry, set_registry
    from alink_tpu.common.mlenv import place_compile_cache, use_local_env

    set_registry(MetricsRegistry())
    env = use_local_env()                    # one session over every device
    dev = device_report()
    check(env.num_workers == dev["count"],
          f"the session spans {env.num_workers} of {dev['count']} devices")
    cache_dir = place_compile_cache()
    check(jax.config.jax_compilation_cache_dir == cache_dir,
          f"compile cache is {jax.config.jax_compilation_cache_dir!r}, "
          f"expected {cache_dir!r}")
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']} x64={bool(jax.config.jax_enable_x64)}")
    say(f"compile cache dir={cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})"
        f" entries_at_start={entries}")
    lib, native_s = _timed(native.get_lib)
    native_state = "built" if lib is not None else "absent"
    say(f"native: {native_state} ({native_s:.1f} s)")
    say(f"model: hashed-CTR logistic regression dim={cfg.dim} "
        f"({cfg.n_fields} fields x {cfg.field_size}) micro-batch={cfg.batch}")

    legs: Dict[str, dict] = {}
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        warm, legs["batch_trainer"] = leg_batch_trainer(cfg)
        say(f"leg batch_trainer ok {_brief(legs['batch_trainer'])}")
        snaps, legs["stream_trainer"] = leg_stream_trainer(cfg, warm)
        say(f"leg stream_trainer ok {_brief(legs['stream_trainer'])}")
        legs["server"] = leg_server(cfg, warm, snaps)
        say(f"leg server ok {_brief(legs['server'])}")
    hidden = [str(w.message) for w in caught
              if any(m in str(w.message) for m in _FALLBACK_WARNING_MARKS)]
    counters = {
        "breaker_opens": legs["server"]["breaker_opens"],
        "fallback_batches": legs["server"]["fallback_batches"],
        "serve_fallback_total": _metric_total("alink_serve_fallback_total"),
        "breaker_fallback_total":
            _metric_total("alink_serve_breaker_fallback_total"),
        "kernel_demotions_total":
            _metric_total("alink_kernel_demotions_total"),
        "aot_refusals_total": _metric_total("alink_aot_refusals_total"),
        # the legs run unsupervised (no OnlineDag): the only stage that
        # can restart is a serving loop
        "stage_restarts": legs["server"]["loop_respawns"],
        "fallback_warnings": len(hidden),
    }
    say("hidden-fallback counters: "
        + " ".join(f"{k}={int(v)}" for k, v in counters.items()))
    check(not hidden, f"a fallback warning fired: {hidden[:3]}")
    for k, v in counters.items():
        check(v == 0, f"{k} = {v}; the run left the compiled/device path")
    return {"device": dev, "jax": jax.__version__,
            "x64": bool(jax.config.jax_enable_x64),
            "compile_cache_dir": cache_dir,
            "compile_cache_entries_at_start": entries,
            "native": native_state, "dim": cfg.dim, "batch": cfg.batch,
            "legs": legs, "counters": counters,
            "first_call_s": round(sum(l["first_s"]
                                      for l in legs.values()), 3),
            "second_call_s": round(sum(l["second_s"]
                                       for l in legs.values()), 3),
            "claim": None}


def _brief(info: dict) -> str:
    keep = ("first_s", "second_s", "supersteps", "psums_per_superstep",
            "snapshots",
            "state_devices", "programs", "requests", "parity_vs_host",
            "served_on", "sharded")
    return " ".join(f"{k}={info[k]}" for k in keep if k in info)


def require_tpu() -> None:
    """Fail — before any leg runs — unless JAX's first device is a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: jax.devices()[0].platform is "
            f"{dev.platform!r} ({dev.device_kind}); this smoke proves the "
            f"program on the chip and does not carry on without one")


def main() -> int:
    require_tpu()
    t0 = time.perf_counter()
    try:
        summary = run_legs(FULL)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    say(f"all legs ok in {time.perf_counter() - t0:.1f} s "
        f"(first calls {summary['first_call_s']} s, "
        f"second calls {summary['second_call_s']} s)")
    say("summary " + json.dumps(summary, sort_keys=False))
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
