"""The sweep executor — N hyperparameter points as one BSP program.

Design contract (ISSUE 12): per-point sweep results must be **bitwise
identical to the serial fit of that point**. The PR 10/11 war story
applies — XLA's shape-dependent tiling (and FMA contraction) rounds the
same reduction differently at different shapes — so the points lane is
NOT a vmap (which would batch the data matvec into a differently-tiled
matmul). Instead the per-point kernel mirrors the serial superstep
op-for-op and the population runs under ``jax.lax.map``: a fixed-order
scan whose body executes at exactly the serial program's shapes. Same
ops, same shapes, same order → same rounding, proven bitwise by
tests/test_sweep.py on the f64 test mesh.

Execution shape:

* carry-resident hyperparameters ride as ``(points,)`` broadcast lanes
  (``swh_*``); per-point model state rides the while-loop carry with a
  ``(points,)`` leading axis (``pt_*``);
* collectives run inside the mapped body through the PR-7 manifest
  wrappers — per superstep the compiled program executes exactly
  ``points ×`` the serial program's collective set (set-identical HLO;
  pruning masks updates and therefore adds NO collectives);
* converged and pruned points FREEZE: their step output is discarded by
  a per-point ``where`` mask, so a survivor's trajectory is untouched
  by its neighbors and a frozen point's final state is its serial
  fixed point;
* ASHA successive halving runs at the engine's chunk boundaries
  (``IterativeComQueue.set_boundary`` → ``recovery.drive``): the rung
  hook fetches the per-point loss lane (the PR-4 probe discipline —
  device scalars read only at boundaries, zero host callbacks inside
  the program), keeps the top ``1/eta`` deterministically (rank by
  ``(loss, point index)``, NaN last — seed-free and reproducible), and
  flips the carry-resident alive mask. Geometry is constant, so the
  compiled program count equals the number of trace-shaping compile
  groups no matter the population size or rung schedule.

Checkpoint/resume and async snapshots (PR 2/5) work unchanged for the
whole population: the sweep carry is an ordinary engine carry, and the
rung hook re-derives its (deterministic) decisions after a resume.
"""

from __future__ import annotations

import functools as _functools
import threading
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .plan import AshaConfig, SweepPlan

__all__ = ["SweepResult", "FtrlSweepResult", "record_sweep_fallback",
           "sweep_enabled", "sweep_eta", "sweep_rung", "sweep_optimize",
           "sweep_kmeans", "sweep_ftrl"]


# -- flags ------------------------------------------------------------------

def sweep_enabled() -> bool:
    """``ALINK_TPU_SWEEP`` (default off): route GridSearchCV /
    GridSearchTVSplit candidate loops through the sweep engine when
    every grid axis is carry-resident for a supported estimator. Folded
    into the sweep program-cache key (registry-declared), so a toggle
    can never reuse a stale compiled sweep program."""
    from ..common.flags import flag_value
    return bool(flag_value("ALINK_TPU_SWEEP", False))


def sweep_eta() -> int:
    """``ALINK_TPU_SWEEP_ETA``: the default ASHA reduction factor."""
    from ..common.flags import flag_value
    return int(flag_value("ALINK_TPU_SWEEP_ETA", 3))


def sweep_rung() -> int:
    """``ALINK_TPU_SWEEP_RUNG``: default rung period in supersteps for
    sweeps that enable pruning without an explicit AshaConfig
    (0 = ``max(1, max_iter // 4)``)."""
    from ..common.flags import flag_value
    return int(flag_value("ALINK_TPU_SWEEP_RUNG", 0))


# -- fallback observability (the serving tier's contract, shared via
# common.metrics.record_fallback_once) --------------------------------------
# A silently-serial sweep is the failure mode this exists to kill: every
# time the tuning layer declines the sweep engine it records a labelled
# counter plus ONE RuntimeWarning per (estimator, reason).

# ``reason`` must stay a SMALL ENUM (metric label): request-specific
# text goes in ``detail`` (warning only).
FALLBACK_REASONS = ("unsupported-estimator", "trace-shaping-axis",
                    "unsupported-evaluator", "sweep-error")


def record_sweep_fallback(estimator: str, reason: str,
                          detail: str = "") -> None:
    """``alink_sweep_fallback_total{estimator=, reason=}`` + one
    RuntimeWarning per (estimator, reason) pair per process."""
    from ..common.metrics import record_fallback_once
    record_fallback_once(
        "sweep", "alink_sweep_fallback_total",
        {"estimator": estimator, "reason": reason},
        f"tuning sweep falls back to the serial candidate loop for "
        f"{estimator}: {reason}{' (' + detail + ')' if detail else ''} "
        f"(recorded as alink_sweep_fallback_total{{estimator="
        f"{estimator!r},reason={reason!r}}}; this warning fires once "
        f"per estimator+reason)")


def _reset_fallback_warnings() -> None:
    """Test hook: re-arm the once-per-(estimator, reason) warnings."""
    from ..common.metrics import reset_fallback_warnings
    reset_fallback_warnings("sweep")


# -- result -----------------------------------------------------------------

@dataclass
class SweepResult:
    """Per-point outcomes of one sweep (all groups merged).

    ``values`` holds the trainer's model state per point — ``coef``
    ``(P, dim)`` for the optimizers; ``centroids`` ``(P, k, d)`` +
    ``cluster_weights`` ``(P, k)`` for k-means (lists of per-point
    arrays instead when a trace-shaping ``k`` axis makes the geometry
    ragged across compile groups). ``steps[p]`` is the
    executed superstep count of point ``p`` (== the serial fit's
    ``step_count``); ``final_loss[p]`` its last computed training loss
    (weighted inertia for k-means — computed regardless of
    ALINK_TPU_HEALTH, so rung decisions never flip with telemetry); ``alive[p]`` whether ASHA kept it; ``rungs`` the
    boundary decisions in order. ``programs`` counts compiled sweep
    programs (== trace-shaping groups)."""
    trainer: str
    points: List[Dict[str, Any]]
    values: Dict[str, np.ndarray]
    steps: np.ndarray
    final_loss: np.ndarray
    alive: np.ndarray
    converged: np.ndarray
    loss_curves: List[np.ndarray]
    rungs: List[Dict[str, Any]] = field(default_factory=list)
    programs: int = 1

    @property
    def num_points(self) -> int:
        return len(self.points)

    @property
    def pruned_at(self) -> Dict[int, int]:
        out: Dict[int, int] = {}
        for r in self.rungs:
            for i in r["pruned"]:
                out.setdefault(int(i), int(r["step"]))
        return out

    def survivors(self) -> List[int]:
        return [int(i) for i in np.flatnonzero(self.alive)]

    @property
    def best(self) -> int:
        """The winning point: lowest final loss among survivors, ties
        broken by lowest point index — deterministic and seed-free."""
        live = np.flatnonzero(self.alive)
        if live.size == 0:          # defensive: never prunes to zero
            live = np.arange(len(self.points))
        key = np.where(np.isfinite(self.final_loss[live]),
                       self.final_loss[live], np.inf)
        order = np.lexsort((live, key))
        return int(live[order[0]])


# -- shared kernel plumbing -------------------------------------------------

def _sweep_criterion(ctx):
    """Stop when every still-alive point has converged (replicated)."""
    import jax.numpy as jnp
    return jnp.all(ctx.get_obj("pt_conv") | ~ctx.get_obj("sw_alive"))


def _freeze_cond(active, step_fn, pc_p):
    """Per-point freeze: a pruned or converged point SKIPS its step
    (``lax.cond`` — the frozen branch returns the carry untouched, so
    pruning buys real wall clock, not just masked writes). The
    predicate is replicated (computed from the replicated alive/conv
    lanes), so every worker takes the same branch and the live branch's
    collectives never deadlock; the compiled program's collective SET is
    the live branch's — identical to the unswept program's — no matter
    how many points are frozen."""
    import jax
    return jax.lax.cond(active, step_fn, lambda pc: dict(pc), pc_p)


def _make_asha_hook(asha: AshaConfig, num_points: int,
                    rung_log: List[Dict[str, Any]]) -> Callable:
    """The chunk-boundary rung: fetch the per-point loss lane (ONE
    batched device_get of three small arrays), keep the deterministic
    top ``ceil(alive/eta)``, flip the carry-resident alive mask. Runs
    AFTER the boundary snapshot published and re-runs after a resume —
    the decision is a pure function of the carry, so kill-and-resume
    reproduces it bitwise.

    Once the population is down to ``min_points`` there are no more
    decisions to make: the hook marks itself ``exhausted`` and the
    driver (persistence off) runs the remaining supersteps as ONE chunk
    — rung boundaries are host syncs, and paying them for a settled
    population is pure overhead."""

    def hook(stacked, step):
        import jax
        alive_s, conv_s, loss_s = jax.device_get(
            [stacked["sw_alive"], stacked["pt_conv"],
             stacked["pt_cur_loss"]])
        alive = np.asarray(alive_s)[0]
        conv = np.asarray(conv_s)[0]
        loss = np.asarray(loss_s)[0].astype(np.float64)
        live = np.flatnonzero(alive)
        keep_n = max(int(asha.min_points),
                     int(np.ceil(live.size / float(asha.eta))))
        pruned: List[int] = []
        new_alive = alive
        if keep_n < live.size:
            # deterministic, seed-free: rank by (loss, point index),
            # non-finite losses last — the reproducibility contract
            key = np.where(np.isfinite(loss[live]), loss[live], np.inf)
            order = np.lexsort((live, key))
            keep = live[order[:keep_n]]
            new_alive = np.zeros(num_points, bool)
            new_alive[keep] = True
            pruned = sorted(int(i) for i in set(live) - set(keep))
        rung_log.append({"step": int(step),
                         "alive_before": int(live.size),
                         "alive_after": int(np.count_nonzero(new_alive)),
                         "pruned": pruned})
        if np.count_nonzero(new_alive) <= int(asha.min_points):
            hook.exhausted = True
        if not pruned:
            return None
        from ..common.metrics import get_registry, metrics_enabled
        if metrics_enabled():
            get_registry().inc("alink_sweep_pruned_points_total",
                               len(pruned))
        nw = np.asarray(alive_s).shape[0]
        out = dict(stacked)
        out["sw_alive"] = np.broadcast_to(new_alive,
                                          (nw, num_points)).copy()
        if np.all(conv | ~new_alive):
            # the surviving population is fully converged: stop now
            # instead of burning one more (frozen) chunk
            out["__stop"] = np.ones(nw, bool)
        return out

    hook.exhausted = False
    return hook


def _run_sweep_queue(*, kind: str, stage, parts: Dict[str, Any],
                     bcast: Dict[str, Any], env, max_iter: int, seed: int,
                     key_tail: Tuple, num_points: int,
                     asha: Optional[AshaConfig],
                     checkpoint_dir: Optional[str],
                     checkpoint_keep: int, resume_from: Optional[str],
                     rung_log: List[Dict[str, Any]]):
    """Build and exec the ONE swept BSP program of a compile group.

    This is the sweep's program factory (an alink-lint factory root):
    every flag read reachable from here must fold into the program key
    or be registry-declared key-neutral. ``ALINK_TPU_SWEEP`` folds —
    resolved at the plan derivation site (``common/plan.sweep_plan``,
    the ENV-KEY-FOLD checked site; the legacy program-key tuple is
    byte-identical) — and the ASHA knobs are key-neutral (host
    boundary pruning of a carry lane; chunk limits are traced
    scalars)."""
    from ..common import compileledger
    from ..common.plan import legacy_sweep_program_key, sweep_plan
    from ..engine import IterativeComQueue

    compileledger.subsystem_start("sweep")
    queue = IterativeComQueue(env=env, max_iter=int(max_iter),
                              seed=int(seed))
    for k, v in parts.items():
        queue.init_with_partitioned_data(k, v)
    for k, v in bcast.items():
        queue.init_with_broadcast_data(k, v)
    queue.add(stage)
    queue.set_compare_criterion(_sweep_criterion)
    queue.set_program_key(
        legacy_sweep_program_key(sweep_plan(kind, tuple(key_tail))))
    if checkpoint_dir:
        queue.set_checkpoint(checkpoint_dir,
                             every=(asha.rung if asha is not None else 1),
                             keep_last=int(checkpoint_keep),
                             resume_from=resume_from)
    if asha is not None:
        queue.set_boundary(asha.rung,
                           _make_asha_hook(asha, num_points, rung_log))
    return queue.exec()


def _group_paths(checkpoint_dir: Optional[str],
                 resume_from: Optional[str], gi: int,
                 n_groups: int) -> Tuple[Optional[str], Optional[str]]:
    """Per-compile-group checkpoint/resume directories: multi-group
    sweeps snapshot each group under its own subdirectory so the
    signatures can never collide."""
    if not checkpoint_dir or n_groups <= 1:
        return checkpoint_dir, resume_from
    import os
    return (os.path.join(checkpoint_dir, f"group{gi}"),
            os.path.join(resume_from, f"group{gi}") if resume_from
            else None)


def _resolve_asha(asha, max_iter: int) -> Optional[AshaConfig]:
    """``None``/``False`` = no pruning; ``True`` = flag-driven defaults
    (``ALINK_TPU_SWEEP_ETA`` / ``ALINK_TPU_SWEEP_RUNG``); an
    ``AshaConfig`` passes through."""
    if not asha:
        return None
    if isinstance(asha, AshaConfig):
        return asha
    rung = sweep_rung() or max(1, int(max_iter) // 4)
    return AshaConfig(rung=rung, eta=sweep_eta())


# -- optimizer sweep kernels ------------------------------------------------
# Each point step mirrors the serial stage code in
# operator/common/optim/optimizers.py OP-FOR-OP (same helper calls, same
# association order); the only differences are (a) the carry-resident
# hypers arrive as traced per-point scalars and (b) the two AllReduce
# stages become manifest_psum calls at the same positions. The bitwise
# parity test (tests/test_sweep.py) is the load-bearing check that this
# mirror never drifts.

_QN_KEYS = ("coef", "coef_prev", "grad_prev", "step_scale", "loss_curve",
            "conv", "cur_loss")
_QN_MEM_KEYS = ("sk", "yk", "pos", "nvalid")


def _qn_point_step(obj, shard, pc, hyp, step, nw, axis, m, owlqn, dtype,
                   dim, steps_base, max_iter):
    import jax
    import jax.numpy as jnp

    from ..engine.communication import manifest_psum
    from ..operator.common.optim.optimizers import (_NUM_SEARCH_STEP,
                                                    _TINY, _pseudo_grad,
                                                    _two_loop, shard_grad,
                                                    shard_line)
    coef = pc["coef"]
    glw, eta = shard_grad(obj, shard, coef, dtype)
    glw = jnp.asarray(manifest_psum(glw, axis, name="sweep_glw",
                                    num_workers=nw))
    l1, l2 = hyp["l1"], hyp["l2"]
    W = jnp.maximum(glw[dim + 1], _TINY)
    g_plain = glw[:dim] / W + obj.l2_grad(coef, l2)
    loss_total = glw[dim] / W + obj.regular_loss(coef, l1, l2)
    loss_curve = jax.lax.dynamic_update_index_in_dim(
        pc["loss_curve"], loss_total.astype(dtype), step - 1, 0)
    if owlqn:
        g_dir = _pseudo_grad(g_plain, coef, l1, obj._reg_mask(coef))
    else:
        g_dir = g_plain
    gnorm = jnp.linalg.norm(g_dir) / jnp.maximum(1.0, jnp.linalg.norm(coef))
    conv = gnorm < hyp["eps"]
    out = {"coef_prev": coef, "grad_prev": g_plain,
           "loss_curve": loss_curve, "conv": conv,
           "cur_loss": loss_total.astype(dtype)}
    if m > 0:
        push = step > 1
        snew = coef - pc["coef_prev"]
        ynew = g_plain - pc["grad_prev"]
        pos = pc["pos"]
        sk = jnp.where(push, pc["sk"].at[pos].set(snew), pc["sk"])
        yk = jnp.where(push, pc["yk"].at[pos].set(ynew), pc["yk"])
        pos = jnp.where(push, (pos + 1) % m, pos)
        nvalid = jnp.where(push, jnp.minimum(pc["nvalid"] + 1, m),
                           pc["nvalid"])
        out.update(sk=sk, yk=yk, pos=pos, nvalid=nvalid)
        d = _two_loop(g_dir, sk, yk, pos, nvalid, m)
    else:
        d = g_dir
    if owlqn:
        d = jnp.where(d * g_dir > 0, d, 0.0)
    steps = (hyp["lr"] * jnp.asarray(steps_base)) * pc["step_scale"]
    line = shard_line(obj, shard, coef, d, steps, eta, dtype)
    line = jnp.asarray(manifest_psum(line, axis, name="sweep_line",
                                     num_workers=nw))
    reg = jax.vmap(lambda s: obj.regular_loss(coef - s * d, l1, l2))(steps)
    total = line[:steps.shape[0]] / W + reg
    best = jnp.argmin(total)
    s_best = steps[best]
    new_coef = coef - s_best * d
    if owlqn:
        orthant = jnp.where(coef != 0, jnp.sign(coef), -jnp.sign(g_dir))
        new_coef = jnp.where(new_coef * orthant < 0, 0.0, new_coef)
    scale = pc["step_scale"]
    scale = jnp.where(best == 0, scale * 0.25,
                      jnp.where(best == 1, scale * 2.0,
                                jnp.where(best == _NUM_SEARCH_STEP,
                                          scale * 0.5, scale)))
    out["coef"] = new_coef
    out["step_scale"] = jnp.clip(scale, 1e-10, 1e6)
    return out


def _sgd_point_step(obj, shard, pc, hyp, step, key, nw, axis, dtype, dim):
    import jax
    import jax.numpy as jnp

    from ..engine.communication import manifest_psum
    from ..operator.common.optim.optimizers import _TINY
    coef = pc["coef"]
    mask = jax.random.bernoulli(key, hyp["frac"], shard["y"].shape)
    sub = dict(shard)
    sub["w"] = shard["w"] * mask.astype(shard["w"].dtype)
    g, loss, wsum = obj.calc_grad_shard(sub, coef)
    glw = jnp.concatenate([g, jnp.stack([loss, wsum])])
    glw = jnp.asarray(manifest_psum(glw, axis, name="sweep_glw",
                                    num_workers=nw))
    l1, l2 = hyp["l1"], hyp["l2"]
    wsum = glw[dim + 1]
    nonempty = wsum > 0
    W = jnp.maximum(wsum, _TINY)
    gg = glw[:dim] / W + obj.l2_grad(coef, l2)
    lr = hyp["lr"] / jnp.sqrt(step.astype(dtype))
    new_coef = coef - lr * gg
    # the serial path applies the L1 prox only when obj.l1 > 0 (a
    # trace-time branch); the lane twin selects on the traced l1 — the
    # branches agree bitwise at l1 == 0 (soft-threshold with thr 0 is
    # the identity up to signed zeros)
    thr = l1 * lr * obj._reg_mask(coef)
    soft = jnp.sign(new_coef) * jnp.maximum(jnp.abs(new_coef) - thr, 0.0)
    new_coef = jnp.where(l1 > 0, soft, new_coef)
    new_coef = jnp.where(nonempty, new_coef, coef)
    loss_total = glw[dim] / W + obj.regular_loss(coef, l1, l2)
    conv = nonempty & (jnp.linalg.norm(lr * gg) <
                       hyp["eps"] * jnp.maximum(1.0, jnp.linalg.norm(coef)))
    return {"coef": new_coef,
            "loss_curve": jax.lax.dynamic_update_index_in_dim(
                pc["loss_curve"], loss_total.astype(dtype), step - 1, 0),
            "conv": conv, "cur_loss": loss_total.astype(dtype)}


def _newton_point_step(obj, shard, pc, hyp, step, nw, axis, dtype, dim):
    import jax
    import jax.numpy as jnp

    from ..engine.communication import manifest_psum
    from ..operator.common.optim.optimizers import _TINY
    coef = pc["coef"]
    H, g, loss, wsum = obj.hessian_shard(shard, coef)
    # the serial program reduces H and glw through two separate
    # AllReduce stages, in this order — mirrored exactly
    H = jnp.asarray(manifest_psum(H, axis, name="sweep_H",
                                  num_workers=nw))
    glw = jnp.concatenate([g, jnp.stack([loss, wsum])])
    glw = jnp.asarray(manifest_psum(glw, axis, name="sweep_glw",
                                    num_workers=nw))
    l1, l2 = hyp["l1"], hyp["l2"]
    W = jnp.maximum(glw[dim + 1], _TINY)
    gg = glw[:dim] / W + obj.l2_grad(coef, l2)
    Hn = H / W
    reg_diag = l2 * obj._reg_mask(coef) + 1e-8
    Hn = Hn + jnp.diag(reg_diag.astype(Hn.dtype))
    d = jnp.linalg.solve(Hn, gg)
    loss_total = glw[dim] / W + obj.regular_loss(coef, l1, l2)
    conv = jnp.linalg.norm(d) < \
        hyp["eps"] * jnp.maximum(1.0, jnp.linalg.norm(coef))
    return {"coef": coef - d,
            "loss_curve": jax.lax.dynamic_update_index_in_dim(
                pc["loss_curve"], loss_total.astype(dtype), step - 1, 0),
            "conv": conv, "cur_loss": loss_total.astype(dtype)}


def _make_optimizer_stage(obj, shard_keys: Tuple[str, ...], P: int,
                          dim: int, dtype, method: str, m: int,
                          max_iter: int, steps_base: np.ndarray):
    """One engine stage sweeping P points of one optimizer family.

    The per-point body runs under ``jax.lax.map`` — the fixed-order
    points lane. Frozen (converged/pruned) points still compute (the
    program's geometry and collective set never depend on the alive
    mask) but their output is discarded by the freeze merge."""
    import jax
    import jax.numpy as jnp

    owlqn = method == "OWLQN"
    sgd = method == "SGD"
    newton = method == "NEWTON"
    pt_keys = (("coef", "loss_curve", "conv", "cur_loss")
               if (sgd or newton) else
               _QN_KEYS + (_QN_MEM_KEYS if m > 0 else ()))
    hyp_names = ("lr", "eps", "l1", "l2") + (("frac",) if sgd else ())

    def stage(ctx):
        shard = {k: ctx.get_obj(k) for k in shard_keys}
        hyp = {n: ctx.get_obj("swh_" + n) for n in hyp_names}
        step = ctx.step_no
        if ctx.is_init_step:
            c0 = ctx.get_obj("swh_coef0")
            pc = {"coef": c0,
                  "loss_curve": jnp.full((P, max_iter), jnp.nan, dtype),
                  "conv": jnp.zeros((P,), bool),
                  "cur_loss": jnp.full((P,), jnp.inf, dtype)}
            if not (sgd or newton):
                pc["coef_prev"] = c0
                pc["grad_prev"] = jnp.zeros((P, dim), dtype)
                pc["step_scale"] = jnp.ones((P,), dtype)
                if m > 0:
                    pc["sk"] = jnp.zeros((P, m, dim), dtype)
                    pc["yk"] = jnp.zeros((P, m, dim), dtype)
                    pc["pos"] = jnp.zeros((P,), jnp.int32)
                    pc["nvalid"] = jnp.zeros((P,), jnp.int32)
            alive = jnp.ones((P,), bool)
            steps_done = jnp.zeros((P,), jnp.int32)
        else:
            pc = {k: ctx.get_obj("pt_" + k) for k in pt_keys}
            alive = ctx.get_obj("sw_alive")
            steps_done = ctx.get_obj("sw_steps")
        active = alive & jnp.logical_not(pc["conv"])
        nw = ctx.num_task
        axis = ctx.AXIS
        key = ctx.rng_key() if sgd else None

        def one(args):
            pc_p, hyp_p, act = args

            def live(pc_q):
                if sgd:
                    return _sgd_point_step(obj, shard, pc_q, hyp_p, step,
                                           key, nw, axis, dtype, dim)
                if newton:
                    return _newton_point_step(obj, shard, pc_q, hyp_p,
                                              step, nw, axis, dtype, dim)
                return _qn_point_step(obj, shard, pc_q, hyp_p, step, nw,
                                      axis, m, owlqn, dtype, dim,
                                      steps_base, max_iter)

            return _freeze_cond(act, live, pc_p)

        out = jax.lax.map(one, (pc, hyp, active))
        for k in pt_keys:
            ctx.put_obj("pt_" + k, out[k])
        ctx.put_obj("sw_alive", alive)
        ctx.put_obj("sw_steps", steps_done + active.astype(jnp.int32))
        # population-health probes (PR 4 channel): replicated scalars
        # only — no collective of their own
        lane = jnp.where(alive, out["cur_loss"], jnp.inf)
        ctx.probe("sweep.best_loss", lane.min())
        ctx.probe("sweep.alive", alive.sum())

    stage.__name__ = f"sweep_{method.lower()}"
    return stage


def sweep_optimize(obj, data: Dict[str, np.ndarray], params, points:
                   Sequence[Dict[str, Any]], env=None, warm_starts=None,
                   asha=None, checkpoint_dir: Optional[str] = None,
                   checkpoint_keep: int = 3,
                   resume_from: Optional[str] = None) -> SweepResult:
    """Sweep N hyperparameter points of the iterative optimizers
    (LBFGS/OWLQN/GD/SGD/Newton) as one BSP program per compile group.

    ``obj``/``data``/``params`` are exactly :func:`~alink_tpu.operator.
    common.optim.optimizers.optimize`'s inputs; ``points`` is a list of
    per-point override dicts over the carry-resident axes
    (``learning_rate``, ``epsilon``, ``l1``, ``l2``,
    ``mini_batch_fraction``) and/or trace-shaping axes (``method``,
    ``max_iter``, ``seed`` — each distinct combination compiles its own
    group program). ``warm_starts`` is an optional ``(P, dim)`` stack.
    ``asha`` is ``None`` (train every point to completion — the
    GridSearchCV mode), ``True`` (flag-driven schedule) or an
    :class:`~alink_tpu.tuning.plan.AshaConfig`.

    Per-point results are bitwise identical to ``optimize()`` with that
    point's parameters (the load-bearing tests in tests/test_sweep.py).
    """
    from ..operator.common.optim.optimizers import (LINE_LADDER, _HISTORY,
                                                    _fb_precompute_ok,
                                                    optimize_dtype)
    base_method = (params.method or "LBFGS").upper()
    plan = SweepPlan("optimizer", [dict(p) for p in points],
                     base={"method": base_method,
                           "max_iter": int(params.max_iter),
                           "seed": int(params.seed)})
    dim = obj.dim
    from ..common.mlenv import MLEnvironmentFactory
    env = env or MLEnvironmentFactory.get_default()
    # the serial optimizers' input form, verbatim: a linear objective's
    # dense table packed into blocks, its fold constants broadcast
    data, consts = obj.prepare_data(data, env.num_workers)
    dtype = optimize_dtype(data)
    if _fb_precompute_ok(obj, data):
        # the serial trainers' one-hot-factor precompute, mirrored so a
        # swept fit runs the identical program family (optimizers.py)
        import jax.numpy as jnp

        from ..engine.comqueue import lazy_jit
        from ..ops.fieldblock import fb_onehot_parts
        A, B = lazy_jit(fb_onehot_parts, static_argnums=(1,))(
            jnp.asarray(data["fb_idx"]), obj.fb_meta)
        data["fb_A"], data["fb_B"] = A, B
    data_keys = tuple(data)

    P_total = plan.num_points
    coefs = np.zeros((P_total, dim), dtype)
    steps_all = np.zeros(P_total, np.int64)
    loss_all = np.full(P_total, np.nan)
    alive_all = np.ones(P_total, bool)
    conv_all = np.zeros(P_total, bool)
    curves: List[Optional[np.ndarray]] = [None] * P_total
    rung_log_all: List[Dict[str, Any]] = []

    from ..engine.comqueue import freeze_config as _freeze
    groups = plan.groups()
    for gi, (tkey, idxs) in enumerate(groups):
        gcfg = dict(tkey)
        method = str(gcfg["method"] or "LBFGS").upper()
        max_iter = int(gcfg["max_iter"])
        seed = int(gcfg["seed"])
        m = {"LBFGS": _HISTORY, "OWLQN": _HISTORY, "GD": 0}.get(method, 0)
        if method not in ("LBFGS", "OWLQN", "GD", "SGD", "NEWTON"):
            raise ValueError(f"unknown optim method {method!r}")
        P = len(idxs)
        pts = [plan.points[i] for i in idxs]

        def lane(name, default):
            return np.asarray([pt.get(name, default) for pt in pts], dtype)

        bcast = {"swh_lr": lane("learning_rate", params.learning_rate),
                 "swh_eps": lane("epsilon", params.epsilon),
                 "swh_l1": lane("l1", obj.l1),
                 "swh_l2": lane("l2", obj.l2)}
        if method == "SGD":
            # the frac lane stays CANONICAL-float (f64; the engine
            # downcasts with x64 off): jax.random.bernoulli draws its
            # uniforms in dtype(p), and the serial path passes a python
            # float — a data-dtype lane would draw f32 uniforms on an
            # x64 rig with f32 training data and break bitwise parity
            bcast["swh_frac"] = np.asarray(
                [pt.get("mini_batch_fraction",
                        params.mini_batch_fraction) for pt in pts],
                np.float64)
        if warm_starts is None:
            c0 = np.zeros((P_total, dim), dtype)
        else:
            c0 = np.asarray(warm_starts, dtype)
        bcast["swh_coef0"] = c0[np.asarray(idxs)]
        # the serial line-search ladder WITHOUT its lr factor (lr is a
        # per-point lane); [0, 2^1, 2^0, ..., 2^-8] in data dtype —
        # multiplying the lane back in is a power-of-two scaling, exact
        steps_base = LINE_LADDER.astype(dtype)
        bcast.update(consts)
        stage = _make_optimizer_stage(obj, data_keys + tuple(consts), P, dim,
                                      dtype, method, m, max_iter, steps_base)
        rung_log: List[Dict[str, Any]] = []
        ck_dir, rs = _group_paths(checkpoint_dir, resume_from, gi,
                                  len(groups))
        res = _run_sweep_queue(
            kind=f"opt_{method.lower()}", stage=stage, parts=data,
            bcast=bcast, env=env, max_iter=max_iter, seed=seed,
            key_tail=(m, str(dtype), data_keys, tuple(consts), _freeze(obj)),
            num_points=P, asha=_resolve_asha(asha, max_iter),
            checkpoint_dir=ck_dir, checkpoint_keep=checkpoint_keep,
            resume_from=rs, rung_log=rung_log)
        g_coef = np.asarray(res.get("pt_coef"))
        g_steps = np.asarray(res.get("sw_steps"))
        g_loss = np.asarray(res.get("pt_cur_loss"))
        g_alive = np.asarray(res.get("sw_alive"))
        g_conv = np.asarray(res.get("pt_conv"))
        g_curves = np.asarray(res.get("pt_loss_curve"))
        for j, i in enumerate(idxs):
            coefs[i] = g_coef[j]
            steps_all[i] = g_steps[j]
            loss_all[i] = g_loss[j]
            alive_all[i] = g_alive[j]
            conv_all[i] = g_conv[j]
            curves[i] = np.array(g_curves[j][:int(g_steps[j])])
        for r in rung_log:
            rung_log_all.append(
                {**r, "group": gi,
                 "pruned": [int(idxs[p]) for p in r["pruned"]]})
        res.release()

    return SweepResult(trainer="optimizer", points=plan.points,
                       values={"coef": coefs}, steps=steps_all,
                       final_loss=loss_all, alive=alive_all,
                       converged=conv_all,
                       loss_curves=[c if c is not None
                                    else np.zeros(0, dtype)
                                    for c in curves],
                       rungs=rung_log_all, programs=len(groups))


# -- k-means sweep ----------------------------------------------------------

def _make_kmeans_stage(P: int, k: int, d: int, dtype, distance_type: str,
                       max_iter: int):
    """The Lloyd superstep of ``kmeans_train`` with a points lane: per
    point its own centroid block and tolerance; the init seed sweeps as
    DATA (the stacked host-computed init centroids), so a seed axis
    never recompiles."""
    import jax
    import jax.numpy as jnp

    from ..engine.communication import manifest_psum
    from ..operator.common.clustering.kmeans import (_lloyd_pass,
                                                     _lloyd_update)

    def stage(ctx):
        Xs, Ws = ctx.get_obj("X"), ctx.get_obj("w")
        tol = ctx.get_obj("swh_tol")
        step = ctx.step_no
        if ctx.is_init_step:
            pc = {"centroids": ctx.get_obj("swh_init_centroids"),
                  "movement": jnp.full((P,), jnp.inf, dtype),
                  "cluster_weights": jnp.zeros((P, k), dtype),
                  "conv": jnp.zeros((P,), bool),
                  "cur_loss": jnp.full((P,), jnp.inf, dtype)}
            alive = jnp.ones((P,), bool)
            steps_done = jnp.zeros((P,), jnp.int32)
        else:
            pc = {n: ctx.get_obj("pt_" + n)
                  for n in ("centroids", "movement", "cluster_weights",
                            "conv", "cur_loss")}
            alive = ctx.get_obj("sw_alive")
            steps_done = ctx.get_obj("sw_steps")
        active = alive & jnp.logical_not(pc["conv"])
        nw = ctx.num_task
        axis = ctx.AXIS

        def one(args):
            pc_p, tol_p, act = args

            def live(pc_q):
                C = pc_q["centroids"]
                # the serial trainer's own blocked pass and update, so a
                # point's centroids are bitwise ``kmeans_train``'s. The
                # inertia row (the ASHA pruning signal) is part of the
                # buffer under either setting of ALINK_TPU_HEALTH: rung
                # decisions must not flip with an observability flag.
                buf = jnp.asarray(manifest_psum(
                    _lloyd_pass(Xs, Ws, C, distance_type), axis,
                    name="sweep_buf", num_workers=nw))
                newC, cnts2, cur, movement, _ = _lloyd_update(buf, C)
                return {"centroids": newC, "movement": movement,
                        "cluster_weights": cnts2, "conv": movement < tol_p,
                        "cur_loss": cur.astype(dtype)}

            return _freeze_cond(act, live, pc_p)

        out = jax.lax.map(one, (pc, tol, active))
        for n in ("centroids", "movement", "cluster_weights", "conv",
                  "cur_loss"):
            ctx.put_obj("pt_" + n, out[n])
        ctx.put_obj("sw_alive", alive)
        ctx.put_obj("sw_steps", steps_done + active.astype(jnp.int32))
        lane = jnp.where(alive, out["cur_loss"], jnp.inf)
        ctx.probe("sweep.best_loss", lane.min())
        ctx.probe("sweep.alive", alive.sum())

    stage.__name__ = "sweep_kmeans"
    return stage


def sweep_kmeans(X: np.ndarray, k: int, points: Sequence[Dict[str, Any]],
                 max_iter: int = 50, tol: float = 1e-4,
                 distance_type: str = "EUCLIDEAN",
                 init: str = "K_MEANS_PARALLEL", seed: int = 0, env=None,
                 sample_weight: Optional[np.ndarray] = None, asha=None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_keep: int = 3,
                 resume_from: Optional[str] = None) -> SweepResult:
    """Sweep N ``kmeans_train`` points as one Lloyd program per compile
    group. Carry-resident axes: ``tol`` and the init ``seed`` (the
    stacked init centroids are host data, so a seed grid shares one
    program); trace-shaping axes: ``k``, ``distance_type``, ``init``,
    ``max_iter``. Per-point centroids are bitwise identical to
    ``kmeans_train`` with that point's parameters."""
    from ..common.mlenv import MLEnvironmentFactory
    from ..common.columnar import LANES, as_block_column, block_weights
    from ..kernels.kmeans import lloyd_path
    from ..operator.common.clustering.kmeans import (kmeans_parallel_init,
                                                     kmeans_plus_plus_init,
                                                     random_init)
    X = np.asarray(X)
    n, d = X.shape
    dt = X.dtype
    col = as_block_column(
        X, (env or MLEnvironmentFactory.get_default()).num_workers)
    plan = SweepPlan("kmeans", [dict(p) for p in points],
                     base={"k": int(k), "distance_type": distance_type,
                           "init": init, "max_iter": int(max_iter)})
    parts = {"X": col.blocks, "w": block_weights(col, sample_weight)}

    P_total = plan.num_points
    # per-point model state collects as LISTS first: a k axis is
    # trace-shaping, so different compile groups may carry different
    # centroid geometries — stacked to (P, k, d) only when uniform
    cent_list: List[Optional[np.ndarray]] = [None] * P_total
    weight_list: List[Optional[np.ndarray]] = [None] * P_total
    steps_all = np.zeros(P_total, np.int64)
    loss_all = np.full(P_total, np.nan)
    alive_all = np.ones(P_total, bool)
    conv_all = np.zeros(P_total, bool)
    curves: List[np.ndarray] = [np.zeros(0, dt)] * P_total
    rung_log_all: List[Dict[str, Any]] = []

    groups = plan.groups()
    for gi, (tkey, idxs) in enumerate(groups):
        gcfg = dict(tkey)
        g_k = int(gcfg["k"])
        g_dist = str(gcfg["distance_type"])
        g_init = str(gcfg["init"]).upper()
        g_iter = int(gcfg["max_iter"])
        pts = [plan.points[i] for i in idxs]
        P = len(idxs)
        init_stack = np.zeros((P, g_k, d), dt)
        for j, pt in enumerate(pts):
            s = int(pt.get("seed", seed))
            if g_init == "RANDOM":
                c0 = random_init(X, g_k, s)
            elif g_init in ("K_MEANS_PARALLEL", "KMEANS_PARALLEL"):
                c0 = kmeans_parallel_init(col, g_k, seed=s, env=env,
                                          sample_weight=parts["w"])
            else:
                c0 = kmeans_plus_plus_init(X, g_k, s)
            init_stack[j] = c0.astype(dt)
        bcast = {"swh_tol": np.asarray(
                     [pt.get("tol", tol) for pt in pts], dt),
                 "swh_init_centroids": init_stack}
        stage = _make_kmeans_stage(P, g_k, d, dt, g_dist, g_iter)
        rung_log: List[Dict[str, Any]] = []
        ck_dir, rs = _group_paths(checkpoint_dir, resume_from, gi,
                                  len(groups))
        res = _run_sweep_queue(
            kind="kmeans", stage=stage, parts=parts,
            bcast=bcast, env=env, max_iter=g_iter, seed=int(seed),
            # the word ``_lloyd_pass`` reads from the same input: a program
            # cached for one path is never run under the other
            key_tail=(g_k, d, g_dist, str(dt),
                      lloyd_path(dt, col.block_rows // LANES, g_k, d, g_dist)),
            num_points=P, asha=_resolve_asha(asha, g_iter),
            checkpoint_dir=ck_dir, checkpoint_keep=checkpoint_keep,
            resume_from=rs, rung_log=rung_log)
        g_c = np.asarray(res.get("pt_centroids"))
        g_w = np.asarray(res.get("pt_cluster_weights"))
        g_steps = np.asarray(res.get("sw_steps"))
        g_loss = np.asarray(res.get("pt_cur_loss"))
        g_alive = np.asarray(res.get("sw_alive"))
        g_conv = np.asarray(res.get("pt_conv"))
        for j, i in enumerate(idxs):
            cent_list[i] = np.array(g_c[j])
            weight_list[i] = np.array(g_w[j])
            steps_all[i] = g_steps[j]
            loss_all[i] = g_loss[j]
            alive_all[i] = g_alive[j]
            conv_all[i] = g_conv[j]
        for r in rung_log:
            rung_log_all.append(
                {**r, "group": gi,
                 "pruned": [int(idxs[p]) for p in r["pruned"]]})
        res.release()

    uniform = len({c.shape for c in cent_list}) == 1
    return SweepResult(trainer="kmeans", points=plan.points,
                       values={"centroids": (np.stack(cent_list)
                                             if uniform else cent_list),
                               "cluster_weights": (np.stack(weight_list)
                                                   if uniform
                                                   else weight_list)},
                       steps=steps_all, final_loss=loss_all,
                       alive=alive_all, converged=conv_all,
                       loss_curves=curves, rungs=rung_log_all,
                       programs=len(groups))


# -- FTRL hyperparameter sweeps (ISSUE 13 satellite; ROADMAP item 3
# leftover) -----------------------------------------------------------------

@dataclass
class FtrlSweepResult:
    """Per-point outcomes of one FTRL staleness-kernel sweep.

    ``z``/``n``: (P, dim_pad) final FTRL state per point — each lane
    round-equal to a serial staleness-kernel drain with that point's
    hyperparameters at the pinned 1e-12 tolerance, and BITWISE
    independent of the population (a lane's result never changes when
    other points join or leave the sweep — tests/test_sweep.py);
    ``margins``:
    (P, total_rows) pre-update margins in arrival order;
    ``pv_logloss``: per-point progressive-validation logloss over the
    whole drain (margins are computed at pre-update weights in the
    staleness kernel, so this is the honest online loss — the
    winner-selection lane); ``programs``: compiled program count (1
    for a carry-resident grid); ``fallback``: True when a
    trace-shaping axis forced the recorded serial path."""
    points: List[Dict[str, Any]]
    z: np.ndarray
    n: np.ndarray
    margins: np.ndarray
    pv_logloss: np.ndarray
    programs: int
    fallback: bool = False

    @property
    def num_points(self) -> int:
        return len(self.points)

    @property
    def best(self) -> int:
        """Lowest progressive-validation logloss, ties broken by lowest
        point index — deterministic and seed-free."""
        key = np.where(np.isfinite(self.pv_logloss), self.pv_logloss,
                       np.inf)
        return int(np.lexsort((np.arange(len(key)), key))[0])


@_functools.lru_cache(maxsize=16)
def _ftrl_sweep_staleness_factory(mesh, K, P_pts):
    """The bounded-staleness FTRL step with a ``(points,)`` lane: the
    per-point body mirrors ``_ftrl_sparse_staleness_step_factory``'s
    shard_fn OP-FOR-OP with the hyperparameters as traced per-point
    scalars (the serial program bakes python floats into the same
    arithmetic), run under a fixed-order ``jax.lax.map`` at exactly
    the serial program's shapes. Lane ``p`` matches the serial kernel
    with point ``p``'s hyperparameters to the pinned 1e-12 tolerance —
    XLA's mul->add FMA contraction is CONTEXT-dependent, so the mapped
    body rounds a last ulp differently from the standalone serial
    program on some ops (measured ~1e-17 on the f64 rig); what IS
    bitwise is population independence: a lane's result never depends
    on which other points share the sweep (same program, same lane
    shapes). One psum per chunk per point (the
    serial program's collective set, times P)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..common.compat import shard_map
    from ..engine.communication import manifest_psum
    from ..operator.stream.onlinelearning.ftrl import _ftrl_weights

    def shard_fn(idx, val, y, hyp, Z, N):
        # hyp: (P_pts, 4) = [alpha, beta, l1, l2] lanes; Z/N:
        # (P_pts, shard) feature-sharded per point
        shard = Z.shape[1]
        lo = jax.lax.axis_index("d") * shard
        B, w = idx.shape
        Bp = -(-B // K) * K
        if Bp != B:               # zero rows are algebraic no-ops
            idx = jnp.concatenate([idx, jnp.zeros((Bp - B, w), idx.dtype)])
            val = jnp.concatenate([val, jnp.zeros((Bp - B, w), val.dtype)])
            y = jnp.concatenate([y, jnp.zeros((Bp - B,), y.dtype)])
        xi3 = idx.reshape(Bp // K, K, w)
        xv3 = val.reshape(Bp // K, K, w)
        yy2 = y.reshape(Bp // K, K)

        def point(args):
            hp, z, n = args
            alpha, beta, l1, l2 = hp[0], hp[1], hp[2], hp[3]
            zn = jnp.stack([z, n], axis=-1)               # (shard, 2)

            def body(zn, xvy):
                xi, xv, yy = xvy
                local = (xi >= lo) & (xi < lo + shard)
                li = jnp.clip(xi - lo, 0, shard - 1)
                flat = li.reshape(-1)
                s = zn[flat].reshape(K, w, 2)
                zj = jnp.where(local, s[..., 0], 0.0)
                nj = jnp.where(local, s[..., 1], 0.0)
                wj = jnp.where(local,
                               _ftrl_weights(zj, nj, alpha, beta, l1, l2),
                               0.0)
                margins = manifest_psum((xv * wj).sum(-1), "d",
                                        name="ftrl_margins",
                                        num_workers=mesh.size)
                p = 1.0 / (1.0 + jnp.exp(-jnp.clip(margins, -35.0, 35.0)))
                g = (p - yy)[:, None] * xv
                sigma = (jnp.sqrt(nj + g * g) - jnp.sqrt(nj)) / alpha
                dz = jnp.where(local, g - sigma * wj, 0.0)
                dn = jnp.where(local, g * g, 0.0)
                zn = zn.at[flat].add(
                    jnp.stack([dz.reshape(-1), dn.reshape(-1)], axis=-1))
                return zn, margins

            zn, margins = jax.lax.scan(body, zn, (xi3, xv3, yy2))
            return zn[..., 0], zn[..., 1], margins.reshape(Bp)[:B]

        Z, N, M = jax.lax.map(point, (hyp, Z, N))
        return Z, N, M

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(), P(), P(), P(), P(None, "d"),
                             P(None, "d")),
                   out_specs=(P(None, "d"), P(None, "d"), P()))
    return jax.jit(fn)


def sweep_ftrl(batches, dim: int, points, base=None, env=None,
               coef0=None) -> FtrlSweepResult:
    """Sweep N FTRL hyperparameter points (alpha/beta/l1/l2 lanes)
    through the bounded-staleness kernel as ONE program.

    ``batches``: padded-COO micro-batches ``[(idx, val, y), ...]``
    (the FTRL encode convention: (B, width) int32/float + (B,) labels,
    padding entries val == 0); ``dim``: model dimension (padded to the
    mesh); ``points``: per-point overrides over ``base`` —
    carry-resident axes alpha/beta/l1/l2 sweep inside one compiled
    program (a ``staleness`` axis whose values all RESOLVE equal keeps
    the one-program path — the compile-group base-fill semantics);
    heterogeneous ``staleness`` values record
    ``alink_sweep_fallback_total{estimator="ftrl"}`` and run the
    serial per-point STALENESS kernels instead (identical numbers,
    serial economics); an ``update_mode`` other than "staleness" is
    REFUSED loudly — this executor implements the bounded-staleness
    kernel only. ``coef0``: warm-start weights — each point's z lane
    initializes to ``-coef0 * (beta/alpha + l2)`` exactly like the
    serial drain's warm start, which is hyperparameter-DEPENDENT, so
    it must be built per point.

    Per-point results match serial
    ``_ftrl_sparse_staleness_step_factory`` drains at the pinned 1e-12
    tolerance and are BITWISE population-independent
    (tests/test_sweep.py); the winner is the lowest
    progressive-validation logloss."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..common.mlenv import MLEnvironmentFactory
    from ..operator.stream.onlinelearning.ftrl import (
        _ftrl_sparse_staleness_step_factory)

    base = dict(base or {})
    base.setdefault("alpha", 0.1)
    base.setdefault("beta", 1.0)
    base.setdefault("l1", 0.0)
    base.setdefault("l2", 0.0)
    base.setdefault("staleness", 32)
    base.setdefault("update_mode", "staleness")
    plan = SweepPlan("ftrl", [dict(p) for p in points], base=base)
    modes = {str(p.get("update_mode", base["update_mode"]))
             for p in plan.points}
    if modes != {"staleness"}:
        # update_mode classifies as a trace axis so SweepPlan accepts
        # it, but this executor only implements the bounded-staleness
        # kernel — running a chained/per-sample point through it would
        # return silently wrong semantics. Refuse loudly instead.
        raise ValueError(
            f"sweep_ftrl sweeps the bounded-staleness kernel only; "
            f"update_mode values {sorted(modes - {'staleness'})} must "
            f"train through the serial drain (FtrlTrainStreamOp)")
    env = env or MLEnvironmentFactory.get_default()
    mesh = env.mesh
    n_dev = int(mesh.devices.size)
    dim_pad = -(-dim // n_dev) * n_dev
    K = int(base["staleness"])
    P_pts = plan.num_points
    coef0 = np.zeros(dim) if coef0 is None else np.asarray(coef0)

    def resolved(i, name):
        return float(plan.points[i].get(name, base[name]))

    hyp = np.stack([[resolved(i, "alpha"), resolved(i, "beta"),
                     resolved(i, "l1"), resolved(i, "l2")]
                    for i in range(P_pts)])

    def z0_for(i):
        # the warm start encodes the initial weights into z at n = 0 —
        # scale = beta/alpha + l2 depends on the POINT's hypers
        scale = resolved(i, "beta") / resolved(i, "alpha") \
            + resolved(i, "l2")
        z = np.zeros(dim_pad)
        z[:dim] = -coef0 * scale
        return z

    # a staleness axis only forces the serial path when its values
    # actually DIFFER: a point that names staleness explicitly but
    # equals every other point's resolved value still has ONE trace
    # group (the plan.groups() base-fill semantics) and sweeps as one
    # program — the sibling sweepers' compile-group discipline
    staleness_vals = {int(p.get("staleness", base["staleness"]))
                      for p in plan.points}
    if len(staleness_vals) == 1:
        K = staleness_vals.pop()
    else:
        record_sweep_fallback(
            "ftrl", "trace-shaping-axis",
            f"staleness values {sorted(staleness_vals)} split the scan "
            f"geometry into {len(plan.groups())} compile groups — "
            f"serial per-point kernels (identical numbers)")
        sh = NamedSharding(mesh, P("d"))
        zs, ns, ms = [], [], []
        progs = set()
        for i in range(P_pts):
            Ki = int(plan.points[i].get("staleness", base["staleness"]))
            step = _ftrl_sparse_staleness_step_factory(
                mesh, resolved(i, "alpha"), resolved(i, "beta"),
                resolved(i, "l1"), resolved(i, "l2"), Ki)
            progs.add((resolved(i, "alpha"), resolved(i, "beta"),
                       resolved(i, "l1"), resolved(i, "l2"), Ki))
            z = jax.device_put(z0_for(i), sh)
            n = jax.device_put(np.zeros(dim_pad), sh)
            mm = []
            for idx, val, y in batches:
                z, n, m = step(idx, val, y, z, n)
                mm.append(m)
            zs.append(np.asarray(z))
            ns.append(np.asarray(n))
            ms.append(np.concatenate([np.asarray(m) for m in mm]))
        Zh, Nh = np.stack(zs), np.stack(ns)
        Mh = np.stack(ms)
        return _finish_ftrl(plan, batches, Zh, Nh, Mh, len(progs), True)

    step = _ftrl_sweep_staleness_factory(mesh, K, P_pts)
    state_sh = NamedSharding(mesh, P(None, "d"))
    Z = jax.device_put(np.stack([z0_for(i) for i in range(P_pts)]),
                       state_sh)
    N = jax.device_put(np.zeros((P_pts, dim_pad)), state_sh)
    margins = []
    for idx, val, y in batches:
        Z, N, M = step(idx, val, y, hyp, Z, N)
        margins.append(M)
    Mh = np.concatenate([np.asarray(m) for m in margins], axis=1) \
        if margins else np.zeros((P_pts, 0))
    return _finish_ftrl(plan, batches, np.asarray(Z), np.asarray(N), Mh,
                        1, False)


def _finish_ftrl(plan, batches, Z, N, M, programs: int,
                 fallback: bool) -> FtrlSweepResult:
    y_all = (np.concatenate([y for _, _, y in batches])
             if batches else np.zeros(0))
    if M.shape[1]:
        m = np.clip(M, -35.0, 35.0)
        ll = (np.logaddexp(0.0, -m) * y_all[None, :]
              + np.logaddexp(0.0, m) * (1.0 - y_all[None, :]))
        # a non-finite margin must surface in the lane's loss, not be
        # laundered by the clip (the drain's pv_stats contract): a
        # diverged point's pv is NaN and ranks LAST in `best`
        pv = np.where(np.isfinite(M).all(axis=1), ll.mean(axis=1),
                      np.nan)
    else:
        pv = np.full(M.shape[0], np.nan)
    return FtrlSweepResult(points=plan.points, z=Z, n=N, margins=M,
                           pv_logloss=pv, programs=programs,
                           fallback=fallback)
