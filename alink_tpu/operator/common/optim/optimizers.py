"""Distributed optimizers on the BSP engine.

Re-design of the reference optimizer stack (common/optim/: Lbfgs.java:82-176,
Sgd.java:82-140, Gd.java, Owlqn.java, Newton.java, subfunc/CalcGradient.java:27-54,
subfunc/UpdateModel.java, PreallocateLossCurve) — each optimizer is an
IterativeComQueue program:

  CalcGradient      -> per-shard fused matmul/gather kernel
  AllReduce(grad)   -> lax.psum
  CalDirection      -> L-BFGS two-loop on a fixed-size ring buffer
                       (the mutable sK/yK heap state of Lbfgs.java:130-174
                       becomes masked carry arrays)
  CalcLosses        -> vectorized parallel line search (losses at a fixed
                       ladder of step sizes in one vmap — the reference's
                       numSearchStep loop, UpdateModel.java)
  AllReduce(losses) -> lax.psum
  UpdateModel       -> argmin step, coef update, loss-curve write

The whole loop is one compiled XLA program; convergence is a carry bit
checked by the engine's while_loop (variable trip count with a preallocated
loss curve, per SURVEY §7 hard-parts).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ....common.mlenv import MLEnvironment
from ....engine import AllReduce, IterativeComQueue
from ..blocked import join_count as _join_count, split_count as _split_count
from .objfunc import OptimObjFunc, blocked

_TINY = 1e-12
_NUM_SEARCH_STEP = 10  # line-search ladder size (reference numSearchStep=4, widened)
_HISTORY = 10          # L-BFGS memory (reference m=10, Lbfgs.java)


from ....engine.comqueue import freeze_config as _freeze


@dataclass
class OptimParams:
    method: str = "LBFGS"
    max_iter: int = 100
    epsilon: float = 1e-6
    learning_rate: float = 1.0
    mini_batch_fraction: float = 0.1
    seed: int = 0
    # superstep durability (engine/recovery.py): snapshot the optimizer
    # carry every N supersteps; resume_from= re-enters a killed run with
    # bitwise-identical final results. None/0 = off. These knobs do not
    # enter the program cache key: checkpointing runs the same superstep
    # body, only chunked.
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 1
    checkpoint_keep: int = 3
    resume_from: Optional[str] = None
    # training-health watchdog (common/health.py): a HealthMonitor fed
    # the run's probe series (loss, grad_norm, update_ratio,
    # nonfinite.grad — recorded by every trainer whenever
    # ALINK_TPU_HEALTH is on) after the run and, on checkpointed runs,
    # at every snapshot boundary. Not part of the program-cache key:
    # probes are recorded regardless; the monitor only READS them.
    health: Optional[object] = None


def _apply_checkpoint(queue, params: "OptimParams"):
    if params.checkpoint_dir:
        # knob validation (every/keep_last >= 1) lives in CheckpointConfig
        queue.set_checkpoint(params.checkpoint_dir,
                             every=int(params.checkpoint_every),
                             keep_last=int(params.checkpoint_keep),
                             resume_from=params.resume_from)
    elif params.resume_from:
        raise ValueError("OptimParams.resume_from requires checkpoint_dir "
                         "(an explicit resume request must not silently "
                         "retrain from scratch)")
    if params.health is not None:
        from ....common.health import warn_if_disabled
        warn_if_disabled("OptimParams.health", stacklevel=4)
        queue.set_health(params.health)
    return queue


def optimize(obj: OptimObjFunc, data: Dict[str, np.ndarray], params: OptimParams,
             env: Optional[MLEnvironment] = None,
             warm_start: Optional[np.ndarray] = None,
             info: Optional[Dict] = None) -> Tuple[np.ndarray, np.ndarray, int]:
    """Run the selected optimizer; returns (coef, loss_curve, num_steps).

    ``data``: dense {"X", "y", "w"} (``X`` host rows or a
    ``DenseBlockColumn``; a linear objective packs it into blocks,
    ``prepare_data``) or sparse {"idx", "val", "y", "w"} host arrays;
    rows are padded/sharded by the engine (w==0 marks padding). ``info``,
    when given, receives what a quasi-Newton fit of a dense table went
    through (``_quasi_newton``).
    """
    method = (params.method or "LBFGS").upper()
    if method == "LBFGS":
        return _quasi_newton(obj, data, params, env, warm_start, owlqn=False,
                             info=info)
    if method == "OWLQN":
        return _quasi_newton(obj, data, params, env, warm_start, owlqn=True,
                             info=info)
    if method == "GD":
        return _quasi_newton(obj, data, params, env, warm_start, owlqn=False,
                             history=0, info=info)
    if method == "SGD":
        return _sgd(obj, data, params, env, warm_start)
    if method == "NEWTON":
        return _newton(obj, data, params, env, warm_start)
    raise ValueError(f"unknown optim method {params.method}")


# ---------------------------------------------------------------------------
# L-BFGS / OWLQN / GD (shared skeleton; GD is history=0)
# ---------------------------------------------------------------------------

def _two_loop(g, sk, yk, pos, nvalid, m):
    """L-BFGS two-loop recursion with ring buffer + validity masks
    (reference Lbfgs.java:109-176 ``CalDirection``)."""
    if m == 0:
        return g
    dt = g.dtype
    q = g
    alphas = []
    for t in range(m):
        j = (pos - 1 - t) % m
        s, yv = sk[j], yk[j]
        sy = jnp.dot(s, yv)
        valid = (t < nvalid) & (sy > _TINY)
        rho = 1.0 / jnp.where(valid, sy, 1.0)
        a = jnp.where(valid, rho * jnp.dot(s, q), 0.0)
        q = q - a * yv
        alphas.append((a, valid, j))
    jlast = (pos - 1) % m
    sy_l = jnp.dot(sk[jlast], yk[jlast])
    yy_l = jnp.dot(yk[jlast], yk[jlast])
    ok = (nvalid > 0) & (sy_l > _TINY) & (yy_l > _TINY)
    gamma = jnp.where(ok, sy_l / jnp.where(yy_l > _TINY, yy_l, 1.0), jnp.asarray(1.0, dt))
    r = gamma * q
    for a, valid, j in reversed(alphas):
        s, yv = sk[j], yk[j]
        sy = jnp.dot(s, yv)
        rho = 1.0 / jnp.where(sy > _TINY, sy, 1.0)
        b = rho * jnp.dot(yv, r)
        r = r + jnp.where(valid, (a - b) * s, 0.0)
    return r


def _pseudo_grad(g_plain, coef, l1, reg_mask):
    """OWLQN pseudo-gradient (reference Owlqn.java)."""
    l1m = l1 * reg_mask
    at_zero = jnp.where(g_plain + l1m < 0, g_plain + l1m,
                        jnp.where(g_plain - l1m > 0, g_plain - l1m, 0.0))
    return jnp.where(coef != 0, g_plain + l1m * jnp.sign(coef), at_zero)


#: the engine names the compiled step program ``jit_<first word of its key>``
QN_PROGRAM = "linear_qn"

#: the line search's ladder without its learning rate: 0, then 2^(1 - i)
LINE_LADDER = np.concatenate(
    [[0.0], np.power(2.0, 1 - np.arange(_NUM_SEARCH_STEP, dtype=np.float64))])


def optimize_dtype(parts) -> np.dtype:
    """The float dtype a fit computes in: its weights', else its labels'
    (a Softmax shard's labels are whole numbers)."""
    from .objfunc import float_dtype
    return float_dtype(parts.get("w"), parts["y"])


def shard_grad(obj, shard, coef, dtype):
    """A worker's half of the gradient psum: ``(glw, eta)``, ``glw`` the
    gradient sum, then the loss and weight sums and, for a dense shard
    (walked: ``grad_pass``), the rows the pass counted as two halves a
    float psum keeps exact; ``eta`` the margins the line search reuses.
    The serial program and the sweep's lane both call it: one rounding."""
    if blocked(shard):
        g, loss, wsum, eta, rows = obj.grad_pass(shard, coef)
        tail = [loss, wsum, *(c.astype(dtype) for c in _split_count(rows))]
    else:
        g, loss, wsum, eta = obj.calc_grad_eta_shard(shard, coef)
        tail = [loss, wsum]
    return jnp.concatenate([g, jnp.stack(tail)]), eta


def shard_line(obj, shard, coef, direction, steps, eta, dtype):
    """A worker's half of the line-search psum: the ladder's loss sums
    and, for a dense shard (``line_pass``), its rows as ``shard_grad``
    carries them."""
    if blocked(shard):
        line, rows = obj.line_pass(shard, coef, direction, steps, eta)
        return jnp.concatenate([line, jnp.stack(
            [c.astype(dtype) for c in _split_count(rows)])])
    return obj.line_losses_shard(shard, coef, direction, steps, eta0=eta)


def _quasi_newton(obj, data, params, env, warm_start, owlqn: bool,
                  history: int = _HISTORY, info: Optional[Dict] = None):
    """L-BFGS / OWLQN / GD as ONE compiled program, ``jit_linear_qn``.

    The tuning axes ``l1``, ``l2``, ``learning_rate`` and ``epsilon`` enter
    as DATA (the broadcast scalars ``hyp_*``): the program is keyed by the
    objective's structure alone, so a second fit at another ``l2`` reuses
    it. A dense shard is walked twice a superstep (``grad_pass``,
    ``line_pass``), each pass counting its rows on the device; ``info``,
    when given, then receives per superstep the coefficients and the
    averaged gradient it started from, the direction, the ladder's scale,
    the chosen rung and step, and the rows each pass counted; and the
    final coefficients (``coef``), all in the standardized space."""
    from ....common.mlenv import MLEnvironmentFactory
    env = env or MLEnvironmentFactory.get_default()
    parts, consts = obj.prepare_data(data, env.num_workers)
    dtype = optimize_dtype(parts)
    w0 = np.zeros(obj.dim, dtype) if warm_start is None \
        else np.asarray(warm_start, dtype)
    hyp = {"hyp_l1": obj.l1, "hyp_l2": obj.l2,
           "hyp_lr": params.learning_rate, "hyp_eps": params.epsilon}
    # what the stages close over: no tuning axis (the engine folds every
    # closure cell's value into the program's key)
    obj = obj.structure()
    if _fb_precompute_ok(obj, parts):
        # build the data-constant one-hot factors ON DEVICE, once, and ship
        # them into the program as static sharded data (NOT loop carry —
        # carrying GB-scale arrays through the while_loop made XLA's layout
        # assignment explode; as closed-over operands they are free)
        from ....ops.fieldblock import fb_onehot_parts
        from ....engine.comqueue import lazy_jit
        A, B = lazy_jit(fb_onehot_parts, static_argnums=(1,))(
            jnp.asarray(parts["fb_idx"]), obj.fb_meta)
        parts["fb_A"], parts["fb_B"] = A, B
    queue = qn_queue(obj, parts, consts, hyp, w0, params.max_iter,
                     params.seed, env, owlqn, history)
    _apply_checkpoint(queue, params)
    res = queue.exec()
    steps = res.step_count
    traces = (("coef_trace", "grad_trace", "dir_trace", "scale_trace",
               "rung_trace", "step_trace", "rows_trace")
              if info is not None and blocked(parts) else ())
    # one fetch for everything the fit hands back
    coef, curve, *kept = res.get_all(["coef", "loss_curve", *traces])
    if info is not None:
        info.update({k: np.asarray(v)[:steps] for k, v in zip(traces, kept)},
                    coef=np.asarray(coef))
    return coef, _trim_curve(curve, steps), steps


def qn_queue(obj, parts: Dict, consts: Dict, hyp: Dict, w0, max_iter: int,
             seed: int, env, owlqn: bool, m: int) -> IterativeComQueue:
    """The quasi-Newton program's queue over inputs already in their
    program form: ``parts`` partitioned (arrays, or ``ShapeDtypeStruct``s
    to lower it at a size no host holds), ``consts`` and the tuning
    scalars ``hyp`` broadcast, ``obj`` the objective's structure."""
    dim = obj.dim
    dense = blocked(parts)
    dtype = np.dtype(w0.dtype)
    hyp_names = tuple(hyp)     # the stages close over the names alone
    ladder = LINE_LADDER.astype(dtype)
    data_keys = tuple(parts)
    const_keys = tuple(consts)
    upd = jax.lax.dynamic_update_index_in_dim

    def calc_grad(ctx):
        if ctx.is_init_step:
            ctx.put_obj("coef", ctx.get_obj("coef0"))
            ctx.put_obj("coef_prev", ctx.get_obj("coef0"))
            ctx.put_obj("grad_prev", jnp.zeros(dim, dtype))
            if m > 0:
                ctx.put_obj("sk", jnp.zeros((m, dim), dtype))
                ctx.put_obj("yk", jnp.zeros((m, dim), dtype))
            ctx.put_obj("pos", jnp.asarray(0, jnp.int32))
            ctx.put_obj("nvalid", jnp.asarray(0, jnp.int32))
            ctx.put_obj("step_scale", jnp.asarray(1.0, dtype))
            ctx.put_obj("loss_curve", jnp.full((max_iter,), jnp.nan, dtype))
            ctx.put_obj("conv", jnp.asarray(False))
            if dense:
                ctx.put_obj("coef_trace", jnp.zeros((max_iter, dim), dtype))
                ctx.put_obj("grad_trace", jnp.zeros((max_iter, dim), dtype))
                ctx.put_obj("dir_trace", jnp.zeros((max_iter, dim), dtype))
                ctx.put_obj("scale_trace", jnp.zeros((max_iter,), dtype))
                ctx.put_obj("rung_trace", jnp.zeros((max_iter,), jnp.int32))
                ctx.put_obj("step_trace", jnp.zeros((max_iter,), dtype))
                ctx.put_obj("rows_trace", jnp.zeros((max_iter, 2), jnp.int32))
        shard = _shard_views(ctx, data_keys, const_keys)
        glw, eta = shard_grad(obj, shard, ctx.get_obj("coef"), dtype)
        if eta is not None:
            ctx.put_obj("eta0", eta)  # reused by the line search (same coef)
        ctx.put_obj("glw", glw)

    def direction_and_losses(ctx):
        glw = ctx.get_obj("glw")
        coef = ctx.get_obj("coef")
        l1, l2, lr, eps = (ctx.get_obj(k) for k in hyp_names)
        W = jnp.maximum(glw[dim + 1], _TINY)
        g_plain = glw[:dim] / W + obj.l2_grad(coef, l2)
        loss_total = glw[dim] / W + obj.regular_loss(coef, l1, l2)
        step = ctx.step_no
        ctx.put_obj("loss_curve", upd(
            ctx.get_obj("loss_curve"), loss_total.astype(dtype), step - 1, 0))
        if dense:
            ctx.put_obj("coef_trace", upd(ctx.get_obj("coef_trace"), coef,
                                          step - 1, 0))
            ctx.put_obj("grad_trace", upd(ctx.get_obj("grad_trace"), g_plain,
                                          step - 1, 0))

        if owlqn:
            g_dir = _pseudo_grad(g_plain, coef, l1, obj._reg_mask(coef))
        else:
            g_dir = g_plain
        gnorm = jnp.linalg.norm(g_dir) / jnp.maximum(1.0, jnp.linalg.norm(coef))
        ctx.put_obj("conv", gnorm < eps)
        # default health probes (common/health.py): replicated scalars
        # only, so no collective is added — the series ride the carry
        ctx.probe("loss", loss_total)
        ctx.probe("grad_norm", gnorm)
        ctx.probe_nonfinite("grad", g_plain)

        with jax.named_scope("qn_direction"):
            if m > 0:
                # push pair (coef - coef_prev, g - g_prev); masked out on step 1
                push = step > 1
                snew = coef - ctx.get_obj("coef_prev")
                ynew = g_plain - ctx.get_obj("grad_prev")
                pos = ctx.get_obj("pos")
                sk = ctx.get_obj("sk")
                yk = ctx.get_obj("yk")
                sk = jnp.where(push, sk.at[pos].set(snew), sk)
                yk = jnp.where(push, yk.at[pos].set(ynew), yk)
                pos = jnp.where(push, (pos + 1) % m, pos)
                nvalid = jnp.where(push, jnp.minimum(ctx.get_obj("nvalid") + 1, m),
                                   ctx.get_obj("nvalid"))
                ctx.put_obj("sk", sk)
                ctx.put_obj("yk", yk)
                ctx.put_obj("pos", pos)
                ctx.put_obj("nvalid", nvalid)
                d = _two_loop(g_dir, sk, yk, pos, nvalid, m)
            else:
                d = g_dir
            if owlqn:
                d = jnp.where(d * g_dir > 0, d, 0.0)
        ctx.put_obj("dir", d)
        if dense:
            ctx.put_obj("dir_trace", upd(ctx.get_obj("dir_trace"), d,
                                         step - 1, 0))
            ctx.put_obj("scale_trace", upd(
                ctx.get_obj("scale_trace"), ctx.get_obj("step_scale"),
                step - 1, 0))
        ctx.put_obj("grad_prev", g_plain)
        ctx.put_obj("pg", g_dir)

        steps = (lr * jnp.asarray(ladder)) * ctx.get_obj("step_scale")
        shard = _shard_views(ctx, data_keys, const_keys)
        eta0 = ctx.get_obj("eta0") if ctx.contains_obj("eta0") else None
        ctx.put_obj("line_losses",
                    shard_line(obj, shard, coef, d, steps, eta0, dtype))
        ctx.put_obj("steps", steps)

    def update_model(ctx):
        coef = ctx.get_obj("coef")
        d = ctx.get_obj("dir")
        steps = ctx.get_obj("steps")
        glw = ctx.get_obj("glw")
        l1, l2 = ctx.get_obj("hyp_l1"), ctx.get_obj("hyp_l2")
        n_steps = steps.shape[0]
        W = jnp.maximum(glw[dim + 1], _TINY)
        with jax.named_scope("qn_update"):
            reg = jax.vmap(
                lambda s: obj.regular_loss(coef - s * d, l1, l2))(steps)
            line = ctx.get_obj("line_losses")
            total = line[:n_steps] / W + reg
            best = jnp.argmin(total)
            s_best = steps[best]
            new_coef = coef - s_best * d
            if owlqn:
                pg = ctx.get_obj("pg")
                orthant = jnp.where(coef != 0, jnp.sign(coef), -jnp.sign(pg))
                new_coef = jnp.where(new_coef * orthant < 0, 0.0, new_coef)
        ctx.put_obj("coef_prev", coef)
        ctx.put_obj("coef", new_coef)
        if dense:
            at = ctx.step_no - 1
            ctx.put_obj("rung_trace", upd(ctx.get_obj("rung_trace"),
                                          best.astype(jnp.int32), at, 0))
            ctx.put_obj("step_trace", upd(ctx.get_obj("step_trace"), s_best,
                                          at, 0))
            ctx.put_obj("rows_trace", upd(
                ctx.get_obj("rows_trace"), jnp.stack(
                    [_join_count(glw[dim + 2], glw[dim + 3]),
                     _join_count(line[n_steps], line[n_steps + 1])]), at, 0))
        ctx.probe("update_ratio", jnp.linalg.norm(new_coef - coef)
                  / jnp.maximum(1.0, jnp.linalg.norm(coef)))
        # adapt the ladder like the reference's step grow/shrink heuristic
        scale = ctx.get_obj("step_scale")
        scale = jnp.where(best == 0, scale * 0.25,
                          jnp.where(best == 1, scale * 2.0,
                                    jnp.where(best == _NUM_SEARCH_STEP, scale * 0.5, scale)))
        ctx.put_obj("step_scale", jnp.clip(scale, 1e-10, 1e6))

    queue = (IterativeComQueue(env=env, max_iter=max_iter, seed=seed)
             .init_with_broadcast_data("coef0", w0)
             .add(calc_grad)
             .add(AllReduce("glw"))
             .add(direction_and_losses)
             .add(AllReduce("line_losses"))
             .add(update_model)
             .set_compare_criterion(lambda ctx: ctx.get_obj("conv"))
             .set_program_key((QN_PROGRAM, owlqn, m, str(dtype), data_keys,
                               const_keys, _freeze(obj))))
    for k, v in parts.items():
        queue.init_with_partitioned_data(k, v)
    for k, v in consts.items():
        queue.init_with_broadcast_data(k, v)
    for k, v in hyp.items():
        queue.init_with_broadcast_data(k, np.asarray(v, dtype))
    return queue


# ---------------------------------------------------------------------------
# mini-batch SGD (reference Sgd.java CalcSubGradient :101-140)
# ---------------------------------------------------------------------------

def _sgd(obj, data, params, env, warm_start):
    from ....common.mlenv import MLEnvironmentFactory
    env = env or MLEnvironmentFactory.get_default()
    dim = obj.dim
    data, consts = obj.prepare_data(data, env.num_workers)
    data_keys, const_keys = tuple(data), tuple(consts)
    dtype = optimize_dtype(data)
    max_iter = params.max_iter
    frac = params.mini_batch_fraction
    w0 = np.zeros(dim, dtype) if warm_start is None else np.asarray(warm_start, dtype)

    def calc_grad(ctx):
        if ctx.is_init_step:
            ctx.put_obj("coef", ctx.get_obj("coef0"))
            ctx.put_obj("loss_curve", jnp.full((max_iter,), jnp.nan, dtype))
            ctx.put_obj("conv", jnp.asarray(False))
        shard = _shard_views(ctx, data_keys, const_keys)
        # per-worker random sub-sample each superstep, on-device RNG
        mask = jax.random.bernoulli(ctx.rng_key(), frac, shard["y"].shape)
        sub = dict(shard)
        sub["w"] = shard["w"] * mask.astype(shard["w"].dtype)
        g, loss, wsum = obj.calc_grad_shard(sub, ctx.get_obj("coef"))
        ctx.put_obj("glw", jnp.concatenate([g, jnp.stack([loss, wsum])]))

    def update(ctx):
        glw = ctx.get_obj("glw")
        coef = ctx.get_obj("coef")
        wsum = glw[dim + 1]
        nonempty = wsum > 0
        W = jnp.maximum(wsum, _TINY)
        g = glw[:dim] / W + obj.l2_grad(coef)
        step = ctx.step_no
        lr = params.learning_rate / jnp.sqrt(step.astype(dtype))
        new_coef = coef - lr * g
        if obj.l1 > 0:  # proximal soft-threshold for L1
            thr = obj.l1 * lr * obj._reg_mask(coef)
            new_coef = jnp.sign(new_coef) * jnp.maximum(jnp.abs(new_coef) - thr, 0.0)
        new_coef = jnp.where(nonempty, new_coef, coef)  # skip empty minibatches
        ctx.put_obj("coef", new_coef)
        loss_total = glw[dim] / W + obj.regular_loss(coef)
        ctx.put_obj("loss_curve", jax.lax.dynamic_update_index_in_dim(
            ctx.get_obj("loss_curve"), loss_total.astype(dtype), step - 1, 0))
        ctx.put_obj("conv", nonempty & (jnp.linalg.norm(lr * g) <
                    params.epsilon * jnp.maximum(1.0, jnp.linalg.norm(coef))))
        # default health probes — replicated post-allreduce scalars only
        ctx.probe("loss", loss_total)
        ctx.probe("grad_norm", jnp.linalg.norm(g))
        ctx.probe_nonfinite("grad", g)
        ctx.probe("update_ratio", jnp.linalg.norm(new_coef - coef)
                  / jnp.maximum(1.0, jnp.linalg.norm(coef)))

    queue = (IterativeComQueue(env=env, max_iter=max_iter, seed=params.seed)
             .init_with_broadcast_data("coef0", w0)
             .add(calc_grad)
             .add(AllReduce("glw"))
             .add(update)
             .set_compare_criterion(lambda ctx: ctx.get_obj("conv"))
             .set_program_key(("sgd", params.learning_rate, params.epsilon,
                               params.mini_batch_fraction, str(dtype),
                               data_keys, const_keys, _freeze(obj))))
    for k, v in data.items():
        queue.init_with_partitioned_data(k, v)
    for k, v in consts.items():
        queue.init_with_broadcast_data(k, v)
    _apply_checkpoint(queue, params)
    res = queue.exec()
    steps = res.step_count
    return res.get("coef"), _trim_curve(res.get("loss_curve"), steps), steps


# ---------------------------------------------------------------------------
# Newton (reference Newton.java — dense Hessian + solve)
# ---------------------------------------------------------------------------

def _newton(obj, data, params, env, warm_start):
    from ....common.mlenv import MLEnvironmentFactory
    env = env or MLEnvironmentFactory.get_default()
    dim = obj.dim
    data, consts = obj.prepare_data(data, env.num_workers)
    data_keys, const_keys = tuple(data), tuple(consts)
    dtype = optimize_dtype(data)
    max_iter = params.max_iter
    w0 = np.zeros(dim, dtype) if warm_start is None else np.asarray(warm_start, dtype)

    def calc(ctx):
        if ctx.is_init_step:
            ctx.put_obj("coef", ctx.get_obj("coef0"))
            ctx.put_obj("loss_curve", jnp.full((max_iter,), jnp.nan, dtype))
            ctx.put_obj("conv", jnp.asarray(False))
        shard = _shard_views(ctx, data_keys, const_keys)
        H, g, loss, wsum = obj.hessian_shard(shard, ctx.get_obj("coef"))
        ctx.put_obj("H", H)
        ctx.put_obj("glw", jnp.concatenate([g, jnp.stack([loss, wsum])]))

    def update(ctx):
        glw = ctx.get_obj("glw")
        coef = ctx.get_obj("coef")
        W = jnp.maximum(glw[dim + 1], _TINY)
        g = glw[:dim] / W + obj.l2_grad(coef)
        H = ctx.get_obj("H") / W
        reg_diag = obj.l2 * obj._reg_mask(coef) + 1e-8
        H = H + jnp.diag(reg_diag.astype(H.dtype))
        d = jnp.linalg.solve(H, g)
        ctx.put_obj("coef", coef - d)
        step = ctx.step_no
        loss_total = glw[dim] / W + obj.regular_loss(coef)
        ctx.put_obj("loss_curve", jax.lax.dynamic_update_index_in_dim(
            ctx.get_obj("loss_curve"), loss_total.astype(dtype), step - 1, 0))
        ctx.put_obj("conv", jnp.linalg.norm(d) <
                    params.epsilon * jnp.maximum(1.0, jnp.linalg.norm(coef)))
        # default health probes — replicated post-allreduce scalars only
        ctx.probe("loss", loss_total)
        ctx.probe("grad_norm", jnp.linalg.norm(g))
        ctx.probe_nonfinite("grad", g)
        ctx.probe("update_ratio", jnp.linalg.norm(d)
                  / jnp.maximum(1.0, jnp.linalg.norm(coef)))

    queue = (IterativeComQueue(env=env, max_iter=max_iter, seed=params.seed)
             .init_with_broadcast_data("coef0", w0)
             .add(calc)
             .add(AllReduce("H"))
             .add(AllReduce("glw"))
             .add(update)
             .set_compare_criterion(lambda ctx: ctx.get_obj("conv"))
             .set_program_key(("newton", params.epsilon, str(dtype),
                               data_keys, const_keys, _freeze(obj))))
    for k, v in data.items():
        queue.init_with_partitioned_data(k, v)
    for k, v in consts.items():
        queue.init_with_broadcast_data(k, v)
    _apply_checkpoint(queue, params)
    res = queue.exec()
    steps = res.step_count
    return res.get("coef"), _trim_curve(res.get("loss_curve"), steps), steps


# ---------------------------------------------------------------------------

def _shard_views(ctx, keys, const_keys=()):
    """Collect this worker's shards of the partitioned training arrays
    (including fb_A/fb_B one-hot factors when precomputed) and, beside
    them, the replicated constants a dense shard folds (``scale``,
    ``shift``)."""
    return {k: ctx.get_obj(k) for k in tuple(keys) + tuple(const_keys)}


def _fb_precompute_ok(obj, data) -> bool:
    """Precompute the one-hot design factors (ops/fieldblock.py
    fb_onehot_parts) when they fit the per-device HBM budget. The factors
    are data-constant, so building them once and reusing them across every
    pass and iteration saves a write+read of the full one-hot per pass
    (Criteo-shape superstep ~15 ms -> ~8 ms on v5e)."""
    meta = getattr(obj, "fb_meta", None)
    if meta is None or "fb_idx" not in data:
        return False
    if jax.process_count() > 1:
        # the factors are built committed to this process's device; the
        # global-mesh jit cannot auto-reshard host-local committed arrays
        return False
    # registry-declared (common/flags.py): key-neutral because toggling
    # the precompute changes the partitioned-input NAME SET, which
    # already rides the program-cache key
    from ....common.flags import flag_value
    budget = float(flag_value("ALINK_TPU_FB_ONEHOT_BYTES"))
    if budget <= 0:
        return False
    from ....ops.fieldblock import LO, _default_dtype
    # budget the FULL build: the factors are materialized on the default
    # device before comqueue shards them, so per-shard accounting would
    # let an n-worker mesh overshoot the single chip's HBM n-fold
    n_total = int(data["fb_idx"].shape[0])
    elem = np.dtype(_default_dtype()).itemsize
    need = n_total * meta.num_fields * (meta.hi_size + LO) * elem
    return need <= budget


def _trim_curve(curve: np.ndarray, steps: int) -> np.ndarray:
    """The executed-prefix of the preallocated loss history.

    Trimmed by the engine's superstep count — the SAME truth the health
    probe series trim by (``ComQueueResult.probe_series``) — never by
    counting non-NaN entries: a mid-run NaN loss (exactly the case the
    health watchdog exists for) would make the count undershoot and
    silently mis-index the curve against the probe series."""
    curve = np.asarray(curve)
    return curve[:int(steps)]
