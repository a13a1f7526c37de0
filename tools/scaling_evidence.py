# -*- coding: utf-8 -*-
"""Scaling-evidence artifact: MEASURED multi-device execution + the
compiled-collective audit (ISSUE 9; supersedes the projected SCALING_r05).

Modes:

  --measured (default)  spawn fresh interpreters with REAL host-platform
                        device meshes (bootenv.cpu_mesh_env — XLA flags
                        latch at backend init, so each device count needs
                        its own process) at 1/4/8 devices, execute the
                        compiled BSP programs, and write SCALING_r06.json
                        with measured per-superstep walltimes, measured
                        superstep efficiency t(1 dev)/t(p dev) at constant
                        per-device rows, and the compiled all-reduce
                        counts for every iterative trainer (logreg,
                        kmeans, ALS, GBDT, FTRL, Word2Vec, FM).
  --projected           the legacy r05 artifact (virtual-mesh audit +
                        ring-model projections), kept for comparison.

Legacy r05 evidence (kept under --projected), written to SCALING_r05.json
and summarized in docs/parallelism.md:

1. **Compiled-collective audit.** Each ComQueue workload's FULL
   multi-chip training program is lowered on an 8-virtual-device mesh
   and its optimized HLO is scanned for collective ops
   (all-reduce/all-gather/collective-permute/all-to-all). The payload
   bytes come from the collectives' OWN result shapes in the compiled
   module — not from hand accounting — so "one small psum per
   superstep" is checked against what XLA actually emits.
   NOTE: collectives are counted per compiled MODULE. The engine runs
   the first superstep OUTSIDE the while_loop (the init pass), so every
   per-superstep collective appears TWICE in the module (init copy +
   loop-body copy): collectives per superstep = num_collectives / 2.

2. **Analytic scaling model.** Ring all-reduce of M bytes over p chips
   moves 2M(p-1)/p bytes per link: t_comm ~ 2M/BW_ici + hop latency *
   (p-1 within a ring). With the per-superstep compute time measured on
   the real v5e chip (BENCH capture) and the public v5e ICI spec
   (1600 Gbps/chip bidirectional), projected weak-scaling efficiency at
   p chips = t_compute / (t_compute + t_comm(p)). The collective
   payloads here are model-sized (KB..MB) while supersteps are
   millisecond-scale, so the model's headroom is large; the table makes
   that statement quantitative and falsifiable.

3. **Virtual-mesh weak scaling.** The engine executes the same programs
   at 8/16/32 virtual CPU devices (per-device data held constant).
   This cannot measure ICI (all "chips" share one host core) — the
   recorded walltimes are CORRECTNESS/overhead evidence: the program
   compiles, runs, and its host-side orchestration cost does not grow
   with the mesh (total walltime tracks total data, i.e. the single
   core emulating p devices).

4. **Measured cross-process collective latency.** 2- and 4-process
   ``jax.distributed`` CPU meshes time a tiny cross-process psum — the
   software collective-launch path, bracketing the 1 us ICI-hop
   hardware assumption from above; the artifact carries projections
   under BOTH latency terms.

Run: JAX_PLATFORMS=cpu \
     XLA_FLAGS=--xla_force_host_platform_device_count=32 \
     python tools/scaling_evidence.py
"""

import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

# v5e public specs
ICI_GBPS = 1600.0 / 8            # 1600 Gbps/chip -> GB/s
HOP_LATENCY_S = 1e-6             # ~1 us per ICI hop (order of magnitude)

_SHAPE = re.compile(
    r"=\s*\(?((?:[a-z0-9]+\[[0-9,]*\][,{}0-9\s]*)+)\)?\s*"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(?:-start)?\(")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1}


def collective_payloads(hlo_text: str):
    """[(op, bytes)] for every collective in an optimized HLO module,
    payload = the op's result shape(s)."""
    out = []
    for m in _SHAPE.finditer(hlo_text):
        shapes, op = m.group(1), m.group(2)
        total = 0
        for sm in re.finditer(r"([a-z0-9]+)\[([0-9,]*)\]", shapes):
            dt, dims = sm.group(1), sm.group(2)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES.get(dt, 4)
        out.append((op, total))
    return out


def build_workloads(env):
    """name -> (queue builder, rows per device, superstep label)."""
    from alink_tpu.engine import AllReduce, IterativeComQueue
    from alink_tpu.operator.common.optim.objfunc import (LogLossFunc,
                                                         UnaryLossObjFunc)
    from alink_tpu.ops.fieldblock import FieldBlockMeta

    nw = env.num_workers
    per_dev = 256                       # weak scaling: rows PER DEVICE

    def logreg_queue():
        # the bench's Criteo-shape L-BFGS program at its real dim
        import alink_tpu.operator.common.optim.optimizers as O
        meta = FieldBlockMeta(32, 2048)
        n = per_dev * nw
        r = np.random.RandomState(0)
        data = {"fb_idx": r.randint(0, 2048, (n, 32)).astype(np.int16),
                "y": r.choice([-1.0, 1.0], n).astype(np.float32),
                "w": np.ones(n, np.float32)}
        obj = UnaryLossObjFunc(LogLossFunc(), meta.dim, l2=1e-4, fb_meta=meta)
        params = O.OptimParams(method="LBFGS", max_iter=3, epsilon=0.0)
        # rebuild the exact queue _quasi_newton builds, via its internals
        return _optimizer_queue(O, obj, data, params, env)

    def kmeans_queue():
        from alink_tpu.operator.common.clustering import kmeans as K
        n = per_dev * nw
        r = np.random.RandomState(0)
        X = r.randn(n, 4).astype(np.float32)
        data = np.concatenate([X, np.ones((n, 1), np.float32)], 1)
        k, d = 3, 4

        def assign(ctx):
            import jax
            import jax.numpy as jnp
            if ctx.is_init_step:
                ctx.put_obj("centroids", ctx.get_obj("init_centroids"))
                ctx.put_obj("movement", jnp.asarray(jnp.inf, jnp.float32))
            block = ctx.get_obj("data")
            Xb, wb = block[:, :d], block[:, d]
            C = ctx.get_obj("centroids")
            ids, _ = K.assign_clusters(Xb, C, "EUCLIDEAN")
            onehot = jax.nn.one_hot(ids, k, dtype=jnp.float32) * wb[:, None]
            sums = onehot.T @ Xb
            cnts = onehot.sum(0)
            ctx.put_obj("buf", jnp.concatenate([sums, cnts[:, None]], 1))

        def update(ctx):
            import jax.numpy as jnp
            buf = ctx.get_obj("buf")
            C = ctx.get_obj("centroids")
            sums, cnts = buf[:, :d], buf[:, d]
            newC = jnp.where(cnts[:, None] > 0,
                             sums / jnp.maximum(cnts[:, None], 1e-12), C)
            ctx.put_obj("movement", jnp.sqrt(((newC - C) ** 2).sum(1)).max())
            ctx.put_obj("centroids", newC)

        return (IterativeComQueue(env=env, max_iter=10)
                .init_with_partitioned_data("data", data)
                .init_with_broadcast_data(
                    "init_centroids", np.eye(k, d, dtype=np.float32))
                .add(assign).add(AllReduce("buf")).add(update)
                .set_program_key(("scaling_ev_kmeans", k, d, nw)))

    def als_queue():
        from alink_tpu.operator.common.recommendation import als as A
        n = per_dev * nw
        r = np.random.RandomState(0)
        users = r.randint(0, 512, n)
        items = r.randint(0, 256, n)
        ratings = r.rand(n).astype(np.float32) * 5

        class Q:
            def lowered(self):
                return _capture_als_lowered(A, users, items, ratings, env)
        return Q()

    def gbdt_queue():
        from alink_tpu.operator.common.tree.trainers import (TreeTrainParams,
                                                             gbdt_train)
        n = per_dev * nw
        r = np.random.RandomState(0)
        X = r.randn(n, 8).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)

        class Q:
            def lowered(self):
                return capture_lowered(lambda: gbdt_train(
                    X, y, TreeTrainParams(num_trees=5, max_depth=4),
                    is_regression=False, env=env))
        return Q()

    def ftrl_sparse_step():
        # the bounded-staleness FTRL stream step (the r05 headline row) —
        # a standalone jitted shard_map program, not a ComQueue: the one
        # psum in the scan body executes B/K times per micro-batch
        from alink_tpu.operator.stream.onlinelearning.ftrl import (
            _ftrl_sparse_staleness_step_factory)
        dim, width, B, K = 65_536, 40, 4096, 32
        dim_pad = -(-dim // nw) * nw
        step = _ftrl_sparse_staleness_step_factory(
            env.mesh, 0.05, 1.0, 1e-5, 1e-5, K)
        idx = np.zeros((B, width), np.int32)
        val = np.zeros((B, width), np.float32)
        yv = np.zeros((B,), np.float32)
        z = np.zeros((dim_pad,), np.float32)
        nacc = np.zeros((dim_pad,), np.float32)

        class Q:
            kind = "stream_step"
            executions_per_batch = B // K

            def lowered(self):
                return step.lower(idx, val, yv, z, nacc)
        return Q()

    def word2vec_queue():
        # periodic psum of the input/output embedding matrices
        # (Word2VecTrainBatchOp.java:329-342) — the AllReduce(mean) stage
        # reduces a TWO-leaf pytree
        from alink_tpu.common.mtable import MTable
        from alink_tpu.operator.common.nlp.word2vec import (Word2VecParams,
                                                            word2vec_train)
        words = [f"w{i}" for i in range(32)]
        rr = np.random.RandomState(0)
        rows = [(" ".join(rr.choice(words, 12)),) for _ in range(16 * nw)]
        table = MTable(rows, "doc STRING")

        class Q:
            def lowered(self):
                return capture_lowered(lambda: word2vec_train(
                    table, "doc",
                    Word2VecParams(vector_size=8, min_count=1, num_iter=3,
                                   window=2, batch_size=32), env=env))
        return Q()

    def fm_queue():
        # FmOptimizer.java:273-295 weighted model average: AllReduce(avg)
        # + AllReduce(lw) adjacent stages
        from alink_tpu.operator.common.fm.fm import FmTrainParams, fm_train
        n = per_dev * nw
        rr = np.random.RandomState(0)
        Xf = rr.randn(n, 16).astype(np.float32)
        yf = np.where(Xf[:, 0] > 0, 1.0, -1.0).astype(np.float32)
        fd = {"X": Xf, "y": yf, "w": np.ones(n, np.float32)}

        class Q:
            def lowered(self):
                return capture_lowered(lambda: fm_train(
                    fd, 16, FmTrainParams(num_factors=4, num_epochs=3),
                    env=env))
        return Q()

    return {"logreg_criteo": logreg_queue, "kmeans": kmeans_queue,
            "als_movielens_shape": als_queue, "gbdt_adult_shape": gbdt_queue,
            "ftrl_sparse_staleness": ftrl_sparse_step,
            "word2vec": word2vec_queue, "fm": fm_queue}


class _Captured(Exception):
    pass


def capture_lowered(fn, program=None):
    """Run ``fn`` (which internally builds and execs an IterativeComQueue)
    with exec() patched to capture the LOWERED program instead of running
    it. Re-raises the underlying error if fn never reached exec().
    ``program``: the label of the queue to capture (the first word of its
    program key) where ``fn`` runs several; the others execute."""
    import alink_tpu.engine.comqueue as cq
    captured = {}
    orig = cq.IterativeComQueue.exec

    def spy(queue_self):
        if program is not None and cq._program_label(
                queue_self._program_key) != program:
            return orig(queue_self)
        captured["lowered"] = queue_self.lowered()
        raise _Captured()    # short-circuit: unwind out of fn

    cq.IterativeComQueue.exec = spy
    try:
        fn()
    except _Captured:
        pass
    finally:
        cq.IterativeComQueue.exec = orig
    if "lowered" not in captured:
        raise RuntimeError("fn returned without building a ComQueue program")
    return captured["lowered"]


def _optimizer_queue(O, obj, data, params, env):
    class Q:
        def lowered(self):
            return capture_lowered(
                lambda: O.optimize(obj, data, params, env))
    return Q()


def _capture_als_lowered(A, users, items, ratings, env):
    return capture_lowered(lambda: A.als_train(
        users, items, ratings,
        A.AlsTrainParams(rank=10, num_iter=5, lambda_reg=0.1), env=env),
        program=A.SWEEP_PROGRAM)


def audit(env):
    rows = {}
    for name, build in build_workloads(env).items():
        q = build()
        low = q.lowered()
        hlo = low.compile().as_text()
        colls = collective_payloads(hlo)
        total = sum(b for _, b in colls)
        if getattr(q, "kind", "comqueue") == "stream_step":
            # standalone stream step: the module IS one micro-batch step;
            # the scan-body collective executes executions_per_batch times
            rows[name] = {
                "collective_ops": [f"{op}:{b}B" for op, b in colls],
                "num_collectives_in_module": len(colls),
                "payload_bytes_in_module": total,
                "module_kind": "stream_step",
                "collective_executions_per_micro_batch":
                    q.executions_per_batch * len(colls),
                "payload_bytes_per_micro_batch":
                    total * q.executions_per_batch,
            }
            continue
        # the module holds init-pass + while_loop-body copies of every
        # per-superstep collective (engine runs superstep 1 outside the
        # loop); guard the /2 against queues where that pairing does not
        # hold (max_iter == 1, or CSE/duplication by XLA)
        from collections import Counter
        counts = Counter(colls)
        assert all(v % 2 == 0 for v in counts.values()), (name, colls)
        rows[name] = {
            "collective_ops": [f"{op}:{b}B" for op, b in colls],
            "num_collectives_in_module": len(colls),
            "payload_bytes_in_module": total,
            "module_kind": "comqueue",
            "payload_bytes_per_superstep": total // 2,
        }
    return rows


def model_efficiency(payload_bytes, superstep_ms, chips,
                     hop_latency_s=HOP_LATENCY_S):
    """Ring all-reduce projection (see module docstring)."""
    t_comm = (2.0 * payload_bytes * (chips - 1) / chips / (ICI_GBPS * 1e9)
              + hop_latency_s * (chips - 1))
    t_comp = superstep_ms / 1e3
    return round(t_comp / (t_comp + t_comm), 4)


_LAT_CHILD = r"""
import sys, time
import numpy as np
coordinator, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
from alink_tpu.common.mlenv import use_remote_env
env = use_remote_env(coordinator_address=coordinator, num_processes=nproc,
                     process_id=pid, parallelism=nproc)
import jax
import jax.numpy as jnp
from alink_tpu.common.compat import shard_map
from jax.sharding import PartitionSpec as P

@jax.jit
def tiny_psum(x):
    return shard_map(lambda v: jax.lax.psum(v, "d"), mesh=env.mesh,
                     in_specs=P("d"), out_specs=P())(x)

x = np.arange(nproc, dtype=np.float32)
r = tiny_psum(x)
jax.block_until_ready(r)                      # compile outside the timing
reps = 300
ts = []
for _ in range(reps):
    t0 = time.perf_counter()
    jax.block_until_ready(tiny_psum(x))
    ts.append(time.perf_counter() - t0)
ts.sort()
if pid == 0:
    print("LAT_US", round(ts[len(ts) // 2] * 1e6, 1),
          round(ts[reps // 10] * 1e6, 1))     # median, p10
"""


def measured_collective_latency():
    """Spawn 2- and 4-process jax.distributed CPU meshes (the
    test_remote_env.py harness) and TIME a tiny cross-process psum.
    This measures the software collective path (gRPC/Gloo loopback on a
    shared host core) — an upper bound on per-collective launch overhead,
    bracketing the 1 us ICI-hop hardware assumption from above."""
    import socket
    import subprocess
    import tempfile
    repo_root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    sys.path.insert(0, repo_root)
    from bootenv import cpu_mesh_env

    out = {}
    for nproc in (2, 4):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        coordinator = f"127.0.0.1:{s.getsockname()[1]}"
        s.close()
        with tempfile.TemporaryDirectory() as td:
            script = os.path.join(td, "lat_child.py")
            with open(script, "w") as f:
                f.write(_LAT_CHILD)
            procs = []
            for pid in range(nproc):
                envv = cpu_mesh_env(1)
                envv["JAX_PLATFORMS"] = "cpu"
                envv["PYTHONPATH"] = (repo_root + os.pathsep +
                                      envv.get("PYTHONPATH", ""))
                procs.append(subprocess.Popen(
                    [sys.executable, script, coordinator, str(pid),
                     str(nproc)],
                    env=envv, stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT, cwd=repo_root))
            texts = []
            ok = True
            for p in procs:
                try:
                    o, _ = p.communicate(timeout=300)
                except subprocess.TimeoutExpired:
                    for q in procs:
                        q.kill()
                    ok = False
                    break
                texts.append(o.decode(errors="replace"))
                ok = ok and p.returncode == 0
            row = {"ok": ok}
            for t in texts:
                for ln in t.splitlines():
                    if ln.startswith("LAT_US"):
                        _, med, p10 = ln.split()
                        row["median_us"] = float(med)
                        row["p10_us"] = float(p10)
            out[f"{nproc}proc"] = row
    return out


def weak_scaling(env_sizes):
    """Same ComQueue program at 8/16/32 virtual devices, constant rows
    per device; records walltime per superstep."""
    from alink_tpu.common.mlenv import MLEnvironment
    out = {}
    for nw in env_sizes:
        env = MLEnvironment(parallelism=nw)
        build = build_workloads(env)["kmeans"]
        build().exec()                       # warm compile (program cache)
        q = build()
        t0 = time.perf_counter()
        res = q.exec()
        np.asarray(res.get("centroids")).sum()   # results fetch lazily:
        dt = time.perf_counter() - t0            # force execution+fetch
        out[str(nw)] = round(dt, 3)
    return out


# ---------------------------------------------------------------------------
# measured multi-device execution (SCALING_r06; ISSUE 9 tentpole 2)
# ---------------------------------------------------------------------------

MEASURED_DEVICE_COUNTS = (1, 4, 8)


def _measure_child(n_devices: int, with_audit: bool) -> dict:
    """Runs INSIDE a child interpreter whose backend was launched with
    ``--xla_force_host_platform_device_count=n_devices``: executes the
    real compiled BSP programs over the n-device mesh and returns
    measured per-superstep walltimes (+ the compiled-HLO collective audit
    when ``with_audit``)."""
    import jax
    assert len(jax.devices()) >= n_devices, (
        f"child expected {n_devices} devices, got {len(jax.devices())}")
    from alink_tpu.common.mlenv import MLEnvironment
    from alink_tpu.engine import AllReduce, IterativeComQueue
    env = MLEnvironment(parallelism=n_devices,
                        devices=jax.devices()[:n_devices])
    per_dev = 256
    out = {"n_devices": n_devices, "workloads": {}}

    def timed_queue(name, build_exec, steps_of):
        """exec twice (compile, then cached) and record the cached run's
        per-superstep wall."""
        build_exec()                       # warm: compile + program cache
        t0 = time.perf_counter()
        res = build_exec()
        steps = steps_of(res)
        wall = time.perf_counter() - t0
        out["workloads"][name] = {
            "supersteps": int(steps),
            "superstep_ms": round(wall * 1e3 / max(steps, 1), 4),
            "wall_s": round(wall, 4)}

    # logreg (L-BFGS, field-blocked Criteo shape scaled down)
    import alink_tpu.operator.common.optim.optimizers as O
    from alink_tpu.operator.common.optim.objfunc import (LogLossFunc,
                                                         UnaryLossObjFunc)
    from alink_tpu.ops.fieldblock import FieldBlockMeta
    r = np.random.RandomState(0)
    meta = FieldBlockMeta(16, 256)
    n = per_dev * n_devices
    data = {"fb_idx": r.randint(0, 256, (n, 16)).astype(np.int16),
            "y": r.choice([-1.0, 1.0], n).astype(np.float32),
            "w": np.ones(n, np.float32)}

    def logreg_exec():
        obj = UnaryLossObjFunc(LogLossFunc(), meta.dim, l2=1e-4,
                               fb_meta=meta)
        coef, curve, steps = O.optimize(
            obj, data, O.OptimParams(method="LBFGS", max_iter=4,
                                     epsilon=0.0), env)
        np.asarray(coef).sum()            # force + fetch
        return steps
    timed_queue("logreg_criteo", logreg_exec, lambda s: s)

    # kmeans (the r05 weak-scaling workload)
    def kmeans_exec():
        build = build_workloads(env)["kmeans"]
        res = build().exec()
        np.asarray(res.get("centroids")).sum()
        return res.step_count
    timed_queue("kmeans", kmeans_exec, lambda s: s)

    # ALS (block-parallel half-sweeps; 3 normal-equation psums per side)
    from alink_tpu.operator.common.recommendation import als as A
    users = r.randint(0, 64 * n_devices, 40 * n_devices)
    items = r.randint(0, 48, 40 * n_devices)
    ratings = (r.rand(40 * n_devices) * 5).astype(np.float32)

    def als_exec():
        uf, if_, rmse, *_ = A.als_train(
            users, items, ratings,
            A.AlsTrainParams(rank=8, num_iter=5, lambda_reg=0.1), env=env)
        np.asarray(uf).sum()
        return 5
    timed_queue("als_movielens_shape", als_exec, lambda s: s)

    # FTRL bounded-staleness stream step: K=32 (B/K margin psums per
    # micro-batch — 64 at the measured B=2048 shape here, 128 at the
    # production 4096-row bench shape) vs K=B (ONE psum per micro-batch —
    # the VERDICT next-round #3 margin-chunking configuration; same
    # staleness CONTRACT, bound = batch)
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        _ftrl_sparse_staleness_step_factory)
    dim, width, B = 16_384, 24, 2048
    dim_pad = -(-dim // n_devices) * n_devices
    idx = r.randint(0, dim, (B, width)).astype(np.int32)
    val = r.rand(B, width).astype(np.float32)
    yv = r.randint(0, 2, B).astype(np.float32)
    for label, K in (("ftrl_staleness_k32", 32),
                     ("ftrl_margin_chunked", B)):
        step = _ftrl_sparse_staleness_step_factory(
            env.mesh, 0.05, 1.0, 1e-5, 1e-5, K)
        import jax.numpy as jnp
        z = jnp.zeros((dim_pad,), jnp.float32)
        nacc = jnp.zeros((dim_pad,), jnp.float32)
        jax.block_until_ready(step(idx, val, yv, z, nacc))   # compile
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            z2, n2, m2 = step(idx, val, yv, z, nacc)
            jax.block_until_ready(m2)
        wall = time.perf_counter() - t0
        out["workloads"][label] = {
            "per_micro_batch_ms": round(wall * 1e3 / reps, 4),
            "margin_psums_per_micro_batch": B // K,
            "staleness_bound": K}

    if with_audit:
        out["audit"] = audit(env)
    return out


def _spawn_child(n_devices: int, args: list, timeout: int = 1800) -> dict:
    """Re-invoke this tool in a fresh interpreter on an n-device
    host-platform CPU mesh (XLA flags latch at backend init — bootenv)."""
    import subprocess
    repo_root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from bootenv import cpu_mesh_env
    envv = cpu_mesh_env(n_devices)
    envv["PYTHONPATH"] = repo_root + os.pathsep + envv.get("PYTHONPATH", "")
    envv["ALINK_TPU_METRICS"] = "0"       # timing children: no registry noise
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + args,
        env=envv, cwd=repo_root, capture_output=True, timeout=timeout)
    if p.returncode != 0:
        raise RuntimeError(
            f"scaling child (n={n_devices}, {args}) failed "
            f"rc={p.returncode}:\n{p.stdout.decode(errors='replace')[-4000:]}"
            f"\n{p.stderr.decode(errors='replace')[-4000:]}")
    # the child prints exactly one JSON document on its last line
    line = p.stdout.decode(errors="replace").strip().splitlines()[-1]
    return json.loads(line)


def _audit_per_superstep(audit_rows: dict) -> dict:
    """Collapse an audit() result to per-superstep all-reduce counts."""
    out = {}
    for name, row in audit_rows.items():
        if row.get("module_kind") == "stream_step":
            out[name] = row["collective_executions_per_micro_batch"]
        else:
            out[name] = row["num_collectives_in_module"] // 2
    return out


def measured_main(out_path: str) -> dict:
    """Orchestrate the measured-scaling capture -> SCALING_r06.json."""
    runs = {}
    nmax = max(MEASURED_DEVICE_COUNTS)
    for n in MEASURED_DEVICE_COUNTS:
        child_args = ["--child-measure", str(n)]
        if n == nmax:
            child_args.append("--with-audit")
        print(f"[scaling_evidence] measuring n={n} ...", file=sys.stderr)
        runs[n] = _spawn_child(n, child_args)

    audit_rows = runs[nmax]["audit"]

    workloads = {}
    for name in runs[MEASURED_DEVICE_COUNTS[0]]["workloads"]:
        row = {f"{n}dev": runs[n]["workloads"][name]
               for n in MEASURED_DEVICE_COUNTS}
        # measured superstep efficiency: t(1 dev) / t(p dev) at constant
        # per-device rows — compute/(compute + comm + launch overhead).
        # NOTE the honest caveat: the virtual devices share host cores,
        # so this is a lower bound on real-ICI efficiency for the compute
        # term but a truthful measurement of the collective/launch path.
        base_key = "superstep_ms" if "superstep_ms" in row["1dev"] \
            else "per_micro_batch_ms"
        t1 = row["1dev"][base_key]
        row["measured_efficiency"] = {
            str(n): round(t1 / max(row[f"{n}dev"][base_key], 1e-9), 4)
            for n in MEASURED_DEVICE_COUNTS if n > 1}
        workloads[name] = row

    artifact = {
        "artifact": "SCALING_r06",
        "method": "MEASURED multi-device execution: real host-platform "
                  "device meshes (1/4/8 devices, one fresh interpreter "
                  "per count — XLA flags latch at backend init), compiled "
                  "BSP programs executed, walltimes from cached-program "
                  "runs; collective counts from the compiled HLO of the "
                  "SAME programs (tools/scaling_evidence.py --measured)",
        "supersedes": "SCALING_r05.json — its efficiency numbers were "
                      "PROJECTED from a ring-allreduce model; every "
                      "number here is measured from executing programs",
        "mesh_note": "host-platform virtual devices share the rig's CPU "
                     "cores, so absolute walltimes are not chip times; "
                     "the per-superstep collective counts are the "
                     "transferable facts (on TPU the same programs run "
                     "unchanged over ICI)",
        "measured_workloads": workloads,
        "allreduce_per_superstep": _audit_per_superstep(audit_rows),
        "collective_audit": audit_rows,
        "dependency_notes": {
            "logreg_criteo": "2/superstep: the line-search loss psum "
                             "consumes the direction built from the "
                             "psummed gradient — no combiner can merge "
                             "them",
            "gbdt_adult_shape": "level-L histogram psum needs level-L-1's "
                                "split: per-level psums are sequential by "
                                "construction",
            "ftrl": "per-chunk margin psums are dependency-forced (state "
                    "updates feed the next chunk); the knob that buys "
                    "collectives is the staleness bound itself — "
                    "ftrl_margin_chunked (bound = batch) pays ONE margin "
                    "psum per micro-batch (VERDICT next-round #3)"},
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps({"written": out_path,
                      "allreduce_per_superstep":
                          artifact["allreduce_per_superstep"]}, indent=1))
    return artifact


def projected_main():
    import jax
    assert jax.default_backend() == "cpu", "run with JAX_PLATFORMS=cpu"
    from alink_tpu.common.mlenv import MLEnvironment
    env8 = MLEnvironment(parallelism=8)

    audit_rows = audit(env8)

    # measured per-superstep / per-micro-batch compute times on the real
    # chip, taken from the r04/r05 bench captures (samples/sec/chip at
    # the bench row's n)
    measured_ms = {
        "logreg_criteo": 1_000_000 / 21.4e6 * 1e3,   # ~46.7 ms/iter
        "kmeans": 1_500_000 / 5.0e9 * 1e3,           # ~0.3 ms/iter
        "als_movielens_shape": 1_000_209 / 22.6e6 * 1e3,
        "gbdt_adult_shape": 48_842 / 6.5e6 * 1e3,    # ms per tree
        # staleness FTRL: 4096-row micro-batch at 538k samples/s (r05)
        "ftrl_sparse_staleness": 4096 / 538e3 * 1e3,
    }
    lat = measured_collective_latency()
    lat_meas = lat.get("2proc", {}).get("p10_us")
    for name, row in audit_rows.items():
        M = row.get("payload_bytes_per_superstep",
                    row.get("payload_bytes_per_micro_batch", 0))
        # launches charged per superstep/micro-batch: ComQueue rows issue
        # num_collectives_in_module/2 collectives each superstep (LogReg
        # 2, ALS 3 — the audit's own count), stream steps their
        # per-micro-batch execution count
        n_coll = (row["num_collectives_in_module"] // 2
                  if row["module_kind"] == "comqueue"
                  else row["collective_executions_per_micro_batch"])
        ms = measured_ms.get(name)
        if ms is None:
            continue   # audit-only workloads (word2vec/fm) have no r05 pin
        row["measured_superstep_ms_1chip"] = round(ms, 3)
        row["projected_efficiency_ici_1us_hop"] = {
            str(p): model_efficiency(M, ms, p) for p in (8, 32, 128)}
        if lat_meas is not None:
            # recalibration: replace the assumed per-hop latency with the
            # MEASURED cross-process collective launch cost (p10 of the
            # 2-process loopback psum), amortized once per collective —
            # a software-path upper bound vs the hardware-hop lower bound
            row["projected_efficiency_measured_launch"] = {
                str(p): model_efficiency(
                    M, ms, p,
                    hop_latency_s=lat_meas * 1e-6 * n_coll / max(p - 1, 1))
                for p in (8, 32, 128)}

    ws = weak_scaling([8, 16, 32])

    artifact = {
        "method": "compiled-HLO collective audit + ring-allreduce model "
                  "+ measured cross-process collective latency "
                  "+ virtual-mesh weak scaling (see tools/scaling_evidence.py)",
        "ici_gbytes_per_s": ICI_GBPS,
        "hop_latency_s_assumed": HOP_LATENCY_S,
        "measured_collective_latency_us": lat,
        "latency_note": "measured = tiny cross-process psum through "
                        "jax.distributed (Gloo/gRPC loopback, processes "
                        "sharing ONE host core): an upper bound on the "
                        "software launch path per collective. The 1 us "
                        "ICI hop is the hardware lower bound; the two "
                        "projection sets bracket the answer. p10 is used "
                        "(median carries scheduler noise from core "
                        "sharing).",
        "workloads": audit_rows,
        "weak_scaling_walltime_s_kmeans_10iters": ws,
        "note": "virtual-mesh walltimes share ONE host core: they are "
                "correctness/overhead evidence, not speedup. Each "
                "per-superstep ComQueue collective appears twice in the "
                "module (init pass + while_loop body): per-superstep "
                "count = num_collectives/2, payload/2. stream_step "
                "modules are per-micro-batch programs counted as-is.",
    }
    out = os.path.join(os.path.dirname(__file__), "..", "SCALING_r05.json")
    with open(os.path.abspath(out), "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps(artifact, indent=1))


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description="Scaling evidence: measured multi-device execution "
                    "(SCALING_r06) / legacy projections")
    ap.add_argument("--measured", action="store_true",
                    help="measured capture -> SCALING_r06.json (default)")
    ap.add_argument("--projected", action="store_true",
                    help="legacy r05 projection artifact")
    ap.add_argument("--out", default=None, help="artifact path override")
    # internal child entry points (spawned by the orchestrator with an
    # n-device host-platform backend already in XLA_FLAGS)
    ap.add_argument("--child-measure", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--with-audit", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child_measure is not None:
        print(json.dumps(_measure_child(args.child_measure,
                                        args.with_audit)))
        return 0
    if args.projected:
        projected_main()
        return 0
    out = args.out or os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "SCALING_r06.json"))
    measured_main(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
