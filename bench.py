"""Benchmark: the BASELINE.md workload configs (BASELINE.md:16-22).

Six benchmarks cover the five BASELINE rows: the Criteo config appears
twice (LogReg L-BFGS warm start — the north-star — and streaming FTRL),
and Softmax/MNIST covers the LR/Softmax row.

Workloads (reference entry points in parentheses):
  1. logreg_criteo  — LogisticRegression L-BFGS on Criteo-shape hashed CTR
                      (FTRLExample.java warm-start path; the north-star).
  2. kmeans_iris    — KMeans on iris (KMeansExample.java:14-32), replicated
                      with jitter to 1.5M rows so the superstep does
                      chip-scale work.
  3. softmax_mnist  — Softmax on MNIST-shape data (pyalink/mnist.ipynb):
                      60k x 784, 10 classes, synthetic class-center blobs
                      (MNIST itself is not redistributable inside this image).
  4. ftrl_criteo    — online FTRL on a Criteo-shape sparse stream
                      (pyalink/ftrl_demo.ipynb; FtrlTrainStreamOp), driven
                      through the production sparse SPMD scan program.
  5. gbdt_adult     — GBDT on adult-shape data (pyalink/adult.ipynb),
                      histogram-psum boosting.
  6. als_movielens  — ALS on MovieLens-1M-shape ratings (ALSExample.java).

Measurement method: every timed call gets distinct inputs (no timed
call can be answered from an earlier call's result), the measured span
covers many supersteps (so the fixed per-call cost — dispatch and the
result fetch — is a small share of it), wall time is the MEDIAN of
adjacent-pair deltas between a 2-iteration and a (1+iters)-iteration
program — both contain the superstep while-loop and are precompiled, see
Harness.delta for why pairing and median. A device->host fetch of the
result ends every run: JAX dispatch is asynchronous, so a timing that
does not wait for the result measures the enqueue.

``vs_baseline`` compares against a numpy/BLAS implementation of the same
superstep on the host CPU — the stand-in for one Flink task-slot worker
(the reference publishes no numbers, BASELINE.md:3-6).

Prints one JSON line per workload as it completes, then the final
combined line {"metric", "value", "unit", "vs_baseline",
"workloads_sps_vs"} where workloads_sps_vs maps workload name ->
[samples/sec/chip, vs_baseline, pct_chip_peak_flops] (the driver parses
the last line; it keeps only a 2000-byte stdout tail, so the final line
is deliberately compact). Full per-workload detail is written to
BENCH_full.json beside this file.
"""

import json
import os
import sys
import time
import traceback

import numpy as np


def _auc(y, s):
    """Rank AUC (ties averaged)."""
    order = np.argsort(s, kind="mergesort")
    ranks = np.empty(len(s), np.float64)
    ranks[order] = np.arange(1, len(s) + 1)
    # average ranks over ties
    sv = s[order]
    i = 0
    while i < len(sv):
        j = i
        while j + 1 < len(sv) and sv[j + 1] == sv[i]:
            j += 1
        if j > i:
            ranks[order[i:j + 1]] = (i + j + 2) / 2.0
        i = j + 1
    pos = y > 0
    n1, n0 = pos.sum(), (~pos).sum()
    if n1 == 0 or n0 == 0:
        return float("nan")
    return float((ranks[pos].sum() - n1 * (n1 + 1) / 2) / (n1 * n0))


# Published peaks of ONE TPU v5e chip (Google Cloud documentation, "TPU
# v5e"): 197 TFLOP/s bf16 (MXU-native; f32 einsums run below this, so
# f32-dominated workloads understate their achievable ceiling) and
# 819 GB/s of HBM. Used to turn samples/sec into "% of chip" so a reader
# can tell compute-bound from memory/gather-bound (VERDICT r2 #2). They
# are the v5e's and nothing else's: until the table is keyed by
# device_kind (ROADMAP S1), no share of peak is written on any other
# device — nothing, not a guess.
PEAK_TFLOPS = 197.0
PEAK_HBM_GBPS = 819.0


def device_stamp():
    """The device as JAX reports it — rides the capture's ``rig`` block
    so every row can be read against the platform it really ran on."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "count": len(devs)}


def chip_peaks():
    """``(peak_tflops, peak_hbm_gbps)`` when this process runs on a TPU
    v5e (JAX reports the kind as "TPU v5 lite"), else ``(None, None)``."""
    d = device_stamp()
    kind = d["device_kind"].lower()
    if d["platform"] == "tpu" and ("v5 lite" in kind or "v5e" in kind):
        return PEAK_TFLOPS, PEAK_HBM_GBPS
    return None, None


def mfu(sps_per_chip, flops_per_sample, bytes_per_sample, bound=None):
    """Uniform roofline accounting fragment (VERDICT r4 #4) — on a v5e
    EVERY row carries all five fields; on any other device the two
    ``pct_chip_peak_*`` shares (and a ``bound`` inferred from them) are
    left out.

    ``flops_per_sample`` counts the FLOPs the kernels actually ISSUE per
    sample per iteration (one-hot MXU formulations issue more than the
    nominal sparse math — that is the design tradeoff being measured).
    ``bytes_per_sample`` is the dominant nominal HBM traffic (formula at
    each call site). ``bound`` names the binding roof
    (compute|hbm|latency|host|link); when omitted it is inferred: the
    larger of the two roof percentages if it exceeds 15% of peak, else
    "latency" (nothing near a hardware roof — op-issue/dispatch
    serialization is what limits the measured rate)."""
    ach = sps_per_chip * flops_per_sample
    bw = sps_per_chip * bytes_per_sample
    row = {"flops_per_sample": int(flops_per_sample),
           "achieved_tflops_per_chip": round(ach / 1e12, 3),
           "hbm_bytes_per_sample": int(bytes_per_sample)}
    peak_tflops, peak_hbm_gbps = chip_peaks()
    if peak_tflops is not None:
        pf = 100.0 * ach / (peak_tflops * 1e12)
        ph = 100.0 * bw / (peak_hbm_gbps * 1e9)
        if bound is None:
            bound = (("compute" if pf >= ph else "hbm")
                     if max(pf, ph) >= 15.0 else "latency")
        row["pct_chip_peak_flops"] = round(pf, 2)
        row["pct_chip_peak_hbm"] = round(ph, 2)
    if bound is not None:
        row["bound"] = bound
    return row


# ---------------------------------------------------------------------------
# Pinned compiled CPU baseline (VERDICT r5 #1 / ISSUE 6 tentpole (c))
# ---------------------------------------------------------------------------
#
# The FTRL `vs_baseline` denominator used to be a per-sample numpy loop
# re-measured every capture; host load swung it ±30-50% and moved the
# strict-FTRL ratio across the 10x bar between rounds with identical
# device throughput (r04 9.55x -> r05 7.0x on a 33k->46k baseline drift).
# The denominator is now a COMPILED single-slot FTRL loop
# (native/parser.cpp ftrl_slot_run, the stand-in for one Flink task-slot
# CalcTask) measured best-of-7 ONCE per rig and committed to
# BASELINE_compiled.json keyed by a rig fingerprint. Later captures on
# the same rig REUSE the pinned rate (no re-measure), so vs_baseline is
# comparable round-over-round; a different rig pins its own entry, and
# tools/bench_compare.py --baseline-provenance refuses to diff captures
# whose fingerprints differ. ALINK_TPU_REPIN_BASELINE=1 forces a
# re-measure (a deliberate, visible act — the file diff shows it).

BASELINE_COMPILED_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BASELINE_compiled.json")


def rig_fingerprint():
    """(fp_hash, info): a stable identity for the measuring host. The
    hash keys BASELINE_compiled.json entries and rides every bench
    artifact as ``baseline_fp`` so cross-rig ratios can be refused."""
    import hashlib
    import platform
    cpu_model = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    cpu_model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        cpu_model = platform.processor() or ""
    info = {"machine": platform.machine(), "system": platform.system(),
            "cpu_model": cpu_model, "cores": os.cpu_count(),
            "python": platform.python_version(),
            "numpy": np.__version__}
    fp = hashlib.blake2b(json.dumps(info, sort_keys=True).encode(),
                         digest_size=6).hexdigest()
    return fp, info


def _numpy_ftrl_slot_loop(idx, val, y, z, n,
                          alpha=0.05, beta=1.0, l1=1e-5, l2=1e-5):
    """THE interpreted per-sample FTRL-proximal loop, in one place: the
    pinned-baseline fallback and the vs_live_numpy context row both call
    it, so the two 'baselines' can never silently diverge. Mutates
    ``z``/``n`` in place (same contract as native ftrl_slot_run)."""
    for i in range(len(y)):
        ii, vv, yy = idx[i], val[i], y[i]
        zi, ni = z[ii], n[ii]
        decay = (beta + np.sqrt(ni)) / alpha + l2
        wi = np.where(np.abs(zi) <= l1, 0.0,
                      -(zi - np.sign(zi) * l1) / decay)
        p = 1.0 / (1.0 + np.exp(-np.clip(wi @ vv, -35, 35)))
        g = (p - yy) * vv
        sigma = (np.sqrt(ni + g * g) - np.sqrt(ni)) / alpha
        z[ii] = zi + g - sigma * wi
        n[ii] = ni + g * g


def _measure_compiled_ftrl_baseline(idx, val, y, reps: int = 7):
    """(sps_best, sps_median, impl): best-of-``reps`` of the compiled
    single-slot loop over the canonical Criteo-shape batch; falls back to
    the interpreted numpy loop (impl="numpy-interpreted") without the
    native lib so the pin is always produced — the impl tag makes the
    fallback visible in the artifact."""
    from alink_tpu.native import ftrl_slot_run
    dim = int(idx.max()) + 1
    rows = idx.shape[0]

    def run_native():
        z = np.zeros(dim)
        n = np.zeros(dim)
        t0 = time.perf_counter()
        ftrl_slot_run(idx, val, y, z, n, 0.05, 1.0, 1e-5, 1e-5)
        return time.perf_counter() - t0, z

    def run_numpy():
        zc = np.zeros(dim)
        nc = np.zeros(dim)
        t0 = time.perf_counter()
        _numpy_ftrl_slot_loop(idx, val, y, zc, nc)
        return time.perf_counter() - t0, zc

    probe_t, probe_z = run_native() if _native_available() else (None, None)
    runner, impl = ((run_native, "native-c") if probe_t is not None
                    else (run_numpy, "numpy-interpreted"))
    ts = sorted(runner()[0] for _ in range(reps))
    return (rows / ts[0], rows / ts[len(ts) // 2], impl)


def _native_available() -> bool:
    from alink_tpu.native import get_lib
    return get_lib() is not None


def pinned_ftrl_baseline(path: str = None):
    """The pinned baseline record for THIS rig: loads the committed
    entry when the fingerprint matches; otherwise measures the compiled
    loop on the canonical workload (best-of-7) and writes the entry —
    the one-time pin. Returns the record dict (fp, sps, impl,
    provenance...)."""
    path = path or BASELINE_COMPILED_PATH
    fp, info = rig_fingerprint()
    doc = {"version": 1, "workload": {
        "name": "ftrl_criteo_single_slot",
        "dim": 65_536, "nnz": 39, "width": 40, "rows": 4096, "seed": 0,
        "alpha": 0.05, "beta": 1.0, "l1": 1e-5, "l2": 1e-5},
        "rigs": {}}
    import sys
    load_failed = False
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            # NEVER rewrite over a file we could not read: the committed
            # file carries OTHER rigs' pins, and resetting it to the
            # default doc would silently erase them all
            load_failed = True
            print(f"WARNING: {path} exists but could not be read ({e}); "
                  f"measuring an in-memory baseline for this run and "
                  f"REFUSING to rewrite the file — restore it from git "
                  f"before the next capture", file=sys.stderr)
    from alink_tpu.common.flags import env_flag as _env_flag
    rec = doc.get("rigs", {}).get(fp)
    if rec is not None and not _env_flag("ALINK_TPU_REPIN_BASELINE"):
        if rec.get("impl") == "numpy-interpreted" and _native_available():
            # the pin predates the native toolchain: dividing by the
            # ~30x-slower interpreted loop would inflate vs_baseline in
            # a way the provenance gate cannot catch (same rig hash).
            # Re-pin with the compiled kernel; the provenance fp changes,
            # so old-vs-new comparisons refuse — correctly, they are not
            # the same denominator.
            print(f"NOTE: replacing this rig's numpy-interpreted baseline "
                  f"pin with the now-available compiled kernel "
                  f"(provenance fingerprint changes)", file=sys.stderr)
        else:
            return {"fp": fp, "provenance_fp": _provenance_fp(fp, rec),
                    **rec}
    # the canonical batch: the SAME make_batch(0) shape the device rows
    # train on (intercept slot + 39 one-hot CTR features, width 40)
    idx, val, y = make_batch_criteo(0)
    best, med, impl = _measure_compiled_ftrl_baseline(idx, val, y)
    import datetime
    rec = {"fingerprint": info, "impl": impl,
           "sps_best": round(best, 1), "sps_median": round(med, 1),
           "reps": 7,
           "pinned_at": datetime.datetime.now(
               datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
           "provenance": {
               "kernel": "alink_tpu/native/parser.cpp:ftrl_slot_run",
               "estimator": "best-of-7 (one-sided contention noise)",
               "note": "single Flink task-slot stand-in; strict "
                       "per-sample FTRL-proximal, compiled -O3"}}
    doc.setdefault("rigs", {})[fp] = rec
    if not load_failed:
        try:
            # write-tmp-then-rename: a killed process can truncate a
            # plain overwrite, and a truncated committed file would cost
            # every rig its pin
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, path)
        except OSError as e:
            # a pin that cannot persist will be RE-MEASURED next capture
            # — the exact drift the pin exists to kill. Say so loudly
            # (the run itself still works against the in-memory record).
            print(f"WARNING: could not persist the compiled baseline pin "
                  f"to {path} ({e}); the next capture will re-measure it "
                  f"and vs_baseline will NOT be comparable "
                  f"round-over-round", file=sys.stderr)
    return {"fp": fp, "provenance_fp": _provenance_fp(fp, rec), **rec}


def _provenance_fp(fp: str, rec: dict) -> str:
    """rig fingerprint + digest of the pinned record itself: changes when
    EITHER the rig or the pinned baseline changes, so
    ``bench_compare --baseline-provenance`` also refuses a SAME-rig
    re-pin (ALINK_TPU_REPIN_BASELINE) from silently moving
    vs_baseline."""
    import hashlib
    digest = hashlib.blake2b(
        json.dumps({"sps_best": rec.get("sps_best"),
                    "pinned_at": rec.get("pinned_at"),
                    "impl": rec.get("impl")}, sort_keys=True).encode(),
        digest_size=4).hexdigest()
    return f"{fp}-{digest}"


def baseline_provenance_fp() -> str:
    """The provenance fingerprint every bench dump carries as
    ``baseline_fp`` (pins the baseline first if this rig has none)."""
    return pinned_ftrl_baseline()["provenance_fp"]


def make_batch_criteo(seed, dim=65_536, nnz=39, B=4096):
    """The canonical Criteo-shape padded COO batch shared by the FTRL
    device rows and the pinned baseline (module-level so both cite ONE
    definition). Every row's slots are DISTINCT: duplicate-slot update
    semantics differ between numpy fancy-assignment (last-write-wins),
    the sequential C loop (read-modify-write) and the device scatter-add
    (delta accumulation), so distinct slots are what put every baseline
    implementation in exact agreement on the canonical workload."""
    width = -(-(nnz + 1) // 8) * 8
    r = np.random.RandomState(seed)
    rngw = np.random.RandomState(0)
    w_true = (rngw.randn(dim) * (rngw.rand(dim) < 0.02)).astype(np.float64)
    idx = np.zeros((B, width), np.int32)
    val = np.zeros((B, width), np.float64)
    raw = r.randint(1, dim, size=(B, nnz)).astype(np.int32)
    for _ in range(64):                  # resample intra-row collisions
        srt = np.sort(raw, axis=1)
        dup = (srt[:, 1:] == srt[:, :-1]).any(1)
        if not dup.any():
            break
        raw[dup] = r.randint(1, dim, size=(int(dup.sum()), nnz))
    idx[:, 0] = 0                        # intercept
    val[:, 0] = 1.0
    idx[:, 1:nnz + 1] = raw
    val[:, 1:nnz + 1] = 1.0              # one-hot CTR features
    margin = w_true[raw].sum(1)
    y = (r.rand(B) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float64)
    return idx, val, y


_DEVICE_CONCAT = None


def _device_concat(*parts):
    """Module-level jitted concatenate: ONE traced function for the whole
    process (jax.jit caches by function identity), so the timed
    from-disk pipeline leg only ever compiles it during warmup."""
    global _DEVICE_CONCAT
    if _DEVICE_CONCAT is None:
        import jax
        import jax.numpy as jnp
        _DEVICE_CONCAT = jax.jit(lambda *xs: jnp.concatenate(xs))
    return _DEVICE_CONCAT(*parts)


def _kernel_loop(scope, n, step_once, fetch):
    """Run ``n`` kernel dispatches plus the one flushing fetch, with
    measured-profiling dispatch/device marks (``ALINK_TPU_PROFILE``) —
    the raw-jit bench kernels never enter the instrumented engine, so
    without these marks their wall time would read as unattributed host
    work. No-op overhead when the flag is off: two perf_counter calls
    per ~100 ms dispatch."""
    from alink_tpu.common.profiling2 import profile_window
    with profile_window(scope) as pw:
        for _ in range(n):
            t0 = time.perf_counter()
            step_once()
            pw.dispatch(time.perf_counter() - t0)
        t0 = time.perf_counter()
        fetch()
        pw.device(time.perf_counter() - t0)


class Harness:
    def __init__(self):
        from alink_tpu.common.mlenv import MLEnvironment, MLEnvironmentFactory
        # the session places the persistent compile cache
        # (mlenv.place_compile_cache): JAX_COMPILATION_CACHE_DIR when
        # set, else the fixed <repo>/.jax_cache every entry point shares
        self.env = MLEnvironment()
        MLEnvironmentFactory.set_default(self.env)
        self.chips = max(self.env.num_workers, 1)

    def delta(self, run, iters, reps: int = 3):
        """median over reps of [time(run(1+iters)) - time(run(2))],
        rescaled by iters/(iters-1).

        Median of paired differences: a difference carries symmetric
        noise from both endpoints, so min() over-claims (see the inline
        comment); the median is the robust estimator here.

        Both endpoints run >= 2 iterations, so both programs contain the
        superstep while-loop and trace/compile identically — round 2
        differenced against run(1), whose program SKIPS the while-loop
        (the engine elides it at max_iter == 1), so the delta silently
        included one extra Python trace of the loop body (~2.4 s for ALS)
        and overcharged every ComQueue workload's per-iteration cost
        (measured: ALS t(11)-t(1) said 365 ms/iter; t(21)-t(11) says
        120 ms/iter). run(2) as the short endpoint keeps the suite's
        wall-clock at round 2's level; the measured span is iters - 1."""
        assert iters >= 2, "delta() needs iters >= 2 (span is iters - 1)"
        run(2)                  # compile short program into the cache
        run(1 + iters)          # compile long program into the cache
        # endpoints are timed in adjacent PAIRS, not two separate blocks:
        # the per-call fixed cost drifts upward over a long bench process
        # (allocator/cache pressure — measured +50% across 6 ALS calls),
        # and with block timing the later block absorbs the drift; for
        # the last workload the drift exceeded the signal and the delta
        # went negative. Pairing makes each difference local in time, and
        # the MEDIAN of the paired differences is the estimator: unlike
        # the endpoint times (whose noise is nonnegative contention, so
        # min is right), a difference carries symmetric noise from both
        # endpoints — min() of differences biases low and over-claims
        # (observed 3x on ALS).
        deltas = []
        for _ in range(reps):
            t1 = self._time(run, 2)
            tf = self._time(run, 1 + iters)
            deltas.append(tf - t1)
        ds = sorted(deltas)
        m = len(ds) // 2
        med = ds[m] if len(ds) % 2 else 0.5 * (ds[m - 1] + ds[m])
        return max(med, 1e-9) * iters / (iters - 1)

    @staticmethod
    def _time(run, n):
        # the ONE timed entry of delta(): marks recorded inside count as
        # steady-state for the measured-profiling attribution (warmup
        # compiles stay outside) — a no-op context without ALINK_TPU_PROFILE
        from alink_tpu.common.profiling2 import measured_region
        t0 = time.perf_counter()
        with measured_region():
            run(n)
        return time.perf_counter() - t0

    @staticmethod
    def put(a):
        """device_put on single-process runs only: host-local committed
        arrays cannot be resharded by a multi-host mesh jit."""
        import jax
        return jax.device_put(a) if jax.process_count() == 1 else a

    def dispatch_gap(self, n: int = 200) -> float:
        """Per-dispatch host gap estimate (seconds): the median wall time
        of one step in a chain of ``n`` back-to-back trivial jitted calls
        (device work ~0, so the chain measures dispatch + queueing, not
        compute). This is the rig's floor for any per-call serial path —
        the latency-bound workloads (gbdt/als/kmeans supersteps, strict
        FTRL micro-batches) cannot beat ``1 / dispatch_gap`` calls/s no
        matter how fast the kernels are, which is exactly what the
        overlap/donation work routes around.

        Memoized per harness (first call's ``n`` wins): the ftrl row and
        the rig header must quote the SAME floor."""
        got = getattr(self, "_dispatch_gap", None)
        if got is not None:
            return got
        import jax
        f = jax.jit(lambda x: x + 1.0)
        x = jax.device_put(np.zeros(8, np.float32))
        np.asarray(f(x))                      # warm the compile
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            y = x
            for _ in range(n):
                y = f(y)
            np.asarray(y)                     # flush the chain
            ts.append((time.perf_counter() - t0) / n)
        self._dispatch_gap = sorted(ts)[1]
        return self._dispatch_gap


# ---------------------------------------------------------------------------
# 1. LogReg / Criteo-shape (north star; unchanged methodology from round 1)
# ---------------------------------------------------------------------------

N_FIELDS, FIELD_SIZE = 32, 2048
DIM = N_FIELDS * FIELD_SIZE


def make_ctr_fieldblock(n_rows, seed=0):
    rng = np.random.RandomState(seed)
    fb_idx = rng.randint(0, FIELD_SIZE, size=(n_rows, N_FIELDS)).astype(np.int32)
    w_true = (rng.randn(DIM) * (rng.rand(DIM) < 0.05)).astype(np.float32)
    flat = fb_idx + (np.arange(N_FIELDS, dtype=np.int32) * FIELD_SIZE)[None, :]
    margin = w_true[flat].sum(-1)
    y = np.where(rng.rand(n_rows) < 1.0 / (1.0 + np.exp(-margin)), 1.0, -1.0
                 ).astype(np.float32)
    return fb_idx, y


def bench_logreg(h: Harness):
    from alink_tpu.operator.common.optim.objfunc import (LogLossFunc,
                                                         UnaryLossObjFunc)
    from alink_tpu.operator.common.optim.optimizers import OptimParams, optimize
    from alink_tpu.ops.fieldblock import FieldBlockMeta

    # the flagship number: a long span (600 supersteps) and 5 paired
    # reps keep per-dispatch jitter from swinging the recorded value
    # between runs
    n_rows, iters = 200_000, 600
    fb_idx, y = make_ctr_fieldblock(n_rows)
    meta = FieldBlockMeta(N_FIELDS, FIELD_SIZE)
    data = {"fb_idx": fb_idx, "y": y, "w": np.ones(n_rows, np.float32)}
    wrng = np.random.RandomState(123)

    def run(n_iter):
        obj = UnaryLossObjFunc(LogLossFunc(), DIM, l2=1e-4, fb_meta=meta)
        w0 = (wrng.randn(DIM) * 1e-6).astype(np.float32)
        coef, _, _ = optimize(obj, data, OptimParams(
            method="LBFGS", max_iter=n_iter, epsilon=0.0), h.env,
            warm_start=w0)
        np.asarray(coef)

    dt = h.delta(run, iters, reps=5)
    sps = n_rows * iters / dt / h.chips

    # iters-to-converge: one run with the production stop criterion
    obj = UnaryLossObjFunc(LogLossFunc(), DIM, l2=1e-4,
                           fb_meta=FieldBlockMeta(N_FIELDS, FIELD_SIZE))
    _, _, n_conv = optimize(obj, data, OptimParams(
        method="LBFGS", max_iter=100, epsilon=1e-6), h.env)

    # CPU baseline: same superstep in numpy
    base_iters = 3
    flat = fb_idx + (np.arange(N_FIELDS, dtype=np.int32) * FIELD_SIZE)[None, :]
    coef = np.zeros(DIM, np.float32)
    w = np.ones(n_rows, np.float32)
    steps = np.concatenate([[0.0], 2.0 ** (1 - np.arange(10))]).astype(np.float32)
    t0 = time.perf_counter()
    for _ in range(base_iters):
        eta = coef[flat].sum(-1)
        c = w * (-y / (1.0 + np.exp(y * eta)))
        g = np.zeros(DIM, np.float32)
        np.add.at(g, flat.reshape(-1), np.repeat(c, N_FIELDS))
        eta_d = g[flat].sum(-1)
        losses = [(w * np.logaddexp(0.0, -(y * (eta - s * eta_d)))).sum()
                  for s in steps]
        coef = coef - steps[int(np.argmin(losses))] * g
    cpu_sps = n_rows * base_iters / (time.perf_counter() - t0)
    # issued FLOPs/sample/iter: the L-BFGS superstep is 3 field-block
    # einsum passes (eta, grad, eta_d), each 2 * DIM MACs-as-flops per
    # sample (ops/fieldblock.py "nfh,fhl->nfl": F*H*LO = DIM MACs)
    # HBM/sample/iter: the 3 passes stream the MATERIALIZED bf16 one-hot
    # factors (fb_onehot_parts: F*(hi+LO) elements x 2B each) — this, not
    # the FLOPs, is the binding roof for the fb formulation
    return {"samples_per_sec_per_chip": round(sps, 1),
            "vs_baseline": round(sps / cpu_sps, 3),
            "iters_to_converge": int(n_conv), "dt_s": round(dt, 3),
            **mfu(sps, 3 * 2 * DIM,
                  3 * N_FIELDS * (FIELD_SIZE // 16 + 16) * 2)}


# ---------------------------------------------------------------------------
# 2. KMeans / iris (replicated to chip scale)
# ---------------------------------------------------------------------------

def bench_kmeans(h: Harness):
    from sklearn.datasets import load_iris

    from alink_tpu.operator.common.clustering.kmeans import kmeans_train

    iris = load_iris().data.astype(np.float32)          # (150, 4)
    rng = np.random.RandomState(0)
    reps = 10_000
    X = np.tile(iris, (reps, 1)) + rng.randn(150 * reps, 4).astype(np.float32) * 0.05
    n = X.shape[0]
    # iris supersteps are tiny (~(1.5M,4)@(4,3) assign) — the iteration count
    # must be large enough that the measured delta clears the ~0.5 s
    # dispatch-noise floor, else sps degenerates to the 1e-9 clamp
    iters = 5_000
    jrng = np.random.RandomState(7)

    def run(n_iter):
        Xj = X + jrng.randn(1, 4).astype(np.float32) * 1e-5
        C, _, _ = kmeans_train(Xj, k=3, max_iter=n_iter, tol=0.0,
                               init="RANDOM", seed=0, env=h.env)
        np.asarray(C)

    # 5 paired reps (the ALS treatment, VERDICT r3 #10): the 3-rep median
    # still swung this row >2x between captures
    dt = h.delta(run, iters, reps=5)
    sps = n * iters / dt / h.chips
    _, _, n_conv = kmeans_train(X, k=3, max_iter=500, tol=1e-4, seed=0,
                                env=h.env)

    # CPU baseline: one assignment+update iteration in numpy —
    # median-of-5 (a single timing carried the row's host-load noise
    # straight into vs_baseline)
    base_iters = 3

    def cpu_pass():
        C = X[rng.choice(n, 3, replace=False)]
        t0 = time.perf_counter()
        for _ in range(base_iters):
            d2 = (X ** 2).sum(1, keepdims=True) - 2 * X @ C.T + (C ** 2).sum(1)
            ids = np.argmin(d2, axis=1)
            sums = np.zeros_like(C)
            np.add.at(sums, ids, X)
            cnts = np.bincount(ids, minlength=3).astype(np.float32)
            C = np.where(cnts[:, None] > 0,
                         sums / np.maximum(cnts[:, None], 1e-12), C)
        return time.perf_counter() - t0

    # min-of-5: endpoint timings carry one-sided contention noise (the
    # delta() docstring's estimator rule) — median would bias cpu_sps low
    # and OVER-claim vs_baseline under host load
    cpu_ts = sorted(cpu_pass() for _ in range(5))
    cpu_sps = n * base_iters / cpu_ts[0]
    # per sample per iter: distance matmul 2*k*d + one-hot scatter-add of
    # (d+1) sums over k centroids 2*k*(d+1) (common/clustering/kmeans.py);
    # HBM: the f32 X row is streamed twice (assign + sum passes) = 2*d*4B
    return {"samples_per_sec_per_chip": round(sps, 1),
            "vs_baseline": round(sps / cpu_sps, 3),
            "iters_to_converge": int(n_conv), "dt_s": round(dt, 3),
            **mfu(sps, 2 * 3 * 4 + 2 * 3 * 5, 2 * 4 * 4)}


# ---------------------------------------------------------------------------
# 3. Softmax / MNIST-shape
# ---------------------------------------------------------------------------

def bench_softmax(h: Harness):
    from alink_tpu.operator.common.optim.objfunc import SoftmaxObjFunc
    from alink_tpu.operator.common.optim.optimizers import OptimParams, optimize

    # the true MNIST train shape (pyalink/mnist.ipynb trains on 60k x 784)
    n, d, k = 60_000, 784, 10
    rng = np.random.RandomState(0)
    centers = rng.randn(k, d).astype(np.float32) * 0.5
    yc = rng.randint(0, k, n)
    X = (centers[yc] + rng.randn(n, d).astype(np.float32)).astype(np.float32)
    X = np.concatenate([np.ones((n, 1), np.float32), X], 1)  # intercept
    import jax
    # device-resident once (single-process only: host-local committed
    # arrays cannot be resharded by a multi-host mesh jit): re-shipping
    # the ~188 MB design matrix host->device on every timed call would
    # put the transfer, not the program, in the measured delta. X stays
    # a host array for the CPU baseline below.
    data = {"X": h.put(X), "y": h.put(yc.astype(np.float32)),
            "w": h.put(np.ones(n, np.float32))}
    iters = 500
    wrng = np.random.RandomState(11)

    def run(n_iter):
        obj = SoftmaxObjFunc(k, d + 1, l2=1e-4, reg_free_cols=1)
        w0 = (wrng.randn((k - 1) * (d + 1)) * 1e-6).astype(np.float32)
        coef, _, _ = optimize(obj, data, OptimParams(
            method="LBFGS", max_iter=n_iter, epsilon=0.0), h.env,
            warm_start=w0)
        np.asarray(coef)

    dt = h.delta(run, iters)
    sps = n * iters / dt / h.chips

    obj = SoftmaxObjFunc(k, d + 1, l2=1e-4, reg_free_cols=1)
    coef, _, n_conv = optimize(obj, data, OptimParams(
        method="LBFGS", max_iter=60, epsilon=1e-6), h.env)
    W = np.asarray(coef).reshape(k - 1, d + 1)
    logits = X @ W.T
    pred = np.argmax(np.concatenate(
        [logits, np.zeros((n, 1), np.float32)], 1), 1)
    acc = float((pred == yc).mean())

    # CPU baseline: one grad + line-search superstep in numpy (same math)
    base_iters = 2
    Wc = np.zeros((k - 1, d + 1), np.float32)
    steps = np.concatenate([[0.0], 2.0 ** (1 - np.arange(10))]).astype(np.float32)
    t0 = time.perf_counter()
    for _ in range(base_iters):
        Z = X @ Wc.T
        Zf = np.concatenate([Z, np.zeros((n, 1), np.float32)], 1)
        Zf -= Zf.max(1, keepdims=True)
        P = np.exp(Zf)
        P /= P.sum(1, keepdims=True)
        delta = P[:, :k - 1].copy()
        delta[np.arange(n), np.minimum(yc, k - 2)] -= (yc < k - 1)
        G = delta.T @ X
        Zd = X @ G.T
        for s in steps:
            Zs = Z - s * Zd
            Zsf = np.concatenate([Zs, np.zeros((n, 1), np.float32)], 1)
            m = Zsf.max(1)
            np.log(np.exp(Zsf - m[:, None]).sum(1))
        Wc = Wc - steps[1] * G
    cpu_sps = n * base_iters / (time.perf_counter() - t0)
    # quality anchor (VERDICT r2 #8): sklearn multinomial LR on the
    # IDENTICAL matrix (saga tolerates the n=60k x d=785 size; the
    # blob data is linearly separable so both should sit near 1.0)
    from sklearn.linear_model import LogisticRegression
    sk = LogisticRegression(max_iter=30, C=1e4, tol=1e-3)
    sk.fit(X[:, 1:], yc)
    sk_acc = float((sk.predict(X[:, 1:]) == yc).mean())
    # L-BFGS superstep = 3 dense (n,785)@(785,10)-class passes (logits,
    # grad, direction-logits): 3 * 2*(d+1)*k flops/sample/iter; HBM: the
    # f32 X row streams through each pass = 3*(d+1)*4B
    return {"samples_per_sec_per_chip": round(sps, 1),
            "vs_baseline": round(sps / cpu_sps, 3),
            "iters_to_converge": int(n_conv), "accuracy": round(acc, 4),
            "sklearn_accuracy": round(sk_acc, 4),
            "dt_s": round(dt, 3),
            **mfu(sps, 3 * 2 * (d + 1) * k, 3 * (d + 1) * 4)}


# ---------------------------------------------------------------------------
# 4. FTRL / Criteo-shape sparse stream
# ---------------------------------------------------------------------------

def bench_ftrl(h: Harness):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        _ftrl_sparse_batch_step_factory, _ftrl_sparse_chained_step_factory,
        _ftrl_sparse_staleness_step_factory, _ftrl_sparse_step_factory,
        _ftrl_weights)

    dim, nnz, B = 65_536, 39, 4096          # Criteo: 39 fields
    n_dev = h.chips
    dim_pad = -(-dim // n_dev) * n_dev
    width = -(-(nnz + 1) // 8) * 8          # +1 intercept slot

    pool = [make_batch_criteo(s, dim=dim, nnz=nnz, B=B) for s in range(24)]
    mesh = h.env.mesh
    step = _ftrl_sparse_step_factory(mesh, alpha=0.05, beta=1.0,
                                     l1=1e-5, l2=1e-5)
    shard = NamedSharding(mesh, P("d"))
    zrng = np.random.RandomState(3)
    sp_idx = h.put(np.stack([p[0] for p in pool]))
    sp_val = h.put(np.stack([p[1] for p in pool]))
    sp_y = h.put(np.stack([p[2] for p in pool]))

    @jax.jit
    def strict_pool(sp_idx, sp_val, sp_y, z, nacc):
        # chain the whole pool in one program: one strict batch is ~35 ms
        # of device scan; per-batch RPC dispatch would dominate the delta
        def body(carry, xs):
            z, nacc = carry
            z, nacc, m = step(xs[0], xs[1], xs[2], z, nacc)
            return (z, nacc), m[0]
        (z, nacc), _ = jax.lax.scan(body, (z, nacc), (sp_idx, sp_val, sp_y))
        return z, nacc

    def run(n_pools):
        st = [jax.device_put(zrng.randn(dim_pad) * 1e-8, shard),
              jax.device_put(np.zeros(dim_pad), shard)]

        def step_once():
            st[0], st[1] = strict_pool(sp_idx, sp_val, sp_y, st[0], st[1])
        _kernel_loop("ftrl.kernel", n_pools, step_once,
                     lambda: np.asarray(st[0]))
        return st[0], st[1]

    K = 8                                    # 8 pools = 192 batches
    dt = h.delta(run, K)
    sps_persample = B * len(pool) * K / dt / h.chips

    # ----- Chained-correction strict kernel (ISSUE 6 tentpole (a)) --------
    # SAME strict semantics (bit-identical on collision-free chunks,
    # f32-round-equal under collisions — tests/test_perf_kernels.py), but
    # the scan is CHAIN_K-fold shorter: one state gather/scatter per
    # chunk and one dense triangular correction matvec per sample instead
    # of the K=4 kernel's O(K^2) pairwise matmuls. This is the strict
    # HEADLINE row (ftrl_criteo_strict); the per-sample K=4 kernel rides
    # alongside as strict_persample_* for continuity.
    chained = {}
    for CHAIN_K in (8, 16):
        cstep = _ftrl_sparse_chained_step_factory(
            mesh, alpha=0.05, beta=1.0, l1=1e-5, l2=1e-5, K=CHAIN_K)

        @jax.jit
        def chain_pool(sp_idx, sp_val, sp_y, z, nacc, cstep=cstep):
            def body(carry, xs):
                z, nacc = carry
                z, nacc, m = cstep(xs[0], xs[1], xs[2], z, nacc)
                return (z, nacc), m[0]
            (z, nacc), _ = jax.lax.scan(body, (z, nacc),
                                        (sp_idx, sp_val, sp_y))
            return z, nacc

        def run_chain(n_pools, chain_pool=chain_pool):
            st = [jax.device_put(zrng.randn(dim_pad) * 1e-8, shard),
                  jax.device_put(np.zeros(dim_pad), shard)]

            def step_once():
                st[0], st[1] = chain_pool(sp_idx, sp_val, sp_y,
                                          st[0], st[1])
            _kernel_loop("ftrl.kernel", n_pools, step_once,
                         lambda: np.asarray(st[0]))

        dt_c = h.delta(run_chain, K)
        chained[CHAIN_K] = B * len(pool) * K / dt_c / h.chips
    # the strict HEADLINE is the fastest strict-semantics kernel, with
    # the winner recorded: on issue-latency-bound backends (TPU) that is
    # the chained scan; on compute-bound hosts (CPU smoke rigs) the
    # per-chunk collision tensor costs real flops and the per-sample
    # kernel can win — the artifact says which ran
    candidates = {"per_sample(K=4)": sps_persample,
                  **{f"chained_correction(K={k})": v
                     for k, v in chained.items()}}
    strict_kernel = max(candidates, key=candidates.get)
    sps_strict = candidates[strict_kernel]

    # ----- Bounded-staleness mode: the reference's ACTUAL semantics -------
    # The reference's sharded CalcTasks apply each sample's update only
    # when its summed margin returns over the cyclic Flink feedback edge
    # (FtrlTrainStreamOp.java:120-135), so gradients are computed at
    # weights stale by the in-flight buffer depth. update_mode="staleness"
    # bounds that delay at 32 samples — a TIGHTER guarantee than the
    # reference's unbounded network buffers — and is the headline row;
    # the strict scan (stronger than the reference) is kept alongside.
    STALE_K = 32
    stale_step = _ftrl_sparse_staleness_step_factory(
        mesh, alpha=0.05, beta=1.0, l1=1e-5, l2=1e-5, K=STALE_K)

    @jax.jit
    def stale_pool(sp_idx, sp_val, sp_y, z, nacc):
        def body(carry, xs):
            z, nacc = carry
            z, nacc, m = stale_step(xs[0], xs[1], xs[2], z, nacc)
            return (z, nacc), m[0]
        (z, nacc), _ = jax.lax.scan(body, (z, nacc), (sp_idx, sp_val, sp_y))
        return z, nacc

    def run_stale(n_pools):
        st = [jax.device_put(zrng.randn(dim_pad) * 1e-8, shard),
              jax.device_put(np.zeros(dim_pad), shard)]

        def step_once():
            st[0], st[1] = stale_pool(sp_idx, sp_val, sp_y, st[0], st[1])
        _kernel_loop("ftrl.kernel", n_pools, step_once,
                     lambda: np.asarray(st[0]))

    Ks = 16
    dt_stale = h.delta(run_stale, Ks)
    sps = B * len(pool) * Ks / dt_stale / h.chips

    # ----- Quality anchors on a DISCRIMINATING corpus (VERDICT r3 #7) -----
    # The r03 anchor (98k samples over 65k dims) left every learnable
    # model ~0.1 AUC under the oracle, so "FTRL matches batch LR" could
    # not detect quality loss. The anchor corpus is now sized so that
    # converged batch LR approaches the generating oracle: 393k samples
    # over 16,640 field-blocked dims -> ~945 observations per feature
    # slot. Anchors: (a) batch L-BFGS LR trained to convergence on the
    # SAME corpus; (b) the oracle (scoring with the generating w_true) —
    # the label-noise ceiling; (c) strict-scan FTRL and (d) batch-mode
    # FTRL, both 2 passes. The north-star clause "identical AUC" is
    # checked as oracle-batch_lr <= 0.02 and |ftrl - batch_lr| small.
    from alink_tpu.operator.common.optim.objfunc import (LogLossFunc,
                                                         UnaryLossObjFunc)
    from alink_tpu.operator.common.optim.optimizers import (OptimParams,
                                                            optimize)
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        _ftrl_fb_batch_step_factory)
    from alink_tpu.ops.fieldblock import FieldBlockMeta

    S_q = 416                     # 40 fields x 416 = 16,640 dims
    # field 0 = intercept (slot 0); 39 feature fields; padded up so the
    # field groups divide the mesh (fb factory guard) — padded fields
    # always point at slot 0 with val 0 (pure no-ops)
    F_DATA = 40
    F_q = -(-F_DATA // h.chips) * h.chips
    meta_q = FieldBlockMeta(F_q, S_q)
    dim_q = meta_q.dim
    qrng = np.random.RandomState(7)
    # margin std ~1.5 (CTR-ish): w ~ N(0, (1.5/sqrt(39))^2)
    w_true_q = (qrng.randn(dim_q) * (1.5 / np.sqrt(39))).astype(np.float64)
    w_true_q[F_DATA * S_q:] = 0.0          # padded fields carry no signal
    n_q_batches = 96

    def make_qbatch(seed):
        r = np.random.RandomState(200_000 + seed)
        fb = np.zeros((B, F_q), np.int32)
        fb[:, 1:F_DATA] = r.randint(0, S_q, size=(B, F_DATA - 1))
        gidx = fb + (np.arange(F_q, dtype=np.int32) * S_q)[None, :]
        margin = w_true_q[gidx].sum(1)
        y = (r.rand(B) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float64)
        return fb, gidx, y

    qpool = [make_qbatch(s) for s in range(n_q_batches)]
    q_gidx = h.put(np.stack([p[1] for p in qpool]).astype(np.int32))
    qv = np.zeros((n_q_batches, B, F_q), np.float32)
    qv[:, :, :F_DATA] = 1.0                # padded fields are no-ops
    q_val = h.put(qv)
    q_y = h.put(np.stack([p[2] for p in qpool]).astype(np.float32))
    hq = [make_qbatch(10_001 + i) for i in range(2)]     # held-out 8192
    h_gidx = np.concatenate([b[1] for b in hq])
    h_y = np.concatenate([b[2] for b in hq])
    oracle_auc = _auc(h_y, w_true_q[h_gidx].sum(1))

    # (a) batch LR to convergence through the field-blocked MXU path
    all_fb = np.concatenate([p[0] for p in qpool])
    all_qy = np.concatenate([p[2] for p in qpool])
    lr_data = {"fb_idx": all_fb,
               "y": np.where(all_qy > 0, 1.0, -1.0).astype(np.float32),
               "w": np.ones(len(all_qy), np.float32)}
    obj = UnaryLossObjFunc(LogLossFunc(), dim_q, l2=1e-6, fb_meta=meta_q)
    coef, _, _ = optimize(obj, lr_data, OptimParams(
        method="LBFGS", max_iter=200, epsilon=1e-8), h.env)
    wb = np.asarray(coef)[:dim_q]
    batch_lr_auc = _auc(h_y, wb[h_gidx].sum(1))

    # (c) strict-scan FTRL, 2 passes over the anchor corpus
    strict_q = _ftrl_sparse_step_factory(mesh, alpha=0.05, beta=1.0,
                                         l1=1e-5, l2=1e-5)

    @jax.jit
    def strict_qpool(gi, gv, gy, z, nacc):
        def body(carry, xs):
            z, nacc = carry
            z, nacc, m = strict_q(xs[0], xs[1], xs[2], z, nacc)
            return (z, nacc), m[0]
        (z, nacc), _ = jax.lax.scan(body, (z, nacc), (gi, gv, gy))
        return z, nacc

    zq = jax.device_put(zrng.randn(dim_q) * 1e-8, shard)
    nq = jax.device_put(np.zeros(dim_q), shard)
    for _ in range(2):
        zq, nq = strict_qpool(q_gidx, q_val, q_y, zq, nq)
    wq = np.asarray(_ftrl_weights(np.asarray(zq), np.asarray(nq),
                                  0.05, 1.0, 1e-5, 1e-5))[:dim_q]
    strict_auc = _auc(h_y, wq[h_gidx].sum(1))

    # (c') bounded-staleness FTRL (the headline row), same 2 passes — its
    # AUC is the one pinned against the batch-LR anchor
    stale_q = _ftrl_sparse_staleness_step_factory(
        mesh, alpha=0.05, beta=1.0, l1=1e-5, l2=1e-5, K=STALE_K)

    @jax.jit
    def stale_qpool(gi, gv, gy, z, nacc):
        def body(carry, xs):
            z, nacc = carry
            z, nacc, m = stale_q(xs[0], xs[1], xs[2], z, nacc)
            return (z, nacc), m[0]
        (z, nacc), _ = jax.lax.scan(body, (z, nacc), (gi, gv, gy))
        return z, nacc

    zsq = jax.device_put(zrng.randn(dim_q) * 1e-8, shard)
    nsq = jax.device_put(np.zeros(dim_q), shard)
    for _ in range(2):
        zsq, nsq = stale_qpool(q_gidx, q_val, q_y, zsq, nsq)
    wsq = np.asarray(_ftrl_weights(np.asarray(zsq), np.asarray(nsq),
                                   0.05, 1.0, 1e-5, 1e-5))[:dim_q]
    auc = _auc(h_y, wsq[h_gidx].sum(1))

    # (d) batch-mode FTRL (fb one-hot MXU program), same 2 passes
    q_fbi = h.put(np.stack([p[0] for p in qpool]).astype(np.int32))
    fstep_q = _ftrl_fb_batch_step_factory(mesh, meta_q, alpha=0.05,
                                          beta=1.0, l1=1e-5, l2=1e-5)

    @jax.jit
    def batchmode_qpool(fi, fv, fy, z, nacc):
        def body(carry, xs):
            z, nacc = carry
            z, nacc, _ = fstep_q(xs[0], xs[1], xs[2], z, nacc)
            return (z, nacc), 0.0
        (z, nacc), _ = jax.lax.scan(body, (z, nacc), (fi, fv, fy))
        return z, nacc

    fb_shard_q = NamedSharding(mesh, P("d"))
    zbq = jax.device_put(zrng.randn(dim_q) * 1e-8, fb_shard_q)
    nbq = jax.device_put(np.zeros(dim_q), fb_shard_q)
    for _ in range(2):
        zbq, nbq = batchmode_qpool(q_fbi, q_val, q_y, zbq, nbq)
    wbm = np.asarray(_ftrl_weights(np.asarray(zbq), np.asarray(nbq),
                                   0.05, 1.0, 1e-5, 1e-5))[:dim_q]
    batch_mode_auc = _auc(h_y, wbm[h_gidx].sum(1))

    # update_mode="batch" on field-aware-hashed rows (ftrl_demo hashes CTR
    # fields, so the stream op auto-detects the layout and routes to the
    # one-hot MXU program — _ftrl_fb_batch_step_factory — instead of the
    # gather/scatter-bound element-addressed programs). One batch step is
    # ~1 ms of device work, so the pool is chained in one jitted scan per
    # call; dispatching batches one call at a time would measure the
    # host's per-dispatch gap, not the program.
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        _ftrl_fb_batch_step_factory)
    from alink_tpu.ops.fieldblock import FieldBlockMeta

    # 39 hashed fields + intercept, padded up so field groups divide the
    # mesh (the factory requires num_fields % chips == 0)
    F_aug = -(-40 // h.chips) * h.chips
    S = 1648
    meta = FieldBlockMeta(F_aug, S)
    dim_fb = meta.dim                        # 65,920 ~ the COO config's 65,536
    frng = np.random.RandomState(1)
    fb_pool = []
    for s_ in range(24):
        fbi = frng.randint(0, S, size=(B, F_aug)).astype(np.int32)
        fbi[:, 0] = 0                        # intercept field, local slot 0
        fbv = np.ones((B, F_aug))
        fb_pool.append((fbi, fbv, pool[s_][2]))
    fstep = _ftrl_fb_batch_step_factory(mesh, meta, alpha=0.05, beta=1.0,
                                        l1=1e-5, l2=1e-5)
    # pool inputs live on device once — re-shipping ~50 MB of host arrays
    # per call would measure the host->device transfer, not the program
    pidx = h.put(np.stack([p[0] for p in fb_pool]))
    pval = h.put(np.stack([p[1] for p in fb_pool]))
    py = h.put(np.stack([p[2] for p in fb_pool]))
    fb_shard = NamedSharding(mesh, P("d"))

    @jax.jit
    def run_pool(pidx, pval, py, z, nacc):
        def body(carry, xs):
            z, nacc = carry
            z, nacc, m = fstep(xs[0], xs[1], xs[2], z, nacc)
            return (z, nacc), m[0]
        (z, nacc), _ = jax.lax.scan(body, (z, nacc), (pidx, pval, py))
        return z, nacc

    def run_batchmode(n_pools):
        z = jax.device_put(zrng.randn(dim_fb) * 1e-8, fb_shard)
        nacc = jax.device_put(np.zeros(dim_fb), fb_shard)
        for _ in range(n_pools):
            z, nacc = run_pool(pidx, pval, py, z, nacc)
        np.asarray(z)

    # the chained fb program runs ~100 us/batch on v5e, so the measured
    # span must be hundreds of pools to clear the dispatch-noise floor
    Kb = 900                                 # 900 pools = 21,600 batches
    sps_batch = B * len(fb_pool) * Kb / h.delta(run_batchmode, Kb) / h.chips

    # End-to-end STREAM rate including hashing/encode (VERDICT r2 #4):
    # raw string rows -> FeatureHasherStreamOp(field_aware) ->
    # FtrlTrainStreamOp, drained through the prefetched stream runtime
    # (host hash/pad of batch t+1 overlaps the device running batch t).
    from alink_tpu.operator.batch.classification.linear import (
        LogisticRegressionTrainBatchOp)
    from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
    from alink_tpu.operator.stream.batch_twins import FeatureHasherStreamOp
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        FtrlTrainStreamOp)
    from alink_tpu.operator.stream.source.sources import MemSourceStreamOp

    n_stream = 262_144                       # 16 x 16384-row micro-batches
    stream_bs = 16_384                       # amortizes per-batch dispatch
    srng = np.random.RandomState(17)
    site_ids = srng.randint(0, 4000, n_stream)
    sites = np.char.add("s", site_ids.astype("U6"))
    devs = np.char.add("d", srng.randint(0, 4000, n_stream).astype("U6"))
    apps = np.char.add("a", srng.randint(0, 4000, n_stream).astype("U6"))
    # click depends on the site (rates 0.1 / 0.9 by parity) so the DAG's
    # windowed eval AUC is a meaningful quality signal: the hashed-slot
    # ceiling is ~0.87 (4000 sites collide into 1648 slots); one
    # conservative-alpha FTRL pass reaches ~0.59 by the final window
    # (visibly learning), while label-shuffled data would pin it at 0.5
    ys = (srng.rand(n_stream) < 0.1 + 0.8 * (site_ids % 2)).astype(np.int64)
    from alink_tpu.common.mtable import MTable
    cols = {"site": sites.astype(object), "dev": devs.astype(object),
            "app": apps.astype(object), "click": ys}
    stream_schema = "site STRING, dev STRING, app STRING, click LONG"
    hash_cols = ["site", "dev", "app"]
    hasher_kw = dict(selected_cols=hash_cols, categorical_cols=hash_cols,
                     output_col="vec", num_features=3 * 1648,
                     field_aware=True)
    warm_src = MemSourceBatchOp(MTable(cols, stream_schema).first_n(4096))
    from alink_tpu.operator.batch.feature.feature_ops import (
        FeatureHasherBatchOp)
    warm_feat = FeatureHasherBatchOp(**hasher_kw).link_from(warm_src)
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="click", max_iter=3).link_from(warm_feat)

    def drain_stream():
        src = MemSourceStreamOp(MTable(cols, stream_schema),
                                batch_size=stream_bs)
        feat = FeatureHasherStreamOp(**hasher_kw).link_from(src)
        ftrl = FtrlTrainStreamOp(warm, vector_col="vec", label_col="click",
                                 alpha=0.05, beta=1.0, l1=1e-5, l2=1e-5,
                                 update_mode="batch",
                                 time_interval=1e9).link_from(feat)
        last = None
        for mt in ftrl.micro_batches():
            last = mt
        return last

    def drain_host_only():
        # the same source -> hasher chain WITHOUT the device leg: its rate
        # is the host ceiling, and e2e vs host attributes the gap
        src = MemSourceStreamOp(MTable(cols, stream_schema),
                                batch_size=stream_bs)
        feat = FeatureHasherStreamOp(**hasher_kw).link_from(src)
        rows = 0
        for _, mt in feat.timed_batches():
            rows += mt.num_rows
        return rows

    def drain_full_dag():
        # the COMPLETE reference online-learning DAG (FTRLExample.java:
        # 18-113; VERDICT r3 #9): source -> hash -> FTRL train (snapshot
        # stream) -> hot-reload predict -> windowed+cumulative eval, with
        # the eval stream fully consumed
        import json as _json
        from alink_tpu.operator.stream.onlinelearning.ftrl import (
            FtrlPredictStreamOp)
        from alink_tpu.operator.stream.evaluation import (
            EvalBinaryClassStreamOp)
        src = MemSourceStreamOp(MTable(cols, stream_schema),
                                batch_size=stream_bs, time_per_batch=1.0)
        feat = FeatureHasherStreamOp(**hasher_kw).link_from(src)
        ftrl = FtrlTrainStreamOp(warm, vector_col="vec", label_col="click",
                                 alpha=0.05, beta=1.0, l1=1e-5, l2=1e-5,
                                 update_mode="batch",
                                 time_interval=4.0).link_from(feat)
        pred = FtrlPredictStreamOp(warm, vector_col="vec",
                                   prediction_col="pred",
                                   prediction_detail_col="details"
                                   ).link_from(ftrl, feat)
        ev = EvalBinaryClassStreamOp(label_col="click",
                                     prediction_detail_col="details",
                                     time_interval=4.0).link_from(pred)
        rows = 0
        last_auc = float("nan")
        for _, mt in ev.timed_batches():
            # final WINDOW AUC: the hot-reloaded model's current quality
            # (the cumulative rows average in the weak warm-start era)
            stats = mt.col("Statistics")
            for s_, d in zip(stats, mt.col("Data")):
                if str(s_) == "window":
                    v = _json.loads(d).get("AUC")
                    last_auc = last_auc if v is None else float(v)
            rows += 1
        assert rows > 0
        return last_auc

    from alink_tpu.common.profiling2 import measured_region
    drain_stream()                           # warm compiles
    t0 = time.perf_counter()
    with measured_region():
        drain_stream()
    stream_e2e_s = time.perf_counter() - t0
    stream_e2e_sps = n_stream / stream_e2e_s / h.chips
    t0 = time.perf_counter()
    assert drain_host_only() == n_stream
    stream_host_s = time.perf_counter() - t0
    # per-HOST rate (the chain does not scale with chips — dividing by
    # h.chips would under-report the host ceiling on multi-chip rigs)
    stream_host_sps = n_stream / stream_host_s
    drain_full_dag()                         # warm the predict/eval legs
    t0 = time.perf_counter()
    dag_auc = drain_full_dag()
    stream_dag_s = time.perf_counter() - t0
    stream_dag_sps = n_stream / stream_dag_s / h.chips

    # LIVE interpreted-loop context (the pre-r06 denominator, kept as
    # vs_live_numpy): per-sample O(nnz) FTRL loop in numpy (one task
    # slot), median-of-7 with the spread RECORDED (VERDICT r3 #4b) — its
    # 30-50% host-load swing is exactly why the HEADLINE denominator is
    # now the pinned compiled baseline (pinned_ftrl_baseline below).
    bidx, bval, by = pool[0]
    n_base = 4096

    def cpu_pass():
        zc = np.zeros(dim)
        nc = np.zeros(dim)
        t0 = time.perf_counter()
        _numpy_ftrl_slot_loop(bidx[:n_base], bval[:n_base], by[:n_base],
                              zc, nc)
        return time.perf_counter() - t0

    # median per the r3 verdict's explicit ask for THIS row ("report the
    # CPU baseline as a median with an error bar"); the min/max spread is
    # in the artifact, so a reader preferring the suite's min-estimator
    # rule can recompute the ratio from cpu_baseline_sps_max
    cpu_ts = sorted(cpu_pass() for _ in range(7))
    cpu_sps = n_base / cpu_ts[len(cpu_ts) // 2]
    cpu_spread = {"cpu_baseline_sps_min": round(n_base / cpu_ts[-1], 1),
                  "cpu_baseline_sps_median": round(cpu_sps, 1),
                  "cpu_baseline_sps_max": round(n_base / cpu_ts[0], 1)}

    # ----- PINNED compiled baseline (tentpole (c)) ------------------------
    # vs_baseline now divides by the committed BASELINE_compiled.json rate
    # for this rig (compiled single-slot loop, best-of-7, measured once) —
    # stable round-over-round where the live numpy loop above drifted
    # ±30-50% with host load. The live spread stays in the artifact as
    # vs_live_numpy context; bench_compare --baseline-provenance gates on
    # the fingerprint.
    pinned = pinned_ftrl_baseline()
    base_sps = float(pinned["sps_best"])
    # FTRL is elementwise over width=40 slots (~15 flops each) —
    # gather/state-bound, not MXU work; its honest peak metric is HBM
    # traffic (~width * 3 state vectors * 2 dirs * 8B). The batch-mode row
    # issues field-block one-hot matmuls instead: 2 passes * 2*dim_fb.
    # both roofs sit ~0.1%: the scan over 65k-state gathers/scatters is
    # op-issue-latency bound (docs/performance.md), which "latency" states
    stale_roof = mfu(sps, width * 15, width * 3 * 2 * 8, bound="latency")
    # batch-mode HBM: inline one-hot idx read (F*4B) + 4 state passes over
    # dim_fb f32 amortized across the 4096-row batch
    batch = mfu(sps_batch, 2 * 2 * dim_fb,
                F_aug * 4 + 4 * dim_fb * 4 // B)
    # HEADLINE = update_mode="staleness" (gradients at weights <= 31
    # samples old) — the reference's own feedback-edge contract with the
    # delay BOUNDED, where the reference's in-flight network buffers leave
    # it unbounded (FtrlTrainStreamOp.java:120-135). Its AUC is pinned
    # against the batch-LR anchor below. The strict per-sample scan (a
    # STRONGER guarantee than the reference) ships as strict_*; batch
    # mode is the whole-micro-batch relaxation.
    return {"update_mode": "staleness", "staleness": STALE_K,
            "samples_per_sec_per_chip": round(sps, 1),
            "vs_baseline": round(sps / base_sps, 3),
            "auc": round(auc, 4),
            "auc_minus_batch_lr": round(auc - batch_lr_auc, 4),
            # strict headline = the chained-correction kernel (exact
            # strict semantics, tests pin parity); the per-sample K=4
            # kernel rides alongside for continuity with r03-r05 rows
            "strict_samples_per_sec_per_chip": round(sps_strict, 1),
            "strict_vs_baseline": round(sps_strict / base_sps, 3),
            "strict_kernel": strict_kernel,
            "strict_chained_sps_by_k": {str(k): round(v, 1)
                                        for k, v in chained.items()},
            "strict_persample_samples_per_sec_per_chip":
                round(sps_persample, 1),
            "strict_auc": round(strict_auc, 4),
            # the pinned compiled denominator + provenance (the fp also
            # digests the pinned record, so a re-pin changes it)
            "baseline_fp": pinned["provenance_fp"],
            "baseline_impl": pinned["impl"],
            "baseline_sps": round(base_sps, 1),
            "baseline_pinned_at": pinned.get("pinned_at"),
            # live interpreted-loop context (the former denominator):
            # vs_live_numpy shows what r05-style ratios would have read
            "vs_live_numpy": round(sps / cpu_sps, 3),
            "strict_vs_live_numpy": round(sps_strict / cpu_sps, 3),
            "batch_mode_auc": round(batch_mode_auc, 4),
            "batch_lr_auc": round(batch_lr_auc, 4),
            "oracle_auc": round(oracle_auc, 4),
            "dt_s": round(dt_stale, 3),
            **stale_roof,
            "batch_mode_samples_per_sec_per_chip": round(sps_batch, 1),
            "batch_mode_vs_baseline": round(sps_batch / base_sps, 3),
            **({"batch_mode_pct_chip_peak_flops":
                batch["pct_chip_peak_flops"]}
               if "pct_chip_peak_flops" in batch else {}),
            "stream_e2e_samples_per_sec_per_chip": round(stream_e2e_sps, 1),
            "stream_e2e_host_samples_per_sec": round(stream_host_sps, 1),
            "stream_e2e_s": round(stream_e2e_s, 3),
            "stream_e2e_host_s": round(stream_host_s, 3),
            "stream_e2e_device_share": round(
                max(0.0, 1.0 - stream_host_s / max(stream_e2e_s, 1e-9)), 3),
            # (no stream_*_bound field: which of encode / host->device
            # transfer / dispatch / device binds the e2e and DAG rates is
            # read from a trace of the run, not written as a constant —
            # ROADMAP S2)
            "stream_dag_samples_per_sec_per_chip": round(stream_dag_sps, 1),
            "stream_dag_s": round(stream_dag_s, 3),
            "stream_dag_auc": round(dag_auc, 4),
            # the rig's per-dispatch serial floor (Harness.dispatch_gap):
            # strict FTRL's samples/s is bounded by ~K_scan_chunks /
            # dispatch_gap; read the latency-bound rows against it
            "dispatch_gap_est_s": round(h.dispatch_gap(), 6),
            **cpu_spread}


# ---------------------------------------------------------------------------
# 4b. LogReg from DISK — the input pipeline at rate (VERDICT r2 #3)
# ---------------------------------------------------------------------------

def bench_logreg_from_disk(h: Harness):
    """Source -> device throughput: a LibSVM fixture on disk, read through
    the sharded byte-range sources (io/sharding.py via read_file_shard)
    and the native C++ LibSVM parser, feeding the field-blocked L-BFGS.

    This is the "Criteo-1TB must shard at the source" plumbing (SURVEY §7)
    made measurable: sustained samples/sec INCLUDING read+parse+encode+
    device_put, next to the same train step fed from RAM, with the
    component split so the bottleneck is identified in the artifact.
    Fixture size scales with ALINK_TPU_DISKBENCH_ROWS (default 1M rows,
    ~360 MB — the multi-GB shape at a bench-budget size)."""
    import os
    import tempfile

    from alink_tpu.io.csv import _load_line_bytes
    from alink_tpu.native import parse_libsvm_bytes, parse_libsvm_fb16
    from alink_tpu.operator.common.optim.objfunc import (LogLossFunc,
                                                         UnaryLossObjFunc)
    from alink_tpu.operator.common.optim.optimizers import OptimParams, optimize
    from alink_tpu.ops.fieldblock import FieldBlockMeta

    from alink_tpu.common.flags import flag_value
    n_rows = int(flag_value("ALINK_TPU_DISKBENCH_ROWS"))
    path = os.path.join(tempfile.gettempdir(),
                        f"alink_diskbench_{n_rows}_{N_FIELDS}.libsvm")
    fb_idx_true, y_true = make_ctr_fieldblock(n_rows, seed=42)
    if not os.path.exists(path):
        # vectorized LibSVM formatting: per-field "global_idx:1" tokens
        # via np.char ops (a Python join over 32M tokens would dominate)
        flat = (fb_idx_true
                + (np.arange(N_FIELDS, dtype=np.int32) * FIELD_SIZE)[None, :]
                + 1)                                    # 1-based indices
        row = np.where(y_true > 0, "1", "-1").astype("U8")
        for k in range(N_FIELDS):
            tok = np.char.add(np.char.add(" ", flat[:, k].astype("U7")), ":1")
            row = np.char.add(row, tok)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write("\n".join(row))
            f.write("\n")
        os.replace(tmp, path)

    # per-host sharded readers, drained in parallel. 64 (not cores): the
    # capture rig has ONE core, so shard parallelism buys IO/CPU overlap
    # rather than multi-core parse — finer shards interleave read waits
    # with parse better (measured on the 253 MB fixture: 16 shards
    # 0.72 s, 32 shards 0.62 s, 64 shards 0.57 s). On a multi-core host
    # the same pool scales out.
    n_shards = 64
    meta = FieldBlockMeta(N_FIELDS, FIELD_SIZE)
    offs = (np.arange(N_FIELDS, dtype=np.int64) * FIELD_SIZE)[None, :]

    def load_from_disk():
        # ISSUE 6 satellite (VERDICT r5 #2): the parse leg now streams
        # through the ORDERED prefetch_map pool (stream/prefetch.py) —
        # shard i's disk read overlaps shard j's parse/encode exactly as
        # before, but completed shards are grouped into a few super-
        # groups and each group's host->device transfer is DISPATCHED
        # (async) while later shards still parse, so the ~60 MB ship
        # that used to serialize inside the train leg hides behind the
        # parse wall. read_s/parse_s/encode_s stay per-shard attribution
        # SUMS; rp_wall_s is the loader wall clock (transfers may still
        # be in flight when it returns — that IS the overlap, they
        # complete under the train leg's first dispatch).
        # Grouped transfers (~16 shards / ~16 MB each, ALINK_TPU_
        # DISK_GROUPS) ship a few large host->device copies instead of
        # one small committed array per shard (a fixed cost per
        # transfer); ALINK_TPU_DISK_COMMIT=0 restores the host-array
        # path. Neither setting has been timed on a local chip.
        import jax
        from alink_tpu.operator.stream.prefetch import prefetch_map

        def load_shard(i):
            t0 = time.perf_counter()
            b = _load_line_bytes(path, False, (i, n_shards))
            t1 = time.perf_counter()
            # fused C fast path: parse straight into int16 field-local ids
            # + f32 labels in one pass (2-byte output, no separate encode
            # pass); falls back to generic CSR + host encode when the rows
            # are not one-hot field-major
            fbp = parse_libsvm_fb16(b, N_FIELDS, FIELD_SIZE, 1)
            t2 = time.perf_counter()
            if fbp is not None:
                lab, fb_i = fbp
                t3 = t2
            else:
                p = parse_libsvm_bytes(b, 1)
                t2 = time.perf_counter()
                fb_i = (p[2].reshape(-1, N_FIELDS) - offs).astype(np.int16)
                lab = p[0].astype(np.float32)
                t3 = time.perf_counter()
            return (fb_i, lab), t1 - t0, t2 - t1, t3 - t2

        from alink_tpu.common.flags import (env_flag as _env_flag,
                                            flag_raw, flag_value)
        commit = (_env_flag("ALINK_TPU_DISK_COMMIT", default=True)
                  and jax.process_count() == 1)
        n_groups = int(flag_value("ALINK_TPU_DISK_GROUPS"))
        per_group = -(-n_shards // n_groups)
        # bench-local contract (deliberately NOT the registry's >= 1
        # clamp): unset/0 means auto-size to the core count
        workers = int(flag_raw("ALINK_TPU_STREAM_WORKERS") or 0)
        if workers <= 0:
            workers = min(8, os.cpu_count() or 1)
        t0 = time.perf_counter()
        fb_parts, lab_parts, pend, stats = [], [], [], [0.0, 0.0, 0.0]

        def flush_group():
            if not pend:
                return
            fb_g = np.concatenate([p[0] for p in pend])
            lab_g = np.concatenate([p[1] for p in pend])
            pend.clear()
            if commit:
                # async dispatch: the transfer overlaps the pool parsing
                # the NEXT group's shards
                fb_g = jax.device_put(fb_g)
                lab_g = jax.device_put(lab_g)
            fb_parts.append(fb_g)
            lab_parts.append(lab_g)

        for k, (part, r_s, p_s, e_s) in enumerate(
                prefetch_map(iter(range(n_shards)), load_shard,
                             workers=workers, name="diskbench")):
            stats[0] += r_s
            stats[1] += p_s
            stats[2] += e_s
            pend.append(part)
            if len(pend) >= per_group:
                flush_group()
        flush_group()
        if commit and len(fb_parts) > 1:
            # one compiled concat on DEVICE — through the module-level
            # jitted helper so jax's cache (keyed on function identity)
            # actually hits across reps: a per-call lambda would re-trace
            # INSIDE the timed pipeline leg and deflate
            # pipeline_vs_memory with compile cost
            fb = _device_concat(*fb_parts)
            labels = _device_concat(*lab_parts)
        else:
            # single part (committed or not) passes through; multiple
            # parts only reach here on the host path (commit=False)
            fb = fb_parts[0] if len(fb_parts) == 1 else \
                np.concatenate(fb_parts)
            labels = (lab_parts[0] if len(lab_parts) == 1
                      else np.concatenate(lab_parts))
        rp_wall = time.perf_counter() - t0
        return fb, labels, {"read_s": round(stats[0], 3),
                            "parse_s": round(stats[1], 3),
                            "encode_s": round(stats[2], 3),
                            "rp_wall_s": round(rp_wall, 3),
                            "ingest_workers": workers,
                            "ingest_committed": bool(commit)}

    def train(fb, labels):
        data = {"fb_idx": fb, "y": labels,
                "w": np.ones(len(labels), np.float32)}
        obj = UnaryLossObjFunc(LogLossFunc(), DIM, l2=1e-4, fb_meta=meta)
        coef, _, _ = optimize(obj, data, OptimParams(
            method="LBFGS", max_iter=3, epsilon=0.0), h.env)
        np.asarray(coef)

    # warm the compile cache so neither timing includes compilation
    fb0, y0, _ = load_from_disk()
    train(fb0, y0)
    assert (np.asarray(fb0) == fb_idx_true).all() and len(y0) == n_rows

    # PAIRED reps: the train leg's wall time swings with host
    # contention, so timing the pipeline and the in-memory legs in
    # separate blocks produced ratios from 0.46 to 1.48 run-to-run. Each
    # rep times both legs back-to-back (local in time, the Harness.delta
    # principle) and the artifact reports the median of the PAIRED
    # ratios next to the median absolute times.
    fb16_true = fb_idx_true.astype(np.int16)   # same encode as the disk leg
    y32_true = y_true.astype(np.float32)
    from alink_tpu.common.profiling2 import measured_region
    tot_ts, mem_ts, ratios, splits = [], [], [], []
    for _ in range(3):
        # only the PIPELINE leg is the workload's measured region (the
        # in-memory twin is a reference, not the reported rate)
        t0 = time.perf_counter()
        with measured_region():
            fb, labels, split = load_from_disk()
            train(fb, labels)
        t_pipe = time.perf_counter() - t0
        t0 = time.perf_counter()
        train(fb16_true, y32_true)
        t_m = time.perf_counter() - t0
        tot_ts.append(t_pipe)
        mem_ts.append(t_m)
        ratios.append(t_m / t_pipe)
        splits.append(split)
    t_total = sorted(tot_ts)[1]
    split = splits[tot_ts.index(t_total)]
    pipeline_sps = n_rows / t_total / h.chips
    t_mem = sorted(mem_ts)[1]
    mem_sps = n_rows / t_mem / h.chips
    paired_ratio = sorted(ratios)[1]

    bytes_read = os.path.getsize(path)
    # the engine's compiled-program cache (comqueue._PROGRAM_CACHE) makes
    # every post-warmup fit reuse one XLA program, so train_s is actual
    # device time, not the former ~8-10 s per-fit retrace;
    # pipeline_vs_memory therefore isolates the disk path's cost, with
    # read_s/parse_s/encode_s attributing it.
    # raw rig-IO ceiling: the same sharded readers with NO parse/encode —
    # proves whether the source phase saturates the rig's read path
    # (page-cache-warm on both sides, so the comparison is apples/apples)
    from alink_tpu.io.sharding import parallel_shard_map as _psm
    t0 = time.perf_counter()
    raw = _psm(lambda i: len(_load_line_bytes(path, False, (i, n_shards))),
               n_shards)
    rig_read_s = time.perf_counter() - t0
    assert sum(raw) == bytes_read

    # roofline at the PIPELINE rate (3 L-BFGS iters of the fb superstep
    # per sample); the binding resource is the host ingest path, stated
    # explicitly — neither device roof is near
    return {"samples_per_sec_per_chip": round(pipeline_sps, 1),
            "in_memory_samples_per_sec_per_chip": round(mem_sps, 1),
            "source_samples_per_sec": round(n_rows / split["rp_wall_s"], 1),
            "pipeline_vs_memory": round(min(paired_ratio, 1.0), 3),
            "pipeline_vs_memory_unclamped": round(paired_ratio, 3),
            "fixture_mb": round(bytes_read / 1e6, 1),
            "source_mb_per_sec": round(
                bytes_read / 1e6 / split["rp_wall_s"], 1),
            "rig_read_mb_per_sec": round(bytes_read / 1e6 / rig_read_s, 1),
            **split, "train_s": round(t_total - split["rp_wall_s"], 3),
            "dt_s": round(t_total, 3),
            **mfu(pipeline_sps, 3 * 3 * 2 * DIM,
                  3 * 3 * N_FIELDS * (FIELD_SIZE // 16 + 16) * 2,
                  bound="host")}


# ---------------------------------------------------------------------------
# 5. GBDT / adult-shape
# ---------------------------------------------------------------------------

def bench_gbdt(h: Harness):
    import jax
    import jax.numpy as jnp

    from alink_tpu.operator.common.tree.hist import (bin_data, make_bin_edges,
                                                     tree_apply_binned)
    from alink_tpu.operator.common.tree.trainers import (TreeTrainParams,
                                                         gbdt_train)

    n, F = 48_842, 14                       # adult shape
    depth, n_bins = 6, 64
    rng = np.random.RandomState(0)
    Xc = rng.randn(n, 6).astype(np.float32)                       # continuous
    Xd = rng.randint(0, 12, size=(n, 8)).astype(np.float32)       # categorical
    X = np.concatenate([Xc, Xd], 1)
    margin = (Xc[:, 0] + 0.8 * Xc[:, 1] * (Xd[:, 0] > 5)
              - 0.6 * (Xd[:, 1] % 3) + 0.4 * Xc[:, 2])
    y = (margin + 0.3 * rng.randn(n) > 0).astype(np.float32)
    trees = 50
    jrng = np.random.RandomState(5)

    def run(n_trees):
        p = TreeTrainParams(num_trees=n_trees, max_depth=depth, n_bins=n_bins,
                            learning_rate=0.3)
        Xj = X + jrng.randn(1, F).astype(np.float32) * 1e-6
        tf, tb, tm, tv, edges, base, curve, _ = gbdt_train(Xj, y, p, False,
                                                           h.env)
        np.asarray(curve)
        return tf, tb, tm, tv, edges, base

    # span must be ~3x the 50-bench trees: the true marginal cost of 49
    # trees (~0.3 s) sat inside the per-call noise and the r3-trial
    # delta came out NEGATIVE (clamped), recording a nonsense 2.4e15
    # samples/s
    span = 150
    # 5 paired reps (ALS treatment): this row swung 15.0x driver vs
    # 27.8x local in r03
    dt = h.delta(run, span, reps=5)
    sps = n * span / dt / h.chips

    tf, tb, tm, tv, edges, base, curve, _ = gbdt_train(
        X, y, TreeTrainParams(num_trees=trees, max_depth=depth,
                              n_bins=n_bins, learning_rate=0.3), False, h.env)
    binned = bin_data(X, edges)
    leaf = jax.vmap(lambda f, b: tree_apply_binned(
        jnp.asarray(binned), f, b, depth))(jnp.asarray(tf), jnp.asarray(tb))
    scores = base + 0.3 * np.asarray(
        jnp.take_along_axis(jnp.asarray(tv), leaf, 1)).sum(0)
    auc = _auc(y, scores)

    # CPU baseline: histogram build + split select per level in numpy
    base_iters = 2
    edges_np = np.asarray(edges)
    b_np = np.asarray(binned)
    cpu_times = []
    for _rep in range(5):
      t0 = time.perf_counter()
      for _ in range(base_iters):
        node = np.zeros(n, np.int64)
        Fcur = np.zeros(n, np.float32)
        prob = 1.0 / (1.0 + np.exp(-Fcur))
        g = prob - y
        hss = np.maximum(prob * (1 - prob), 1e-6)
        for level in range(depth):
            n_nodes = 1 << level
            hist = np.zeros((n_nodes * F * n_bins, 3), np.float64)
            flat = (node[:, None] * F + np.arange(F)[None, :]) * n_bins + b_np
            np.add.at(hist, flat.reshape(-1),
                      np.repeat(np.stack([g, hss, np.ones(n)], 1), F, axis=0))
            hist = hist.reshape(n_nodes, F, n_bins, 3)
            cum = np.cumsum(hist, axis=2)
            tot = cum[:, :, -1:, :]
            left = cum[:, :, :-1, :]
            right = tot - left
            gain = (left[..., 0] ** 2 / (left[..., 1] + 1.0)
                    + right[..., 0] ** 2 / (right[..., 1] + 1.0))
            best = gain.reshape(n_nodes, -1).argmax(1)
            bf = best // (n_bins - 1)
            bb = best % (n_bins - 1)
            node = node * 2 + (b_np[np.arange(n), bf[node]] > bb[node])
      cpu_times.append(time.perf_counter() - t0)
    # min-of-5 per the suite's estimator rule (one-sided endpoint noise)
    cpu_sps = n * base_iters / min(cpu_times)
    # quality anchor (VERDICT r2 #8): sklearn HistGradientBoosting on the
    # IDENTICAL matrix — proves the trainer extracts the planted signal
    # as well as a reference implementation does, not just "learns"
    from sklearn.ensemble import HistGradientBoostingClassifier
    hgb = HistGradientBoostingClassifier(
        max_iter=trees, max_depth=depth, learning_rate=0.3,
        max_bins=n_bins, early_stopping=False)
    hgb.fit(X, y)
    sk_auc = _auc(y, hgb.decision_function(X))

    # per sample per TREE: depth levels of one-hot histogram einsums over
    # (F features x n_bins) x 3 stats channels (tree/hist.py): issued
    # flops = depth * F * 2*n_bins*3 (samples/sec already counts trees);
    # HBM: binned rows (F bytes int8) + grad/hess (8B) re-read per level.
    # Both roofs sit ~0.1% — the limiter is the per-level chain of small
    # kernels (split argmax, node routing), i.e. latency, as the auto
    # rule reports.
    return {"samples_per_sec_per_chip": round(sps, 1),
            "vs_baseline": round(sps / cpu_sps, 3),
            "iters_trees_x_depth": f"{trees}x{depth}", "auc": round(auc, 4),
            "sklearn_auc": round(sk_auc, 4),
            "dt_s": round(dt, 3),
            **mfu(sps, depth * F * 2 * n_bins * 3, depth * (F + 8))}


# ---------------------------------------------------------------------------
# 5b. GBDT at 10x-adult — the large-shape roofline row (VERDICT r5 #5)
# ---------------------------------------------------------------------------

def bench_gbdt_large(h: Harness):
    """GBDT at 10x the adult shape with the FUSED histogram kernel on the
    measured path (ALINK_TPU_FUSED_HIST, ISSUE 6 tentpole (b)): at 488k
    rows the per-level one-hot contractions do chip-scale work and the
    row leaves `bound: latency` for a hardware roof. The uniform roofline
    fields use the FUSED formulation's issued flops (the two MXU dots per
    level) — the design tradeoff being measured. Scale knob for smoke
    rigs: ALINK_TPU_GBDT_LARGE_ROWS."""
    from alink_tpu.operator.common.tree.hist import (FUSED_HIST_ENV,
                                                     fused_hist_mode)
    from alink_tpu.operator.common.tree.trainers import (TreeTrainParams,
                                                         gbdt_train)

    from alink_tpu.common.flags import flag_value
    n = int(flag_value("ALINK_TPU_GBDT_LARGE_ROWS"))
    F, depth, n_bins = 14, 6, 64
    rng = np.random.RandomState(0)
    Xc = rng.randn(n, 6).astype(np.float32)
    Xd = rng.randint(0, 12, size=(n, 8)).astype(np.float32)
    X = np.concatenate([Xc, Xd], 1)
    margin = (Xc[:, 0] + 0.8 * Xc[:, 1] * (Xd[:, 0] > 5)
              - 0.6 * (Xd[:, 1] % 3) + 0.4 * Xc[:, 2])
    y = (margin + 0.3 * rng.randn(n) > 0).astype(np.float32)
    jrng = np.random.RandomState(5)
    from alink_tpu.common.flags import flag_raw
    prev = flag_raw(FUSED_HIST_ENV)
    # "pallas" on TPU backends that lower it; the XLA fused formulation
    # is the portable default
    os.environ[FUSED_HIST_ENV] = str(flag_value("ALINK_TPU_GBDT_LARGE_HIST"))
    try:
        mode = fused_hist_mode()

        def run(n_trees):
            p = TreeTrainParams(num_trees=n_trees, max_depth=depth,
                                n_bins=n_bins, learning_rate=0.3)
            Xj = X + jrng.randn(1, F).astype(np.float32) * 1e-6
            out = gbdt_train(Xj, y, p, False, h.env)
            np.asarray(out[6])               # loss curve: full fetch

        span = 24
        dt = h.delta(run, span, reps=3)
        sps = n * span / dt / h.chips

        # quality: one short fit; the planted signal must survive the
        # fused kernel (parity with the default kernel is pinned by
        # tests — this is the in-artifact anchor)
        import jax
        import jax.numpy as jnp
        from alink_tpu.operator.common.tree.hist import (bin_data,
                                                         tree_apply_binned)
        trees_q = 20
        tf, tb, tm, tv, edges, base, curve, _ = gbdt_train(
            X, y, TreeTrainParams(num_trees=trees_q, max_depth=depth,
                                  n_bins=n_bins, learning_rate=0.3),
            False, h.env)
        binned = bin_data(X, edges)
        leaf = jax.vmap(lambda f, b: tree_apply_binned(
            jnp.asarray(binned), f, b, depth))(jnp.asarray(tf),
                                               jnp.asarray(tb))
        scores = base + 0.3 * np.asarray(
            jnp.take_along_axis(jnp.asarray(tv), leaf, 1)).sum(0)
        auc = _auc(y, scores)
    finally:
        if prev is None:
            os.environ.pop(FUSED_HIST_ENV, None)
        else:
            os.environ[FUSED_HIST_ENV] = prev

    # issued flops/sample/tree of the fused contraction: the level-l
    # histogram dot contracts (n, n_nodes*2m) x (n, F*n_bins) ->
    # 2*n_nodes*2m*F*n_bins per sample; sum(n_nodes) over levels =
    # 2^depth - 1. HBM/sample/tree: the bf16 ohB (F*n_bins*2B) + s2
    # (2m*2B) stream through every level.
    m = 3
    fps = 2 * ((1 << depth) - 1) * (2 * m) * (F * n_bins)
    bps = depth * (F * n_bins * 2 + 2 * m * 2)
    return {"samples_per_sec_per_chip": round(sps, 1),
            "rows": n, "hist_kernel": mode,
            "iters_trees_x_depth": f"{span}x{depth}",
            "auc": round(auc, 4), "dt_s": round(dt, 3),
            **mfu(sps, fps, bps)}


# ---------------------------------------------------------------------------
# 6. ALS / MovieLens-1M shape
# ---------------------------------------------------------------------------

def bench_als(h: Harness):
    from alink_tpu.operator.common.recommendation.als import (AlsTrainParams,
                                                              als_train)

    U, I, nnz, rank = 6040, 3706, 1_000_000, 10   # MovieLens-1M shape
    rng = np.random.RandomState(0)
    users = rng.randint(0, U, nnz).astype(np.int32)
    items = rng.randint(0, I, nnz).astype(np.int32)
    uf_true = rng.randn(U, rank).astype(np.float32) / np.sqrt(rank)
    if_true = rng.randn(I, rank).astype(np.float32) / np.sqrt(rank)
    ratings = ((uf_true[users] * if_true[items]).sum(1) * 1.5 + 3.5
               + 0.2 * rng.randn(nnz)).astype(np.float32)
    # span must clear the noise on the fixed per-call cost (trace +
    # 30 MB input transfer): at iters=10 the ~1.2 s signal sat inside
    # the fixed cost's variance and the delta repeatedly came out
    # negative (clamped -> absurd sps in two r3 trial runs)
    iters = 40
    jrng = np.random.RandomState(9)

    def run(n_iter):
        p = AlsTrainParams(rank=rank, num_iter=n_iter, lambda_reg=0.1)
        rj = ratings + jrng.randn(1).astype(np.float32) * 1e-6
        out = als_train(users, items, rj, p, h.env, num_users=U, num_items=I)
        np.asarray(out[0])
        return out

    # 5 paired reps: the ~11 s fixed per-call cost leaves the 40-iter
    # signal noisy at 3 (the recorded row swung 14-25 M samples/s)
    dt = h.delta(run, iters, reps=5)
    sps = nnz * iters / dt / h.chips

    # quality + iters-to-converge: one run with the production RMSE-delta
    # stop criterion (round 2 reported the configured constant here)
    p_conv = AlsTrainParams(rank=rank, num_iter=30, lambda_reg=0.1, tol=1e-3)
    uf, if_, curve = als_train(users, items, ratings, p_conv, h.env,
                               num_users=U, num_items=I)
    n_conv = len(curve)
    uf, if_ = np.asarray(uf), np.asarray(if_)
    preds = (uf[users] * if_[items]).sum(1)
    rmse = float(np.sqrt(((preds - ratings) ** 2).mean()))

    # CPU baseline: one ALS sweep (both sides) via batched normal equations
    base_iters = 1
    ufc = rng.rand(U, rank).astype(np.float32)
    ifc = rng.rand(I, rank).astype(np.float32)
    eye = np.eye(rank, dtype=np.float32)
    t0 = time.perf_counter()
    for _ in range(base_iters):
        for ids, oids, nrows, fac, ofac in ((users, items, U, ufc, ifc),
                                            (items, users, I, ifc, ufc)):
            x = ofac[oids]
            A = np.zeros((nrows, rank, rank), np.float32)
            b = np.zeros((nrows, rank), np.float32)
            np.add.at(A, ids, x[:, :, None] * x[:, None, :])
            np.add.at(b, ids, ratings[:, None] * x)
            fac[:] = np.linalg.solve(A + 0.1 * eye, b[:, :, None])[:, :, 0]
    cpu_sps = nnz * base_iters / (time.perf_counter() - t0)
    # per sample per iter: 2 half-sweeps x packed-symmetric contribution
    # rows (tril r(r+1)/2 + r + 1 columns) ~ 2 * 2*K flops; the (U+I)
    # batched r^3 GJ solves amortize to ~(U+I)*2*r^3/nnz. The prefix
    # pipeline is HBM-bound: ~6 passes over the (nnz, K) f32 contrib per
    # side.
    K_cols = rank * (rank + 1) // 2 + rank + 1
    fps = 2 * 2 * K_cols + (U + I) * 2 * rank ** 3 // nnz
    bps = 2 * 6 * K_cols * 4
    return {"samples_per_sec_per_chip": round(sps, 1),
            "vs_baseline": round(sps / cpu_sps, 3),
            "iters_to_converge": int(n_conv), "rmse": round(rmse, 4),
            "dt_s": round(dt, 3), **mfu(sps, fps, bytes_per_sample=bps)}


# ---------------------------------------------------------------------------
# 6b. ALS at MovieLens-10M shape — the large-shape roofline row
# ---------------------------------------------------------------------------

def bench_als_large(h: Harness):
    """ALS at the MovieLens-10M shape (69,878 x 10,677 users/items, 10M
    ratings, rank 10): ten times the 1M row's work per sweep, so the
    prefix-sum/normal-equation pipeline streams enough bytes to press
    the HBM roof instead of the dispatch floor (VERDICT r5 #5). Scale
    knob for smoke rigs: ALINK_TPU_ALS_LARGE_NNZ."""
    from alink_tpu.operator.common.recommendation.als import (AlsTrainParams,
                                                              als_train)

    U, I, rank = 69_878, 10_677, 10          # MovieLens-10M shape
    from alink_tpu.common.flags import flag_value
    nnz = int(flag_value("ALINK_TPU_ALS_LARGE_NNZ"))
    rng = np.random.RandomState(0)
    users = rng.randint(0, U, nnz).astype(np.int32)
    items = rng.randint(0, I, nnz).astype(np.int32)
    uf_true = rng.randn(U, rank).astype(np.float32) / np.sqrt(rank)
    if_true = rng.randn(I, rank).astype(np.float32) / np.sqrt(rank)
    ratings = ((uf_true[users] * if_true[items]).sum(1) * 1.5 + 3.5
               + 0.2 * rng.randn(nnz)).astype(np.float32)
    # at 10M nnz one sweep is ~10x the 1M row's device work, so a short
    # span clears the fixed-cost noise the 1M row needed 40 iters for
    iters = 8
    jrng = np.random.RandomState(9)

    def run(n_iter):
        p = AlsTrainParams(rank=rank, num_iter=n_iter, lambda_reg=0.1)
        rj = ratings + jrng.randn(1).astype(np.float32) * 1e-6
        out = als_train(users, items, rj, p, h.env, num_users=U, num_items=I)
        np.asarray(out[0])
        return out

    dt = h.delta(run, iters, reps=2)
    sps = nnz * iters / dt / h.chips

    # quality anchor: one short fit's training RMSE (the generating
    # noise floor is 0.2)
    uf, if_, curve = als_train(users, items, ratings,
                               AlsTrainParams(rank=rank, num_iter=5,
                                              lambda_reg=0.1),
                               h.env, num_users=U, num_items=I)
    uf, if_ = np.asarray(uf), np.asarray(if_)
    preds = (uf[users] * if_[items]).sum(1)
    rmse = float(np.sqrt(((preds - ratings) ** 2).mean()))

    # same roofline accounting as the 1M row (packed-symmetric
    # contribution columns; 6 prefix passes per side over the (nnz, K)
    # f32 contribs is the binding HBM term)
    K_cols = rank * (rank + 1) // 2 + rank + 1
    fps = 2 * 2 * K_cols + (U + I) * 2 * rank ** 3 // nnz
    bps = 2 * 6 * K_cols * 4
    return {"samples_per_sec_per_chip": round(sps, 1),
            "nnz": nnz, "shape": f"{U}x{I}", "rank": rank,
            "rmse": round(rmse, 4), "dt_s": round(dt, 3),
            **mfu(sps, fps, bytes_per_sample=bps)}


# ---------------------------------------------------------------------------
# --quick: the <60 s smoke suite (the perf regression gate's input)
# ---------------------------------------------------------------------------
#
# Same workload NAMES and JSON shape as the full suite so the dump feeds
# tools/bench_compare.py unchanged, but tiny fixtures and short spans: the
# point is a tier-1-adjacent gate (run before/after a change, diff with
# --threshold), not publishable absolute numbers. The final line carries
# "mode": "quick" and bench_compare warns when quick and full dumps are
# mixed. Workflow: docs/performance.md "Quick bench gate".

def quick_logreg(h: Harness):
    n_rows, iters = 8_000, 12
    from alink_tpu.operator.common.optim.objfunc import (LogLossFunc,
                                                         UnaryLossObjFunc)
    from alink_tpu.operator.common.optim.optimizers import OptimParams, optimize
    from alink_tpu.ops.fieldblock import FieldBlockMeta
    fb_idx, y = make_ctr_fieldblock(n_rows)
    meta = FieldBlockMeta(N_FIELDS, FIELD_SIZE)
    data = {"fb_idx": fb_idx, "y": y, "w": np.ones(n_rows, np.float32)}
    wrng = np.random.RandomState(123)

    def run(n_iter):
        obj = UnaryLossObjFunc(LogLossFunc(), DIM, l2=1e-4, fb_meta=meta)
        w0 = (wrng.randn(DIM) * 1e-6).astype(np.float32)
        coef, _, _ = optimize(obj, data, OptimParams(
            method="LBFGS", max_iter=n_iter, epsilon=0.0), h.env,
            warm_start=w0)
        np.asarray(coef)

    dt = h.delta(run, iters, reps=2)
    sps = n_rows * iters / dt / h.chips
    return {"samples_per_sec_per_chip": round(sps, 1),
            "dt_s": round(dt, 3)}


def quick_kmeans(h: Harness):
    from sklearn.datasets import load_iris
    from alink_tpu.operator.common.clustering.kmeans import kmeans_train
    iris = load_iris().data.astype(np.float32)
    rng = np.random.RandomState(0)
    X = np.tile(iris, (300, 1)) + rng.randn(150 * 300, 4).astype(
        np.float32) * 0.05
    iters = 200
    jrng = np.random.RandomState(7)

    def run(n_iter):
        Xj = X + jrng.randn(1, 4).astype(np.float32) * 1e-5
        C, _, _ = kmeans_train(Xj, k=3, max_iter=n_iter, tol=0.0,
                               init="RANDOM", seed=0, env=h.env)
        np.asarray(C)

    dt = h.delta(run, iters, reps=2)
    return {"samples_per_sec_per_chip":
            round(X.shape[0] * iters / dt / h.chips, 1),
            "dt_s": round(dt, 3)}


def quick_ftrl(h: Harness):
    """Strict + staleness sparse FTRL KERNEL rates on a shrunken Criteo
    shape, chained in one jitted scan exactly like the full row (inner
    donation is inlined away here — the production drain's donated/
    pooled path is the separate ftrl_stream_drain row)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        _ftrl_sparse_chained_step_factory,
        _ftrl_sparse_staleness_step_factory, _ftrl_sparse_step_factory)
    dim, nnz, B, n_pool = 4_096, 16, 256, 4
    n_dev = h.chips
    dim_pad = -(-dim // n_dev) * n_dev
    width = -(-(nnz + 1) // 8) * 8
    rng = np.random.RandomState(0)

    def make_batch(seed):
        r = np.random.RandomState(seed)
        idx = np.zeros((B, width), np.int32)
        val = np.zeros((B, width), np.float64)
        idx[:, 0], val[:, 0] = 0, 1.0
        idx[:, 1:nnz + 1] = r.randint(1, dim, size=(B, nnz))
        val[:, 1:nnz + 1] = 1.0
        y = (r.rand(B) < 0.5).astype(np.float64)
        return idx, val, y

    pool = [make_batch(s) for s in range(n_pool)]
    mesh = h.env.mesh
    shard = NamedSharding(mesh, P("d"))
    sp_idx = h.put(np.stack([p[0] for p in pool]))
    sp_val = h.put(np.stack([p[1] for p in pool]))
    sp_y = h.put(np.stack([p[2] for p in pool]))
    zrng = np.random.RandomState(3)
    out = {}
    for key, step in (
            ("strict", _ftrl_sparse_step_factory(
                mesh, alpha=0.05, beta=1.0, l1=1e-5, l2=1e-5)),
            ("chained", _ftrl_sparse_chained_step_factory(
                mesh, alpha=0.05, beta=1.0, l1=1e-5, l2=1e-5, K=16)),
            ("stale", _ftrl_sparse_staleness_step_factory(
                mesh, alpha=0.05, beta=1.0, l1=1e-5, l2=1e-5, K=32))):
        @jax.jit
        def pool_fn(sp_idx, sp_val, sp_y, z, nacc, step=step):
            def body(carry, xs):
                z, nacc = carry
                z, nacc, m = step(xs[0], xs[1], xs[2], z, nacc)
                return (z, nacc), m[0]
            (z, nacc), _ = jax.lax.scan(body, (z, nacc),
                                        (sp_idx, sp_val, sp_y))
            return z, nacc

        def run(n_pools, pool_fn=pool_fn):
            st = [jax.device_put(zrng.randn(dim_pad) * 1e-8, shard),
                  jax.device_put(np.zeros(dim_pad), shard)]

            def step_once():
                st[0], st[1] = pool_fn(sp_idx, sp_val, sp_y, st[0], st[1])
            _kernel_loop("ftrl.kernel", n_pools, step_once,
                         lambda: np.asarray(st[0]))

        dt = h.delta(run, 3, reps=2)
        out[key] = B * n_pool * 3 / dt / h.chips
    return {"samples_per_sec_per_chip": round(out["stale"], 1),
            # strict headline = best strict-semantics kernel (the full
            # row's rule): chained wins on issue-latency-bound backends,
            # per-sample on compute-bound smoke rigs
            "strict_samples_per_sec_per_chip":
                round(max(out["chained"], out["strict"]), 1),
            "strict_chained_samples_per_sec_per_chip":
                round(out["chained"], 1),
            "strict_persample_samples_per_sec_per_chip":
                round(out["strict"], 1),
            "dispatch_gap_est_s": round(h.dispatch_gap(50), 6)}


def quick_from_disk(h: Harness):
    """The full logreg_from_disk pipeline (sharded read -> native parse
    -> fb encode -> train) on a small fixture: pipeline_vs_memory is the
    gate column the overlap work targets."""
    from alink_tpu.common.flags import flag_raw
    prev = flag_raw("ALINK_TPU_DISKBENCH_ROWS")
    os.environ["ALINK_TPU_DISKBENCH_ROWS"] = prev or "30000"
    try:
        return bench_logreg_from_disk(h)
    finally:
        # restore the EXACT prior state ("" included) — a smoke row must
        # not leak its fixture size into later workloads/processes
        if prev is None:
            del os.environ["ALINK_TPU_DISKBENCH_ROWS"]
        else:
            os.environ["ALINK_TPU_DISKBENCH_ROWS"] = prev


def quick_logreg_ckpt(h: Harness):
    """Checkpointed L-BFGS — the DONATED cont chunk program plus the
    async snapshot writer on its hot path (the plain quick_logreg row
    never enters recovery.drive, so without this row the gate is blind
    to regressions in exactly the paths the overlap work changed).
    Measures one whole checkpointed fit, boundary persistence included."""
    import shutil
    import tempfile
    from alink_tpu.operator.common.optim.objfunc import (LogLossFunc,
                                                         UnaryLossObjFunc)
    from alink_tpu.operator.common.optim.optimizers import OptimParams, optimize
    n, d, iters = 20_000, 32, 12
    rng = np.random.RandomState(2)
    X = rng.randn(n, d).astype(np.float32)
    y = (X @ rng.randn(d) > 0).astype(np.float32) * 2 - 1
    data = {"X": X, "y": y, "w": np.ones(n, np.float32)}

    def fit(ckdir):
        obj = UnaryLossObjFunc(LogLossFunc(), dim=d)
        coef, _, _ = optimize(obj, data, OptimParams(
            method="LBFGS", max_iter=iters, epsilon=0.0,
            checkpoint_dir=ckdir, checkpoint_every=3), h.env)
        np.asarray(coef)

    base = tempfile.mkdtemp(prefix="alink_quick_ckpt_")
    from alink_tpu.common.profiling2 import measured_region
    try:
        fit(os.path.join(base, "warm"))       # compile outside the timing
        ts = []
        for i in range(3):
            t0 = time.perf_counter()
            with measured_region():
                fit(os.path.join(base, f"r{i}"))
            ts.append(time.perf_counter() - t0)
        dt = sorted(ts)[1]
    finally:
        shutil.rmtree(base, ignore_errors=True)
    return {"samples_per_sec_per_chip": round(n * iters / dt / h.chips, 1),
            "dt_s": round(dt, 3)}


def quick_ftrl_drain(h: Harness):
    """The PRODUCTION stream drain at quick scale: raw rows ->
    field-aware hash -> FtrlTrainStreamOp, i.e. the prefetch_map encode
    pool, the donated (z, n) step programs, and the batched emission
    fetch — none of which the chained-jit quick_ftrl row touches."""
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch.classification.linear import (
        LogisticRegressionTrainBatchOp)
    from alink_tpu.operator.batch.feature.feature_ops import (
        FeatureHasherBatchOp)
    from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
    from alink_tpu.operator.stream.batch_twins import FeatureHasherStreamOp
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        FtrlTrainStreamOp)
    from alink_tpu.operator.stream.source.sources import MemSourceStreamOp
    n_stream, bs = 32_768, 4_096
    srng = np.random.RandomState(17)
    site_ids = srng.randint(0, 1000, n_stream)
    cols = {"site": np.char.add("s", site_ids.astype("U6")).astype(object),
            "dev": np.char.add("d", srng.randint(0, 1000, n_stream)
                               .astype("U6")).astype(object),
            "click": (srng.rand(n_stream)
                      < 0.1 + 0.8 * (site_ids % 2)).astype(np.int64)}
    schema = "site STRING, dev STRING, click LONG"
    hk = dict(selected_cols=["site", "dev"], categorical_cols=["site", "dev"],
              output_col="vec", num_features=2 * 1024, field_aware=True)
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="click", max_iter=2).link_from(
        FeatureHasherBatchOp(**hk).link_from(
            MemSourceBatchOp(MTable(cols, schema).first_n(2048))))

    def drain():
        src = MemSourceStreamOp(MTable(cols, schema), batch_size=bs)
        feat = FeatureHasherStreamOp(**hk).link_from(src)
        ftrl = FtrlTrainStreamOp(warm, vector_col="vec", label_col="click",
                                 alpha=0.05, update_mode="batch",
                                 time_interval=1e9).link_from(feat)
        for _ in ftrl.micro_batches():
            pass

    from alink_tpu.common.profiling2 import measured_region
    drain()                                   # warm compiles
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        with measured_region():
            drain()
        ts.append(time.perf_counter() - t0)
    dt = sorted(ts)[1]
    return {"samples_per_sec_per_chip": round(n_stream / dt / h.chips, 1),
            "dt_s": round(dt, 3)}


def quick_gbdt_hist(h: Harness):
    """GBDT with the FUSED histogram kernel (ALINK_TPU_FUSED_HIST=xla) on
    the measured path at smoke scale — without this row the gate is
    blind to regressions in exactly the kernel the large-shape roofline
    row (gbdt_adult_large) depends on."""
    from alink_tpu.operator.common.tree.hist import FUSED_HIST_ENV
    from alink_tpu.operator.common.tree.trainers import (TreeTrainParams,
                                                         gbdt_train)
    n, F, depth, n_bins = 8_000, 10, 5, 32
    rng = np.random.RandomState(0)
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    jrng = np.random.RandomState(5)
    from alink_tpu.common.flags import flag_raw
    prev = flag_raw(FUSED_HIST_ENV)
    os.environ[FUSED_HIST_ENV] = "xla"
    try:
        def run(n_trees):
            p = TreeTrainParams(num_trees=n_trees, max_depth=depth,
                                n_bins=n_bins, learning_rate=0.3)
            Xj = X + jrng.randn(1, F).astype(np.float32) * 1e-6
            out = gbdt_train(Xj, y, p, False, h.env)
            np.asarray(out[6])

        span = 12
        dt = h.delta(run, span, reps=2)
    finally:
        if prev is None:
            os.environ.pop(FUSED_HIST_ENV, None)
        else:
            os.environ[FUSED_HIST_ENV] = prev
    return {"samples_per_sec_per_chip": round(n * span / dt / h.chips, 1),
            "dt_s": round(dt, 3)}


# ---------------------------------------------------------------------------
# Serving tier (alink_tpu/serving): micro-batched compiled predict rows
# ---------------------------------------------------------------------------

def _serve_fixture(n_rows, dim, seed=0, with_detail=False):
    """A trained dense-LR model + request table for the serving rows.

    Dense vector features: the dense score kernel is the one whose
    device scores are bitwise-identical to the host mapper path, so the
    row's parity field is an exact check, not a tolerance."""
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.params import Params
    from alink_tpu.common.vector import DenseVector
    from alink_tpu.operator.batch.classification.linear import (
        LogisticRegressionTrainBatchOp)
    from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
    from alink_tpu.operator.common.linear.mapper import LinearModelMapper
    rng = np.random.RandomState(seed)
    X = rng.randn(n_rows, dim)
    y = (X @ rng.randn(dim) > 0).astype(np.int64)
    vecs = np.empty(n_rows, object)
    vecs[:] = [DenseVector(X[i]) for i in range(n_rows)]
    tbl = MTable({"vec": vecs, "label": y}, "vec VECTOR, label LONG")
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=4).link_from(
        MemSourceBatchOp(tbl.first_n(min(512, n_rows))))
    data_schema = tbl.select(["vec"]).schema
    pp = {"prediction_col": "pred", "vector_col": "vec"}
    if with_detail:
        pp["prediction_detail_col"] = "det"
    mapper = LinearModelMapper(warm.get_output_table().schema, data_schema,
                               Params(pp))
    mapper.load_model(warm.get_output_table())
    return tbl, warm, mapper, data_schema


def _bench_serve_logreg(h: Harness, requests: int, serial_requests: int,
                        n_rows: int = 2000, dim: int = 64):
    """Micro-batched serving QPS vs the single-request serial-dispatch
    baseline — BOTH legs run the same server machinery (queue, futures,
    compiled predictor); the serial leg just caps max_batch at 1, so
    the delta is exactly what request coalescing buys."""
    from alink_tpu.serving import (CompiledPredictor, LoadGenerator,
                                   PredictServer)
    tbl, _warm, mapper, _schema = _serve_fixture(n_rows, dim)
    req = tbl.select(["vec"])
    pred = CompiledPredictor(mapper)
    for b in pred.buckets:                    # compile outside the timing
        pred.predict_table(req.first_n(min(b, n_rows)))
    # bitwise parity: the compiled/bucketed path against the host mapper
    sample = req.first_n(min(300, n_rows))
    ref, got = mapper.map_table(sample), pred.predict_table(sample)
    parity = "bitwise" if all(
        all(a == b for a, b in zip(got.col(c), ref.col(c)))
        for c in ref.col_names) else "MISMATCH"
    rows = [req.row(i) for i in range(min(64, n_rows))]
    t0 = time.perf_counter()
    serial_srv = PredictServer(pred, max_batch=1, name="serve_serial")
    slg = LoadGenerator(serial_srv.submit, rows, clients=1, pipeline=1)
    slg.run(max(50, serial_requests // 4))            # warm the loop
    from alink_tpu.common.profiling2 import measured_region
    with measured_region():
        srep = slg.run(serial_requests)
    serial_srv.close()
    srv = PredictServer(pred, name="serve")
    lg = LoadGenerator(srv.submit, rows, clients=4, pipeline=32)
    lg.run(max(100, requests // 8))                   # warm the loop
    with measured_region():
        rep = lg.run(requests)
    stats = srv.stats()
    srv.close()
    dt = time.perf_counter() - t0
    qps = rep.qps
    return {
        # serving is a single-replica tier: QPS/chip == QPS of one chip
        "samples_per_sec_per_chip": round(qps, 1),
        "qps_per_chip": round(qps, 1),
        "serial_qps_per_chip": round(srep.qps, 1),
        "speedup_vs_serial": round(qps / max(srep.qps, 1e-9), 1),
        "p50_ms": round(rep.p50_s * 1e3, 3),
        "p99_ms": round(rep.p99_s * 1e3, 3),
        "serial_p50_ms": round(srep.p50_s * 1e3, 3),
        "serial_p99_ms": round(srep.p99_s * 1e3, 3),
        "bucket_hit_rate": round(stats["bucket_hit_rate"], 4),
        "batch_occupancy": round(stats["mean_occupancy"], 4),
        "mean_batch_rows": round(stats["mean_batch_rows"], 1),
        "failed_requests": rep.failures + srep.failures + stats["failed"],
        "compiled_programs": stats["programs"],
        "parity": parity,
        "bound": "serving-host",
        "dt_s": round(dt, 3),
    }


def _bench_serve_hot_swap(h: Harness, requests_per_phase: int,
                          n_rows: int = 3072, dim: int = 64,
                          batch_rows: int = 128):
    """Sustained serving across live FTRL model swaps: the trainer's
    model-snapshot stream hot-swaps the served model (double-buffered
    slot flip) while a closed-loop load runs; every response is
    validated post-hoc against the exact set of models that was ever
    active — a response matching NO version would be a torn model."""
    from alink_tpu.common.params import Params
    from alink_tpu.operator.common.linear.mapper import LinearModelMapper
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        FtrlTrainStreamOp)
    from alink_tpu.operator.stream.source.sources import MemSourceStreamOp
    from alink_tpu.serving import (CompiledPredictor, LoadGenerator,
                                   ModelStreamFeeder, PredictServer)
    tbl, warm, mapper, data_schema = _serve_fixture(n_rows, dim, seed=7)
    req = tbl.select(["vec"])
    pred = CompiledPredictor(mapper)
    for b in pred.buckets:
        pred.predict_table(req.first_n(min(b, n_rows)))
    srv = PredictServer(pred, name="serve_swap")
    probe = req.row(0)        # one fixed probe row -> exact validation
    src = MemSourceStreamOp(tbl, batch_size=batch_rows)
    ftrl = FtrlTrainStreamOp(warm, vector_col="vec", label_col="label",
                             alpha=0.1, update_mode="batch",
                             time_interval=1.0).link_from(src)
    lg = LoadGenerator(srv.submit, [probe], clients=4, pipeline=8,
                       collect_responses=True)
    t0 = time.perf_counter()
    lg.run(max(100, requests_per_phase // 4))         # warm the loop
    from alink_tpu.common.profiling2 import measured_region
    with measured_region():
        rep_before = lg.run(requests_per_phase)
        feeder = ModelStreamFeeder(srv, ftrl).start()
        rep_during = lg.run(2 * requests_per_phase)
        swaps = feeder.join(timeout=120)
        rep_after = lg.run(requests_per_phase)
    stats = srv.stats()
    srv.close()
    dt = time.perf_counter() - t0
    # torn-response check: HOST mappers per swapped version (bitwise-
    # identical to the compiled dense path) give the legitimate set
    expected = set()
    for _v, mt in [(0, warm.get_output_table())] + feeder.versions:
        m2 = LinearModelMapper(mt.schema, data_schema, mapper.params)
        m2.load_model(mt)
        expected.add(repr(m2.map_row(probe)))
    observed = {repr(r) for phase in (rep_before, rep_during, rep_after)
                for r in phase.responses}
    torn = len(observed - expected)
    failures = (rep_before.failures + rep_during.failures
                + rep_after.failures + stats["failed"])
    return {
        "samples_per_sec_per_chip": round(rep_during.qps, 1),
        "qps_per_chip": round(rep_during.qps, 1),
        "model_swaps": swaps,
        "failed_requests": failures,
        "torn_responses": torn,
        "p99_ms_before": round(rep_before.p99_s * 1e3, 3),
        "p99_ms_during": round(rep_during.p99_s * 1e3, 3),
        "p99_ms_after": round(rep_after.p99_s * 1e3, 3),
        "p50_ms_during": round(rep_during.p50_s * 1e3, 3),
        "bucket_hit_rate": round(stats["bucket_hit_rate"], 4),
        "batch_occupancy": round(stats["mean_occupancy"], 4),
        "bound": "serving-host",
        "dt_s": round(dt, 3),
    }


def _bench_serve_sharded(h: Harness, requests: int, swaps: int,
                         devices=(1, 4, 8)):
    """Multi-chip serving (ISSUE 11): the sharded bucket programs at
    1/4/8-device HOST-PLATFORM meshes. Device counts latch at backend
    init, so each mesh size runs in a fresh child interpreter
    (tools/serve_shard_bench.py, the scaling_evidence mechanism) — on
    the CPU, because a chip belongs to the parent process; the row
    carries ``platform`` so its ``qps_per_chip_*`` figures are never
    read as chip numbers. It reports QPS per virtual device per mesh
    size, measured cross-mesh BITWISE parity (probe digests), and
    swap-storm integrity on the feature-sharded model."""
    import tools.serve_shard_bench as ssb
    return ssb.measure(devices, requests, swaps)


def bench_serve_sharded(h: Harness):
    return _bench_serve_sharded(h, requests=4_000, swaps=12)


def quick_serve_sharded(h: Harness):
    return _bench_serve_sharded(h, requests=1_000, swaps=8)


def _bench_serve_fused(h: Harness, n_rows, dim, passes, reps):
    """The fused serving score kernel (ALINK_TPU_SERVE_FUSED) + the
    opt-in low-precision path (ALINK_TPU_SERVE_DTYPE): whole-table
    scoring rate through CompiledPredictor per (fused, dtype) setting,
    with the parity fields the gate checks — fused f32 BITWISE vs the
    XLA path, bf16/int8 label agreement vs the f32 labels. HONEST RIG
    NOTE: off-TPU the kernel runs in interpret mode (a simulated grid
    — the HBM-round-trip elimination only shows on a physical slice),
    so ``dtype_winner``/``fused_vs_xla`` on this rig measure the
    arithmetic cost, not the memory win."""
    import jax
    from alink_tpu.common.flags import flag_raw
    from alink_tpu.serving import CompiledPredictor
    from alink_tpu.common.profiling2 import measured_region
    tbl, _warm, mapper, _schema = _serve_fixture(n_rows, dim)
    req = tbl.select(["vec"])

    saved = {k: flag_raw(k) for k in
             ("ALINK_TPU_SERVE_FUSED", "ALINK_TPU_SERVE_DTYPE",
              "ALINK_TPU_PALLAS_INTERPRET")}

    def setenv(fused, dtype):
        for k in saved:
            os.environ.pop(k, None)
        if jax.default_backend() != "tpu":
            os.environ["ALINK_TPU_PALLAS_INTERPRET"] = "1"
        if fused:
            os.environ["ALINK_TPU_SERVE_FUSED"] = "1"
        if dtype != "f32":
            os.environ["ALINK_TPU_SERVE_DTYPE"] = dtype

    def restore():
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def measure(pred):
        for b in pred.buckets:
            pred.predict_table(req.first_n(min(b, n_rows)))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            with measured_region():
                for _ in range(passes):
                    pred.predict_table(req)
            ts.append(time.perf_counter() - t0)
        return n_rows * passes / sorted(ts)[len(ts) // 2]

    try:
        preds, rates = {}, {}
        setenv(False, "f32")
        preds["base"] = CompiledPredictor(mapper)
        rates["base"] = measure(preds["base"])
        for name, (fused, dtype) in (("fused", (True, "f32")),
                                     ("bf16", (True, "bf16")),
                                     ("int8", (True, "int8"))):
            setenv(fused, dtype)
            preds[name] = CompiledPredictor(mapper)
            rates[name] = measure(preds[name])
    finally:
        restore()
    sample = req.first_n(min(300, n_rows))
    base_out = preds["base"].predict_table(sample)
    fused_out = preds["fused"].predict_table(sample)
    parity = "bitwise" if all(
        all(str(a) == str(b) for a, b in
            zip(fused_out.col(c), base_out.col(c)))
        for c in base_out.col_names) else "MISMATCH"
    base_labels = [str(v) for v in base_out.col(base_out.col_names[-1])]
    agree = {}
    for name in ("bf16", "int8"):
        out = preds[name].predict_table(sample)
        got = [str(v) for v in out.col(out.col_names[-1])]
        agree[name] = sum(a == b for a, b in zip(got, base_labels)) \
            / max(len(base_labels), 1)
    dtype_winner = max(("fused", "bf16", "int8"), key=lambda k: rates[k])
    return {
        "samples_per_sec_per_chip": round(rates["fused"] / h.chips, 1),
        "xla_rows_per_sec_per_chip": round(rates["base"] / h.chips, 1),
        "fused_vs_xla": round(rates["fused"] / max(rates["base"], 1e-9),
                              3),
        "bf16_rows_per_sec_per_chip": round(rates["bf16"] / h.chips, 1),
        "int8_rows_per_sec_per_chip": round(rates["int8"] / h.chips, 1),
        "dtype_winner": {"fused": "f32"}.get(dtype_winner, dtype_winner),
        "label_agreement_bf16": round(agree["bf16"], 4),
        "label_agreement_int8": round(agree["int8"], 4),
        "parity": parity,
        "bound": "serving-host",
        "rig_note": ("interpret-mode Pallas (no TPU): arithmetic cost "
                     "only — the HBM-round-trip elimination needs a "
                     "physical slice"
                     if jax.default_backend() != "tpu"
                     else "native Mosaic kernels"),
    }


def bench_serve_fused(h: Harness):
    return _bench_serve_fused(h, n_rows=2000, dim=64, passes=4, reps=3)


def quick_serve_fused(h: Harness):
    return _bench_serve_fused(h, n_rows=512, dim=64, passes=2, reps=2)


def bench_serve_logreg(h: Harness):
    return _bench_serve_logreg(h, requests=20_000, serial_requests=2_000)


def bench_serve_hot_swap(h: Harness):
    return _bench_serve_hot_swap(h, requests_per_phase=4_000,
                                 n_rows=6_144, batch_rows=256)


def quick_serve_logreg(h: Harness):
    return _bench_serve_logreg(h, requests=6_000, serial_requests=600)


def quick_serve_hot_swap(h: Harness):
    return _bench_serve_hot_swap(h, requests_per_phase=1_500)


def _bench_serve_fleet(h: Harness, tenants: int, requests: int,
                       baseline_requests: int, swaps: int,
                       n_rows: int = 256, dim: int = 16,
                       sentinels: int = 8, extra: int = None):
    """Multi-tenant fleet serving (ISSUE 17): ``tenants`` same-geometry
    models behind ONE FleetServer, coalescing cross-tenant batches
    through shared lane-stacked programs. Two phases on one server:

    * the MEASURED phase drives all ``tenants`` serving-set models
      (resident under the HBM budget) and reports the p99 RATIO vs a
      single-model PredictServer under the same load shape — the fleet
      claim is that hundreds of tenants serve at single-model latency;
    * the STORM phase adds ``extra`` over-budget tenants plus a
      concurrent swap storm, forcing LRU eviction / snapshot
      re-admission in the dispatch path (reported as ``storm_p99_ms``
      — honest, but not the steady-state headline).

    Leak proof, through BOTH phases: ``sentinels`` tenants keep fixed
    distinct models with per-tenant fixed probe rows validated BITWISE
    against dedicated single-tenant CompiledPredictors — a response
    carrying any other tenant's scores (or torn weights) is a
    ``leaked_row``. Swapped tenants are validated bitwise against
    dedicated predictors for their exact version set."""
    import copy as _copy
    import tempfile
    import threading as _threading

    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.common.linear.mapper import LinearModelMapper
    from alink_tpu.serving import (CompiledPredictor, FleetServer,
                                   LoadGenerator, ModelRegistry,
                                   PredictServer)
    if extra is None:
        extra = max(8, tenants // 4)
    total = tenants + extra
    tbl, warm, mapper, data_schema = _serve_fixture(
        n_rows, dim, seed=21, with_detail=True)
    _t2, warm2, _m2, _s2 = _serve_fixture(n_rows, dim, seed=22,
                                          with_detail=True)
    req = tbl.select(["vec"])
    # same-geometry tenants: deterministically perturbed copies (each
    # serves genuinely different weights — that is what the leak probe
    # discriminates on)
    tenant_mappers = {}
    for i in range(total):
        m = _copy.deepcopy(mapper)
        rng = np.random.RandomState(5000 + i)
        m.model.coef = np.asarray(m.model.coef) \
            + 0.05 * rng.randn(*np.shape(m.model.coef))
        tenant_mappers[f"t{i}"] = m
    per_tenant = sum(
        int(np.asarray(a).nbytes) for a in
        tenant_mappers["t0"].serving_kernel().model_arrays)
    # the budget holds exactly the serving set; the ``extra`` tail is
    # over budget by construction, so the storm phase is guaranteed to
    # evict and re-admit through the snapshot store
    budget = tenants * per_tenant
    snap_dir = tempfile.mkdtemp(prefix="alink-bench-fleet-")
    registry = ModelRegistry(snapshot_dir=snap_dir, hbm_budget=budget,
                             name="serve_fleet")
    t0 = time.perf_counter()
    for tid, m in tenant_mappers.items():
        registry.register(tid, m)
    register_s = time.perf_counter() - t0
    probes = {tid: req.row(i % n_rows)
              for i, tid in enumerate(tenant_mappers)}
    serving_ids = list(tenant_mappers)[:tenants]
    sentinel_ids = [f"t{i}" for i in range(min(sentinels, tenants))]

    # Reference outputs for one probe row under a given model, at EVERY
    # serving bucket: a coalesced batch runs the probe through whichever
    # bucket covers it, and XLA's vectorization can shift the sigmoid by
    # an ULP between program shapes, so "bitwise" is defined per shape.
    # A foreign tenant's weights move the probabilities by ~1e-3 — three
    # orders above an ULP — so matching ANY own-model bucket still
    # rejects every leaked or torn response.
    def _bucket_wants(m2, probe):
        pred = CompiledPredictor(m2, buckets=registry.buckets)
        wants = []
        for b in registry.buckets:
            out = pred.predict_table(MTable([probe] * b, data_schema))
            wants.append(tuple(out.col(c)[0] for c in out.col_names))
        return wants

    sentinel_want = {tid: _bucket_wants(tenant_mappers[tid],
                                        probes[tid])
                     for tid in sentinel_ids}

    # -- the single-model baseline leg (same load shape) ----------------
    base_pred = CompiledPredictor(mapper, buckets=registry.buckets)
    base_srv = PredictServer(base_pred, name="serve_fleet_base")
    base_lg = LoadGenerator(base_srv.submit, [probes["t0"]],
                            clients=4, pipeline=8)
    base_lg.run(max(200, baseline_requests // 2))     # warm the loop
    from alink_tpu.common.profiling2 import measured_region
    with measured_region():
        base_rep = base_lg.run(baseline_requests)
    base_srv.close()

    # -- the fleet legs ------------------------------------------------
    srv = FleetServer(registry, name="serve_fleet")
    fleet_rows = [(tid, probes[tid]) for tid in serving_ids]
    lg = LoadGenerator(lambda tr: srv.submit(tr[0], tr[1]), fleet_rows,
                       clients=4, pipeline=8)
    # storm traffic touches EVERY registered tenant, including the
    # over-budget tail — each tail dispatch re-admits from snapshot
    storm_rows = [(tid, probes[tid]) for tid in tenant_mappers]
    storm_lg = LoadGenerator(lambda tr: srv.submit(tr[0], tr[1]),
                             storm_rows, clients=4, pipeline=8)
    swap_tables = [warm.get_output_table(), warm2.get_output_table()]
    swap_targets = [tid for tid in serving_ids
                    if tid not in sentinel_ids]
    swapped_versions = {}
    swap_errors = []

    def _swapper():
        try:
            for i in range(swaps):
                tid = swap_targets[i % len(swap_targets)]
                mt = swap_tables[i % 2]
                srv.swap_tenant(tid, mt)
                swapped_versions.setdefault(tid, []).append(mt)
        except BaseException as e:              # surfaces in the row
            swap_errors.append(f"{type(e).__name__}: {e}")

    leaked = [0]
    probed = [0]
    # Device references for the two swap tables, per probed tenant.
    # Any swapped tenant only ever serves from {its original model,
    # warm, warm2}, so the candidate set is fixed up front — no race
    # against the swap thread's version bookkeeping — and every
    # candidate is a dedicated single-tenant CompiledPredictor, so the
    # version-set check is BITWISE just like the sentinel check.
    swap_mappers = []
    for mt in swap_tables:
        m2 = LinearModelMapper(mt.schema, data_schema, mapper.params)
        m2.load_model(mt)
        swap_mappers.append(m2)
    _want_cache = {}

    def _version_wants(tid):
        if tid not in _want_cache:
            _want_cache[tid] = [
                w for m2 in [tenant_mappers[tid]] + swap_mappers
                for w in _bucket_wants(m2, probes[tid])]
        return _want_cache[tid]

    # Warm the reference predictors for the tenants the probe loop will
    # sample (the swap schedule is deterministic: first 4 targets), so
    # reference compilation never competes with the measured storm.
    for tid in swap_targets[:4]:
        _version_wants(tid)

    def _match(got, wants):
        return any(all(str(a) == str(b) for a, b in zip(got, w))
                   for w in wants)

    def _validate():
        # sentinels: BITWISE vs the dedicated single-tenant predictors
        for tid in sentinel_ids:
            got = tuple(srv.submit(tid, probes[tid]).result(60))
            probed[0] += 1
            if not _match(got, sentinel_want[tid]):
                leaked[0] += 1
        # a sample of swapped tenants: the answer must belong to the
        # tenant's OWN version set, bitwise
        for tid in list(swapped_versions)[:4]:
            got = tuple(srv.submit(tid, probes[tid]).result(60))
            probed[0] += 1
            if not _match(got, _version_wants(tid)):
                leaked[0] += 1

    rep_box = {}

    def _measured_load():
        with measured_region():
            rep_box["rep"] = lg.run(requests)

    storm_requests = max(total * 4, requests // 4)

    def _storm_load():
        rep_box["storm"] = storm_lg.run(storm_requests)

    # -- phase 1 (measured): steady-state serving set, live probes -----
    # the warm pass rotates the full serving set back in (registration
    # left the over-budget tail resident) and — because the probe loop
    # runs alongside, exactly like the measured pass — compiles every
    # (bucket, lanes) program the measured traffic shape can reach,
    # outside the measured region
    warm_done = [False]

    def _warm_load():
        lg.run(max(200, requests // 4))
        warm_done[0] = True

    warm_th = _threading.Thread(target=_warm_load)
    warm_th.start()
    while not warm_done[0]:
        _validate()
        time.sleep(0.02)
    warm_th.join()
    t1 = time.perf_counter()
    load_th = _threading.Thread(target=_measured_load)
    load_th.start()
    while load_th.is_alive():                  # probe DURING the load
        _validate()
        time.sleep(0.02)                       # sample, don't hammer
    load_th.join()
    measured_dt = time.perf_counter() - t1
    # coalescing stats snapshot BEFORE the coalescing-off comparator
    # leg, which would otherwise dilute the rate
    stats_measured = srv.stats()

    # -- phase 1b: the coalescing-off comparator (same server) ---------
    # per-tenant dispatch is the real alternative at this tenant count;
    # the delta against it is what cross-tenant coalescing buys
    _prev_coal = os.environ.get("ALINK_TPU_FLEET_COALESCE")
    os.environ["ALINK_TPU_FLEET_COALESCE"] = "0"
    try:
        lg.run(max(100, requests // 16))   # warm per-tenant programs
        uncoal_rep = lg.run(max(500, requests // 8))
    finally:
        if _prev_coal is None:
            os.environ.pop("ALINK_TPU_FLEET_COALESCE", None)
        else:
            os.environ["ALINK_TPU_FLEET_COALESCE"] = _prev_coal

    # -- phase 2 (storm): over-budget tail + concurrent swaps ----------
    t2_ = time.perf_counter()
    storm_th = _threading.Thread(target=_storm_load)
    swap_th = _threading.Thread(target=_swapper)
    storm_th.start()
    swap_th.start()
    while storm_th.is_alive():                 # probe DURING the storm
        _validate()
        time.sleep(0.02)
    storm_th.join()
    swap_th.join(120)
    _validate()                                # and after it settles
    storm_dt = time.perf_counter() - t2_
    rep = rep_box["rep"]
    storm_rep = rep_box["storm"]
    stats = srv.stats()
    srv.close()
    rstats = stats["registry"]
    p99_ms = round(rep.p99_s * 1e3, 3)
    p99_single = round(base_rep.p99_s * 1e3, 3)
    row = {
        "tenants": tenants,
        "registered_tenants": total,
        "samples_per_sec_per_chip": round(rep.qps, 1),
        "qps_per_chip": round(rep.qps, 1),
        "p50_ms": round(rep.p50_s * 1e3, 3),
        "p99_ms": p99_ms,
        "p99_ms_single": p99_single,
        "p99_vs_single": round(p99_ms / max(p99_single, 1e-9), 3),
        "uncoalesced_qps_per_chip": round(uncoal_rep.qps, 1),
        "p99_ms_uncoalesced": round(uncoal_rep.p99_s * 1e3, 3),
        "p99_vs_uncoalesced": round(
            p99_ms / max(uncoal_rep.p99_s * 1e3, 1e-9), 3),
        "storm_qps_per_chip": round(storm_rep.qps, 1),
        "storm_p99_ms": round(storm_rep.p99_s * 1e3, 3),
        "coalesce_rate": round(stats_measured["coalesce_rate"], 4),
        "coalesced_batches": stats_measured["coalesced_batches"],
        "uncoalesced_batches": stats_measured["uncoalesced_batches"],
        "lane_rebuilds": stats["lane_rebuilds"],
        "evictions": rstats["evictions"],
        "readmissions": rstats["readmissions"],
        "resident_bytes": rstats["resident_bytes"],
        "hbm_budget": budget,
        "geometry_groups": rstats["geometry_groups"],
        "compiled_programs": rstats["programs"],
        "model_swaps": swaps if not swap_errors else len(
            sum(swapped_versions.values(), [])),
        "leak_probes": probed[0],
        "leaked_rows": leaked[0],
        "parity": "bitwise" if leaked[0] == 0 else "MISMATCH",
        "failed_requests": rep.failures + storm_rep.failures
        + uncoal_rep.failures + base_rep.failures + stats["failed"],
        "register_s": round(register_s, 3),
        "bound": "serving-host",
        "dt_s": round(measured_dt + storm_dt, 3),
    }
    if swap_errors:
        row["swap_errors"] = swap_errors[:3]
    return row


def bench_serve_fleet(h: Harness):
    # requests >> 100x the client*pipeline in-flight ceiling: one stall
    # (a late compile, a GC pause) can delay at most ~32 in-flight
    # requests, which must stay below the 1% bucket for p99 to reflect
    # the steady state rather than a single hiccup
    return _bench_serve_fleet(h, tenants=250, requests=12_000,
                              baseline_requests=2_000, swaps=60)


def quick_serve_fleet(h: Harness):
    return _bench_serve_fleet(h, tenants=100, requests=4_000,
                              baseline_requests=600, swaps=16)


def _bench_serve_chaos(h: Harness, requests_per_phase: int,
                       n_rows: int = 2048, dim: int = 48,
                       batch_rows: int = 128):
    """Serving under a scripted fault storm (ISSUE 14): transient
    ``serve.dispatch`` errors + injected latency + one corrupt FTRL
    snapshot + a concurrent swap storm, driven by the deterministic
    ``ALINK_TPU_FAULT_INJECT`` windows. The row records the SLO
    contract — zero torn responses, zero silent drops (results + typed
    rejections == submissions), measurable breaker recovery to the
    compiled path — plus shed/breaker/retry counts and p99 before/
    during/after. Typed rejections during the storm are BY DESIGN
    (that is what load shedding and closed-state failure accounting
    are); torn or silent is what fails the gate."""
    import time as _time

    from alink_tpu.common.faults import reset_faults
    from alink_tpu.operator.common.linear.mapper import LinearModelMapper
    from alink_tpu.operator.stream.onlinelearning.ftrl import (
        FtrlTrainStreamOp)
    from alink_tpu.operator.stream.source.sources import MemSourceStreamOp
    from alink_tpu.serving import (CompiledPredictor, LoadGenerator,
                                   ModelStreamFeeder, PredictServer)
    tbl, warm, mapper, data_schema = _serve_fixture(n_rows, dim, seed=13)
    req = tbl.select(["vec"])
    pred = CompiledPredictor(mapper, name="serve_chaos")
    for b in pred.buckets:
        pred.predict_table(req.first_n(min(b, n_rows)))
    srv = PredictServer(pred, name="serve_chaos")
    probe = req.row(0)
    saved_fault = os.environ.pop("ALINK_TPU_FAULT_INJECT", None)
    saved_maxms = os.environ.get("ALINK_TPU_SERVE_BREAKER_MAX_MS")
    os.environ["ALINK_TPU_SERVE_BREAKER_MAX_MS"] = "200"
    tally = {"submitted": 0, "results": 0, "typed": 0, "silent": 0}
    responses = []

    def lg(requests):
        gen = LoadGenerator(srv.submit, [probe], clients=4, pipeline=8,
                            collect_responses=True)
        rep = gen.run(requests)
        tally["submitted"] += rep.requests
        tally["results"] += rep.requests - rep.failures
        # timeouts = futures that never resolved: SILENT drops, even
        # inside the load-generator phases (the gated invariant)
        tally["typed"] += rep.failures - rep.timeouts
        tally["silent"] += rep.timeouts
        responses.extend(rep.responses)
        return rep

    def one(deadline_s=None):
        tally["submitted"] += 1
        try:
            responses.append(tuple(
                srv.submit(probe, deadline_s=deadline_s).result(60)))
            tally["results"] += 1
        except TimeoutError:
            tally["silent"] += 1
        except BaseException:
            tally["typed"] += 1

    t0 = time.perf_counter()
    try:
        lg(max(100, requests_per_phase // 4))             # warm the loop
        from alink_tpu.common.profiling2 import measured_region
        with measured_region():
            rep_before = lg(requests_per_phase)
            # -- the storm: error window + one corrupt snapshot + swaps
            reset_faults()
            os.environ["ALINK_TPU_FAULT_INJECT"] = \
                "serve.dispatch:1-14:error;feeder.snapshot:1-1:corrupt"
            src = MemSourceStreamOp(tbl, batch_size=batch_rows)
            ftrl = FtrlTrainStreamOp(warm, vector_col="vec",
                                     label_col="label", alpha=0.1,
                                     update_mode="batch",
                                     time_interval=1.0).link_from(src)
            feeder = ModelStreamFeeder(srv, ftrl).start()
            rep_storm = lg(requests_per_phase)
            # latency + deadline leg (same counter timeline — the
            # corrupt window stays exactly-once)
            wait_until = _time.monotonic() + 20
            while srv.breaker_stats()["state"] != "closed" \
                    and _time.monotonic() < wait_until:
                one()
                _time.sleep(0.05)
            os.environ["ALINK_TPU_FAULT_INJECT"] = \
                "serve.dispatch:1:delay:30;feeder.snapshot:1-1:corrupt"
            f_first = srv.submit(probe)
            tally["submitted"] += 1
            _time.sleep(0.01)
            shed_futs = [srv.submit(probe, deadline_s=0.004)
                         for _ in range(6)]
            tally["submitted"] += 6
            for f in [f_first] + shed_futs:
                try:
                    responses.append(tuple(f.result(60)))
                    tally["results"] += 1
                except TimeoutError:
                    tally["silent"] += 1
                except BaseException:
                    tally["typed"] += 1
            swaps = feeder.join(timeout=180)
            # -- the storm clears: recovery phase
            del os.environ["ALINK_TPU_FAULT_INJECT"]
            reset_faults()
            _time.sleep(0.25)
            batches_pre = srv.stats()["batches"]
            fallback_pre = srv.stats()["fallback_batches"]
            rep_after = lg(requests_per_phase)
        stats = srv.stats()
        compiled_after = (stats["batches"] - batches_pre) \
            - (stats["fallback_batches"] - fallback_pre)
    finally:
        srv.close()
        os.environ.pop("ALINK_TPU_FAULT_INJECT", None)
        if saved_fault is not None:
            os.environ["ALINK_TPU_FAULT_INJECT"] = saved_fault
        if saved_maxms is None:
            os.environ.pop("ALINK_TPU_SERVE_BREAKER_MAX_MS", None)
        else:
            os.environ["ALINK_TPU_SERVE_BREAKER_MAX_MS"] = saved_maxms
        reset_faults()
    dt = time.perf_counter() - t0
    # torn check: every response must match a model version that was
    # actually active (warm start or a completed swap)
    expected = set()
    for _v, mt in [(0, warm.get_output_table())] + feeder.versions:
        m2 = LinearModelMapper(mt.schema, data_schema, mapper.params)
        m2.load_model(mt)
        expected.add(repr(tuple(m2.map_row(probe))))
    torn = len({repr(tuple(r)) for r in responses} - expected)
    brk = stats["breaker"]
    recovered = (brk["state"] == "closed" and compiled_after > 0
                 and stats["breaker"]["opens"] >= 1)
    return {
        "samples_per_sec_per_chip": round(rep_storm.qps, 1),
        "qps_per_chip": round(rep_storm.qps, 1),
        "qps_before": round(rep_before.qps, 1),
        "qps_after": round(rep_after.qps, 1),
        "p99_ms_before": round(rep_before.p99_s * 1e3, 3),
        "p99_ms_during": round(rep_storm.p99_s * 1e3, 3),
        "p99_ms_after": round(rep_after.p99_s * 1e3, 3),
        "p50_ms_during": round(rep_storm.p50_s * 1e3, 3),
        "requests_total": tally["submitted"],
        "typed_rejections": tally["typed"],
        "silent_drops": tally["silent"],
        "torn_responses": torn,
        "shed_requests": int(stats["shed"]),
        "breaker_opens": int(brk["opens"]),
        "breaker_reopens": int(brk["reopens"]),
        "breaker_probes": int(brk["probes"]),
        "fallback_batches": int(stats["fallback_batches"]),
        "loop_respawns": int(stats["loop_respawns"]),
        "feeder_retries": int(feeder.retried),
        "feeder_skipped": int(feeder.skipped),
        "model_swaps": int(swaps),
        "post_storm_compiled_batches": int(compiled_after),
        "recovered_compiled": bool(recovered),
        "bound": "serving-host",
        "dt_s": round(dt, 3),
    }


def bench_serve_chaos(h: Harness):
    return _bench_serve_chaos(h, requests_per_phase=3_000, n_rows=4096)


def quick_serve_chaos(h: Harness):
    return _bench_serve_chaos(h, requests_per_phase=800)


def _bench_serve_online_e2e(h: Harness, n_rows: int, dim: int,
                            storm_rows: int, batch_rows: int = 128):
    """The whole online-learning loop as ONE supervised program
    (ISSUE 15; ROADMAP item 5): stream ingest -> FTRL training with
    checkpoints -> model-snapshot stream -> hot-swap serving (breaker +
    deadlines armed) -> windowed stream eval, run by
    ``alink_tpu.online.OnlineDag`` with per-stage restart policies and
    an end-to-end SloContract. Four phases:

    1. steady state (``pacing="throughput"``): scoring QPS, p99, swap
       staleness, per-window + final-window AUC, SLO verdicts — the
       armed contract (generous latency bounds + the 0.75 AUC anchor)
       must hold on a clean run;
    2. a deterministic-pacing golden run on a shorter stream — the
       bitwise reference for the storms;
    3. trainer-side storm (ftrl.batch kill + ckpt.save fault +
       ingest.batch kill + prefetch.get delay): every restart is typed
       with a MEASURED recovery time and the run's eval journals are
       bitwise the golden run's (no drop, no double-apply);
    4. serve-side storm (serve.dispatch error window + one corrupt
       model snapshot): the breaker opens, degrades to the host
       fallback, and measurably recovers to the compiled path — the
       final scored batch is bitwise the golden run's — while the
       poisoned snapshot is skipped with the last good model serving.

    Zero silent drops is gated across ALL phases (every scoring future
    resolves to a result or a typed rejection)."""
    import tempfile

    from alink_tpu.common.faults import FAULT_ENV, scoped_fault_env
    from alink_tpu.online import OnlineDag, SloContract
    from alink_tpu.operator.stream.source.sources import MemSourceStreamOp
    tbl, warm, _mapper, _schema = _serve_fixture(n_rows, dim, seed=17)
    storm_tbl = tbl.first_n(storm_rows)

    def mkdag(source_tbl, art, interval, **kw):
        return OnlineDag(
            source_fn=lambda: MemSourceStreamOp(source_tbl,
                                                batch_size=batch_rows),
            warm_model=warm, artifacts_dir=art, label_col="label",
            vector_col="vec", time_interval=interval,
            checkpoint_every=2, name="serve_online_e2e", **kw)

    def eval_files(art):
        return (open(os.path.join(art, "eval", "windows.jsonl")).read(),
                open(os.path.join(art, "eval", "scores.jsonl")).read())

    saved_maxms = os.environ.get("ALINK_TPU_SERVE_BREAKER_MAX_MS")
    os.environ["ALINK_TPU_SERVE_BREAKER_MAX_MS"] = "200"
    t0 = time.perf_counter()
    try:
        # -- phase 1: steady state under the armed SLO contract ----------
        slo = SloContract(serve_p99_s=2.0, swap_staleness_s=30.0,
                          final_window_auc=0.75, name="serve_online_e2e")
        with scoped_fault_env(None):
            steady = mkdag(tbl, tempfile.mkdtemp(prefix="e2e_steady_"),
                           interval=3.0, pacing="throughput",
                           slo=slo).run()
        if steady.failed is not None:
            return {"error": f"steady-state phase failed: {steady.failed}"}

        # -- phase 2: the deterministic golden reference -----------------
        with scoped_fault_env(None):
            g_art = tempfile.mkdtemp(prefix="e2e_gold_")
            golden = mkdag(storm_tbl, g_art, interval=2.0).run()
        if golden.failed is not None:
            return {"error": f"golden phase failed: {golden.failed}"}
        gold_files = eval_files(g_art)

        # -- phase 3: trainer-side storm, bitwise + measured recovery ----
        def clear_trainer_kill(stage, exc):
            # the kill is keyed on the batch NUMBER, which the
            # checkpoint replay revisits — the supervisor's crash
            # callback clears that one entry so the restart survives
            if getattr(exc, "site", None) == "ftrl.batch":
                os.environ[FAULT_ENV] = ";".join(
                    e for e in os.environ.get(FAULT_ENV, "").split(";")
                    if e and not e.startswith("ftrl.batch"))

        with scoped_fault_env("ftrl.batch:4-4;ckpt.save:2-2:error;"
                              "ingest.batch:3-3;prefetch.get:1-60:delay:1"):
            s3_art = tempfile.mkdtemp(prefix="e2e_storm_train_")
            r3 = mkdag(storm_tbl, s3_art, interval=2.0,
                       on_stage_event=clear_trainer_kill).run()
        if r3.failed is not None:
            return {"error": f"trainer-storm phase failed: {r3.failed}"}
        storm_bitwise = eval_files(s3_art) == gold_files
        recovery = {}
        for rec in r3.restarts:
            site = rec.get("site") or rec.get("error")
            if rec.get("recovery_s") is not None:
                recovery[site] = rec["recovery_s"]
        train_recs = [r for r in r3.restarts if r["stage"] == "train"]

        # -- phase 4: serve-side storm, breaker recovery + last-good -----
        with scoped_fault_env("serve.dispatch:1-8:error;"
                              "feeder.snapshot:1-1:corrupt"):
            s4_art = tempfile.mkdtemp(prefix="e2e_storm_serve_")
            r4 = mkdag(storm_tbl, s4_art, interval=2.0).run()
        if r4.failed is not None:
            return {"error": f"serve-storm phase failed: {r4.failed}"}
        brk = (r4.server_stats.get("breaker") or {})
        tail_bitwise = (eval_files(s4_art)[1].splitlines()[-1]
                        == gold_files[1].splitlines()[-1])
        recovered = bool(brk.get("opens") and brk.get("state") == "closed"
                         and tail_bitwise)
    finally:
        if saved_maxms is None:
            os.environ.pop("ALINK_TPU_SERVE_BREAKER_MAX_MS", None)
        else:
            os.environ["ALINK_TPU_SERVE_BREAKER_MAX_MS"] = saved_maxms
    dt = time.perf_counter() - t0
    silent = (steady.silent_drops + golden.silent_drops
              + r3.silent_drops + r4.silent_drops)
    return {
        "samples_per_sec_per_chip": round(steady.qps, 1),
        "qps": round(steady.qps, 1),
        "p99_ms": (round(steady.p99_s * 1e3, 3)
                   if steady.p99_s is not None else None),
        "swap_staleness_max_ms": (
            round(steady.swap_staleness_max_s * 1e3, 3)
            if steady.swap_staleness_max_s is not None else None),
        "swap_staleness_mean_ms": (
            round(steady.swap_staleness_mean_s * 1e3, 3)
            if steady.swap_staleness_mean_s is not None else None),
        "model_swaps": int(steady.swaps),
        "windows": len(steady.windows),
        "window_auc": [round(w["auc"], 4) for w in steady.windows
                       if w["auc"] is not None],
        "final_window_auc": (round(steady.final_window_auc, 4)
                             if steady.final_window_auc is not None
                             else None),
        "auc_note": steady.auc_note,
        "slo_ok": steady.slo_ok(),
        "slo": [v.to_dict() for v in steady.slo],
        "slo_breaches": len(steady.breaches),
        "scored_rows": int(steady.scored_rows),
        "shed_requests": int(steady.shed_requests),
        "silent_drops": int(silent),
        "typed_rejections": int(r4.typed_rejections),
        "storm_restarts": len(r3.restarts),
        "storm_bitwise_journals": bool(storm_bitwise),
        "recovery_s_by_fault": recovery,
        "recovery_train_restart_s": (train_recs[0].get("recovery_s")
                                     if train_recs else None),
        "recovery_ingest_s": recovery.get("ingest.batch"),
        "breaker_opens": int(brk.get("opens") or 0),
        "fallback_batches": int(
            r4.server_stats.get("fallback_batches") or 0),
        "feeder_skipped": int(r4.feeder_skipped),
        "recovered_compiled": bool(recovered),
        "bound": "serving-host",
        "dt_s": round(dt, 3),
    }


def bench_serve_online_e2e(h: Harness):
    return _bench_serve_online_e2e(h, n_rows=4096, dim=32,
                                   storm_rows=2048)


def quick_serve_online_e2e(h: Harness):
    # the storm stream needs a post-storm tail long enough for the
    # breaker's half-open probe to re-close and re-serve compiled
    # (12 batches; measured — a 6-batch stream ends still degraded)
    return _bench_serve_online_e2e(h, n_rows=1536, dim=24,
                                   storm_rows=1536)


def _tuning_sweep_row(h: Harness, n_rows, d, iters, P, rung, eta, reps):
    """Mesh-parallel tuning sweep (ROADMAP item 3): N hyperparameter
    points as ONE BSP program with ASHA early stopping, measured against
    the reference-shaped serial candidate loop (N full ``optimize()``
    execs — each its own compiled program, prepare, dispatch and fetch).
    The l2-ladder fixture keeps the loss ranking rung-stable, so 'equal
    best-point quality' is CHECKED, not assumed: the ASHA winner must be
    the serial grid's argmin AND its model bitwise-equal to that point's
    serial fit. The serial leg times cache-hit execs only (the N
    per-candidate compiles the sweep also eliminates stay OUTSIDE the
    timing — the speedup is conservative). Legs interleave per rep so
    rig load drift charges both sides."""
    from alink_tpu.operator.common.optim.objfunc import (LogLossFunc,
                                                         UnaryLossObjFunc)
    from alink_tpu.operator.common.optim.optimizers import (OptimParams,
                                                            optimize)
    from alink_tpu.tuning import AshaConfig, sweep_optimize
    from alink_tpu.common.profiling2 import measured_region
    rng = np.random.RandomState(0)
    X = rng.randn(n_rows, d)
    y = np.sign(X @ rng.randn(d) + 0.3 * rng.randn(n_rows))
    data = {"X": X, "y": y, "w": np.ones(n_rows)}
    obj = UnaryLossObjFunc(LogLossFunc(), d)
    base = OptimParams(method="LBFGS", max_iter=iters, epsilon=0.0)
    l2s = [0.0] + [float(3e-4 * (1.45 ** i)) for i in range(P - 1)]
    pts = [{"l2": l2} for l2 in l2s]
    asha = AshaConfig(rung=rung, eta=eta)

    def serial():
        outs = []
        for pt in pts:
            o = UnaryLossObjFunc(LogLossFunc(), d, l2=pt["l2"])
            coef, curve, _ = optimize(o, data, OptimParams(
                method="LBFGS", max_iter=iters, epsilon=0.0), h.env)
            outs.append((np.asarray(coef), np.asarray(curve)))
        return outs

    def sweep():
        return sweep_optimize(obj, data, base, pts, env=h.env, asha=asha)

    s_out = serial()        # warmup: compiles (one per candidate!) stay
    res = sweep()           # outside the timed legs, both sides
    res_full = sweep_optimize(obj, data, base, pts, env=h.env)  # no ASHA
    ts_serial, ts_sweep = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        with measured_region():
            serial()
        ts_serial.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        with measured_region():
            res = sweep()
        ts_sweep.append(time.perf_counter() - t0)
    t_serial = sorted(ts_serial)[len(ts_serial) // 2]
    t_sweep = sorted(ts_sweep)[len(ts_sweep) // 2]
    t0 = time.perf_counter()
    res_full = sweep_optimize(obj, data, base, pts, env=h.env)
    t_full = time.perf_counter() - t0
    finals = [c[-1] for _, c in s_out]
    serial_best = int(np.argmin(finals))
    parity_all = all(
        np.array_equal(s_out[i][0], res_full.values["coef"][i])
        for i in range(P))
    parity_winner = np.array_equal(s_out[res.best][0],
                                   res.values["coef"][res.best])
    return {
        # the shared rate column: candidate points tuned per second
        # through the ASHA sweep (bench_history labels it points/s)
        "samples_per_sec_per_chip": round(P / t_sweep / h.chips, 2),
        "points": P, "iters": iters, "dt_s": round(t_sweep, 3),
        "serial_s": round(t_serial, 3),
        "speedup_vs_serial": round(t_serial / t_sweep, 2),
        "sweep_full_speedup": round(t_serial / max(t_full, 1e-9), 2),
        "rungs": len(res.rungs), "rung_every": rung, "eta": eta,
        "pruned_fraction": round(1.0 - float(res.alive.sum()) / P, 3),
        "winner_match": bool(res.best == serial_best),
        # bitwise contract: EVERY point of the full (no-ASHA) sweep
        # equals its serial fit; the ASHA winner equals its serial fit
        "parity": "bitwise" if (parity_all and parity_winner)
                  else "MISMATCH",
        "compiled_programs": int(res.programs),
    }


def bench_tuning_sweep(h: Harness):
    return _tuning_sweep_row(h, 4000, 32, 100, 24, rung=5, eta=5, reps=3)


def quick_tuning_sweep(h: Harness):
    return _tuning_sweep_row(h, 4000, 32, 100, 24, rung=5, eta=5, reps=2)


def quick_cold_start(h: Harness):
    """Restart-to-first-response, cold vs AOT-warmed (ISSUE 20).

    Two fresh CPU-mesh child interpreters (the coldstart_smoke fixture)
    share one artifact directory: the first pays the full trace+XLA
    compile on its first request and exports every program; the second
    restarts against the warmed store and deserializes instead.  The
    row reports both first-response walls, the restart speedup, and the
    ledger's per-subsystem time-to-first-program — the measured
    evidence for the 'kill the cold start' claim.  A chip belongs to
    one process — the parent harness — so the children are forced onto
    a CPU mesh and the row carries ``platform`` to say so: its seconds
    are host-platform seconds, not the chip's."""
    import subprocess
    import sys
    import tempfile

    import bootenv

    root = os.path.dirname(os.path.abspath(__file__))
    script = os.path.join(root, "tools", "coldstart_smoke.py")
    cache_dir = tempfile.mkdtemp(prefix="alink-bench-aot-")
    run_dir = tempfile.mkdtemp(prefix="alink-bench-aot-run-")
    res = {}
    for role in ("cold", "warm"):
        env = bootenv.warm_restart_cache_env(bootenv.cpu_mesh_env(4),
                                             cache_dir)
        env["ALINK_COLDSTART_SMOKE_CHILD"] = "1"
        env["ALINK_TPU_AOT_CACHE_DIR"] = cache_dir
        env.pop("ALINK_TPU_AOT_CACHE", None)
        env["ALINK_COLDSTART_SMOKE_DIR"] = run_dir
        env["ALINK_COLDSTART_SMOKE_OUT"] = os.path.join(
            run_dir, f"{role}.json")
        subprocess.run([sys.executable, script], cwd=root, env=env,
                       check=True, timeout=900)
        with open(env["ALINK_COLDSTART_SMOKE_OUT"]) as fh:
            res[role] = json.load(fh)
    cold, warm = res["cold"], res["warm"]
    return {
        # the children's platform (forced to the host platform above),
        # not the parent harness's
        "platform": "/".join(sorted({cold["platform"], warm["platform"]})),
        "cold_first_response_s": round(cold["first_response_s"], 4),
        "warm_first_response_s": round(warm["first_response_s"], 4),
        "restart_speedup": round(cold["first_response_s"]
                                 / max(warm["first_response_s"], 1e-9),
                                 2),
        "cold_startup_to_response_s": round(
            cold["startup_to_response_s"], 3),
        "warm_startup_to_response_s": round(
            warm["startup_to_response_s"], 3),
        "warm_serve_misses": warm["serve_misses"],
        "warm_disk_hits": warm["serve_disk_hits"],
        "warm_admission_warmed": warm["warmed_programs"],
        "ttfp_cold_s": {k: round(float(v), 3)
                        for k, v in sorted(cold["ttfp"].items())},
        "ttfp_warm_s": {k: round(float(v), 3)
                        for k, v in sorted(warm["ttfp"].items())},
        "parity": ("bitwise" if warm["digest"] == cold["digest"]
                   else "MISMATCH"),
        "bound": "compile-plane",
    }


QUICK_WORKLOADS = (("logreg_criteo", quick_logreg),
                   ("logreg_ckpt", quick_logreg_ckpt),
                   ("kmeans_iris", quick_kmeans),
                   ("ftrl_criteo", quick_ftrl),
                   ("ftrl_stream_drain", quick_ftrl_drain),
                   ("gbdt_hist_fused", quick_gbdt_hist),
                   ("logreg_from_disk", quick_from_disk),
                   ("tuning_sweep", quick_tuning_sweep),
                   ("serve_logreg", quick_serve_logreg),
                   ("serve_fused", quick_serve_fused),
                   ("serve_ftrl_hot_swap", quick_serve_hot_swap),
                   ("serve_logreg_sharded", quick_serve_sharded),
                   ("serve_chaos", quick_serve_chaos),
                   ("serve_fleet", quick_serve_fleet),
                   ("serve_online_e2e", quick_serve_online_e2e),
                   ("cold_start", quick_cold_start))


# ---------------------------------------------------------------------------

def _annotate_profile(row, name):
    """Attach the measured-profiling attribution to one workload row
    (``ALINK_TPU_PROFILE``): dispatch/transfer/device/collective seconds
    + fractions under ``profile``, and the MEASURED ``bound:``
    classification — the static projection is preserved as
    ``bound_static`` (rows without a static label gain only the
    measured one). No-op without the flag or when nothing measured was
    recorded for the workload."""
    from alink_tpu.common.profiling2 import (get_profiler, measured_bound,
                                             profile_enabled)
    if not profile_enabled() or not isinstance(row, dict) or "error" in row:
        return row
    attr = get_profiler().workload_attribution(name)
    if attr is None:
        return row
    # the compute-vs-hbm refinement normalizes the row's headline rate
    # by the DEVICE share — only honest when that device time came from
    # one program leg (multi-leg rows like full ftrl merge kernels +
    # drain; their split would be cross-leg, so keep the aggregate
    # dominant-bucket label instead)
    one_leg = len(attr.get("device_scopes") or ()) <= 1
    peak_tflops, peak_hbm_gbps = chip_peaks()
    bound, fracs = measured_bound(
        attr,
        flops_per_sample=row.get("flops_per_sample") if one_leg else None,
        bytes_per_sample=row.get("hbm_bytes_per_sample"),
        samples_per_sec_per_chip=row.get("samples_per_sec_per_chip"),
        peak_tflops=peak_tflops, peak_hbm_gbps=peak_hbm_gbps)
    prof = dict(attr)
    prof["fractions"] = {k: round(v, 4) for k, v in fracs.items()}
    prof["bound_measured"] = bound
    if "bound" in row:
        row["bound_static"] = row["bound"]
    row["bound"] = bound
    row["profile"] = prof
    return row


def _resolve_run_dir(path):
    """The ``--run-dir`` contract: a fresh path is used as-is (callers
    pick the name, e.g. mktemp); an existing non-empty directory gets a
    timestamped subdirectory so repeated captures never clobber each
    other's artifacts."""
    path = os.path.abspath(path)
    if os.path.exists(path) and not os.path.isdir(path):
        raise SystemExit(f"bench.py: --run-dir {path}: exists and is "
                         f"not a directory")
    if os.path.isdir(path) and os.listdir(path):
        path = os.path.join(
            path, time.strftime("run-%Y%m%d-%H%M%SZ", time.gmtime()))
    os.makedirs(path, exist_ok=True)
    return path


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description="alink_tpu benchmark suite")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the runtime MetricsRegistry (JSONL) to PATH "
                         "after the suite and attach its snapshot to "
                         "BENCH_full.json (default: off — existing BENCH "
                         "json schemas are unchanged without the flag; "
                         "render with tools/run_report.py)")
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: tiny fixtures, <60 s — same workload "
                         "names/JSON shape so the dump feeds "
                         "tools/bench_compare.py --threshold as a perf "
                         "regression gate (not publishable numbers)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the final combined JSON line to PATH too "
                         "(--quick default: BENCH_quick.json; pass "
                         "distinct paths for the before/after gate pair)")
    ap.add_argument("--run-dir", default=None, metavar="DIR",
                    help="write every capture artifact (bench.json, "
                         "metrics.jsonl, profile.json, trace.jsonl, xprof "
                         "captures) under one directory instead of "
                         "scattering top-level files; an existing "
                         "non-empty DIR gets a timestamped subdirectory. "
                         "tools/run_report.py and tools/doctor.py accept "
                         "the directory directly")
    args = ap.parse_args(argv)
    from alink_tpu.common.flags import flag_raw
    from alink_tpu.common.profiling2 import (donation_probe, get_profiler,
                                             profile_enabled, workload)
    run_dir = _resolve_run_dir(args.run_dir) if args.run_dir else None
    if run_dir and profile_enabled() and not flag_raw("ALINK_TPU_PROFILE_DIR"):
        # xprof captures (if armed) land with the other run artifacts
        os.environ["ALINK_TPU_PROFILE_DIR"] = run_dir
    h = Harness()
    if profile_enabled():
        # measured donation verification, once per capture: the doctor's
        # HBM section renders it (the PR-5 claim, measured not asserted)
        donation_probe()
    workloads = {}
    suite = QUICK_WORKLOADS if args.quick else (
                     ("logreg_criteo", bench_logreg),
                     ("kmeans_iris", bench_kmeans),
                     ("softmax_mnist", bench_softmax),
                     ("ftrl_criteo", bench_ftrl),
                     ("logreg_from_disk", bench_logreg_from_disk),
                     ("gbdt_adult", bench_gbdt),
                     ("gbdt_adult_large", bench_gbdt_large),
                     ("als_movielens", bench_als),
                     ("als_movielens_large", bench_als_large),
                     ("tuning_sweep", bench_tuning_sweep),
                     ("serve_logreg", bench_serve_logreg),
                     ("serve_fused", bench_serve_fused),
                     ("serve_ftrl_hot_swap", bench_serve_hot_swap),
                     ("serve_logreg_sharded", bench_serve_sharded),
                     ("serve_chaos", bench_serve_chaos),
                     ("serve_fleet", bench_serve_fleet),
                     ("serve_online_e2e", bench_serve_online_e2e))
    for name, fn in suite:
        try:
            with workload(name):
                r = fn(h)
        except Exception as e:
            # a failed workload is reported as an error row so the
            # remaining cells still run — and the process exits non-zero
            # below: a capture with a failed cell is not a capture
            traceback.print_exc()
            if profile_enabled():
                get_profiler().discard_workload(name)
            r = {"error": f"{type(e).__name__}: {e}"}
        workloads[name] = _annotate_profile(r, name)
        print(json.dumps({"workload": name, **r}), flush=True)
    failed = sorted(n for n, r in workloads.items() if "error" in r)

    # runtime-emitted telemetry: the registry was filled by the engine /
    # collective / stream instrumentation DURING the workloads above; with
    # --metrics-out the JSONL dump is written for tools/run_report.py and
    # the snapshot rides inside BENCH_full.json (opt-in, so the recorded
    # BENCH_r*.json schema is unchanged when the flag is absent)
    mode = "quick" if args.quick else "full"
    full_doc = {"workloads": workloads, "mode": mode,
                # the rig's serial per-dispatch floor, measured once per
                # capture so latency-bound rows can be read against it —
                # plus the chip roofs, so tools/doctor.py can compute
                # measured achieved-vs-roof without re-importing bench
                "rig": {"dispatch_gap_est_s": round(h.dispatch_gap(), 6),
                        "baseline_fp": baseline_provenance_fp(),
                        "device": device_stamp(),
                        "profile": profile_enabled()}}
    peak_tflops, peak_hbm_gbps = chip_peaks()
    if peak_tflops is not None:
        full_doc["rig"]["peak_tflops"] = peak_tflops
        full_doc["rig"]["peak_hbm_gbps"] = peak_hbm_gbps
    if args.metrics_out:
        from alink_tpu.common.metrics import get_registry
        try:
            p = get_registry().dump(args.metrics_out)
            full_doc["metrics_report"] = os.path.abspath(p)
            # embed the DUMPED records (not a second snapshot), so the
            # file and the BENCH_full.json copy can never disagree
            with open(p) as f:
                full_doc["metrics"] = [
                    rec for rec in map(json.loads, f)
                    if rec.get("kind") != "meta"]
        except OSError as e:
            full_doc["metrics_error"] = str(e)

    # full per-workload detail goes to a file (and was printed per-row
    # above); the FINAL stdout line must stay well under the driver's
    # 2000-byte tail buffer or it arrives head-truncated and unparseable
    # (BENCH_r03.json: parsed=null). Keep it to the flagship metric plus
    # a compact per-workload (sps, vs_baseline) map. Quick mode never
    # touches BENCH_full.json (a smoke capture must not shadow the last
    # full capture's detail) — its artifact is --out below.
    if not args.quick:
        try:
            with open(os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "BENCH_full.json"), "w") as f:
                json.dump(full_doc, f)
        except OSError:
            pass  # best-effort: per-row lines carry the full detail
    flag = workloads["logreg_criteo"]
    # error rows are omitted (not encoded as zeros) so the README
    # generator renders them as "(failed)" rather than a measured 0;
    # the share of peak is null wherever mfu() wrote none (not a v5e)
    compact = {name: [r["samples_per_sec_per_chip"],
                      r.get("vs_baseline", 0.0),
                      r.get("pct_chip_peak_flops")]
               for name, r in workloads.items()
               if "samples_per_sec_per_chip" in r}
    ftrl = workloads.get("ftrl_criteo", {})
    if "strict_samples_per_sec_per_chip" in ftrl:
        # ftrl_criteo itself is the bounded-staleness headline; the strict
        # per-sample row (gold semantics) rides alongside
        compact["ftrl_criteo_strict"] = [
            ftrl["strict_samples_per_sec_per_chip"],
            ftrl.get("strict_vs_baseline", 0.0), None]
    if "batch_mode_samples_per_sec_per_chip" in ftrl:
        compact["ftrl_criteo_batch"] = [
            ftrl["batch_mode_samples_per_sec_per_chip"],
            ftrl.get("batch_mode_vs_baseline", 0.0),
            ftrl.get("batch_mode_pct_chip_peak_flops")]
    cs = workloads.get("cold_start", {})
    if cs.get("warm_first_response_s"):
        # warm restart-to-first-response as a RATE (1/s) so
        # bench_compare --threshold gates a persistent-cache regression
        # (slower warm restart) exactly like a throughput drop
        compact["cold_start_warm1stinv"] = [
            round(1.0 / cs["warm_first_response_s"], 3), 0.0, None]
    serve = workloads.get("serve_logreg", {})
    if serve.get("p99_ms"):
        # p99 as a RATE (1/p99) so bench_compare --threshold gates p99
        # regressions exactly like throughput regressions (a p99
        # increase reads as a rate drop)
        compact["serve_logreg_p99inv"] = [
            round(1e3 / serve["p99_ms"], 3), 0.0, None]
    head = {
        "metric": "logreg_criteo_lbfgs_samples_per_sec_per_chip",
        # null, not 0.0, when the flagship cell failed (exit code 1)
        "value": flag.get("samples_per_sec_per_chip"),
        "unit": "samples/sec/chip",
        "vs_baseline": flag.get("vs_baseline"),
        # rig + pinned-record identity: rides every dump so
        # bench_compare --baseline-provenance can refuse cross-rig AND
        # same-rig-re-pinned comparisons (a re-measured baseline can
        # then never silently inflate vs_baseline round-over-round)
        "baseline_fp": baseline_provenance_fp(),
    }
    if args.quick:
        # quick dumps must be distinguishable: bench_compare warns when
        # a quick and a full capture are diffed against each other
        head["mode"] = "quick"
    line = json.dumps({**head, "workloads_sps_vs": compact})
    if len(line) >= 1900:
        # never let the final line overflow the driver's tail buffer —
        # degrade by dropping the per-workload map, keeping the parseable
        # flagship metric (full detail is in BENCH_full.json anyway)
        line = json.dumps(head)
    print(line)
    out_path = args.out or ("BENCH_quick.json" if args.quick else None)
    bench_doc = {**head, "workloads_sps_vs": compact,
                 "workloads": workloads, "rig": full_doc["rig"]}
    if not args.quick:
        bench_doc["mode"] = "full"
    if out_path:
        # the gate artifact: the combined final-line object (the shape
        # tools/bench_compare.py reads) plus the per-workload detail
        with open(out_path, "w") as f:
            json.dump(bench_doc, f)
    if run_dir:
        # artifact hygiene (--run-dir): every capture product under one
        # directory — bench json, metrics dump, measured profile, host
        # trace (when armed) — the shape run_report.py/doctor.py accept
        with open(os.path.join(run_dir, "bench.json"), "w") as f:
            json.dump(bench_doc, f)
        try:
            from alink_tpu.common.metrics import get_registry
            get_registry().dump(os.path.join(run_dir, "metrics.jsonl"))
        except OSError as e:  # pragma: no cover - disk trouble
            print(f"WARNING: could not write metrics.jsonl: {e}",
                  file=sys.stderr)
        if profile_enabled():
            get_profiler().export(os.path.join(run_dir, "profile.json"))
        from alink_tpu.common.tracing import get_tracer, tracing_enabled
        if tracing_enabled():
            get_tracer().export_jsonl(os.path.join(run_dir, "trace.jsonl"))
        print(f"run artifacts: {run_dir}", file=sys.stderr)
    if failed:
        print(f"bench: {len(failed)} workload(s) failed: "
              f"{', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
