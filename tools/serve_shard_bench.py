"""Multi-chip serving bench + gate smoke (ISSUE 11).

Measures the sharded serving tier at REAL 1/4/8-device host-platform
meshes. XLA device counts latch at backend init, so each mesh size runs
in a FRESH interpreter (``bootenv.cpu_mesh_env`` — the
``tools/scaling_evidence.py`` mechanism). Every child builds the SAME
deterministic feature-sharded linear model (synthetic weights, no
training — trainers would converge differently per mesh), serves a
closed-loop load through ``PredictServer`` over sharded bucket
programs, hot-swaps a deterministic model sequence under load, and
reports:

* ``qps`` / ``qps_per_chip`` — closed-loop load-generator throughput;
* ``digest`` — sha256 over the rendered predictions of a fixed probe
  table: equal digests across children == measured BITWISE parity of
  the sharded bucket programs at mesh 1 vs 4 vs 8;
* ``torn`` / ``failed`` — swap-storm integrity (every response must
  match one model version that was ever active).

Modes:
  ``--child``     (internal) one mesh size, prints one JSON line;
  ``--json``      parent: spawn children for ``--devices`` (default
                  1,4,8), print the combined serve_logreg_sharded row;
  ``--smoke``     the perf_gate leg: mesh 1 vs 4, parity + zero torn
                  swaps; exits 5 (a DISTINCT gate code) on failure.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)   # children run as a script from tools/
DIM = 96
SEED = 2026


def _build_model_table(seed: int, dim: int = DIM):
    """A deterministic binary LR model table (intercept + dim weights):
    the serving fixture must be IDENTICAL across mesh sizes, so it is
    synthesized, never trained."""
    import numpy as np

    from alink_tpu.common.types import AlinkTypes
    from alink_tpu.operator.common.linear.base import (
        LinearModelData, LinearModelDataConverter, LinearModelType)
    rng = np.random.RandomState(seed)
    coef = rng.randn(dim + 1)
    m = LinearModelData("serve_sharded", LinearModelType.LR, True, "vec",
                        None, dim, coef, [1, 0], AlinkTypes.LONG)
    return LinearModelDataConverter(AlinkTypes.LONG).save_model(m)


def _fixture(dim: int = DIM, n_rows: int = 256):
    import numpy as np

    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.params import Params
    from alink_tpu.common.vector import DenseVector
    from alink_tpu.operator.common.linear.mapper import LinearModelMapper
    rng = np.random.RandomState(SEED + 1)
    X = rng.randn(n_rows, dim)
    vecs = np.empty(n_rows, object)
    vecs[:] = [DenseVector(X[i]) for i in range(n_rows)]
    tbl = MTable({"vec": vecs}, "vec VECTOR")
    model = _build_model_table(SEED)
    mapper = LinearModelMapper(
        model.schema, tbl.schema,
        Params({"prediction_col": "pred", "prediction_detail_col": "det",
                "vector_col": "vec"}))
    mapper.load_model(model)
    return tbl, mapper


def _digest(table) -> str:
    h = hashlib.sha256()
    for i in range(table.num_rows):
        h.update(repr(tuple(map(str, table.row(i)))).encode())
    return h.hexdigest()[:16]


def run_child(n_devices: int, requests: int, swaps: int) -> dict:
    """One mesh size, inside an interpreter whose XLA host platform was
    widened to ``n_devices`` BEFORE jax loaded."""
    import jax

    from alink_tpu.common.mlenv import use_local_env
    from alink_tpu.serving import (CompiledPredictor, LoadGenerator,
                                   PredictServer)
    assert len(jax.devices()) >= n_devices, (
        f"child expected {n_devices} devices, got {jax.devices()}")
    use_local_env(parallelism=n_devices)
    tbl, mapper = _fixture()
    pred = CompiledPredictor(mapper, sharded=True, name="serve_sharded")
    assert pred.sharded and int(pred.mesh.devices.size) == n_devices
    for b in pred.buckets:                    # compile outside the timing
        pred.predict_table(tbl.first_n(min(b, tbl.num_rows)))
    probe_out = pred.predict_table(tbl)       # the cross-mesh parity probe
    digest = _digest(probe_out)

    rows = [tbl.row(i) for i in range(64)]
    srv = PredictServer(pred, name="serve_sharded")
    lg = LoadGenerator(srv.submit, rows, clients=4, pipeline=16)
    lg.run(max(100, requests // 8))           # warm the loop
    rep = lg.run(requests)

    # deterministic swap storm: every version's probe response is known
    # up front (same program, same mesh -> same bits), so any response
    # outside the set is a torn model
    probe = tbl.row(0)
    tables = [_build_model_table(SEED + 10 + i) for i in range(swaps)]
    expected = {str(pred.predict_row(probe))}
    for t in tables:
        m2 = type(mapper)(t.schema, tbl.schema, mapper.params)
        m2.load_model(t)
        expected.add(str(CompiledPredictor(
            m2, sharded=True, name="ref").predict_row(probe)))
    plg = LoadGenerator(srv.submit, [probe], clients=2, pipeline=8,
                        collect_responses=True)
    results = {"swapped": 0}

    import threading

    def storm():
        for t in tables:
            srv.swap_model(t)
            results["swapped"] += 1
    th = threading.Thread(target=storm)
    th.start()
    srep = plg.run(max(400, requests // 4))
    th.join(60)
    stats = srv.stats()
    srv.close()
    observed = {str(r) for r in srep.responses}
    torn = len(observed - expected)
    import jax
    return {
        "devices": n_devices,
        # the platform this child REALLY ran on (the parent forces the
        # host platform: a chip belongs to the parent process)
        "platform": jax.devices()[0].platform,
        "qps": round(rep.qps, 1),
        "qps_per_chip": round(rep.qps / n_devices, 1),
        "p50_ms": round(rep.p50_s * 1e3, 3),
        "p99_ms": round(rep.p99_s * 1e3, 3),
        "digest": digest,
        "model_swaps": results["swapped"],
        "torn_responses": torn,
        "failed_requests": rep.failures + srep.failures + stats["failed"],
        "requests": rep.requests + srep.requests,
        "bucket_hit_rate": round(stats["bucket_hit_rate"], 4),
    }


def _spawn_child(n_devices: int, requests: int, swaps: int,
                 timeout: int = 420) -> dict:
    sys.path.insert(0, ROOT)
    import bootenv
    env = bootenv.cpu_mesh_env(n_devices)
    env.pop("ALINK_TPU_MESH_DEVICES", None)   # the child mesh IS the rig
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--devices", str(n_devices), "--requests", str(requests),
           "--swaps", str(swaps)]
    out = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(
            f"serve_shard_bench child ({n_devices} devices) failed "
            f"rc={out.returncode}:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def measure(devices=(1, 4, 8), requests: int = 4000,
            swaps: int = 12) -> dict:
    """The ``serve_logreg_sharded`` bench row: per-mesh-size children,
    cross-mesh bitwise parity via probe digests, QPS/chip trajectory."""
    t0 = time.perf_counter()
    rows = {}
    for n in devices:
        rows[n] = _spawn_child(n, requests, swaps)
    digests = {r["digest"] for r in rows.values()}
    base = rows[min(rows)]
    top = rows[max(rows)]
    cores = os.cpu_count() or 1
    row = {
        # headline rate: QPS/chip at the WIDEST mesh (the fleet-scale
        # claim is per-chip throughput holding as chips are added)
        "samples_per_sec_per_chip": top["qps_per_chip"],
        "qps_per_chip": top["qps_per_chip"],
        "parity": "bitwise" if len(digests) == 1 else "MISMATCH",
        "torn_responses": sum(r["torn_responses"] for r in rows.values()),
        "failed_requests": sum(r["failed_requests"]
                               for r in rows.values()),
        "model_swaps": sum(r["model_swaps"] for r in rows.values()),
        "bound": "serving-host",
        # every *_per_chip figure in this row comes from children on
        # THIS platform, not from the parent's devices
        "platform": "/".join(sorted({r["platform"]
                                     for r in rows.values()})),
        "cores": cores,
        # on a host-platform mesh, N virtual chips SHARE the host's
        # cores: dividing a fixed compute roof by N is rig-pessimistic
        # by construction (the SCALING_r06 precedent). The rig-valid
        # signals are the bitwise cross-mesh parity, the swap-storm
        # integrity, and total-QPS RETENTION as the mesh widens
        # (qps_vs_1dev_*: the serving tier's own overhead does not
        # collapse) — per-chip QPS is the physical-TPU reading, where
        # each mesh step adds real silicon.
        "mesh_note": (f"host-platform mesh: virtual devices share "
                      f"{cores} cores; qps/chip divides a fixed "
                      f"compute roof and is rig-pessimistic — the "
                      f"same programs run unchanged over ICI"),
        "dt_s": round(time.perf_counter() - t0, 3),
    }
    for n, r in rows.items():
        row[f"qps_{n}dev"] = r["qps"]
        row[f"qps_per_chip_{n}dev"] = r["qps_per_chip"]
        row[f"p99_ms_{n}dev"] = r["p99_ms"]
        if base["qps"] > 0:
            row[f"qps_vs_1dev_{n}dev"] = round(r["qps"] / base["qps"], 3)
    if base["qps_per_chip"] > 0:
        row["per_chip_scaling"] = round(
            top["qps_per_chip"] / base["qps_per_chip"], 3)
    return row


def smoke() -> int:
    """perf_gate.sh leg: mesh 1 vs mesh 4, bitwise parity + clean swap
    storm. Exit 5 (distinct from lint=1/2, bench_compare=2/3, serve=4)
    so the gate log names the failing leg."""
    bad = []
    try:
        r1 = _spawn_child(1, requests=600, swaps=6)
        r4 = _spawn_child(4, requests=600, swaps=6)
    except (RuntimeError, subprocess.TimeoutExpired) as e:
        print(f"serve-shard smoke FAILED to run: {e}", file=sys.stderr)
        return 5
    if r1["digest"] != r4["digest"]:
        bad.append(f"sharded programs NOT bitwise across meshes: "
                   f"1-dev {r1['digest']} vs 4-dev {r4['digest']}")
    for r in (r1, r4):
        if r["torn_responses"]:
            bad.append(f"{r['devices']}-dev: {r['torn_responses']} TORN "
                       f"responses under sharded swap")
        if r["failed_requests"]:
            bad.append(f"{r['devices']}-dev: {r['failed_requests']} "
                       f"failed requests")
        if r["model_swaps"] < 6:
            bad.append(f"{r['devices']}-dev: only {r['model_swaps']} "
                       f"swaps completed")
    if bad:
        print("serve-shard smoke FAILED:", file=sys.stderr)
        for b in bad:
            print(f"  {b}", file=sys.stderr)
        return 5
    print(f"serve-shard smoke clean: mesh 1 vs 4 bitwise "
          f"({r1['digest']}), {r1['model_swaps']}+{r4['model_swaps']} "
          f"sharded swaps, zero torn")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--devices", default="1,4,8")
    ap.add_argument("--requests", type=int, default=4000)
    ap.add_argument("--swaps", type=int, default=12)
    args = ap.parse_args(argv)
    if args.child:
        n = int(args.devices)
        print(json.dumps(run_child(n, args.requests, args.swaps)))
        return 0
    if args.smoke:
        return smoke()
    devices = tuple(int(d) for d in str(args.devices).split(","))
    row = measure(devices, args.requests, args.swaps)
    print(json.dumps(row, indent=None if args.json else 2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
