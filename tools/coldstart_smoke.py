#!/usr/bin/env python
"""Cold-start smoke (perf_gate leg, ISSUE 20) — exit 14.

Gates the persistent AOT store's one promise: a RESTART against a
warmed cache directory answers its first request without compiling
anything the previous process already compiled.

Two fresh child interpreters share one artifact directory:

  * child A (cold) trains the demo-LR fixture, serves one request per
    bucket, and exports every compiled program — its ledger shows the
    cold-start ``miss`` set;
  * child B (warm) runs the identical workload against the same
    directory — its serve cache must record ZERO ``miss`` events (every
    program deserializes as a ``disk-hit``), its first response must be
    faster than the cold baseline, and its predictions must be
    bitwise-identical to child A's;
  * child B's ``/compilez`` document, written to a run dir, must be
    enough for ``tools/doctor.py --run-dir`` to render the warm-restart
    verdict offline (disk hits named in the compile-plane section).
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT = 14
_MARK = "ALINK_COLDSTART_SMOKE_CHILD"


def _child() -> int:
    import hashlib
    import time

    import numpy as np

    from alink_tpu.common import aotcache, compileledger
    from alink_tpu.common.metrics import MetricsRegistry, set_registry
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.params import Params
    from alink_tpu.common.vector import DenseVector
    from alink_tpu.operator.batch.classification.linear import (
        LogisticRegressionTrainBatchOp)
    from alink_tpu.operator.batch.source.sources import MemSourceBatchOp
    from alink_tpu.operator.common.linear.mapper import LinearModelMapper
    from alink_tpu.serving import CompiledPredictor

    set_registry(MetricsRegistry())
    t_start = time.perf_counter()

    n_rows, dim = 64, 16
    rng = np.random.RandomState(11)
    X = rng.randn(n_rows, dim)
    y = (X @ rng.randn(dim) > 0).astype(np.int64)
    vecs = np.empty(n_rows, object)
    vecs[:] = [DenseVector(X[i]) for i in range(n_rows)]
    tbl = MTable({"vec": vecs, "label": y}, "vec VECTOR, label LONG")
    warm = LogisticRegressionTrainBatchOp(
        vector_col="vec", label_col="label", max_iter=2).link_from(
        MemSourceBatchOp(tbl.first_n(32)))
    model = warm.get_output_table()
    mapper = LinearModelMapper(model.schema, tbl.select(["vec"]).schema,
                               Params({"prediction_col": "pred",
                                       "vector_col": "vec"}))
    mapper.load_model(model)
    req = tbl.select(["vec"]).first_n(16)

    pred = CompiledPredictor(mapper, buckets=(16,), name="cold_smoke")
    warmed = pred.warm_from_disk()
    t0 = time.perf_counter()
    out = pred.predict_table(req)
    first_response_s = time.perf_counter() - t0

    col = out.col("pred")
    digest = hashlib.blake2b(
        np.asarray(col, dtype=np.float64).tobytes(),
        digest_size=16).hexdigest()

    doc = compileledger.compilez_doc()
    cache = f"serve.{pred.name}"
    serve_events = [e for e in doc.get("events") or []
                    if e.get("cache") == cache]
    import jax
    result = {
        "platform": jax.devices()[0].platform,
        "warmed_programs": warmed,
        "first_response_s": first_response_s,
        "startup_to_response_s": time.perf_counter() - t_start,
        "digest": digest,
        "serve_misses": sum(1 for e in serve_events
                            if e.get("kind", "miss") == "miss"),
        "serve_disk_hits": sum(1 for e in serve_events
                               if e.get("kind") == "disk-hit"),
        "ttfp": (doc.get("cold_start") or {}).get(
            "time_to_first_program_s") or {},
        "aot": aotcache.stats(),
    }
    run_dir = os.environ["ALINK_COLDSTART_SMOKE_DIR"]
    with open(os.path.join(run_dir, "compilez.json"), "w") as fh:
        json.dump(doc, fh, indent=1)
    with open(os.environ["ALINK_COLDSTART_SMOKE_OUT"], "w") as fh:
        json.dump(result, fh)
    return 0


def main() -> int:
    if os.environ.get(_MARK) == "1":
        return _child()

    import tempfile

    import bootenv

    cache_dir = tempfile.mkdtemp(prefix="alink-coldstart-aot-")
    run_dir = tempfile.mkdtemp(prefix="alink-coldstart-run-")
    results = {}
    for role in ("cold", "warm"):
        env = bootenv.warm_restart_cache_env(bootenv.cpu_mesh_env(4),
                                             cache_dir)
        env[_MARK] = "1"
        env["ALINK_TPU_AOT_CACHE_DIR"] = cache_dir
        env.pop("ALINK_TPU_AOT_CACHE", None)
        env["ALINK_COLDSTART_SMOKE_DIR"] = run_dir
        env["ALINK_COLDSTART_SMOKE_OUT"] = os.path.join(
            run_dir, f"{role}.json")
        out = subprocess.run([sys.executable, os.path.abspath(__file__)],
                             cwd=ROOT, env=env, timeout=900)
        if out.returncode != 0:
            print(f"coldstart_smoke: {role} child exited "
                  f"{out.returncode}", file=sys.stderr)
            return EXIT
        with open(env["ALINK_COLDSTART_SMOKE_OUT"]) as fh:
            results[role] = json.load(fh)

    cold, warm = results["cold"], results["warm"]
    bad = []
    if cold["serve_misses"] < 1:
        bad.append("cold child compiled no serving program — the "
                   "fixture is not exercising the serve cache")
    if cold["aot"]["stores"] < 1:
        bad.append("cold child exported nothing — store() never ran")
    if warm["serve_misses"] != 0:
        bad.append(f"warm restart recompiled {warm['serve_misses']} "
                   f"serving program(s) — the warmed set must come "
                   f"entirely from disk")
    if warm["serve_disk_hits"] + warm["warmed_programs"] < 1:
        bad.append("warm restart loaded nothing from the artifact "
                   "store (zero disk hits, zero admission-warmed "
                   "programs)")
    if warm["digest"] != cold["digest"]:
        bad.append(f"deserialized programs changed the predictions: "
                   f"cold {cold['digest']} != warm {warm['digest']} — "
                   f"the store must be bitwise-transparent")
    if warm["first_response_s"] >= cold["first_response_s"]:
        bad.append(f"warm first response "
                   f"({warm['first_response_s']:.3f}s) is not below "
                   f"the cold baseline "
                   f"({cold['first_response_s']:.3f}s)")

    doctor = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "doctor.py"),
         "--run-dir", run_dir],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    if doctor.returncode != 0:
        bad.append(f"doctor --run-dir exited {doctor.returncode}: "
                   f"{doctor.stderr[-400:]}")
    elif "disk hit" not in doctor.stdout:
        bad.append("doctor --run-dir did not surface the disk-hit "
                   "count from the warm child's compilez.json")

    if bad:
        print("coldstart_smoke: FAILED:", file=sys.stderr)
        for m in bad:
            print(f"  {m}", file=sys.stderr)
        return EXIT
    print(f"coldstart_smoke: clean — cold first response "
          f"{cold['first_response_s']:.3f}s ({cold['serve_misses']} "
          f"compile(s), {cold['aot']['stores']} artifact(s) exported); "
          f"warm restart {warm['first_response_s']:.3f}s with "
          f"{warm['serve_disk_hits']} disk hit(s) + "
          f"{warm['warmed_programs']} admission-warmed program(s), "
          f"zero recompiles, bitwise-identical predictions; doctor "
          f"rendered the warm-restart verdict offline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
