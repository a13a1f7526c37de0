#!/usr/bin/env python
"""Pallas kernel-tier smoke (perf_gate leg, ISSUE 13) — exit 7 on
failure.

The fused serving score kernel's contract, cheap enough for every gate
run, executed in a fresh 4-virtual-device f64 child with
``ALINK_TPU_PALLAS_INTERPRET=1`` (interpret mode is the CPU rig's
availability gate — the same programs run unchanged as Mosaic kernels
on a physical TPU): BITWISE vs the seq_chunk_sum XLA programs at
buckets 1/4/16, and bf16/int8 label-exact on boundary-safe rows.
"""

import os
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT = 7
_MARK = "ALINK_KERNEL_SMOKE_CHILD"


def main() -> int:
    if os.environ.get(_MARK) != "1":
        import bootenv
        env = bootenv.cpu_mesh_env(4)
        env[_MARK] = "1"
        env["JAX_ENABLE_X64"] = "1"
        env["ALINK_TPU_PALLAS_INTERPRET"] = "1"
        out = subprocess.run([sys.executable, os.path.abspath(__file__)],
                             cwd=ROOT, env=env, timeout=900)
        return out.returncode

    import jax
    import jax.numpy as jnp

    from alink_tpu.kernels.serve import (lowp_model_arrays,
                                         make_fused_score_fns,
                                         make_xla_score_fns)
    from alink_tpu.serving.sharded import seq_chunk_sum
    bad = []
    rng = np.random.RandomState(0)
    dim8 = 128
    w = rng.randn(dim8)
    b = 0.25
    mdl = (jnp.asarray(w), jnp.asarray(b))

    def xla_dense(mdl, X):
        w, b = mdl
        return seq_chunk_sum(X * w[None, :], axis=1) + b

    for bucket in (1, 4, 16):
        X = jnp.asarray(rng.randn(bucket, dim8))
        sx = np.asarray(jax.jit(xla_dense)(mdl, X))
        sf = np.asarray(jax.jit(
            make_fused_score_fns("f32", np.float64)["dense"])(mdl, X))
        if not np.array_equal(sx.view(np.int64), sf.view(np.int64)):
            bad.append(f"fused serve score NOT bitwise vs seq_chunk_sum "
                       f"at bucket {bucket}")
    X = jnp.asarray(rng.randn(16, dim8))
    ref = np.asarray(jax.jit(xla_dense)(mdl, X))
    for dt in ("bf16", "int8"):
        lmdl = tuple(jnp.asarray(a) for a in lowp_model_arrays(w, b, dt))
        sx = np.asarray(jax.jit(
            make_xla_score_fns(dt, np.float64)["dense"])(lmdl, X))
        sf = np.asarray(jax.jit(
            make_fused_score_fns(dt, np.float64)["dense"])(lmdl, X))
        if not np.array_equal(sx.view(np.int32), sf.view(np.int32)):
            bad.append(f"{dt} fused and XLA twins NOT bitwise")
        tol = 0.02 * max(1.0, float(np.abs(ref).max()))
        safe = np.abs(ref) > tol
        if not (np.sign(sx[safe]) == np.sign(ref[safe])).all():
            bad.append(f"{dt} labels NOT exact on boundary-safe rows")
        if not np.allclose(sx, ref, atol=tol):
            bad.append(f"{dt} scores outside the pinned tolerance")

    if bad:
        print("kernel_smoke: FAILED:", file=sys.stderr)
        for m in bad:
            print(f"  {m}", file=sys.stderr)
        return EXIT
    print("kernel_smoke: clean (fused serve bitwise + bf16/int8 parity)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
