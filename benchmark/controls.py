"""The controls of ``correct``: what the comparison reads when the work is
done in the next precision down, or with a fault planted.

``python3 -m benchmark.controls --workload <cell> --seeds 1,2,3`` prints,
per seed, the numbers that cell compares, read with the plain reference put
in the program's place and computed in bfloat16 (the step below the
float32 the configurations state), and with each fault the cell can have
planted in that reference. A benchmark run never calls this; the readings
it gave on the chip stand in PERF.md beside the limits they set, and
``tests/benchmark_suite`` keeps the same readings at a tiny size.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict

import numpy as np

from . import data
from .generators import closed_drain, open_poisson
from .reference import logistic as ref_logistic


def drain_readings(seed: int, config: Dict, traffic: Dict) -> Dict[str, Dict]:
    """``{control or fault: {number: reading}}`` for a closed-drain cell:
    the first snapshot cycle, exactly as ``closed_drain`` compares it."""
    hp = {k: float(config["ftrl"][k]) for k in ("alpha", "beta", "l1", "l2")}
    dim = 1 << int(config["dim_log2"])
    B = int(config["batch_rows"])
    n_first = (int(traffic["snapshot_every"]) + 1) * B
    idx, val, click = data.make_rows(seed, int(traffic["pool_rows"]),
                                     config["row_shape"], dim - 1)
    coef = data.host_weights(seed, dim, float(config["warm_scale"]), 0)
    check_idx = closed_drain.check_coordinates(seed, idx, n_first, dim)
    w1, w0, touched, _ = closed_drain.reference_first_cycle(
        check_idx, idx, val, click, coef, n_first, hp, "float32")

    def against(**kw):
        g1, _, _, _ = closed_drain.reference_first_cycle(
            check_idx, idx, val, click, coef, n_first, hp, **kw)
        return closed_drain.gaps(g1, w1, w0, touched)

    half = np.ones(n_first, bool)
    half.reshape(-1, B)[:, B // 2:] = False     # the second half of each batch
    return {
        "float32_again": against(dtype="float32"),
        "bfloat16": against(dtype="bfloat16"),
        "half_batch_left_out": against(dtype="float32", keep_rows=half),
        "state_unchanged": closed_drain.gaps(w0, w1, w0, touched),
    }


def serve_readings(seed: int, config: Dict, traffic: Dict) -> Dict[str, Dict]:
    """The same for an open-loop serving cell: the widest gap between the
    float64 score and the score in bfloat16, or with an answer altered."""
    dim = 1 << int(config["dim_log2"])
    n = int(traffic["check_sample"])
    idx, val, _ = data.make_rows(seed, int(traffic["request_pool"]),
                                 config["row_shape"], dim - 1)
    idx, val = idx[:n], val[:n]
    scale = float(config["weight_scale"])
    # the window's model, made as the cell makes it
    w = data.device_weights(seed, open_poisson.padded(dim - 1), scale, 2)
    w_at = np.asarray(w[idx.reshape(-1)]).reshape(idx.shape)
    bias = float(data.host_weights(seed, 1, scale, 3)[0])
    want = ref_logistic.score(w_at, val, bias, "float64")
    low = ref_logistic.score(w_at, val, bias, "bfloat16")
    f32 = ref_logistic.score(w_at, val, bias, "float32")
    altered = want.copy()
    altered[len(altered) // 2] += 1e-3          # one answer in the sample
    return {
        "float32": {"prob_gap": float(np.abs(f32 - want).max())},
        "bfloat16": {"prob_gap": float(np.abs(low - want).max())},
        "one_answer_altered": {"prob_gap": float(np.abs(altered - want).max())},
    }


READINGS = {"closed_drain": drain_readings, "open_poisson": serve_readings}


def main(argv=None) -> int:
    from .run import load_cell, tiny
    ap = argparse.ArgumentParser(prog="benchmark.controls")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tiny", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    found = load_cell(args.workload, parked=True)
    config, traffic = found["config"], found["traffic"]
    if args.tiny:
        config, traffic = tiny(config), tiny(traffic)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = READINGS[traffic["generator"]](seed, config, traffic)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
