"""The controls of ``correct`` for an L-BFGS-loop cell: what its comparison
reads when the work is done in the next precision down, or with a fault
planted (``benchmark/controls_kmeans.py``, ``controls_gbdt.py`` and
``controls_als.py`` do the same for their cells).

``python3 -m benchmark.controls_softmax --workload softmax-fit --seeds 1,2``
prints, per seed, the numbers the cell compares, read with the plain
reference's own stand-in for a fit (``reference.softmax.fit``, a few
supersteps of its own L-BFGS) put in the program's place: clean
(``float32_again``: it has to read float32's rounding and no more), with
the products' coefficient operands in plain bfloat16 (the step below),
and with each fault planted in it: one block of the table left out, the
standardization left out, the line search started from logits one
superstep stale, the ladder's argmin off by one rung, the direction made
without the newest (s, y) pair, the passes' row counts added in float32. Each stand-in is read by the clean reference
exactly as the program's fit is. A benchmark run never calls this; the
readings it gave on the chip stand in PERF.md beside the limits they set,
and ``tests/benchmark_suite`` keeps the same readings at a tiny size. The
table is made as the cell makes it.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict

from . import mnist8m
from .reference import softmax as ref

CONTROLS = {"float32_again": ("float32", None),
            "bfloat16": ("bfloat16", None),
            "block_left_out": ("float32", "block_left_out"),
            "no_standardization": ("float32", "no_standardization"),
            "stale_logits": ("float32", "stale_logits"),
            "rung_off_by_one": ("float32", "rung_off_by_one"),
            "pair_dropped": ("float32", "pair_dropped"),
            "float32_counts": ("float32", "float32_counts")}


def readings(seed: int, config: Dict, supersteps: int = 4, only=None
             ) -> Dict[str, Dict]:
    """``{control or fault: {number: reading}}``: each stand-in for the
    fit read against the raw table by the clean reference."""
    n = int(config["rows"])
    params = ref.learner(config)
    table, labels = mnist8m.make_table(seed, n, int(config["block_rows"]),
                                       config["generator"])
    out = {}
    for name, (dtype, fault) in CONTROLS.items():
        if only and name not in only:
            continue
        info = ref.fit(table, labels, n, params, params["l2_ladder"][0],
                       supersteps, dtype, fault)
        out[name] = ref.gaps(info, table, labels, n, params)
        out[name]["rows_gap"] = float(abs(
            info["rows_counted"] - n * info["passes"]))
    return out


def main(argv=None) -> int:
    from .run import load_cell, tiny
    ap = argparse.ArgumentParser(prog="benchmark.controls_softmax")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--supersteps", type=int, default=4)
    ap.add_argument("--tiny", type=int, choices=(0, 1), default=0)
    ap.add_argument("--only", default="",
                    help="comma-separated controls; all by default")
    args = ap.parse_args(argv)
    config = load_cell(args.workload)["config"]
    if args.tiny:
        config = tiny(config)
    only = [c for c in args.only.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"seed": seed, "readings": readings(
            seed, config, args.supersteps, only)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
