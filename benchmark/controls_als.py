"""The controls of ``correct`` for an ALS-loop cell: what its comparison
reads when the work is done in the next precision down, or with a fault
planted (``benchmark/controls_kmeans.py`` and ``controls_gbdt.py`` do the
same for their cells).

``python3 -m benchmark.controls_als --workload als-fit --seeds 1,2``
prints, per seed, the numbers the cell compares, read with the plain
reference's own stand-in for a fit (``reference.als.stand_in``) put in the
program's place: clean (``float64_again``: it has to read nothing;
``float32``: the Gram products' operands rounded to the precision the
configuration states), with them rounded to bfloat16 (the step below), and
with each fault planted in it: one block of ratings left out, the factors
an item half-sweep reads one half-sweep stale, plain lambda in place of
lambda n, counts from offsets carried in float32. Each stand-in is read by
the clean reference exactly as the program's fit is. A benchmark run never
calls this; the readings it gave on the chip stand in PERF.md beside the
limits they set, and ``tests/benchmark_suite`` keeps the same readings at
a tiny size. The table is made as the cell makes it.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict

from . import yahoo
from .reference import als as ref

CONTROLS = {"float64_again": ("float64", None),
            "float32": ("float32", None),
            "bfloat16": ("bfloat16", None),
            "block_left_out": ("float64", "block_left_out"),
            "stale_factors": ("float64", "stale_factors"),
            "plain_lambda": ("float64", "plain_lambda"),
            "float32_counts": ("float64", "float32_counts")}


def readings(seed: int, config: Dict, only=None) -> Dict[str, Dict]:
    """``{control or fault: {number: reading}}``: each stand-in for the
    fit read against the raw table by the clean reference."""
    n = int(config["ratings"])
    users, items = int(config["users"]), int(config["items"])
    params = ref.learner(config)
    table = yahoo.make_table(seed, n, int(config["block_rows"]), users,
                             items, config["generator"])
    out = {}
    for name, (dtype, fault) in CONTROLS.items():
        if only and name not in only:
            continue
        info = ref.stand_in(table, n, users, items, params, seed, dtype,
                            fault)
        out[name] = ref.gaps(info, table, n, params, seed)
    return out


def main(argv=None) -> int:
    from .run import load_cell, tiny
    ap = argparse.ArgumentParser(prog="benchmark.controls_als")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tiny", type=int, choices=(0, 1), default=0)
    ap.add_argument("--only", default="",
                    help="comma-separated controls; all by default")
    args = ap.parse_args(argv)
    config = load_cell(args.workload)["config"]
    if args.tiny:
        config = tiny(config)
    only = [c for c in args.only.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"seed": seed,
                          "readings": readings(seed, config, only)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
