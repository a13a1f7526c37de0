"""The controls of ``correct`` for a boost-loop cell: what its comparison
reads when the work is done in the next precision down, or with a fault
planted (``benchmark/controls_kmeans.py`` does the same for the fit-loop
cell).

``python3 -m benchmark.controls_gbdt --workload gbdt-fit --seeds 1,2``
prints, per seed, the numbers the cell compares, read with the plain
reference's own fit (``reference.gbdt.fit``) put in the program's place:
clean (``float32_again``: it has to pass), with its statistics in
bfloat16 and no compensation (the step below the float32 the
configuration states), and with each fault planted in it: one row block
left out, margins one tree stale, the rows not descended at one level,
counts added in float32. Each stand-in is recounted by the clean
reference exactly as the program's fit is. A benchmark run never calls
this; the readings it gave on the chip stand in PERF.md beside the limits
they set, and ``tests/benchmark_suite`` keeps the same readings at a tiny
size. The table is made as the cell makes it; the stand-ins' cut points
are exact quantiles of the table's first blocks.

The cut points have controls of their own, each read by ``edge_rank_gap``
as the program's are: the whole table's exact quantiles (``exact_edges``:
it has to read 0), the exact quantiles of a SAMPLE (the table's first
blocks), an equal-width grid between a column's least and largest value
(the step below a quantile), and the quantiles of half as many bins.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict

import numpy as np

from . import airline
from .reference import gbdt as ref

CONTROLS = {"float32_again": ("float32", None),
            "bfloat16": ("bfloat16", None),
            "block_left_out": ("float32", "block_left_out"),
            "stale_margins": ("float32", "stale_margins"),
            "no_descent": ("float32", "no_descent"),
            "float32_counts": ("float32", "float32_counts")}
EDGE_CONTROLS = ("exact_edges", "sampled_edges", "uniform_edges",
                 "half_the_edges")
EDGE_BLOCKS = 4


def sample_edges(table, n_rows: int, n_bins: int,
                 blocks: int = EDGE_BLOCKS) -> np.ndarray:
    """Exact quantile cut points of the table's first blocks."""
    head = np.asarray(table[:blocks])
    cols = head.transpose(1, 0, 2, 3).reshape(head.shape[1], -1)
    return ref.exact_edges(cols[:, :n_rows], n_bins)


def edge_stand_ins(table, n_rows: int, n_bins: int) -> Dict[str, np.ndarray]:
    """``{control: cut points (F, n_bins - 1)}`` in the program's edges'
    place. The whole table's columns are sorted on the host one at a time
    (460 MB each at full size); the sample is the first ``EDGE_BLOCKS``
    blocks, or the first half of a table that has fewer than twice
    that."""
    nb, F = int(table.shape[0]), int(table.shape[1])
    out = {k: np.full((F, n_bins - 1), np.inf)
           for k in ("exact_edges", "uniform_edges", "half_the_edges")}
    for f in range(F):
        v = np.sort(np.asarray(table[:, f]).reshape(-1)[:n_rows])
        grid = v[0] + (v[-1] - v[0]) * np.arange(1, n_bins) / n_bins
        for name, e in (("exact_edges", ref.cuts_of_sorted(v, n_bins)),
                        ("half_the_edges",
                         ref.cuts_of_sorted(v, n_bins // 2)),
                        ("uniform_edges", np.unique(grid))):
            out[name][f, :e.size] = e
    out["sampled_edges"] = sample_edges(
        table, n_rows, n_bins, max(1, min(EDGE_BLOCKS, nb // 2)))
    return out


def readings(seed: int, config: Dict, only=None) -> Dict[str, Dict]:
    """``{control or fault: {number: reading}}``: each stand-in for the
    fit against its clean float32 recount on the same table, each
    stand-in for the cut points by its ranks in the table."""
    n = int(config["rows"])
    params = ref.learner(config)
    n_bins = int(params["max_bins"])
    table, labels = airline.make_table(seed, n, int(config["block_rows"]),
                                       config["generator"])
    T = int(params["num_trees"])
    out = {}
    if not only or set(only) & set(EDGE_CONTROLS):
        for name, e in edge_stand_ins(table, n, n_bins).items():
            if not only or name in only:
                out[name] = {"edge_rank_gap": ref.edge_rank_gap(
                    table, n, e, n_bins)}
    edges = sample_edges(table, n, n_bins)
    for name, (dtype, fault) in CONTROLS.items():
        if only and name not in only:
            continue
        info = ref.fit(table, labels, n, edges, params, dtype, fault)
        want = ref.recount(table, labels, n, edges, info, params,
                           hist_trees=sorted({0, T - 1}))
        out[name] = ref.gaps(info, want, params)
    return out


def main(argv=None) -> int:
    from .run import load_cell, tiny
    ap = argparse.ArgumentParser(prog="benchmark.controls_gbdt")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tiny", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trees", type=int, default=0,
                    help="grow this many trees in place of the "
                         "configuration's (the stand-ins cost a pass a "
                         "level, so two keep a full-size call short)")
    ap.add_argument("--only", default="",
                    help="comma-separated controls; all by default")
    args = ap.parse_args(argv)
    config = load_cell(args.workload)["config"]
    if args.tiny:
        config = tiny(config)
    if args.trees:
        config = dict(config, num_trees=args.trees)
    only = [c for c in args.only.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = readings(seed, config, only)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "trees": int(config["num_trees"]),
                          "readings": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
