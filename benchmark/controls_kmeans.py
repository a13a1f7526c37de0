"""The controls of ``correct`` for a fit-loop cell: what its comparison
reads when the work is done in the next precision down, or with a fault
planted (``benchmark/controls.py`` does the same for the other cells).

``python3 -m benchmark.controls_kmeans --workload kmeans-fit --seeds 1,2``
prints, per seed, the numbers the cell compares, read with the plain
reference put in the program's place and computed in bfloat16 (the step
below the float32 the configuration states), and with each fault planted
in it: one row block left out, a superstep that hands its centroids back
unchanged, a table whose last block is stale (it holds the rows of the
table made from the seed before). A benchmark run never calls
this; the readings it gave on the chip stand in PERF.md beside the limits
they set, and ``tests/benchmark_suite`` keeps the same readings at a tiny
size. The table is made as the cell makes it; the initial centroids and
the candidate set are rows of it drawn from the seed.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

import numpy as np

from . import blobs
from .generators import fit_loop
from .reference import kmeans as ref


def _as_fit(steps: List[Dict]) -> Dict[str, List]:
    """``reference.kmeans.lloyd``'s return in the shape a fit's train
    info has, so it can stand in the program's place."""
    return {name: [s[name] for s in steps]
            for name in ("centroids", "weights", "inertia")}


def _rows_of(table, n_rows: int, rng, count: int) -> np.ndarray:
    """``count`` distinct rows of the blocked table, on the host."""
    S, L = table.shape[2], table.shape[3]
    at = rng.choice(n_rows, count, replace=False)
    b, r = np.divmod(at, S * L)
    return np.stack([np.asarray(table[int(bi), :, int(ri) // L, int(ri) % L])
                     for bi, ri in zip(b, r)])


def readings(seed: int, config: Dict, traffic: Dict) -> Dict[str, Dict]:
    """``{control or fault: {number: reading}}``, each against the clean
    float32 reference on the same table."""
    import jax
    n, d = int(config["rows"]), int(config["dimensions"])
    steps = int(config["max_iter"])
    mix = blobs.mixture(seed, int(config["num_of_clusters"]), d,
                        config["generator"])
    table = blobs.make_table(seed, n, d, int(config["block_rows"]), mix)
    rng = np.random.default_rng([int(seed), 11])
    m = fit_loop.folded_candidates(int(config["init_rounds"]),
                                   int(config["init_oversample"]))
    rows = _rows_of(table, n, rng, int(config["k"]) + m)
    init_c, cands = rows[:int(config["k"])], rows[int(config["k"]):]
    spread = ref.rms_spread(table, n)
    clean = ref.lloyd(table, n, None, init_c, steps)
    counts = ref.candidate_weights(table, n, None, cands)

    def against(got_steps, got_counts=None, got_table=None) -> Dict[str, float]:
        out = ref.gaps(_as_fit(got_steps), clean, float(n), spread)
        if got_counts is not None:
            out["init_weight_gap"] = float(
                np.abs(got_counts - counts).sum()) / n
        if got_table is not None:
            out["init_member_gap"] = float(
                ref.member_gaps(got_table, n, cands).max()) / spread
        return out

    mid = int(table.shape[0]) // 2
    out = {
        "float32_again": against(
            ref.lloyd(table, n, None, init_c, steps),
            ref.candidate_weights(table, n, None, cands), table),
        "bfloat16": against(
            ref.lloyd(table, n, None, init_c, steps, "bfloat16"),
            ref.candidate_weights(table, n, None, cands, "bfloat16")),
        "block_left_out": against(
            ref.lloyd(table, n, None, init_c, steps, skip_blocks=[mid])),
        "centroids_unchanged": against(
            ref.lloyd(table, n, None, init_c, steps, frozen=True)),
    }
    # last: the table's last block goes stale IN PLACE (the buffer is
    # donated, so no second table exists): it holds what the table of the
    # seed before held there
    last = int(table.shape[0]) - 1
    old = blobs.make_block(
        seed - 1, n, d, int(config["block_rows"]),
        blobs.mixture(seed - 1, int(config["num_of_clusters"]), d,
                      config["generator"]), last)
    stale = jax.jit(lambda t, b: t.at[last].set(b), donate_argnums=0)(
        table, old)
    del table
    out["stale_last_block"] = against(
        ref.lloyd(stale, n, None, init_c, steps),
        ref.candidate_weights(stale, n, None, cands), stale)
    return out


def main(argv=None) -> int:
    from .run import load_cell, tiny
    ap = argparse.ArgumentParser(prog="benchmark.controls_kmeans")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tiny", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    found = load_cell(args.workload)
    config, traffic = found["config"], found["traffic"]
    if args.tiny:
        config, traffic = tiny(config), tiny(traffic)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = readings(seed, config, traffic)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
