#!/usr/bin/env python3
"""How much work a seed gives each cell, counted on the CPU.

    JAX_PLATFORMS=cpu python3 bench/work_count.py --workload direct_pgn --seeds 12
    JAX_PLATFORMS=cpu python3 bench/work_count.py --workload served_pgn --seeds 12 --no-solve

For each seed: the shapes of the cell's design pool, the bucket each
served request lands in per table cycle, and, unless ``--no-solve``, one
pass of the cell's traffic over the pool through the program with the
σ-steps and FISTA iterations of every answer.  The last line is a JSON
summary: a seed may change only the iterations.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

import numpy as np

import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def shape_table(cfg, traffic) -> list[tuple[int, int]]:
    """The (n, p) of every design a cell's pool holds (seed-free)."""
    if traffic["mode"] == "closed":
        return harness.request_table(traffic)
    return [(cfg["n"], cfg["p"])] * traffic["pool"]


def bucket_split(traffic) -> dict:
    """Requests per executed (N, P) bucket in one cycle of a closed mix."""
    from repro.serve.buckets import default_policy

    pol = default_policy()
    return dict(collections.Counter(
        "x".join(map(str, pol.shape_bucket(n, p)))
        for n, p in harness.request_table(traffic)))


def count(cfg, traffic, seed: int, solve: bool = True) -> dict:
    """One seed's pool shapes and, with ``solve``, one pass over it."""
    pool = harness.make_pool(cfg, traffic, seed)
    out = {"seed": seed, "shapes": [list(d.X.shape) for d in pool]}
    if traffic["mode"] == "closed":
        out["buckets"] = bucket_split(traffic)
    if not solve:
        return out
    driver = (harness.ClosedLoop(cfg, traffic, pool, np.float32)
              if traffic["mode"] == "closed"
              else harness.Sequential(cfg, traffic, pool, np.float32))
    try:
        answers = driver.warm_pass()
    finally:
        driver.close()
    answers.sort(key=lambda a: a.index)
    out["steps"] = [list(a.betas.shape[:-1]) for a in answers]
    out["iters"] = [a.info["iters"] for a in answers]
    out["modes"] = [a.info.get("mode") for a in answers]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1)
    ap.add_argument("--no-solve", action="store_true",
                    help="count shapes and buckets only")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    r = harness.resolve(ROOT, args.workload)
    rows = []
    for s in range(args.first_seed, args.first_seed + args.seeds):
        row = count(r["cfg"], r["traffic"], s, solve=not args.no_solve)
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": args.workload, "seeds": len(rows),
               "same_shapes": all(r["shapes"] == rows[0]["shapes"]
                                  for r in rows),
               "same_buckets": all(r.get("buckets") == rows[0].get("buckets")
                                   for r in rows)}
    if not args.no_solve:
        its = np.array([r["iters"] for r in rows], float)
        summary["same_steps"] = all(r["steps"] == rows[0]["steps"]
                                    for r in rows)
        summary["iters_per_seed"] = its.sum(axis=1).tolist()
        q = np.quantile(its.sum(axis=1), [0.25, 0.5, 0.75])
        summary["iters_quartile_spread"] = float((q[2] - q[0]) / q[1])
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
