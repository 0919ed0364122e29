#!/usr/bin/env python3
"""Readings of the correctness check, for setting its limits.

    python3 bench/control.py --workload direct_pgn --seconds 5 \
        --program-seeds 11,12,13 --control-seeds 21,22,23

Runs the cell in one process: first the program as the configuration
states it (float32) on each program seed, then the control, the same
program fed bfloat16, the precision below float32, on each control seed.
Each run prints one JSON line with the numbers compared (``kkt_worst``,
``failed_answers``); the limit of a number is set between the largest
program reading and the smallest control reading.  The benchmark's own
runs never run the control.  Needs the chip, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import ml_dtypes
import numpy as np

import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    runs = [(int(s), "float32", np.float32)
            for s in args.program_seeds.split(",") if s]
    runs += [(int(s), "bfloat16", ml_dtypes.bfloat16)
             for s in args.control_seeds.split(",") if s]
    for seed, name, dtype in runs:
        t0 = time.perf_counter()
        r = harness.resolve(root, args.workload)
        if name != "float32":
            # nothing of the control is timed: its window's answers are
            # what is read, so it skips the warm-up passes
            r["traffic"]["warm_passes"] = 0
        try:
            res = harness.run(root, args.workload, seed, args.seconds, False,
                              dtype=dtype, resolved=r)
            out = {"checks": {k: v for k, (v, _) in res.checks.items()},
                   "correct": res.correct, "attempted": res.attempted,
                   "failed": res.failed, "notes": res.notes}
        except harness.SetupError:
            raise
        except Exception as e:  # a control that crashes has failed
            out = {"crashed": f"{type(e).__name__}: {e}"[:500]}
        out.update(workload=args.workload, seed=seed, dtype=name,
                   wall_s=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
