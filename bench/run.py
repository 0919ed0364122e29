#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload direct_pgn --seed 7 --seconds 45 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  Run it from the
root of a checkout; the system under test is the checkout's ``src/``.
Without an accelerator, or with fewer chips than the cell asks for, it
exits with code 3 and prints no result.  The numbers the correctness
check compared are printed last on standard error, each beside its
limit, and under ``checks`` at the end of the result line.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        res = harness.run(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except harness.SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, (value, limit) in res.checks.items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    print(res.line(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
