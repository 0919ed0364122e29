"""Shared set-up of the benchmark's own tests (CPU, small sizes).

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

import copy
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# sizes a test run holds: same designs, penalty and grid rule as the cells
TINY_CFG = {
    "pgn_equi_ols": dict(n=30, p=160),
    "tall_ar_ols": dict(n=600, p=40),
}
TINY_TRAFFIC = {
    "path_full": dict(path_length=12),
    "cv10_top20": dict(folds=4, path_length=6, pool=2, check_sample=2),
    "closed16_top3": dict(n_range=[20, 32], p_ranges=[[40, 64, 8],
                                                      [65, 120, 8]]),
}


# mixes with no cell in BENCHMARK.json yet (PERF.md, Open questions):
# their generators are tested as the cells they were written for
DEFERRED = {
    "served_pgn": ("pgn_equi_ols", "closed16_top3"),
    "direct_tall": ("tall_ar_ols", "path_full"),
    "cv10_pgn": ("pgn_equi_ols", "cv10_top20"),
}


def resolve(workload):
    import harness

    names = [w["name"] for w in harness.manifest(ROOT)["workloads"]]
    if workload in names:
        return harness.resolve(ROOT, workload)
    config, traffic = DEFERRED[workload]
    return dict(
        cell=dict(name=workload, config=config, traffic=traffic, chips=1),
        cfg=harness.load_json(os.path.join(BENCH, "configs",
                                           config + ".json")),
        traffic=harness.load_json(os.path.join(BENCH, "traffic",
                                               traffic + ".json")),
        limits={"kkt_worst": 0.1}, end_to_end=[], per_layer=[])


@pytest.fixture
def tiny():
    """``tiny(workload)``: the cell's resolved files at a test size."""

    def make(workload, **traffic):
        r = copy.deepcopy(resolve(workload))
        r["cfg"].update(TINY_CFG[r["cell"]["config"]])
        r["traffic"].update(TINY_TRAFFIC[r["cell"]["traffic"]], **traffic)
        return r

    return make
