"""The correctness check fails where it must: the control (the program
fed bfloat16, the precision below the configurations' float32) and each
fault a cell can have, planted in the timed path, turn ``correct`` false;
a sound run stays correct.  Runs skip the harness's look for a chip."""

import ml_dtypes
import numpy as np
import pytest

import harness
from conftest import ROOT

PATH_CELLS = ["direct_pgn", "direct_tall", "cv10_pgn"]


def _run(r, workload, dtype=np.float32, seed=2147483661):
    return harness.run(ROOT, workload, seed, 0.5, False, require_chip=False,
                       resolved=r, dtype=dtype)


@pytest.mark.parametrize("workload", PATH_CELLS + ["served_pgn"])
def test_sound_run_is_correct(tiny, workload):
    res = _run(tiny(workload), workload)
    assert res.correct and res.failed == 0 and res.attempted >= 1
    assert res.checks["kkt_worst"][0] < res.checks["kkt_worst"][1]


@pytest.mark.parametrize("workload", PATH_CELLS + ["served_pgn"])
def test_bfloat16_control_is_not_correct(tiny, workload):
    res = _run(tiny(workload), workload, dtype=ml_dtypes.bfloat16)
    assert not res.correct


def _patch_paths(monkeypatch, alter):
    """Alter what ``slope_path`` returns, where it is produced."""
    from repro import api

    real = api.slope_path

    def patched(problem, spec, policy):
        res = real(problem, spec, policy)
        held = res.fold_paths if hasattr(res, "fold_paths") else res
        held.betas = np.array(held.betas)  # a writable copy to plant in
        alter(res)
        return res

    monkeypatch.setattr(api, "slope_path", patched)


def _alter_coefficient(betas):
    """The largest coefficient of the last step comes back with its sign
    flipped."""
    b = betas[-1]
    j = int(np.argmax(np.abs(b)))
    b[j] = -b[j]


def _stale_step(betas):
    """The last step returns the state of the step before, unchanged."""
    betas[-1] = betas[-2]


@pytest.mark.parametrize("workload", ["direct_pgn", "direct_tall"])
@pytest.mark.parametrize("fault", [_alter_coefficient, _stale_step])
def test_path_faults_are_caught(tiny, monkeypatch, workload, fault):
    _patch_paths(monkeypatch, lambda res: fault(res.betas))
    assert not _run(tiny(workload), workload).correct


def _half_folds_left_out(res):
    b = res.fold_paths.betas
    b[len(b) // 2:] = b[0]


@pytest.mark.parametrize("fault", [
    lambda res: _alter_coefficient(res.fold_paths.betas[1]),
    lambda res: _stale_step(res.fold_paths.betas[1]),
    _half_folds_left_out,
])
def test_cv_faults_are_caught(tiny, monkeypatch, fault):
    _patch_paths(monkeypatch, fault)
    assert not _run(tiny("cv10_pgn"), "cv10_pgn").correct


def _patch_service(monkeypatch, alter):
    from repro.serve import AsyncPathService

    real = AsyncPathService.submit
    count = [0]

    def patched(self, *a, **kw):
        fut = real(self, *a, **kw)
        count[0] += 1
        k = count[0]
        get = fut.result

        def result(timeout=None):
            r = get(timeout=timeout)
            r.betas = np.array(r.betas)  # a writable copy to plant in
            alter(r, k)
            return r

        fut.result = result
        return fut

    monkeypatch.setattr(AsyncPathService, "submit", patched)


@pytest.mark.parametrize("fault", [
    lambda r, k: _alter_coefficient(r.betas) if k % 5 == 0 else None,
    lambda r, k: _stale_step(r.betas) if k % 5 == 0 else None,
    # half of each batch left out: every other request comes back unsolved
    lambda r, k: r.betas.__setitem__(slice(1, None), 0.0) if k % 2 else None,
])
def test_served_faults_are_caught(tiny, monkeypatch, fault):
    _patch_service(monkeypatch, fault)
    assert not _run(tiny("served_pgn"), "served_pgn").correct
