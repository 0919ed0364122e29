"""repro.api — the declarative front door (ISSUE 4).

Contracts under test:

* the public API surface is snapshot-pinned (spec field renames are
  breaking changes and must fail CI);
* the planner selects the compact engine for p ≫ n batches, the masked
  engine for n ≳ p batches, and the gathered host driver for single
  problems — and planner-selected execution is BIT-IDENTICAL to spelling
  the same backend out with the explicit legacy kwargs;
* every legacy call signature from PRs 1–3 still returns bit-identical
  results and warns exactly once per (function, kwarg);
* specs are pytrees; OLS sample weights reduce exactly to row duplication;
* `PathService.submit` accepts the same spec triple and stays bit-identical
  to direct padded execution, with executed plans visible in `stats()`.
"""

import dataclasses
import warnings

import numpy as np
import pytest

import jax

import repro.api as api
from repro.api import (
    ExecutionPlan,
    LambdaSpec,
    PathSpec,
    Problem,
    SlopE,
    SolverPolicy,
    plan_execution,
    slope_path,
)
from repro.api.compat import reset_legacy_warnings
from repro.core import bh_sequence, cv_path, fit_path, fit_path_batched, logistic, ols
from repro.core.engine import _WS_BUCKETS
from repro.data import make_classification, make_regression

KW = dict(path_length=6, solver_tol=1e-10, max_iter=20000, kkt_tol=1e-4)
POL = dict(solver_tol=1e-10, max_iter=20000, kkt_tol=1e-4)


def _problem(n, p, seed=0, k=4, noise=1.0, rho=0.2):
    X, y, _ = make_regression(n, p, k=k, rho=rho, seed=seed, noise=noise)
    return X, y, np.asarray(bh_sequence(p, q=0.1))


def _batch(B, n, p, *, k=4, rho=0.2, noise=1.0, q=0.1):
    probs = [make_regression(n, p, k=k, rho=rho, seed=s, noise=noise)[:2]
             for s in range(B)]
    return (np.stack([X for X, _ in probs]), np.stack([y for _, y in probs]),
            np.asarray(bh_sequence(p, q=q)))


# ---------------------------------------------------------------------------
# public API surface (CI satellite: accidental breakage must fail fast)
# ---------------------------------------------------------------------------

EXPECTED_ALL = {
    "Problem", "LambdaSpec", "PathSpec", "SolverPolicy", "ExecutionPlan",
    "plan_execution", "slope_path", "SlopE", "as_lambda_spec",
    "default_service", "default_async_service", "shared_canonicalizer",
    "ValidationError", "find_nonfinite", "ResamplePlan",
}

EXPECTED_FIELDS = {
    Problem: ["X", "y", "family", "weights"],
    LambdaSpec: ["kind", "q", "values"],
    PathSpec: ["lam", "path_length", "sigma_ratio", "sigmas", "early_stop",
               "cv_folds", "stratify", "selection", "resample"],
    SolverPolicy: ["backend", "working_set", "ws_tiers", "pad", "screening",
                   "solver_tol", "max_iter", "kkt_tol", "max_refits",
                   "verbose", "deadline_ms", "priority", "validate",
                   "telemetry", "solve_timeout_ms"],
    ExecutionPlan: ["backend", "mode", "batch", "n", "p", "working_set",
                    "ws_tiers", "pad", "exec_shape", "screening", "device",
                    "reasons"],
}


def test_public_api_surface_snapshot():
    assert set(api.__all__) == EXPECTED_ALL
    for cls, fields in EXPECTED_FIELDS.items():
        assert [f.name for f in dataclasses.fields(cls)] == fields, cls


def test_spec_validation_errors():
    X, y, lam = _problem(20, 24)
    with pytest.raises(ValueError):
        Problem(X[0], y)                      # 1-D X
    with pytest.raises(ValueError):
        Problem(X, y[:-1])                    # row mismatch
    with pytest.raises(ValueError):
        Problem(X, y, weights=np.ones(3))     # weight shape
    with pytest.raises(ValueError):
        PathSpec(selection="best")
    with pytest.raises(ValueError):
        PathSpec(cv_folds=1)
    with pytest.raises(ValueError):
        SolverPolicy(backend="gpu")
    with pytest.raises(ValueError):
        SolverPolicy(working_set="big")
    with pytest.raises(ValueError):
        SolverPolicy(pad="always")
    with pytest.raises(ValueError):
        SolverPolicy(screening="weak")
    with pytest.raises(ValueError):
        SolverPolicy(deadline_ms=0.0)
    with pytest.raises(ValueError):
        SolverPolicy(deadline_ms=-5.0)
    with pytest.raises(ValueError):
        SolverPolicy(priority=1.5)
    with pytest.raises(ValueError):
        SolverPolicy(priority=True)
    with pytest.raises(ValueError):
        SolverPolicy(telemetry="verbose")


def test_planner_routes_slo_knobs_to_serve():
    X, y, lam = _problem(20, 24)
    pb = Problem(X, y)
    for pol in (SolverPolicy(deadline_ms=500.0), SolverPolicy(priority=3)):
        pln = plan_execution(pb, PathSpec(lam=lam), pol)
        assert pln.backend == "serve"
        assert any("SLO" in r for r in pln.reasons)
    # pinned non-serve backends cannot honour SLO knobs
    for backend in ("host", "masked", "compact"):
        with pytest.raises(ValueError, match="SLO"):
            plan_execution(pb, PathSpec(lam=lam),
                           SolverPolicy(backend=backend, deadline_ms=100.0))
    # explicit serve + SLO knobs is fine
    pln = plan_execution(pb, PathSpec(lam=lam),
                         SolverPolicy(backend="serve", deadline_ms=100.0))
    assert pln.backend == "serve"


def test_specs_are_pytrees():
    X, y, lam = _problem(20, 24)
    w = np.ones(20)
    pb = Problem(X, y, family=logistic, weights=w)
    leaves, treedef = jax.tree_util.tree_flatten(pb)
    pb2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert pb2.family is logistic
    np.testing.assert_array_equal(pb2.X, X)
    np.testing.assert_array_equal(pb2.weights, w)

    spec = PathSpec(lam=LambdaSpec.explicit(lam), sigmas=np.ones(4))
    doubled = jax.tree_util.tree_map(lambda a: a * 2, spec)
    np.testing.assert_array_equal(np.asarray(doubled.lam.values), 2 * lam)
    np.testing.assert_array_equal(doubled.sigmas, 2 * np.ones(4))
    assert doubled.path_length == spec.path_length  # aux data untouched

    leaves, _ = jax.tree_util.tree_flatten(SolverPolicy())
    assert leaves == []                       # policy is pure static config


# ---------------------------------------------------------------------------
# the planner (ISSUE 4 acceptance)
# ---------------------------------------------------------------------------

def test_planner_compact_for_p_much_greater_than_n():
    Xs, ys, lam = _batch(2, 20, 256, k=3, rho=0.0, noise=0.3, q=0.05)
    _WS_BUCKETS.pop((20, 256, 1, "ols", "strong"), None)
    pln = plan_execution(Problem(Xs, ys), PathSpec(lam=lam))
    assert (pln.backend, pln.mode) == ("device", "compact")
    assert pln.working_set == 64              # min(2^⌈log₂ max(2n,64)⌉, p)
    text = pln.explain()
    assert "compact" in text and "O(n·W)" in text and "W=64" in text


def test_planner_masked_for_n_over_p():
    Xs, ys, lam = _batch(3, 40, 60)           # p < 2n
    pln = plan_execution(Problem(Xs, ys), PathSpec(lam=lam))
    assert (pln.backend, pln.mode) == ("device", "masked")
    assert pln.working_set is None
    assert "masked" in pln.explain()


def test_planner_host_for_single_problem():
    X, y, lam = _problem(30, 40)
    pln = plan_execution(Problem(X, y), PathSpec(lam=lam))
    assert (pln.backend, pln.mode) == ("host", "gathered")
    assert "host" in pln.explain()


def test_planner_cv_uses_fold_geometry():
    X, y, lam = _problem(30, 40)
    pln = plan_execution(Problem(X, y), PathSpec(lam=lam, cv_folds=3))
    assert pln.backend == "device" and pln.batch == 3
    assert pln.n == 20                        # training rows per fold
    with pytest.raises(ValueError):           # CV needs a single problem
        Xs, ys, lam2 = _batch(2, 20, 24)
        plan_execution(Problem(Xs, ys), PathSpec(lam=lam2, cv_folds=3))


def test_planner_rejects_impossible_pins():
    X, y, lam = _problem(20, 24)
    Xs, ys, lam2 = _batch(2, 20, 24)
    with pytest.raises(ValueError, match="cannot execute cv_folds"):
        plan_execution(Problem(X, y), PathSpec(lam=lam, cv_folds=3),
                       SolverPolicy(backend="host"))
    with pytest.raises(ValueError, match="single"):
        plan_execution(Problem(Xs, ys), PathSpec(lam=lam2),
                       SolverPolicy(backend="host"))
    with pytest.raises(ValueError, match="canonical bucket"):
        plan_execution(Problem(X, y), PathSpec(lam=lam),
                       SolverPolicy(backend="serve", pad=None))


def test_legacy_entry_points_accept_plain_lists():
    """PR 1-3 entry points took lists (np.asarray'd internally); the shims
    must keep that working through Problem's coercion."""
    X, y, lam = _problem(15, 12)
    a = fit_path(X.tolist(), y.tolist(), lam.tolist(), ols,
                 early_stop=False, **KW)
    b = fit_path(X, y, lam, ols, early_stop=False, **KW)
    np.testing.assert_array_equal(a.betas, b.betas)


def test_planner_screening_none_stays_masked():
    Xs, ys, lam = _batch(2, 20, 256)
    pln = plan_execution(Problem(Xs, ys), PathSpec(lam=lam),
                         SolverPolicy(screening="none"))
    assert pln.mode == "masked"


def test_planner_agreement_compact_bit_identical():
    """Acceptance: on a p ≫ n batch the planner selects the compact engine
    and its execution is bit-identical to the explicit legacy kwargs for
    the same backend (shallow grid: no overflow, so the registry state the
    two runs see is identical)."""
    Xs, ys, lam = _batch(2, 20, 256, k=3, rho=0.0, noise=0.3, q=0.05)
    key = (20, 256, 1, "ols", "strong")
    spec = PathSpec(lam=lam, path_length=6, sigma_ratio=0.5)

    _WS_BUCKETS.pop(key, None)
    auto = slope_path(Problem(Xs, ys), spec, SolverPolicy(**POL))
    assert auto.plan.mode == "compact" and auto.working_set == 64
    assert not auto.compact_fallback.any()

    _WS_BUCKETS.pop(key, None)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy = fit_path_batched(Xs, ys, lam, ols, working_set="auto",
                                  sigma_ratio=0.5, **KW)
    np.testing.assert_array_equal(auto.betas, legacy.betas)
    np.testing.assert_array_equal(auto.n_violations, legacy.n_violations)
    np.testing.assert_array_equal(auto.ws_size, legacy.ws_size)


def test_planner_agreement_masked_bit_identical():
    """Acceptance: on an n ≳ p batch the planner selects the masked engine,
    bit-identical to the legacy default kwargs."""
    Xs, ys, lam = _batch(3, 40, 60)
    auto = slope_path(Problem(Xs, ys), PathSpec(lam=lam, path_length=6),
                      SolverPolicy(**POL))
    assert auto.plan.mode == "masked"
    legacy = fit_path_batched(Xs, ys, lam, ols, **KW)
    np.testing.assert_array_equal(auto.betas, legacy.betas)
    np.testing.assert_array_equal(auto.n_screened, legacy.n_screened)


@pytest.mark.parametrize("B,backend", [(1, "masked"), (3, "masked"),
                                       (1, "compact"), (9, "masked")])
def test_planner_exec_shape_is_what_runs(B, backend):
    """The plan's padded program shape is the one the engine executes —
    batch slots included (a direct bucket fit runs the services' width)."""
    Xs, ys, lam = _batch(B, 20, 40)
    res = slope_path(Problem(Xs, ys), PathSpec(lam=lam, path_length=4),
                     SolverPolicy(backend=backend, pad="bucket", **POL))
    assert res.plan.exec_shape == res.pad_shape
    assert res.pad_shape[0] == max(8, 1 << (B - 1).bit_length())


def test_planner_agreement_host_bit_identical():
    X, y, lam = _problem(30, 40)
    auto = slope_path(Problem(X, y),
                      PathSpec(lam=lam, path_length=6, early_stop=False),
                      SolverPolicy(**POL))
    assert auto.plan.mode == "gathered"
    legacy = fit_path(X, y, lam, ols, early_stop=False, **KW)
    np.testing.assert_array_equal(auto.betas, legacy.betas)


# ---------------------------------------------------------------------------
# deprecation shims (ISSUE 4 satellite): bit-identical, warn exactly once
# ---------------------------------------------------------------------------

def _legacy_warnings(w, kwarg):
    return [x for x in w if issubclass(x.category, DeprecationWarning)
            and f"({kwarg}=...)" in str(x.message)]


def test_legacy_fit_path_engine_pad_warn_once():
    X, y, lam = _problem(20, 24)
    reset_legacy_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        a = fit_path(X, y, lam, ols, engine="device", pad="bucket",
                     early_stop=False, **KW)
        b = fit_path(X, y, lam, ols, engine="device", pad="bucket",
                     early_stop=False, **KW)
    assert len(_legacy_warnings(w, "engine")) == 1
    assert len(_legacy_warnings(w, "pad")) == 1
    np.testing.assert_array_equal(a.betas, b.betas)
    spec_res = slope_path(Problem(X, y),
                          PathSpec(lam=lam, path_length=6, early_stop=False),
                          SolverPolicy(backend="masked", pad="bucket", **POL))
    np.testing.assert_array_equal(a.betas, spec_res.betas)


def test_legacy_fit_path_batched_working_set_warns_once():
    Xs, ys, lam = _batch(3, 40, 96)
    reset_legacy_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        a = fit_path_batched(Xs, ys, lam, ols, working_set=64, **KW)
        b = fit_path_batched(Xs, ys, lam, ols, working_set=64, **KW)
    assert len(_legacy_warnings(w, "working_set")) == 1
    np.testing.assert_array_equal(a.betas, b.betas)
    spec_res = slope_path(Problem(Xs, ys), PathSpec(lam=lam, path_length=6),
                          SolverPolicy(backend="compact", working_set=64,
                                       **POL))
    np.testing.assert_array_equal(a.betas, spec_res.betas)
    assert spec_res.working_set == 64


def test_legacy_cv_path_stratify_selection_warn_once():
    X, y, _ = make_classification(36, 20, k=3, rho=0.1, seed=14)
    lam = np.asarray(bh_sequence(20, q=0.1))
    kw = dict(path_length=8, solver_tol=1e-9, max_iter=5000)
    reset_legacy_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        a = cv_path(X, y, lam, logistic, n_folds=3, stratify="auto",
                    selection="1se", **kw)
        b = cv_path(X, y, lam, logistic, n_folds=3, stratify="auto",
                    selection="1se", **kw)
    assert len(_legacy_warnings(w, "stratify")) == 1
    assert len(_legacy_warnings(w, "selection")) == 1
    np.testing.assert_array_equal(a.val_deviance, b.val_deviance)
    assert a.best_index == b.best_index
    spec_res = slope_path(
        Problem(X, y, family=logistic),
        PathSpec(lam=lam, path_length=8, cv_folds=3, stratify="auto",
                 selection="1se"),
        SolverPolicy(backend="masked", solver_tol=1e-9, max_iter=5000))
    np.testing.assert_array_equal(a.val_deviance, spec_res.val_deviance)
    assert a.best_index == spec_res.best_index
    assert a.best_index_1se == spec_res.best_index_1se


def test_legacy_default_calls_do_not_warn():
    X, y, lam = _problem(20, 24)
    Xs, ys, lam2 = _batch(2, 20, 24)
    reset_legacy_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        fit_path(X, y, lam, ols, early_stop=False, **KW)
        fit_path_batched(Xs, ys, lam2, ols, **KW)
        cv_path(X, y, lam, ols, n_folds=3, **KW)
    assert not [x for x in w if issubclass(x.category, DeprecationWarning)]


# ---------------------------------------------------------------------------
# sample weights (Problem.weights, OLS row-scaling reduction)
# ---------------------------------------------------------------------------

def test_ols_weights_equal_row_duplication():
    n, p = 15, 25
    X, y, _ = make_regression(n, p, k=3, rho=0.0, seed=4, noise=0.3)
    w = np.ones(n)
    w[3] = 2.0
    Xd = np.vstack([X, X[3:4]])
    yd = np.concatenate([y, y[3:4]])
    lam = np.asarray(bh_sequence(p, 0.1))
    sig = 2.0 * np.linspace(1.0, 0.2, 8)   # shared grid: losses are equal
    spec = lambda: PathSpec(lam=lam, sigmas=sig, early_stop=False)  # noqa: E731
    pol = SolverPolicy(solver_tol=1e-12, max_iter=30000)
    a = slope_path(Problem(X, y, weights=w), spec(), pol)
    b = slope_path(Problem(Xd, yd), spec(), pol)
    np.testing.assert_allclose(a.betas, b.betas, atol=1e-10)


def test_weights_rejected_for_non_ols():
    X, y, _ = make_classification(20, 10, k=2, rho=0.0, seed=1)
    with pytest.raises(ValueError, match="OLS"):
        slope_path(Problem(X, y, family=logistic, weights=np.ones(20)),
                   PathSpec(path_length=4))
    with pytest.raises(ValueError, match="positive"):
        slope_path(Problem(X[:, :5], y.astype(float),
                           weights=np.zeros(20)),
                   PathSpec(path_length=4))


# ---------------------------------------------------------------------------
# SlopE estimator
# ---------------------------------------------------------------------------

def test_slope_estimator_cv_fit_predict():
    X, y, _ = make_regression(60, 50, k=4, rho=0.0, seed=2, noise=0.3)
    est = SlopE(lam=LambdaSpec("bh", q=0.1),
                path=PathSpec(lam=LambdaSpec("bh", q=0.1), cv_folds=4,
                              path_length=25),
                policy=SolverPolicy(solver_tol=1e-9, max_iter=5000))
    assert est.fit(X, y) is est
    assert est.coef_.shape == (50,)
    assert 0 < est.sigma_index_ < 25
    assert est.cv_.val_deviance.shape == (4, 25)
    assert est.cv_.plan.batch == 4            # CV selection ran fold-batched
    assert est.plan_ is est.path_.plan        # plan_ describes coef_'s fit
    assert est.plan_.mode == "gathered"       # full-data refit, B=1 → host
    pred = est.predict(X)
    ss_res = float(np.sum((y - pred) ** 2))
    ss_tot = float(np.sum((y - y.mean()) ** 2))
    assert 1 - ss_res / ss_tot > 0.5          # real signal recovered
    with pytest.raises(ValueError):
        est.predict_proba(X)                  # OLS has no classes


def test_slope_estimator_no_cv_and_classifier():
    X, y, _ = make_classification(40, 20, k=3, rho=0.1, seed=3)
    clf = SlopE(family=logistic, cv=None,
                path=PathSpec(path_length=12, early_stop=False),
                policy=SolverPolicy(solver_tol=1e-9, max_iter=5000))
    clf.fit(X, y)
    assert clf.cv_ is None
    assert clf.sigma_index_ == 11             # last grid point without CV
    labels = clf.predict(X)
    assert set(np.unique(labels)) <= {0, 1}
    assert (labels == y).mean() > 0.7         # least-regularized train fit
    proba = clf.predict_proba(X)
    assert proba.shape == (40, 2)
    np.testing.assert_allclose(proba.sum(axis=1), 1.0)
    unfit = SlopE()
    with pytest.raises(ValueError, match="not fitted"):
        unfit.predict(X)


# ---------------------------------------------------------------------------
# specs through the service (plan decisions identical, plans telemetry)
# ---------------------------------------------------------------------------

def test_service_spec_submit_bit_identical_and_plans_exposed():
    from repro.serve import PathService

    X, y, lam = _problem(20, 24)
    # early_stop=False: served responses always carry the full σ grid, so
    # the direct comparator must not truncate post-hoc
    spec = PathSpec(lam=lam, path_length=6, early_stop=False)
    svc = PathService(max_batch=4, max_delay=1000.0)
    rid = svc.submit(problem=Problem(X, y), path=spec,
                     policy=SolverPolicy(**POL))
    resp = svc.poll(rid, flush=True)
    direct = slope_path(Problem(X, y), spec,
                        SolverPolicy(backend="masked", pad="bucket", **POL))
    np.testing.assert_array_equal(resp.betas, direct.betas)
    st = svc.stats()
    assert st["plans"] and all(k.startswith("serve/") for k in st["plans"])
    assert st["ws_buckets"]["capacity"] == 256
    assert "entries" in st["ws_buckets"]      # JSON-safe registry snapshot

    with pytest.raises(ValueError):           # specs and arrays don't mix
        svc.submit(X, y, problem=Problem(X, y))
    with pytest.raises(ValueError):           # the service cannot run host
        svc.submit(problem=Problem(X, y), policy=SolverPolicy(backend="host"))
    with pytest.raises(ValueError):           # one problem per request
        Xs, ys, lam2 = _batch(2, 20, 24)
        svc.submit(problem=Problem(Xs, ys))


def test_slope_path_serve_backend_round_trip():
    X, y, lam = _problem(18, 30, seed=5)
    spec = PathSpec(lam=lam, path_length=6, early_stop=False)
    out = slope_path(Problem(X, y), spec, SolverPolicy(backend="serve", **POL))
    assert out is not None and out.kkt_ok
    assert out.plan.backend == "serve"        # served results carry .plan too
    direct = slope_path(Problem(X, y), spec,
                        SolverPolicy(backend="masked", pad="bucket", **POL))
    np.testing.assert_array_equal(out.betas, direct.betas)


# ---------------------------------------------------------------------------
# benchmarks --only parsing (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def test_benchmarks_resolve_only():
    from benchmarks.run import resolve_only

    assert resolve_only("kernels") == ["kernels"]
    assert resolve_only(" serve , kernels,serve,,") == ["serve", "kernels"]
    with pytest.raises(ValueError, match="unknown sweep"):
        resolve_only("kernels,typo_sweep")
    with pytest.raises(ValueError, match="no sweeps"):
        resolve_only(" , ")
