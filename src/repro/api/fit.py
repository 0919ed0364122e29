"""``slope_path`` — the one declarative front door for SLOPE path fitting.

Every way this repo can fit a regularization path — gathered host driver,
masked/compact batched device engines, K-fold CV, canonical-bucket padding,
the micro-batching path service — is reachable from one call::

    from repro.api import Problem, PathSpec, SolverPolicy, slope_path

    res = slope_path(Problem(X, y, family=ols),
                     PathSpec(lam=LambdaSpec("bh", q=0.1), path_length=50),
                     SolverPolicy())          # backend="auto" → planned

``slope_path`` resolves the spec triple through
:func:`repro.api.plan.plan_execution` and dispatches to the SAME private
implementations the legacy entry points (``fit_path``,
``fit_path_batched``, ``cv_path`` — now thin shims over this layer) used,
so planner-selected execution is bit-identical to the equivalent explicit
legacy kwargs.  The resolved :class:`~repro.api.plan.ExecutionPlan` is
attached to every result as ``.plan`` (``res.plan.explain()`` says why).

Returns by spec shape: a :class:`~repro.core.path.PathResult` for one
``(n, p)`` problem, a :class:`~repro.core.engine.BatchedPathResult` for a
``(B, n, p)`` batch, a :class:`~repro.core.engine.CvPathResult` when
``PathSpec.cv_folds`` is set — and, for ``SolverPolicy(backend="serve")``,
the service's :class:`~repro.serve.service.PathResponse` /
:class:`~repro.serve.service.CvResponse` (telemetry included), bit-identical
to the direct padded call by the serve layer's contract.
"""

from __future__ import annotations

import threading

import numpy as np

from .plan import ExecutionPlan, plan_execution
from .specs import (
    PathSpec,
    Problem,
    SolverPolicy,
    ValidationError,
    apply_weights,
    check_weights,
    find_nonfinite,
)

__all__ = ["slope_path", "default_service", "default_async_service"]

_SERVICE_LOCK = threading.Lock()
_DEFAULT_SERVICE = None
_DEFAULT_ASYNC_SERVICE = None


def default_service():
    """The process-wide :class:`~repro.serve.PathService` backing
    ``SolverPolicy(backend="serve")`` calls (created on first use)."""
    global _DEFAULT_SERVICE
    with _SERVICE_LOCK:
        if _DEFAULT_SERVICE is None:
            from ..serve.service import PathService

            _DEFAULT_SERVICE = PathService()
        return _DEFAULT_SERVICE


def default_async_service():
    """The process-wide :class:`~repro.serve.AsyncPathService` backing
    serve calls that carry SLO knobs (``deadline_ms`` / ``priority``).

    Created on first use — the worker thread only exists once someone asks
    for SLO enforcement.  Separate from :func:`default_service` because the
    two enforce different contracts: the sync service flushes on the next
    call, the async one on a timer.
    """
    global _DEFAULT_ASYNC_SERVICE
    with _SERVICE_LOCK:
        if _DEFAULT_ASYNC_SERVICE is None:
            from ..serve.dispatch import AsyncPathService

            _DEFAULT_ASYNC_SERVICE = AsyncPathService()
        return _DEFAULT_ASYNC_SERVICE


def _ws_arg(plan: ExecutionPlan, policy: SolverPolicy):
    """The engine-facing working_set knob for a resolved plan.

    The RAW policy value is passed through (not the plan's previewed W):
    the engines re-resolve "auto" through the same shared registry, which
    keeps grow-on-overflow semantics identical to the legacy entry points.
    """
    if plan.mode != "compact":
        return None
    ws = policy.working_set
    return "auto" if ws is None or ws == "auto" else ws


def slope_path(problem: Problem, path: PathSpec | None = None,
               policy: SolverPolicy | None = None, *,
               plan: ExecutionPlan | None = None):
    """Fit a SLOPE path for a declarative ``(problem, path, policy)`` triple.

    ``plan`` overrides the planner (pass a pre-computed
    :func:`~repro.api.plan.plan_execution` result to skip re-planning);
    otherwise the triple is planned here and the plan is threaded through
    to the executing layer (including the service).  Served responses
    always carry the full σ grid — apply early stopping through
    ``resp.path_result(early_stop=True)``.
    """
    from ..core.engine import _cv_path, _fit_path_batched
    from ..core.path import _fit_path_device, _fit_path_host

    if not isinstance(problem, Problem):
        raise TypeError(f"problem must be a repro.api.Problem, got "
                        f"{type(problem).__name__}")
    path = path if path is not None else PathSpec()
    policy = policy if policy is not None else SolverPolicy()
    pln = plan if plan is not None else plan_execution(problem, path, policy)

    if pln.backend == "serve":
        # the service enforces policy.validate at admission
        return _serve_path(problem, path, policy, pln)

    if path.resample is not None:
        return _resample_path(problem, path, policy, pln)

    # weighted single problems on the device engines ride the replicate
    # row-weight path (B = 1) instead of materialising √w·X — the same
    # code path weighted replicates use (one weighting seam, satellite of
    # the resample subsystem); host/CV/padded routes keep the exact
    # √w-scaling reduction
    rw = None
    if (problem.weights is not None and pln.backend == "device"
            and not path.cv_folds and not problem.batched
            and pln.pad != "bucket"):
        rw = check_weights(problem)
        X, y = np.asarray(problem.X), np.asarray(problem.y)
    else:
        X, y = apply_weights(problem)
    family = problem.family
    n, p, m = problem.n, problem.p, family.n_classes
    lam = path.lam.resolve(p * m, n=n, dtype=X.dtype)
    if policy.validate == "strict":
        issues = find_nonfinite(X=X, y=y, lam=lam, sigmas=path.sigmas)
        if issues:
            raise ValidationError(issues)
    # validate="quarantine"/"off": direct device backends still flag sick
    # members in-graph (BatchedPathResult.path_health); the gathered host
    # driver has no in-graph detector, so there "quarantine" degrades to
    # "off" (documented in README failure semantics)
    if getattr(lam, "ndim", 1) == 2 and not problem.batched:
        raise ValueError(
            f"a per-problem (B, p·m) λ stack (got {lam.shape}) needs a "
            f"batched (B, n, p) problem; this Problem is a single (n, p)")

    kw = dict(screening=policy.screening, path_length=path.path_length,
              sigma_ratio=path.sigma_ratio, sigmas=path.sigmas,
              solver_tol=policy.solver_tol, max_iter=policy.max_iter,
              kkt_tol=policy.kkt_tol)

    if path.cv_folds:
        if path.sigmas is not None:
            raise ValueError(
                "PathSpec.sigmas cannot be combined with cv_folds for "
                "direct execution: the CV grid is computed once from the "
                "full data so every fold shares it")
        kw.pop("sigmas")
        res = _cv_path(X, y, lam, family, n_folds=path.cv_folds,
                       max_refits=policy.max_refits,
                       working_set=_ws_arg(pln, policy),
                       ws_tiers=policy.ws_tiers,
                       stratify=path.stratify, selection=path.selection,
                       pad=pln.pad, **kw)
    elif pln.mode == "gathered":
        res = _fit_path_host(X, y, lam, family, early_stop=path.early_stop,
                             verbose=policy.verbose, **kw)
    elif problem.batched:
        res = _fit_path_batched(X, y, lam, family,
                                max_refits=policy.max_refits,
                                working_set=_ws_arg(pln, policy),
                                ws_tiers=policy.ws_tiers,
                                pad=pln.pad, telemetry=policy.telemetry,
                                **kw)
    elif rw is not None:
        # single weighted problem on a device engine: a 1-member replicate
        # batch against the shared design (no √w·X materialisation)
        from ..core.engine import _fit_replicate_batched, null_sigma_grid

        if kw["sigmas"] is None:
            # the σ grid must see the weighted problem — same statistics
            # the √w-scaled host reference derives its grid from
            sw = np.sqrt(rw)
            kw["sigmas"] = null_sigma_grid(
                X * sw[:, None], y * sw, lam, family,
                path_length=path.path_length, sigma_ratio=path.sigma_ratio)
        batched = _fit_replicate_batched(X, y, lam, family, rw[None, :],
                                         max_refits=policy.max_refits,
                                         working_set=_ws_arg(pln, policy),
                                         ws_tiers=policy.ws_tiers,
                                         telemetry=policy.telemetry, **kw)
        res = batched.path_results(early_stop=path.early_stop)[0]
    elif pln.mode == "masked":
        # identical call path to the legacy fit_path(engine="device")
        res = _fit_path_device(X, y, lam, family, early_stop=path.early_stop,
                               max_refits=policy.max_refits, pad=pln.pad,
                               **kw)
    else:  # compact, single problem: batch of one through the device engine
        batched = _fit_path_batched(X[None], y[None], lam, family,
                                    max_refits=policy.max_refits,
                                    working_set=_ws_arg(pln, policy),
                                    ws_tiers=policy.ws_tiers,
                                    pad=pln.pad,
                                    telemetry=policy.telemetry, **kw)
        res = batched.path_results(early_stop=path.early_stop)[0]
    res.plan = pln
    return res


def _resample_path(problem: Problem, path: PathSpec, policy: SolverPolicy,
                   pln: ExecutionPlan):
    """Fit the B-replicate weight-fused batch a :class:`ResamplePlan` asks
    for: one shared (n, p) design, per-member row weights, one compiled
    program.  Returns a :class:`~repro.core.engine.BatchedPathResult` over
    the replicates with ``.plan`` and ``.resample`` attached."""
    from ..core.engine import _fit_replicate_batched, null_sigma_grid
    from ..resample.metrics import RESAMPLE_METRICS

    rs = path.resample
    X = np.asarray(problem.X)
    y = np.asarray(problem.y)
    family = problem.family
    n, p, m = problem.n, problem.p, family.n_classes
    lam = path.lam.resolve(p * m, n=n, dtype=X.dtype)
    if getattr(lam, "ndim", 1) != 1:
        raise ValueError(
            "replicates share ONE design, so they share one (p·m,) λ "
            f"sequence; got a per-problem stack of shape {lam.shape}")
    if policy.validate == "strict":
        issues = find_nonfinite(X=X, y=y, lam=lam, sigmas=path.sigmas,
                                weights=problem.weights)
        if issues:
            raise ValidationError(issues)

    W = np.asarray(rs.row_weights(n, dtype=X.dtype))
    if problem.weights is not None:
        # weighted resampling: the member weight is w ⊙ c_b — exactly the
        # weighted loss of the member's resampled rows (OLS-only gate,
        # same messages as every other weighted route)
        W = W * check_weights(problem)[None, :]
    sigmas = path.sigmas
    if sigmas is None:
        sigmas = null_sigma_grid(X, y, lam, family,
                                 path_length=path.path_length,
                                 sigma_ratio=path.sigma_ratio)
    sigmas = np.asarray(sigmas)
    y_fit = np.asarray(rs.permuted_targets(y)) if rs.kind == "permutation" \
        else y

    RESAMPLE_METRICS.set_gauge("replicates_in_flight", rs.n_replicates,
                               kind=rs.kind)
    RESAMPLE_METRICS.inc("replicates", rs.n_replicates, kind=rs.kind,
                         backend=pln.mode)
    try:
        res = _fit_replicate_batched(
            X, y_fit, lam, family, W,
            screening=policy.screening, sigmas=sigmas,
            solver_tol=policy.solver_tol, max_iter=policy.max_iter,
            kkt_tol=policy.kkt_tol, max_refits=policy.max_refits,
            working_set=_ws_arg(pln, policy), ws_tiers=policy.ws_tiers,
            telemetry=policy.telemetry)
    finally:
        RESAMPLE_METRICS.set_gauge("replicates_in_flight", 0, kind=rs.kind)
    res.plan = pln
    res.resample = rs
    return res


def _serve_path(problem: Problem, path: PathSpec, policy: SolverPolicy,
                pln: ExecutionPlan):
    """Route one spec triple through the default PathService and wait.

    Requests carrying SLO knobs go through the async service — its worker
    thread enforces the deadline on a timer and its futures block here —
    plain serve requests keep the synchronous submit/poll round trip.
    """
    if problem.batched:
        raise ValueError(
            "backend='serve' takes single (n, p) problems — submit batch "
            "members individually; the service micro-batches them")
    if policy.deadline_ms is not None or policy.priority != 0:
        from ..serve.dispatch import Rejection

        svc = default_async_service()
        fut = svc.submit(problem=problem, path=path, policy=policy, plan=pln)
        resp = fut.result()
        if isinstance(resp, Rejection):
            raise RuntimeError(
                f"serve request rejected by admission control: {resp.reason} "
                f"(queued={resp.queued}, max_queue={resp.max_queue})")
        resp.plan = pln
        return resp
    svc = default_service()
    rid = svc.submit(problem=problem, path=path, policy=policy, plan=pln)
    resp = svc.poll(rid, flush=True)
    if resp is not None:
        resp.plan = pln  # same introspection surface as direct results
    return resp
