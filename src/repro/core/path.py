"""Regularization-path front-end: the strong-set and previous-set algorithms
(paper Algorithms 3 and 4) plus a no-screening baseline, over two backends.

``engine="host"`` is the classic driver: host-side NumPy orchestration
around three jit'd primitives (gradient, FISTA sub-solve, screen).  Column
gathers shrink every sub-problem to the screened set — the right trade for
a single huge p ≫ n problem, where the gathered matvec is the whole win —
and sub-problem widths are padded to power-of-four buckets so one path
reuses a handful of XLA compilations.

``engine="device"`` routes to :mod:`repro.core.engine`: the whole per-step
loop (screen → masked FISTA → KKT repair) runs inside one compiled
``lax.scan``, eliminating the per-step host↔device round-trips.  That is
the backend the batched/CV entry points build on.  ``engine="auto"``
currently selects "host" for this single-problem API (gathered sub-problems
beat masked full-width solves once p is large); batched workloads should
call :func:`repro.core.engine.fit_path_batched` directly, and *streams* of
heterogeneous single fits belong on :class:`repro.serve.PathService`, which
micro-batches them into the device engine.  ``pad="bucket"`` (device
backend) pads a single problem to the serve layer's canonical power-of-two
execution shape so heterogeneous one-off fits share compiled programs —
and return bit-identical results to the same request routed through the
service.

Both backends honour the same ``fit_path`` signature and return the same
:class:`PathResult` contract, and agree within solver tolerance (see
``tests/test_engine.py``).

Since PR 4 ``fit_path`` is a thin shim over :func:`repro.api.slope_path`:
the kwargs become a ``(Problem, PathSpec, SolverPolicy)`` spec triple, the
backend choice is made (or validated) by :func:`repro.api.plan_execution`,
and the private ``_fit_path_host`` / ``_fit_path_device`` implementations
below are invoked by the api layer — so legacy calls stay bit-identical
while new code gets one declarative front door.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Literal

import numpy as np

import jax.numpy as jnp

from ..obs.trace import Trace, keep
from .engine import EnginePath, null_gradient, null_sigma_grid, path_engine
from .kkt import kkt_violations
from .losses import Family
from .screening import strong_rule
from .sorted_l1 import pool_prox_applies
from .solver import (
    DEFAULT_KKT_TOL,
    DEFAULT_MAX_REFITS,
    DEFAULT_PATH_MAX_ITER,
    DEFAULT_PATH_TOL,
    fista,
)
from .transfer import get, put

__all__ = ["fit_path", "PathResult", "PathStep", "engine_to_path_result"]

# "kwarg not passed" sentinel (legacy defaults must not warn); local for the
# same import-cycle reason as repro.core.engine's — see the note there
_UNSET = object()


@dataclasses.dataclass
class PathStep:
    sigma: float
    active: np.ndarray          # bool (p,) — predictors with any nonzero coef
    n_active: int
    n_screened: int             # card of screened set fed to the solver
    n_violations: int           # KKT failures while solving this step
    refits: int
    deviance: float
    solver_iters: int
    wall_time: float


@dataclasses.dataclass
class PathResult:
    betas: np.ndarray           # (l, p) or (l, p, m)
    sigmas: np.ndarray
    steps: list[PathStep]
    lam: np.ndarray
    total_time: float
    total_violations: int
    plan: object | None = None  # repro.api ExecutionPlan (slope_path only)
    trace: Trace | None = None  # the host driver's phase spans (host only)

    @property
    def screen_efficiency(self) -> np.ndarray:
        """card(screened)/card(active) per step (paper's 'efficiency')."""
        return np.array(
            [s.n_screened / max(1, s.n_active) for s in self.steps]
        )


def _bucket(width: int, p: int) -> int:
    """Sub-problem width bucket: ×4 growth from 64, capped at p.

    Coarse buckets bound the number of distinct jit shapes a path can see
    at log₄(p) — the screening rule must not pay recompilation overhead in
    the n ≫ p regime (paper Fig. 5).
    """
    b = 64
    while b < width:
        b *= 4
    return min(b, p)


def _stop_triggered(beta: np.ndarray, dev: float, prev_dev: float,
                    null_dev: float, n: int) -> bool:
    """The paper's stopping rules 1–3: unique-magnitude saturation,
    deviance plateau, deviance explained.  The ONE predicate shared by the
    host loop (inline break) and the device backend (post-hoc truncation)."""
    mags = np.unique(np.abs(beta[np.abs(beta) > 0]))
    frac_change = abs(prev_dev - dev) / max(abs(null_dev), 1e-12)
    dev_explained = 1.0 - dev / null_dev if null_dev > 0 else 1.0
    return len(mags) > n or frac_change < 1e-5 or dev_explained > 0.995


def _early_stop_len(betas_pm: np.ndarray, devs: np.ndarray, null_dev: float,
                    n: int) -> int:
    """First path length at which :func:`_stop_triggered` fires."""
    prev_dev = null_dev
    for i in range(1, len(devs)):
        dev = float(devs[i])
        if _stop_triggered(betas_pm[i], dev, prev_dev, null_dev, n):
            return i + 1
        prev_dev = dev
    return len(devs)


def engine_to_path_result(ep: EnginePath, sigmas, lam, wall_time: float, *,
                          early_stop: bool = True, n: int | None = None
                          ) -> PathResult:
    """Convert a device :class:`~repro.core.engine.EnginePath` (full σ grid)
    into the host :class:`PathResult` contract, applying the early-stopping
    rules post-hoc (the device scan cannot truncate)."""
    betas_pm = np.asarray(ep.betas)          # (L, p, m)
    devs = np.asarray(ep.deviance)
    sigmas = np.asarray(sigmas)
    L = betas_pm.shape[0]
    if early_stop:
        if n is None:
            raise ValueError("early_stop requires the sample count n")
        L = _early_stop_len(betas_pm, devs, float(devs[0]), n)
    per_step = wall_time / max(L, 1)
    steps = [
        PathStep(
            sigma=float(sigmas[i]),
            active=(np.abs(betas_pm[i]) > 0).any(axis=1),
            n_active=int(ep.n_active[i]),
            n_screened=int(ep.n_screened[i]),
            n_violations=int(ep.n_violations[i]),
            refits=int(ep.refits[i]),
            deviance=float(devs[i]),
            solver_iters=int(ep.solver_iters[i]),
            wall_time=per_step,
        )
        for i in range(L)
    ]
    betas = betas_pm[:L]
    if betas.shape[2] == 1:
        betas = betas[:, :, 0]
    return PathResult(
        betas=betas,
        sigmas=sigmas[:L],
        steps=steps,
        lam=np.asarray(lam),
        total_time=wall_time,
        total_violations=int(np.asarray(ep.n_violations)[:L].sum()),
    )


def fit_path(
    X,
    y,
    lam,
    family: Family,
    *,
    screening: Literal["strong", "previous", "none"] = "strong",
    path_length: int = 100,
    sigma_ratio: float | None = None,
    sigmas: np.ndarray | None = None,
    solver_tol: float = DEFAULT_PATH_TOL,
    max_iter: int = DEFAULT_PATH_MAX_ITER,
    kkt_tol: float = DEFAULT_KKT_TOL,
    early_stop: bool = True,
    verbose: bool = False,
    engine: Literal["auto", "host", "device"] = _UNSET,
    max_refits: int = DEFAULT_MAX_REFITS,
    pad: str | None = _UNSET,
) -> PathResult:
    """Fit a full SLOPE path.

    ``screening='strong'``  → Algorithm 3 (E = strong ∪ previously-active),
    ``screening='previous'``→ Algorithm 4 (E = previously-active; check the
    strong set first, then the full set),
    ``screening='none'``    → always solve on all p predictors (baseline).

    Legacy entry point, now a thin shim over :func:`repro.api.slope_path`:
    the kwargs become a ``(Problem, PathSpec, SolverPolicy)`` triple and
    results are bit-identical to the PR-1..3 behaviour.  ``engine`` picks
    the backend ("auto" keeps the gathered host driver for this
    single-problem API); it and ``pad`` have spec replacements
    (``SolverPolicy(backend=..., pad=...)``) and warn once per process —
    see ``docs/MIGRATION.md``.  ``max_refits`` caps the device engine's
    bounded KKT repair loop; ``verbose`` is host-only.
    """
    from ..api import LambdaSpec, PathSpec, Problem, SolverPolicy, slope_path
    from ..api.compat import warn_legacy

    if engine is _UNSET:
        engine = "auto"
    else:
        warn_legacy("fit_path", "engine", "SolverPolicy(backend=...)")
    if pad is _UNSET:
        pad = None
    else:
        warn_legacy("fit_path", "pad", "SolverPolicy(pad=...)")
    if engine not in ("auto", "host", "device"):
        raise ValueError(f"engine must be 'auto', 'host' or 'device', got {engine!r}")
    if screening not in ("strong", "previous", "none"):
        raise ValueError(f"unknown screening mode {screening!r}")
    if engine == "auto":
        engine = "host"
    if pad is not None and engine != "device":
        raise ValueError("pad='bucket' requires engine='device' (the host "
                         "driver gathers sub-problems; it has no use for "
                         "canonical padded shapes)")
    return slope_path(
        Problem(X, y, family=family),
        PathSpec(lam=LambdaSpec.explicit(lam), path_length=path_length,
                 sigma_ratio=sigma_ratio, sigmas=sigmas,
                 early_stop=early_stop),
        SolverPolicy(backend="host" if engine == "host" else "masked",
                     pad=pad, screening=screening, solver_tol=solver_tol,
                     max_iter=max_iter, kkt_tol=kkt_tol,
                     max_refits=max_refits, verbose=verbose),
    )


def _fit_path_device(X, y, lam, family, *, screening, path_length,
                     sigma_ratio, sigmas, solver_tol, max_iter, kkt_tol,
                     early_stop, max_refits, pad=None):
    from .engine import _fit_path_batched, _warn_unrepaired

    t0 = time.perf_counter()
    X = np.asarray(X)
    y = np.asarray(y)
    n, p = X.shape
    m = family.n_classes
    lam = np.asarray(lam, dtype=X.dtype)
    assert lam.shape[0] == p * m, "λ must have one entry per coefficient"
    if sigmas is None:
        sigmas = null_sigma_grid(X, y, lam, family, path_length=path_length,
                                 sigma_ratio=sigma_ratio)
    sigmas = np.asarray(sigmas)
    if pad == "bucket":
        # route through the batched entry point's canonical bucket padding
        # (B padded to ≥ 2 inert slots): shares compiled programs across
        # nearby shapes, bit-identical to the PathService serving this
        # request (same policy, same execution shape)
        res = _fit_path_batched(
            X[None], y[None], lam, family, screening=screening,
            sigmas=sigmas, solver_tol=solver_tol, max_iter=max_iter,
            kkt_tol=kkt_tol, max_refits=max_refits, pad="bucket")
        return res.path_results(early_stop=early_stop)[0]
    ep = path_engine(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(lam), jnp.asarray(sigmas),
        family, screening=screening, max_iter=max_iter, tol=solver_tol,
        kkt_tol=kkt_tol, max_refits=max_refits,
    )
    ep = EnginePath(*(np.asarray(a) for a in ep))
    _warn_unrepaired(ep.kkt_unrepaired, max_refits)
    return engine_to_path_result(ep, sigmas, lam,
                                 time.perf_counter() - t0,
                                 early_stop=early_stop, n=n)


def _fit_path_host(
    X, y, lam, family, *, screening, path_length, sigma_ratio, sigmas,
    solver_tol, max_iter, kkt_tol, early_stop, verbose,
) -> PathResult:
    # one gap-free trace per path: its spans time the steps and the path,
    # and count every transfer (the span's dict rides into put/get)
    tr = Trace()

    def phase(name, step, refit=0, **attrs):
        return tr.phase(name, step=step, refit=refit, h2d_bytes=0,
                        fetches=0, d2h_bytes=0, **attrs)

    X = np.asarray(X)
    y = np.asarray(y)
    n, p = X.shape
    m = family.n_classes
    lam = np.asarray(lam, dtype=X.dtype)
    assert lam.shape[0] == p * m, "λ must have one entry per coefficient"

    def _b(b):
        # family code works with (p,) for scalar families, (p, m) otherwise
        return b[:, 0] if m == 1 else b

    # on a TPU the XLA stack PAVA runs one loop trip of small device ops
    # per element; the pool kernel runs the same arithmetic on the scalar
    # unit
    prox_method = "pool" if pool_prox_applies(X.dtype) else "stack"
    beta = np.zeros((p, m), dtype=X.dtype)
    with phase("setup", 0) as sp:
        grad_full = null_gradient(X, y, family, sp)
        null_dev = float(get(family.loss(put(X, sp), put(y, sp),
                                         put(_b(beta), sp)), sp))
        if sigmas is None:
            sigmas = null_sigma_grid(X, y, lam, family,
                                     path_length=path_length,
                                     sigma_ratio=sigma_ratio, grad0=grad_full,
                                     span=sp)
    sigmas = np.asarray(sigmas)

    betas = [beta.copy()]
    steps: list[PathStep] = [
        PathStep(float(sigmas[0]), np.zeros(p, bool), 0, 0, 0, 0, null_dev, 0,
                 tr.total_s)
    ]
    prev_active = np.zeros(p, dtype=bool)
    prev_dev = null_dev
    total_viol = 0

    for step_idx in range(1, len(sigmas)):
        t_step = tr.cursor
        sig_prev, sig = float(sigmas[step_idx - 1]), float(sigmas[step_idx])
        lam_next = sig * lam
        with phase("screen", step_idx) as sp:
            n_screened = p
            strong_mask = np.ones(p, dtype=bool)

            if screening != "none":
                k, order = strong_rule(put(grad_full, sp),
                                       put(sig_prev * lam, sp),
                                       put(lam_next, sp))
                kept_flat = get(order, sp)[: int(get(k, sp))]
                strong_mask = np.zeros(p, dtype=bool)
                strong_mask[np.unique(kept_flat // m)] = True
                n_screened = int(strong_mask.sum())

            if screening == "strong":
                E = strong_mask | prev_active
            elif screening == "previous":
                E = prev_active.copy()
                if not E.any():
                    E = strong_mask.copy()
            else:
                E = np.ones(p, dtype=bool)
            if E.sum() >= 0.5 * p:
                # screening keeps most predictors (n ≳ p regime): solve the
                # full problem — shares one compiled shape with the
                # unscreened path
                E = np.ones(p, dtype=bool)

        viol_count = 0
        refits = 0
        iters_total = 0
        checked_full = False
        while True:
            with phase("gather", step_idx, refits):
                E_idx = np.nonzero(E)[0]
                width = max(len(E_idx), 1)
                bucket = _bucket(width, p)
                Xs = np.zeros((n, bucket), dtype=X.dtype)
                Xs[:, :width] = X[:, E_idx] if len(E_idx) else 0.0
                lam_sub = np.zeros(bucket * m, dtype=lam.dtype)
                lam_sub[: len(E_idx) * m] = lam_next[: len(E_idx) * m]
                warm = np.zeros((bucket, m), dtype=X.dtype)
                if len(E_idx):
                    warm[:width] = beta[E_idx]

            with phase("solve", step_idx, refits, width=width,
                       bucket=bucket) as sp:
                res = fista(
                    put(Xs, sp),
                    put(y, sp),
                    put(lam_sub, sp),
                    put(warm if m > 1 else warm[:, 0], sp),
                    family,
                    max_iter=max_iter,
                    tol=solver_tol,
                    prox_method=prox_method,
                )
                iters_total += int(get(res.iters, sp))
                beta_sub = get(res.beta, sp).reshape(bucket, m)
                beta = np.zeros((p, m), dtype=X.dtype)
                if len(E_idx):
                    beta[E_idx] = beta_sub[:width]

            with phase("gradient", step_idx, refits) as sp:
                grad_full = get(family.gradient(put(X, sp), put(y, sp),
                                                put(_b(beta), sp)),
                                sp).reshape(p, m)

            if screening == "none":
                break

            with phase("kkt", step_idx, refits) as sp:
                ever_flat = np.repeat(E, m)
                if screening == "previous" and not checked_full:
                    subset_flat = np.repeat(strong_mask, m)
                    viol = kkt_violations(
                        grad_full.ravel(), lam_next, ever_flat,
                        subset_mask=subset_flat, tol=kkt_tol, span=sp)
                    if not viol.any():
                        checked_full = True
                        viol = kkt_violations(grad_full.ravel(), lam_next,
                                              ever_flat, tol=kkt_tol, span=sp)
                else:
                    viol = kkt_violations(grad_full.ravel(), lam_next,
                                          ever_flat, tol=kkt_tol, span=sp)

            if not viol.any():
                break
            viol_rows = np.unique(np.nonzero(viol)[0] // m)
            # Violations against the *strong* set are the rule's failures
            # (paper §2.2.3); previous-set warm misses are algorithmic.
            viol_count += int((~strong_mask[viol_rows]).sum()) if screening == "strong" else int(
                (~strong_mask[viol_rows] & ~prev_active[viol_rows]).sum()
            )
            E[viol_rows] = True
            refits += 1

        with phase("loss", step_idx, refits) as sp:
            active = np.abs(beta).max(axis=1) > 0
            dev = float(get(family.loss(put(X, sp), put(y, sp),
                                        put(_b(beta), sp)), sp))
        total_viol += viol_count
        betas.append(beta.copy())
        steps.append(
            PathStep(
                sigma=sig,
                active=active,
                n_active=int(active.sum()),
                n_screened=n_screened,
                n_violations=viol_count,
                refits=refits,
                deviance=dev,
                solver_iters=iters_total,
                wall_time=tr.cursor - t_step,
            )
        )
        prev_active = active
        if verbose:
            print(
                f"[path {step_idx:3d}] σ={sig:.4g} active={int(active.sum()):5d} "
                f"screened={n_screened:5d} viol={viol_count} iters={iters_total}"
            )

        if early_stop and _stop_triggered(beta, dev, prev_dev, null_dev, n):
            prev_dev = dev
            break
        prev_dev = dev

    keep(tr)
    arr = np.stack(betas)
    if m == 1:
        arr = arr[:, :, 0]
    return PathResult(
        betas=arr,
        sigmas=sigmas[: len(betas)],
        steps=steps,
        lam=lam,
        total_time=tr.total_s,
        total_violations=total_viol,
        trace=tr,
    )
