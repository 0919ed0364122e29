"""Declarative specs for the one-front-door SLOPE API.

Four immutable, pytree-registered dataclasses describe a fit completely:

* :class:`Problem` — the data: ``X``, ``y``, GLM family, optional sample
  weights.  ``X`` may be ``(n, p)`` (one problem) or ``(B, n, p)`` (a batch
  of same-shape problems).
* :class:`LambdaSpec` — the penalty *sequence*: a named recipe
  (``bh`` / ``gaussian`` / ``oscar`` / ``lasso``) with its parameter, or an
  explicit array.  Named specs resolve through one process-wide memoised
  :class:`~repro.serve.batcher.LambdaCanonicalizer` (absorbed from the
  serve layer), so equal specs map to the same immutable bytes everywhere —
  direct calls and served requests build byte-equal operands.
* :class:`PathSpec` — the path: λ spec, grid length/ratio or explicit σ
  grid, early stopping, and the CV block (folds / stratify / selection).
* :class:`SolverPolicy` — *how* to execute: backend (``"auto"`` resolves
  through :func:`repro.api.plan.plan_execution`), compact working-set
  sizing, canonical-bucket padding, screening mode and solver tolerances.

Everything here is declarative — no array math happens until
:func:`repro.api.fit.slope_path` executes a resolved
:class:`~repro.api.plan.ExecutionPlan`.  The pytree registration makes the
specs legal jit/static carriers: array-valued fields (``X``, ``y``,
``weights``, explicit λ values, explicit σ grids) are leaves, everything
else is auxiliary data.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax

from ..core.losses import Family, ols
from ..core.solver import (
    DEFAULT_KKT_TOL,
    DEFAULT_MAX_REFITS,
    DEFAULT_PATH_MAX_ITER,
    DEFAULT_PATH_TOL,
    DEFAULT_WS_TIERS,
)
from ..resample.plans import ResamplePlan
from ..serve.batcher import LambdaCanonicalizer, lambda_kinds

__all__ = [
    "Problem",
    "LambdaSpec",
    "PathSpec",
    "SolverPolicy",
    "ValidationError",
    "as_lambda_spec",
    "apply_weights",
    "check_weights",
    "find_nonfinite",
    "shared_canonicalizer",
]

_NAMED_KINDS = lambda_kinds()

# the ONE process-wide named-λ memo table: LambdaSpec.resolve() and the
# PathService default both canonicalize through this instance, so a named
# sequence is generated once and shared byte-for-byte by every consumer
_SHARED_CANONICALIZER = LambdaCanonicalizer()


def shared_canonicalizer() -> LambdaCanonicalizer:
    """The process-wide named-λ-sequence memo shared by specs and serving."""
    return _SHARED_CANONICALIZER


def _shape_of(x) -> tuple | None:
    s = getattr(x, "shape", None)
    return None if s is None else tuple(s)


class ValidationError(ValueError):
    """Structured admission-time rejection: non-finite operands.

    ``issues`` is a tuple of ``(name, count, first_index)`` triples — one
    per offending array — so callers can report *which* operand is
    poisoned and where, instead of parsing a message string.  Raised
    host-side under ``validate="strict"`` (the default) before any device
    work is scheduled; ``validate="quarantine"`` admits the request and
    lets the engine's in-graph health word flag it instead.
    """

    def __init__(self, issues):
        self.issues = tuple(issues)
        parts = ", ".join(
            f"{name}: {count} non-finite value(s), first at flat index {idx}"
            for name, count, idx in self.issues)
        super().__init__(f"non-finite input rejected ({parts}); pass "
                         f"validate='quarantine' to admit and flag in-graph, "
                         f"or validate='off' to skip host-side checks")


def find_nonfinite(**arrays) -> tuple[tuple[str, int, int], ...]:
    """Scan named arrays for NaN/Inf: ``(name, count, first_flat_index)``
    per offender, empty when all finite.  ``None`` values are skipped."""
    issues = []
    for name, arr in arrays.items():
        if arr is None:
            continue
        a = np.asarray(arr)
        if not np.issubdtype(a.dtype, np.number):
            continue
        bad = ~np.isfinite(a)
        n = int(bad.sum())
        if n:
            issues.append((name, n, int(np.flatnonzero(bad.reshape(-1))[0])))
    return tuple(issues)


@dataclasses.dataclass(frozen=True, eq=False)
class Problem:
    """One fit problem (or a same-shape batch of them), family included.

    ``weights`` are per-row sample weights (OLS only — they fold into the
    quadratic loss exactly as row scaling by √w; other families have no
    such reduction and raise at execution time).
    """

    X: Any
    y: Any
    family: Family = ols
    weights: Any = None

    def __post_init__(self):
        for f in ("X", "y", "weights"):  # legacy entry points accept lists
            v = getattr(self, f)
            if isinstance(v, (list, tuple)):
                object.__setattr__(self, f, np.asarray(v))
        xs, ys = _shape_of(self.X), _shape_of(self.y)
        if xs is None or ys is None:  # pytree unflatten mid-transform
            return
        if len(xs) not in (2, 3):
            raise ValueError(f"X must be (n, p) or (B, n, p), got {xs}")
        lead = len(xs) - 1
        if tuple(ys[:lead]) != xs[:lead]:
            raise ValueError(
                f"y must be ({', '.join(str(d) for d in xs[:lead])}[, ...]) "
                f"matching X {xs}, got {ys}")
        ws = _shape_of(self.weights)
        if ws is not None and tuple(ws) != (xs[-2],):
            raise ValueError(
                f"weights must be one value per row ({xs[-2]},), got {ws}")

    def check_finite(self) -> None:
        """Raise :class:`ValidationError` if X/y/weights hold NaN/Inf."""
        issues = find_nonfinite(X=self.X, y=self.y, weights=self.weights)
        if issues:
            raise ValidationError(issues)

    @property
    def batched(self) -> bool:
        return len(_shape_of(self.X)) == 3

    @property
    def batch(self) -> int:
        xs = _shape_of(self.X)
        return xs[0] if len(xs) == 3 else 1

    @property
    def n(self) -> int:
        return _shape_of(self.X)[-2]

    @property
    def p(self) -> int:
        return _shape_of(self.X)[-1]


def check_weights(problem: Problem) -> np.ndarray:
    """Validate ``problem.weights`` and return them as an (n,) array.

    The ONE admission gate every weighted execution route shares — the
    √w-scaling host path, the device per-member row-weight path, and
    weighted resampling — so they reject identically: OLS only (no exact
    reduction exists for the other GLM losses), strictly positive.
    """
    X = np.asarray(problem.X)
    if problem.family.name != "ols":
        raise ValueError(
            "sample weights are currently supported for the OLS family only "
            f"(got {problem.family.name!r}); no exact row-scaling reduction "
            "exists for the other GLM losses")
    w = np.asarray(problem.weights, dtype=X.dtype)
    if (w <= 0).any():
        raise ValueError("sample weights must be strictly positive")
    return w


def apply_weights(problem: Problem):
    """Materialise ``problem.weights`` into transformed ``(X, y)`` arrays.

    OLS only: ``0.5·Σ wᵢ(xᵢβ − yᵢ)²`` is exactly the unweighted loss on
    ``(√w·X, √w·y)``, so the whole path stack (screening, KKT, deviances)
    applies unchanged to the scaled data.  Returns ``(X, y)`` untouched when
    no weights are set.  This is the *host/batched* weighting route; the
    device engines instead thread ``check_weights`` output through the
    replicate row-weight path (no X copy — see ``repro.resample``).
    """
    X = np.asarray(problem.X)
    y = np.asarray(problem.y)
    if problem.weights is None:
        return X, y
    sw = np.sqrt(check_weights(problem))
    return (X * sw.reshape((1,) * (X.ndim - 2) + (-1, 1)),
            y * sw.reshape((1,) * (y.ndim - 1) + (-1,)))


@dataclasses.dataclass(frozen=True, eq=False)
class LambdaSpec:
    """A penalty sequence by name (+ parameter) or by explicit values.

    ``kind`` is one of ``"bh"`` / ``"gaussian"`` / ``"oscar"`` /
    ``"lasso"`` / ``"explicit"``; ``q`` parameterizes the named recipes
    (ignored by ``lasso``); ``values`` holds the array for ``"explicit"``.
    """

    kind: str = "bh"
    q: float = 0.1
    values: Any = None

    def __post_init__(self):
        if self.kind not in _NAMED_KINDS + ("explicit",):
            raise ValueError(
                f"unknown λ sequence {self.kind!r}; choose from "
                f"{sorted(_NAMED_KINDS)} or 'explicit'")
        if self.kind == "explicit" and self.values is None:
            raise ValueError("LambdaSpec(kind='explicit') needs values")

    @classmethod
    def explicit(cls, values) -> "LambdaSpec":
        return cls(kind="explicit", values=values)

    def resolve(self, size: int, *, n: int | None = None,
                canonicalizer: LambdaCanonicalizer | None = None,
                dtype=None) -> np.ndarray:
        """The concrete ``(size,)`` sequence (size = p·m coefficients); a
        named one is built in the float dtype of ``dtype`` (the design's)."""
        if self.kind == "explicit":
            lam = np.asarray(self.values)
            # (size,) shared sequence, or a per-problem (B, size) stack for
            # batched problems (the serve layer's co-batching convention)
            if lam.ndim not in (1, 2) or lam.shape[-1] != size:
                raise ValueError(
                    f"explicit λ must have p·m = {size} entries per problem, "
                    f"got shape {lam.shape}")
            return lam
        canon = canonicalizer if canonicalizer is not None else _SHARED_CANONICALIZER
        return canon.get(self.kind, self.q, size, n=n, dtype=dtype)


def as_lambda_spec(lam) -> LambdaSpec:
    """Coerce ``lam`` to a :class:`LambdaSpec`: specs pass through, strings
    name a recipe at its default parameter, arrays become explicit specs."""
    if isinstance(lam, LambdaSpec):
        return lam
    if isinstance(lam, str):
        return LambdaSpec(kind=lam)
    return LambdaSpec.explicit(lam)


@dataclasses.dataclass(frozen=True, eq=False)
class PathSpec:
    """What path to fit: penalty, σ grid, early stop, the CV block, and the
    resampling block (``resample`` is a
    :class:`~repro.resample.ResamplePlan`: the path is then fit B times
    against the ONE shared design with per-member row weights — bootstrap /
    permutation / subsample replicates, see ``repro.resample``)."""

    lam: Any = LambdaSpec()
    path_length: int = 100
    sigma_ratio: float | None = None
    sigmas: Any = None
    early_stop: bool = True
    cv_folds: int | None = None
    stratify: Any = "auto"
    selection: str = "min"
    resample: ResamplePlan | None = None

    def __post_init__(self):
        object.__setattr__(self, "lam", as_lambda_spec(self.lam))
        if self.selection not in ("min", "1se"):
            raise ValueError(
                f"selection must be 'min' or '1se', got {self.selection!r}")
        if self.cv_folds is not None and self.cv_folds < 2:
            raise ValueError(f"cv_folds must be ≥ 2, got {self.cv_folds}")
        if self.resample is not None:
            if not isinstance(self.resample, ResamplePlan):
                raise ValueError(
                    f"resample must be a repro.resample.ResamplePlan, got "
                    f"{type(self.resample).__name__}")
            if self.cv_folds is not None:
                raise ValueError(
                    "resample and cv_folds are mutually exclusive: fold "
                    "geometry and replicate weighting both own the batch "
                    "axis — run them as separate fits")


_BACKENDS = ("auto", "host", "masked", "compact", "serve")
_SCREENINGS = ("strong", "previous", "none")


@dataclasses.dataclass(frozen=True, eq=False)
class SolverPolicy:
    """How to execute a path: backend, compact sizing, padding, tolerances.

    ``backend="auto"`` defers the host/masked/compact choice to the planner
    (:func:`repro.api.plan.plan_execution`); ``"serve"`` routes through a
    :class:`repro.serve.PathService`.  ``working_set`` controls the compact
    engine: ``None`` forbids compaction, an int pins the W bucket, and
    ``"auto"`` lets the planner size it (grow-on-overflow registry
    included).  ``ws_tiers`` controls the compact engine's second tier at
    2·W (``"auto"``: two tiers whenever 2·W < p; ``1``: single-tier; ``2``:
    demand the second tier) — a member whose screened set outgrows W but
    fits 2·W is served by the wider gather instead of dragging the whole
    batch into the masked fallback.  ``pad="auto"`` resolves to
    canonical-bucket padding exactly when serving (direct uniform batches
    keep their native shapes).

    ``deadline_ms`` / ``priority`` are serving SLO knobs: a latency budget
    (tightens the serving layer's flush deadline; measured by its
    telemetry) and a queue-ordering rank (higher first, FIFO within a
    rank).  Setting either routes ``backend="auto"`` through the serving
    layer — only a service can enforce them — and pinning a non-serve
    backend alongside them is a planning error.  ``solve_timeout_ms`` is
    the serving watchdog budget for this request's device dispatches: a
    chunk/batch call exceeding it is abandoned and the request's cohort
    recovers through the service's retry/bisection path (sync services
    accept but only the async dispatcher enforces mid-flight).

    ``validate`` is the admission-validation policy for non-finite
    operands: ``"strict"`` (default) rejects NaN/Inf in X/y/λ host-side
    with :class:`ValidationError` before any device work; ``"quarantine"``
    admits the request and relies on the engine's in-graph health word to
    flag the member (``PathHealth`` / ``PathResponse.health``); ``"off"``
    skips the host-side scan (the in-graph detector stays on regardless).

    ``telemetry`` selects solver introspection: ``"off"`` (default) skips
    it entirely, ``"summary"`` attaches per-member aggregates and
    ``"steps"`` the full per-σ-step diagnostics as a
    :class:`repro.obs.PathTrace` on ``BatchedPathResult.path_trace``.
    Built host-side from arrays the fit already transfers — it never
    changes the compiled program or the coefficients.
    """

    backend: str = "auto"
    working_set: int | str | None = "auto"
    ws_tiers: int | str = DEFAULT_WS_TIERS
    pad: str | None = "auto"
    screening: str = "strong"
    solver_tol: float = DEFAULT_PATH_TOL
    max_iter: int = DEFAULT_PATH_MAX_ITER
    kkt_tol: float = DEFAULT_KKT_TOL
    max_refits: int = DEFAULT_MAX_REFITS
    verbose: bool = False
    deadline_ms: float | None = None
    priority: int = 0
    validate: str = "strict"
    telemetry: str = "off"
    solve_timeout_ms: float | None = None

    def __post_init__(self):
        if self.validate not in ("strict", "quarantine", "off"):
            raise ValueError(
                f"validate must be 'strict', 'quarantine' or 'off', "
                f"got {self.validate!r}")
        if self.telemetry not in ("off", "summary", "steps"):
            raise ValueError(
                f"telemetry must be 'off', 'summary' or 'steps', "
                f"got {self.telemetry!r}")
        if self.backend not in _BACKENDS:
            raise ValueError(
                f"backend must be one of {_BACKENDS}, got {self.backend!r}")
        if self.screening not in _SCREENINGS:
            raise ValueError(f"unknown screening mode {self.screening!r}")
        ws = self.working_set
        if not (ws is None or ws == "auto"
                or (isinstance(ws, int) and not isinstance(ws, bool))):
            raise ValueError(
                f"working_set must be None, an int or 'auto', got {ws!r}")
        if self.ws_tiers not in ("auto", 1, 2) or isinstance(self.ws_tiers,
                                                            bool):
            raise ValueError(
                f"ws_tiers must be 'auto', 1 or 2, got {self.ws_tiers!r}")
        if self.pad not in (None, "auto", "bucket"):
            raise ValueError(
                f"pad must be None, 'auto' or 'bucket', got {self.pad!r}")
        if self.deadline_ms is not None and not self.deadline_ms > 0:
            raise ValueError(
                f"deadline_ms must be > 0, got {self.deadline_ms!r}")
        if isinstance(self.priority, bool) or not isinstance(self.priority,
                                                             int):
            raise ValueError(
                f"priority must be an int, got {self.priority!r}")
        if (self.solve_timeout_ms is not None
                and not self.solve_timeout_ms > 0):
            raise ValueError(
                f"solve_timeout_ms must be > 0, "
                f"got {self.solve_timeout_ms!r}")


def _register(cls, leaf_fields: tuple[str, ...]):
    """Register a spec dataclass as a pytree: array-valued fields are
    leaves, everything else rides along as auxiliary (static) data."""
    aux_fields = tuple(f.name for f in dataclasses.fields(cls)
                       if f.name not in leaf_fields)

    def flatten(obj):
        return (tuple(getattr(obj, f) for f in leaf_fields),
                tuple(getattr(obj, f) for f in aux_fields))

    def unflatten(aux, children):
        kw = dict(zip(leaf_fields, children))
        kw.update(zip(aux_fields, aux))
        return cls(**kw)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)


_register(Problem, ("X", "y", "weights"))
_register(LambdaSpec, ("values",))
_register(PathSpec, ("lam", "sigmas"))
_register(SolverPolicy, ())
