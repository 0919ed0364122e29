"""`PathService` — a synchronous, shape-bucketed SLOPE path service.

The front door for a stream of heterogeneous fit requests::

    svc = PathService(max_batch=8, max_delay=0.02)
    rid = svc.submit(X, y, family=ols, lam_kind="bh", lam_q=0.1)
    ...                       # more submits; groups flush as they fill
    svc.flush()               # or wait for deadlines
    resp = svc.poll(rid)      # PathResponse with native-shape betas

or, declaratively, the same ``(Problem, PathSpec, SolverPolicy)`` triple
the direct :func:`repro.api.slope_path` front door takes::

    rid = svc.submit(problem=Problem(X, y, family=ols),
                     path=PathSpec(lam=LambdaSpec("bh", q=0.1)),
                     policy=SolverPolicy())   # planned like a direct call

Requests are padded into power-of-two buckets (:mod:`repro.serve.buckets`),
micro-batched per compiled-program group (:mod:`repro.serve.batcher`), and
executed through an AOT compiled-program cache (:mod:`repro.serve.cache`).
Per-request results are unpadded back to native shapes before they are
returned, with KKT status and queue/solve/occupancy telemetry attached.

Guarantees and their boundaries:

* A served request returns **bit-identical** coefficients to a direct
  ``fit_path_batched(X[None], y[None], ..., pad="bucket")`` call: both
  resolve execution shapes through the same policy/registry and batch
  slots are bitwise member-invariant (B ≥ 2).  Exception: under the
  *compact* backend, a co-batched neighbour overflowing the working-set
  bucket sends the whole batch to the masked fallback for that repair
  round — results then agree with the direct call only to solver
  tolerance, and the response flags it in ``compact_fallback``.
* The service is synchronous: deadlines are enforced on the next
  ``submit``/``poll``/``flush`` call, bounding queueing latency under
  load (there is no timer thread to wake an idle queue).

CV requests (``cv_folds=K``) expand into K same-shape fold fits that ride
the same queues as plain fits — they batch with anything else in their
bucket — and aggregate into a :class:`CvResponse` (deviance-based min and
1-SE selection) once every fold has been served.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict

import numpy as np

from ..api.plan import plan_execution
from ..api.specs import (
    PathSpec,
    Problem,
    SolverPolicy,
    ValidationError,
    apply_weights,
    as_lambda_spec,
    check_weights,
    find_nonfinite,
    shared_canonicalizer,
)
from ..core.engine import (
    CompactStats,
    EnginePath,
    _ws_bucket,
    _WS_BUCKETS,
    cv_fold_indices,
    cv_select,
    cv_val_deviance,
    grow_ws_bucket,
    null_sigma_grid,
    resolve_ws_tiers,
    second_tier_width,
)
from ..core.solver import DEFAULT_WS_TIERS
from ..core.losses import Family, ols
from ..obs import MetricsRegistry, Trace
from ..obs.profile import annotate
from ..resample.metrics import (
    RESAMPLE_METRICS,
    resample_stats,
    track_in_flight,
)
from ..resample.plans import ResamplePlan
from .batcher import (
    LambdaCanonicalizer,
    MicroBatcher,
    QueueFull,
    Rejection,
    RejectionError,
)
from .buckets import ShapeBucketPolicy, default_policy, pad_batch
from .cache import ProgramCache, ProgramSpec
from .durable import (
    CircuitBreaker,
    LoadShedGovernor,
    WatchdogTimeout,
    run_with_watchdog,
)
from .faults import FaultPlan, InjectedFault, NO_FAULTS

__all__ = ["PathService", "PathResponse", "CvResponse", "ResampleResponse"]


@dataclasses.dataclass
class _Item:
    """One admitted request, λ/σ already canonicalized, at native shape."""

    X: np.ndarray
    y: np.ndarray
    lam: np.ndarray        # native (p·m,)
    sigmas: np.ndarray     # native (L,)
    family: Family
    working_set: int | str | None
    weights: np.ndarray | None = None  # (n,) replicate row weights — set
    #   only on resample members; every item in a replicate group shares
    #   the SAME X object, so the flush pads the design once


@dataclasses.dataclass(frozen=True)
class _GroupKey:
    """Everything that must match for two requests to share one compiled
    program (and hence one batch slot assignment)."""

    family: Family
    n_rows: int
    n_cols: int
    path_length: int
    screening: str
    solver_tol: float
    max_iter: int
    kkt_tol: float
    max_refits: int
    working_set: int | str | None   # None | resolved pow2 int | "auto"
    ws_tiers: int                   # canonical tier policy (1 | 2; "auto"
    #   normalizes to 2 at submit, masked requests to 1)
    dtype: str
    y_dtype: str
    replicates: int = 0             # resample-request token (0 = plain
    #   fit): members of ONE ResamplePlan share a token — and hence one
    #   group, one compiled weight-fused program, and ONE padded design —
    #   and never co-batch with plain fits or other resample requests


@dataclasses.dataclass
class PathResponse:
    """One served path fit, unpadded to the request's native shape."""

    rid: int
    betas: np.ndarray            # (L, p) or (L, p, m)
    sigmas: np.ndarray           # (L,)
    lam: np.ndarray              # (p·m,)
    n_samples: int
    n_active: np.ndarray         # (L,)
    n_screened: np.ndarray
    n_violations: np.ndarray
    refits: np.ndarray
    solver_iters: np.ndarray
    deviance: np.ndarray
    kkt_unrepaired: np.ndarray   # (L,) bool per path step
    kkt_ok: bool                 # no step hit the repair cap unclean
    working_set: int | None
    working_set_top: int | None  # second compact tier (None: single tier)
    ws_size: np.ndarray | None
    ws_tier: np.ndarray | None   # (L,) serving tier per step (0 = fallback)
    compact_fallback: np.ndarray | None
    queue_s: float               # admission → flush
    solve_s: float               # batch device wall (shared by the batch)
    batch_size: int              # real requests in the flushed batch
    batch_occupancy: float       # real requests / executed slots
    padding_ratio: float         # padded n·p over native n·p
    cache_hit: bool              # compiled program was already resident
    health: np.ndarray | None = None  # (L,) int32 per-step health word
    #   (sticky; see repro.core.engine.PathHealth — None on pre-PR-7 paths)
    trace: Trace | None = None   # opt-in span timeline (service tracing=True)

    @property
    def total_violations(self) -> int:
        return int(self.n_violations.sum())

    @property
    def quarantined(self) -> bool:
        """True when the engine quarantined this member in-graph (the
        coefficients past the first sick step are zeroed placeholders)."""
        return self.health is not None and bool(np.asarray(self.health)[-1])

    @property
    def health_causes(self) -> tuple[str, ...]:
        from ..core.engine import health_causes

        if self.health is None:
            return ()
        return health_causes(int(np.asarray(self.health)[-1]))

    def path_result(self, *, early_stop: bool = True):
        """The same :class:`repro.core.path.PathResult` contract
        ``fit_path`` returns, early stopping applied post-hoc."""
        from ..core.path import engine_to_path_result

        betas = self.betas
        if betas.ndim == 2:
            betas = betas[:, :, None]
        ep = EnginePath(
            betas=betas, n_active=self.n_active, n_screened=self.n_screened,
            n_violations=self.n_violations, refits=self.refits,
            solver_iters=self.solver_iters, deviance=self.deviance,
            kkt_unrepaired=self.kkt_unrepaired,
            health=(self.health if self.health is not None
                    else np.zeros(len(self.sigmas), np.int32)),
        )
        return engine_to_path_result(ep, self.sigmas, self.lam, self.solve_s,
                                     early_stop=early_stop, n=self.n_samples)


@dataclasses.dataclass
class CvResponse:
    """Aggregated K-fold CV request (fold fits served like plain fits)."""

    rid: int
    sigmas: np.ndarray             # (L,) shared grid
    lam: np.ndarray
    val_deviance: np.ndarray       # (K, L)
    mean_val_deviance: np.ndarray  # (L,)
    se_val_deviance: np.ndarray    # (L,)
    best_index: int                # per the request's selection rule
    best_sigma: float
    best_index_min: int
    best_index_1se: int
    selection: str
    fold_responses: list[PathResponse]


@dataclasses.dataclass
class _CvPending:
    fold_rids: list[int]
    val_indices: list[np.ndarray]
    X: np.ndarray
    y: np.ndarray
    lam: np.ndarray
    sigmas: np.ndarray
    family: Family
    selection: str


@dataclasses.dataclass
class ResampleResponse:
    """Aggregated B-replicate resample request (members served like plain
    fits, chunked through the weight-fused replicate program)."""

    rid: int
    betas: np.ndarray              # (B, L, p) or (B, L, p, m)
    sigmas: np.ndarray             # (L,) shared grid
    lam: np.ndarray
    weights: np.ndarray            # (B, n) per-member row weights
    resample: ResamplePlan
    member_responses: list[PathResponse]

    @property
    def n_replicates(self) -> int:
        return self.betas.shape[0]

    def selection_frequencies(self, *, tol: float = 0.0) -> np.ndarray:
        """Per-(grid-point, predictor) selection frequencies over the
        replicates — the stability-selection statistic."""
        from ..resample.select import selection_frequencies

        betas = self.betas
        if betas.ndim == 3:
            betas = betas[..., None]
        return selection_frequencies(betas, tol=tol)


@dataclasses.dataclass
class _RsPending:
    member_rids: list[int]
    weights: np.ndarray            # (B, n)
    resample: ResamplePlan
    sigmas: np.ndarray
    lam: np.ndarray


class PathService:
    """Shape-bucketed micro-batching front-end over the device path engine.

    ``max_batch`` requests per group trigger a fill flush; a lone request
    flushes once ``max_delay`` seconds old (checked on the next service
    call).  ``max_batch`` is padded up to the policy's batch bucket, so the
    executed program always has the same slot count — unused slots carry
    inert dummy problems.
    """

    def __init__(self, *, max_batch: int | None = None,
                 max_delay: float = 0.02,
                 max_queue: int | None = None,
                 policy: ShapeBucketPolicy | None = None,
                 cache: ProgramCache | None = None,
                 canonicalizer: LambdaCanonicalizer | None = None,
                 clock=time.perf_counter,
                 faults: FaultPlan | None = None,
                 tracing: bool = False,
                 store=None,
                 solve_timeout_ms: float | None = None,
                 breaker_threshold: int = 5,
                 breaker_cooldown: float = 5.0,
                 shed_threshold: float = 0.9,
                 shed_priority: int = 0,
                 shed_window: int = 8):
        # explicit None checks: the cache and canonicalizer define __len__,
        # so a freshly shared (still empty) instance is falsy.  The default
        # canonicalizer is the process-wide one repro.api.LambdaSpec
        # resolves through, so named sequences are generated once and
        # shared byte-for-byte between direct and served execution.
        self.policy = policy if policy is not None else default_policy()
        if cache is not None and store is not None:
            if cache.store is not None and cache.store is not store:
                raise ValueError("cache already carries a different durable "
                                 "store; pass one or the other")
            cache.store = store
        self.cache = (cache if cache is not None
                      else ProgramCache(store=store))
        self.store = self.cache.store
        self.canonicalizer = (canonicalizer if canonicalizer is not None
                              else shared_canonicalizer())
        if solve_timeout_ms is not None and not solve_timeout_ms > 0:
            raise ValueError(
                f"solve_timeout_ms must be > 0, got {solve_timeout_ms!r}")
        # watchdog budget on device dispatch: service-wide default, further
        # tightened per request via submit(solve_timeout_ms=...) /
        # SolverPolicy.solve_timeout_ms (the batch runs under the tightest
        # budget of its members)
        self.solve_timeout_ms = solve_timeout_ms
        self._solve_timeouts: dict[int, float] = {}   # rid → seconds
        self._breaker = CircuitBreaker(threshold=breaker_threshold,
                                       cooldown=breaker_cooldown,
                                       clock=clock)
        self._governor = LoadShedGovernor(threshold=shed_threshold,
                                          priority_cutoff=shed_priority,
                                          min_window=shed_window)
        if max_batch is None:
            max_batch = self.policy.serve_slots
        self.slots = self.policy.batch_bucket(max_batch)
        self._batcher = MicroBatcher(max_batch=max_batch, max_delay=max_delay,
                                     max_queue=max_queue)
        self._clock = clock
        # fault injection (tests/chaos benches only; inert by default)
        self._faults = faults if faults is not None else NO_FAULTS
        self._lock = threading.RLock()
        self._next_rid = 0
        # finished-but-unclaimed responses are bounded: clients that never
        # poll must not pin betas arrays forever (oldest evicted, counted)
        self.max_unclaimed = 4096
        self._done: OrderedDict[int, PathResponse] = OrderedDict()
        self._cv: dict[int, _CvPending] = {}
        self._cv_hold: OrderedDict[int, PathResponse] = OrderedDict()
        self._cv_fold_rids: set[int] = set()
        self._rs: dict[int, _RsPending] = {}
        self._rs_hold: OrderedDict[int, PathResponse] = OrderedDict()
        self._rs_member_rids: set[int] = set()
        # every counter/distribution this service reports lives in ONE
        # thread-safe registry; stats() is a read-through view over it, so
        # the dict schema and the incremented numbers cannot drift.
        # Counters: submitted, completed, batches, rejected,
        # validation_rejected, results_evicted, flush{trigger=...},
        # plans{plan=...}, and kkt_violations — the paper's "simple check
        # of the optimality conditions", made observable: strong-rule
        # violations caught by the KKT repair loop.  Histograms (bounded
        # windows — one eviction policy for what used to be ad-hoc deques):
        # batch_occupancy, padding_ratio, and latency_s split by
        # scope=user/internal, because a caller's SLO is measured on what
        # the caller sees and CV fold fits would skew the percentiles
        # toward the service's own internal work.
        self.metrics = MetricsRegistry("serve")
        # opt-in request tracing: when enabled, every admitted request
        # carries a Trace whose cursor-built spans cover admit → deliver
        # with no gaps (PathResponse.trace).  Off by default — every
        # touch-point is guarded by `self._traces` truthiness, so the
        # disabled cost is one falsy dict check.
        self.tracing = bool(tracing)
        self._traces: dict[int, Trace] = {}
        # boot-time warmup: replay the durable store's manifest so every
        # program the previous process compiled for live traffic is
        # resident (loaded from the store, not rebuilt) before the first
        # request arrives
        if self.store is not None:
            self.store.replay(self.cache)

    # -- admission ----------------------------------------------------------

    def submit(self, X=None, y=None, *, family: Family = ols,
               lam: np.ndarray | None = None,
               lam_kind: str = "bh", lam_q: float = 0.1,
               sigmas: np.ndarray | None = None,
               path_length: int = 100, sigma_ratio: float | None = None,
               screening: str = "strong", solver_tol: float = 1e-8,
               max_iter: int = 5000, kkt_tol: float = 1e-4,
               max_refits: int = 32,
               working_set: int | str | None = None,
               ws_tiers: int | str = DEFAULT_WS_TIERS,
               cv_folds: int | None = None, stratify="auto",
               selection: str = "min",
               deadline_ms: float | None = None, priority: int = 0,
               solve_timeout_ms: float | None = None,
               validate: str = "strict",
               _cv_fold: bool = False,
               problem: Problem | None = None,
               path: PathSpec | None = None,
               policy: SolverPolicy | None = None,
               plan=None) -> int:
        """Queue one fit (or, with ``cv_folds``, one K-fold CV) request.

        Returns a request id for :meth:`poll`.  λ can be an explicit array
        (length p·m) or a named sequence (``lam_kind``/``lam_q``) resolved
        through the canonicalizer; the σ grid defaults to the paper's
        recipe evaluated on the *native* (unpadded) problem, so served
        results match direct ``fit_path_batched(pad="bucket")`` calls
        bit-for-bit.

        Spec form: ``submit(problem=Problem(...), path=PathSpec(...),
        policy=SolverPolicy(...))`` (or positionally, ``submit(Problem(...),
        PathSpec(...))``) — a request is then literally the serialized
        ``(Problem, PathSpec, SolverPolicy)`` triple the direct
        :func:`repro.api.slope_path` front door takes, and backend choices
        resolve through the same :func:`repro.api.plan.plan_execution`, so
        plan decisions are identical between direct and served execution.

        ``deadline_ms`` is the request's end-to-end latency budget: it
        tightens the flush deadline (queueing gets at most half the budget)
        and is the SLO the serving telemetry measures against.
        ``priority`` (higher first, default 0) orders requests within a
        group's queue; equal priorities keep FIFO order.  Both are advisory
        for this synchronous service — deadlines still need a service call
        to act on; the async front-end
        (:class:`repro.serve.AsyncPathService`) enforces them on a timer.
        """
        if problem is None and isinstance(X, Problem):
            problem, X = X, None
            if path is None and isinstance(y, PathSpec):
                path, y = y, None
        if problem is not None:
            if X is not None or y is not None:
                raise ValueError("pass either (X, y, ...) kwargs or the "
                                 "problem=/path=/policy= spec triple, not "
                                 "both")
            return self._submit_spec(problem, path, policy, plan=plan,
                                     _cv_fold=_cv_fold)
        if deadline_ms is not None and not deadline_ms > 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms!r}")
        if solve_timeout_ms is not None and not solve_timeout_ms > 0:
            raise ValueError(
                f"solve_timeout_ms must be > 0, got {solve_timeout_ms!r}")
        if isinstance(priority, bool) or not isinstance(priority, int):
            raise ValueError(f"priority must be an int, got {priority!r}")
        X = np.asarray(X)
        y = np.asarray(y)
        if X.ndim != 2 or y.shape[0] != X.shape[0]:
            raise ValueError(f"X must be (n, p) with matching y; got "
                             f"{X.shape} / {y.shape}")
        n, p = X.shape
        m = family.n_classes
        if lam is None:
            lam = self.canonicalizer.get(lam_kind, lam_q, p * m, n=n,
                                         dtype=X.dtype)
        lam = np.asarray(lam)
        if lam.shape != (p * m,):
            raise ValueError(f"lam must have p·m = {p * m} entries, got "
                             f"{lam.shape}")
        if ws_tiers not in ("auto", 1, 2) or isinstance(ws_tiers, bool):
            raise ValueError(
                f"ws_tiers must be 'auto', 1 or 2, got {ws_tiers!r}")
        if validate not in ("strict", "quarantine", "off"):
            raise ValueError(f"validate must be 'strict', 'quarantine' or "
                             f"'off', got {validate!r}")
        if validate != "off":
            issues = find_nonfinite(X=X, y=y, lam=lam, sigmas=sigmas)
            if issues and validate == "strict":
                # reject host-side before any padding/compile/device work;
                # "quarantine" admits instead and the engine's in-graph
                # health word flags the member (PathResponse.health)
                self.metrics.inc("validation_rejected")
                raise ValidationError(issues)
        # canonical tier knob for the group key: the knob is irrelevant to
        # masked programs, "auto" IS 2 under the shared recipe, and an
        # explicit W whose 2W would span the bucket degenerates to single
        # tier for every knob value — two requests that compile the same
        # program must share a micro-batch.  ("auto" working sets resolve W
        # at flush time, so their degenerate case cannot be folded here.)
        if working_set is None or ws_tiers == 1:
            ws_tiers = 1
        else:
            ws_tiers = 2
        if cv_folds is not None:
            return self._submit_cv(
                X, y, lam, family, n_folds=cv_folds, stratify=stratify,
                selection=selection, sigmas=sigmas, path_length=path_length,
                sigma_ratio=sigma_ratio, screening=screening,
                solver_tol=solver_tol, max_iter=max_iter, kkt_tol=kkt_tol,
                max_refits=max_refits, working_set=working_set,
                ws_tiers=ws_tiers, deadline_ms=deadline_ms,
                priority=priority, solve_timeout_ms=solve_timeout_ms,
                validate=validate)
        if sigmas is None:
            sigmas = null_sigma_grid(X, y, lam, family,
                                     path_length=path_length,
                                     sigma_ratio=sigma_ratio)
        sigmas = np.asarray(sigmas)
        N, P = self.policy.shape_bucket(n, p, family.name)
        ws = working_set
        if isinstance(ws, bool) or not (ws is None or ws == "auto"
                                        or isinstance(ws, int)):
            raise ValueError(f"working_set must be None, an int or 'auto', "
                             f"got {ws!r}")
        if isinstance(ws, int):
            # resolve through the engine's own rule (validation + pow2 cap)
            # so the service can never diverge from the direct path
            ws = _ws_bucket(ws, N, P, (N, P, m, family.name, screening))
            if ws_tiers == 2 and second_tier_width(ws, 2, P) is None:
                ws_tiers = 1  # 2W spans the bucket: single tier either way
        key = _GroupKey(
            family=family, n_rows=N, n_cols=P, path_length=len(sigmas),
            screening=screening, solver_tol=solver_tol, max_iter=max_iter,
            kkt_tol=kkt_tol, max_refits=max_refits, working_set=ws,
            ws_tiers=ws_tiers, dtype=X.dtype.name, y_dtype=y.dtype.name)
        item = _Item(X=X, y=y, lam=lam, sigmas=sigmas, family=family,
                     working_set=ws)
        return self._admit(key, item, deadline_ms=deadline_ms,
                           priority=priority,
                           solve_timeout_ms=solve_timeout_ms,
                           _cv_fold=_cv_fold)

    def _flush_by(self, now: float, deadline_ms: float | None) -> float:
        """Flush deadline for one admission: ``max_delay`` of queueing, or —
        when the request carries a latency budget — at most half the budget,
        leaving the other half for padding/solve/unpad."""
        if deadline_ms is None:
            return now + self._batcher.max_delay
        return now + min(self._batcher.max_delay, deadline_ms / 2e3)

    def _admission_control(self, key: _GroupKey, rid: int, *,
                           priority: int,
                           deadline_ms: float | None) -> Rejection | None:
        """Pre-queue gates (caller holds the lock): the per-program circuit
        breaker first, then adaptive load shedding.  Returns the
        :class:`Rejection` verdict (the request is NOT queued) or None.

        Both verdicts are deterministic: the breaker's state is a pure
        function of the recorded compile/execute outcomes and the clock,
        and the shed decision a pure function of the latency window — the
        ``overload`` fault site forces the shed verdict for chaos tests.
        """
        if not self._breaker.allow(key):
            self.metrics.inc("rejected")
            self.metrics.inc("breaker_rejected")
            return Rejection(
                rid=rid, reason="circuit_open",
                queued=self._batcher.pending(), max_queue=None)
        shed = False
        if self._faults.active():
            try:
                self._faults.fire("overload", rids=(rid,))
            except InjectedFault:
                shed = True
        if not shed and deadline_ms is not None:
            lat = self.metrics.histogram("latency_s", scope="user")
            shed = self._governor.should_shed(
                lat.percentile(95), deadline_ms, priority, lat.retained)
        if shed:
            self.metrics.inc("rejected")
            self.metrics.inc("shed")
            return Rejection(
                rid=rid, reason="shed",
                queued=self._batcher.pending(), max_queue=None)
        return None

    def _admit(self, key: _GroupKey, item: _Item, *,
               deadline_ms: float | None = None, priority: int = 0,
               solve_timeout_ms: float | None = None,
               _cv_fold: bool = False, _rs_member: bool = False) -> int:
        """Queue one canonicalized request; the async subclass overrides
        this to return a future and to reject-with-status at capacity.

        At queue capacity — or on an admission-control verdict (circuit
        breaker open, load shed) — raises :class:`RejectionError`, a
        :class:`QueueFull` subclass carrying the structured
        :class:`Rejection` (``err.rejection``)."""
        t_in = self._clock()
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            self.metrics.inc("submitted")
            verdict = self._admission_control(
                key, rid, priority=priority, deadline_ms=deadline_ms)
            if verdict is not None:
                raise RejectionError(verdict)
            if _cv_fold:
                # register BEFORE admission: admitting can flush this very
                # group (fill, or a deadline on a neighbour) synchronously,
                # and the flush routes responses by this membership
                self._cv_fold_rids.add(rid)
            if _rs_member:
                self._rs_member_rids.add(rid)  # same ordering constraint
            if solve_timeout_ms is not None:
                self._solve_timeouts[rid] = solve_timeout_ms / 1e3
            item = self._maybe_corrupt(rid, item)
            now = self._clock()
            try:
                filled = self._batcher.admit(
                    key, rid, item, now, priority=priority,
                    deadline=self._flush_by(now, deadline_ms))
            except QueueFull as e:
                self.metrics.inc("rejected")
                self._cv_fold_rids.discard(rid)
                self._rs_member_rids.discard(rid)
                self._solve_timeouts.pop(rid, None)
                raise RejectionError(Rejection(
                    rid=rid, reason=str(e), queued=self._batcher.pending(),
                    max_queue=self._batcher.max_queue)) from None
            self._start_trace(rid, t_in)
            if filled:
                self._flush_group(key, trigger="fill")
            self._flush_due(now)
            return rid

    def _start_trace(self, rid: int, t_in: float) -> None:
        """Open a request trace (tracing opt-in only): the "admit" span
        covers rid assignment, fault hooks and queue insertion.  Must run
        BEFORE any flush this admission triggers — a fill flush delivers
        (and closes) the trace synchronously.  Caller holds the lock."""
        if self.tracing:
            tr = Trace(rid=rid, t0=t_in)
            tr.mark("admit", self._clock())
            self._traces[rid] = tr

    def _maybe_corrupt(self, rid: int, item: _Item) -> _Item:
        """Fault-injection "admit" site: a ``nan`` spec poisons this
        request's design matrix (chaos tests only; inert in production)."""
        if not self._faults.active():
            return item
        Xf = self._faults.corrupt("admit", rid, item.X)
        if Xf is item.X:
            return item
        return dataclasses.replace(item, X=Xf)

    def _submit_spec(self, problem: Problem, path: PathSpec | None,
                     policy: SolverPolicy | None, *, plan=None,
                     _cv_fold: bool = False) -> int:
        """Admit a declarative ``(Problem, PathSpec, SolverPolicy)`` triple.

        The triple is planned through the SAME :func:`plan_execution` the
        direct front door uses (with the serving context made explicit), so
        masked-vs-compact and working-set choices can never diverge between
        ``slope_path(policy=SolverPolicy(backend="serve"))`` and a direct
        ``submit``.  ``plan`` skips re-planning when the caller (e.g.
        ``slope_path``) already resolved the triple.
        """
        path = path if path is not None else PathSpec()
        policy = policy if policy is not None else SolverPolicy()
        if policy.backend == "host":
            raise ValueError(
                "PathService cannot execute host plans; call "
                "repro.api.slope_path directly for the gathered host driver")
        if problem.batched:
            raise ValueError("PathService serves single (n, p) problems; "
                             "submit batch members individually (the "
                             "service micro-batches them)")
        if plan is None:
            plan_policy = (dataclasses.replace(policy, backend="serve")
                           if policy.backend == "auto" else policy)
            plan = plan_execution(problem, path, plan_policy)
        pln = plan
        if path.resample is not None:
            return self._submit_resample(problem, path, policy, pln)
        ws = None
        if pln.mode == "compact":
            ws = policy.working_set
            ws = "auto" if ws is None or ws == "auto" else ws
        Xw, yw = apply_weights(problem)
        m = problem.family.n_classes
        lam = as_lambda_spec(path.lam).resolve(
            problem.p * m, n=problem.n, canonicalizer=self.canonicalizer,
            dtype=Xw.dtype)
        return self.submit(
            Xw, yw, family=problem.family, lam=lam, sigmas=path.sigmas,
            path_length=path.path_length, sigma_ratio=path.sigma_ratio,
            screening=policy.screening, solver_tol=policy.solver_tol,
            max_iter=policy.max_iter, kkt_tol=policy.kkt_tol,
            max_refits=policy.max_refits, working_set=ws,
            ws_tiers=policy.ws_tiers,
            cv_folds=path.cv_folds, stratify=path.stratify,
            selection=path.selection, deadline_ms=policy.deadline_ms,
            priority=policy.priority,
            solve_timeout_ms=policy.solve_timeout_ms,
            validate=policy.validate,
            _cv_fold=_cv_fold)

    def _submit_cv(self, X, y, lam, family, *, n_folds, stratify, selection,
                   sigmas, path_length, sigma_ratio, screening, solver_tol,
                   max_iter, kkt_tol, max_refits, working_set,
                   ws_tiers=DEFAULT_WS_TIERS, deadline_ms=None,
                   priority=0, solve_timeout_ms=None,
                   validate="strict") -> int:
        if sigmas is None:
            sigmas = null_sigma_grid(X, y, lam, family,
                                     path_length=path_length,
                                     sigma_ratio=sigma_ratio)
        sigmas = np.asarray(sigmas)
        trains, vals = cv_fold_indices(y, n_folds, family=family,
                                       stratify=stratify)
        # fold fits inherit the CV request's budget and priority: the CV
        # answer is only as timely as its slowest fold
        fold_rids = [
            self.submit(X[tr], y[tr], family=family, lam=lam, sigmas=sigmas,
                        screening=screening, solver_tol=solver_tol,
                        max_iter=max_iter, kkt_tol=kkt_tol,
                        max_refits=max_refits, working_set=working_set,
                        ws_tiers=ws_tiers, deadline_ms=deadline_ms,
                        priority=priority,
                        solve_timeout_ms=solve_timeout_ms,
                        validate=validate, _cv_fold=True)
            for tr in trains
        ]
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            self.metrics.inc("submitted")
            self._cv[rid] = _CvPending(
                fold_rids=fold_rids, val_indices=vals, X=X, y=y, lam=lam,
                sigmas=sigmas, family=family, selection=selection)
            return rid

    def _submit_resample(self, problem: Problem, path: PathSpec,
                         policy: SolverPolicy, pln):
        """Fan a :class:`~repro.resample.ResamplePlan` out into B replicate
        members riding the normal shape-bucketed queues.

        Every member carries its (n,) row-weight vector and a reference to
        the SAME native design; the group key's ``replicates`` token keeps
        one request's members together, so each flushed chunk runs the
        weight-fused replicate program against ONE padded X (operands stay
        O(n·p + slots·n) per chunk — no (B, n, p) stack, no per-member X
        copies).  Chunks of up to ``slots`` members form by the same fill /
        deadline rules as plain fits — continuous chunked batching over the
        replicate axis.  Members aggregate like CV folds: collection (sync
        ``poll`` / async future) returns a :class:`ResampleResponse` once
        every member has been served.
        """
        rs = path.resample
        X = np.asarray(problem.X)
        y = np.asarray(problem.y)
        family = problem.family
        n, p = X.shape
        m = family.n_classes
        lam = as_lambda_spec(path.lam).resolve(
            p * m, n=n, canonicalizer=self.canonicalizer, dtype=X.dtype)
        lam = np.asarray(lam)
        if policy.validate == "strict":
            issues = find_nonfinite(X=X, y=y, lam=lam, sigmas=path.sigmas)
            if issues:
                self.metrics.inc("validation_rejected")
                raise ValidationError(issues)
        sigmas = path.sigmas
        if sigmas is None:
            # shared grid from the ORIGINAL problem — replicates compare
            # like with like, exactly as CV folds share the full-data grid
            sigmas = null_sigma_grid(X, y, lam, family,
                                     path_length=path.path_length,
                                     sigma_ratio=path.sigma_ratio)
        sigmas = np.asarray(sigmas)
        W = np.asarray(rs.row_weights(n, dtype=X.dtype))
        if problem.weights is not None:
            W = W * check_weights(problem)[None, :]
        y_members = (np.asarray(rs.permuted_targets(y))
                     if rs.kind == "permutation" else None)

        ws = None
        ws_tiers = 1
        if pln.mode == "compact":
            ws = policy.working_set
            ws = "auto" if ws is None or ws == "auto" else ws
            ws_tiers = 1 if policy.ws_tiers == 1 else 2
        N, P = self.policy.shape_bucket(n, p, family.name)
        if isinstance(ws, int):
            ws = _ws_bucket(ws, N, P, (N, P, m, family.name, policy.screening))
            if ws_tiers == 2 and second_tier_width(ws, 2, P) is None:
                ws_tiers = 1
        with self._lock:
            parent_rid = self._next_rid
            self._next_rid += 1
            self.metrics.inc("submitted")
        key = _GroupKey(
            family=family, n_rows=N, n_cols=P, path_length=len(sigmas),
            screening=policy.screening, solver_tol=policy.solver_tol,
            max_iter=policy.max_iter, kkt_tol=policy.kkt_tol,
            max_refits=policy.max_refits, working_set=ws, ws_tiers=ws_tiers,
            dtype=X.dtype.name, y_dtype=y.dtype.name,
            replicates=parent_rid + 1)
        handles = [
            self._admit(
                key,
                _Item(X=X, y=(y if y_members is None else y_members[b]),
                      lam=lam, sigmas=sigmas, family=family, working_set=ws,
                      weights=W[b]),
                deadline_ms=policy.deadline_ms, priority=policy.priority,
                solve_timeout_ms=policy.solve_timeout_ms, _rs_member=True)
            for b in range(rs.n_replicates)
        ]
        RESAMPLE_METRICS.inc("replicates", rs.n_replicates, kind=rs.kind,
                             backend="serve")
        track_in_flight(rs.kind, rs.n_replicates)
        return self._register_resample(parent_rid, handles, W, rs, sigmas,
                                       lam)

    def _register_resample(self, rid: int, member_rids: list[int],
                           W: np.ndarray, rs: ResamplePlan,
                           sigmas: np.ndarray, lam: np.ndarray) -> int:
        """Record the pending aggregation (``poll`` collects it); the async
        subclass overrides this to aggregate member futures instead."""
        with self._lock:
            self._rs[rid] = _RsPending(member_rids=member_rids, weights=W,
                                       resample=rs, sigmas=sigmas, lam=lam)
        return rid

    # -- flushing -----------------------------------------------------------

    def flush(self) -> int:
        """Force-flush every pending group; returns batches executed."""
        with self._lock:
            count = 0
            for key in self._batcher.groups():
                while self._flush_group(key, trigger="forced"):
                    count += 1
            return count

    def _flush_due(self, now: float) -> None:
        for key in self._batcher.due(now):
            self._flush_group(key, trigger="deadline")

    def _flush_group(self, key: _GroupKey, *, trigger: str) -> bool:
        batch = self._batcher.take(key)
        if not batch:
            return False
        self._note_taken(batch)
        self._execute_batch(key, batch, trigger=trigger)
        return True

    def _note_taken(self, batch) -> None:
        """In-flight cohort hook: the async subclass records the requests a
        serve implicates, so a worker failure is scoped to exactly that
        cohort.  Base (synchronous) service: no-op — exceptions propagate
        to the submitting caller directly."""

    def _pad_replicate(self, batch, N: int, P: int, m: int):
        """Padded operands for one weight-fused replicate chunk.

        Returns ``((X, ys, lam, sigmas, weights, p_valid), n_batch)`` in the
        replicate program's call convention: ONE shared padded (N, P)
        design, (slots, N) member responses and row weights (zero rows on
        padding and on empty slots — exactly inert under the engine's
        zero-weight guard), shared λ/σ, scalar ``p_valid``.
        """
        item0 = batch[0].item
        X0 = item0.X
        n, p = X0.shape
        dtype = X0.dtype
        Xp = np.zeros((N, P), dtype)
        Xp[:n, :p] = X0
        lam = np.zeros((P * m,), dtype)
        lam[: p * m] = np.asarray(item0.lam)[: p * m]
        ys = np.zeros((self.slots, N), item0.y.dtype)
        Wts = np.zeros((self.slots, N), dtype)
        for i, pending in enumerate(batch):
            it = pending.item
            ys[i, :n] = it.y
            Wts[i, :n] = it.weights
        sigmas = np.asarray(item0.sigmas, dtype)
        return (Xp, ys, lam, sigmas, Wts, np.int32(p)), len(batch)

    def _watchdog_budget(self, rids) -> float | None:
        """Effective watchdog budget (seconds) for one device dispatch: the
        tightest of the service-wide ``solve_timeout_ms`` and the
        per-request budgets of the batch members (None: unbounded)."""
        with self._lock:
            per = [self._solve_timeouts[r] for r in rids
                   if r in self._solve_timeouts]
        if self.solve_timeout_ms is not None:
            per.append(self.solve_timeout_ms / 1e3)
        return min(per) if per else None

    def _execute_batch(self, key: _GroupKey, batch, *, trigger: str) -> None:
        """Pad, compile-or-fetch, execute and deliver one taken batch.

        Also the retry/bisection re-dispatch path: serving the same
        pendings through here is bit-identical to the original serve (same
        program, same padded operands, slot assignment by batch order).

        Compile and execute run under the per-program circuit breaker
        (consecutive faults open it — admissions then reject with
        ``reason="circuit_open"`` until the half-open probe) and the device
        call under the watchdog: past the effective ``solve_timeout_ms``
        the dispatch is abandoned and :class:`WatchdogTimeout` raised — the
        synchronous service propagates it to the caller, the async
        dispatcher recovers the cohort through retry/bisection.
        """
        now = self._clock()
        family = key.family
        m = family.n_classes
        N, P, L = key.n_rows, key.n_cols, key.path_length
        W = key.working_set
        W2 = None
        ws_key = None
        if W is not None:
            # resolve tier widths through the engine's own recipe so the
            # served program shape can never diverge from a direct call
            ws_key = (N, P, m, family.name, key.screening)
            W, W2 = resolve_ws_tiers(W, key.ws_tiers, N, P, ws_key)
            if key.working_set != "auto":
                ws_key = None  # explicit widths never touch the registry
        spec = ProgramSpec(
            family=family, batch=self.slots, n_rows=N, n_cols=P,
            path_length=L, screening=key.screening,
            solver_tol=key.solver_tol, max_iter=key.max_iter,
            kkt_tol=key.kkt_tol, max_refits=key.max_refits, working_set=W,
            working_set_top=W2, dtype=key.dtype, y_dtype=key.y_dtype,
            variant="replicate" if key.replicates else "path")
        rids = [p.rid for p in batch]
        # opt-in tracing: traces for the rids this serve carries (empty
        # dict when tracing is off — the disabled cost is one falsy check)
        trs = ([t for t in (self._traces.get(r) for r in rids)
                if t is not None] if self._traces else [])
        for t in trs:
            # the queue span ended when the batcher released the request;
            # flush covers padding + program-spec assembly
            t.mark("queue", now)
        if key.replicates:
            # replicate chunk: every member references the SAME native X
            # (the group token guarantees it), so the design is padded
            # ONCE and members contribute only a (N,) response row and a
            # (N,) weight row — empty slots keep all-zero weights, which
            # the weight-fused engine solves as exact null members
            operands, n_batch = self._pad_replicate(batch, N, P, m)
        else:
            pb = pad_batch(
                [(it.item.X, it.item.y, it.item.lam, it.item.sigmas)
                 for it in batch],
                n_rows=N, n_cols=P, n_slots=self.slots, n_classes=m)
            operands = (pb.Xs, pb.ys, pb.lam, pb.sigmas, pb.p_valid)
            n_batch = pb.n_batch
        t0 = self._clock()

        def _device_call():
            # the worker fault site fires INSIDE the watched call, so an
            # injected "hang" trips the watchdog exactly like a stuck
            # device dispatch would
            self._faults.fire("worker", rids=rids)
            with annotate(f"repro.serve.execute/{spec.short()}"):
                out = prog(*operands)
                stats = None
                if W is not None:
                    out, stats = out
                ep = EnginePath(*(np.asarray(a) for a in out))
                if stats is not None:
                    stats = CompactStats(*(np.asarray(a) for a in stats))
            return ep, stats

        try:
            self._faults.fire("compile", rids=rids)
            for t in trs:
                t.mark("flush", self._clock(), trigger=trigger,
                       slots=self.slots, batch=n_batch)
            prog, hit = self.cache.get(spec)
            for t in trs:
                t.mark("compile", self._clock(), hit=hit,
                       program=spec.short())
            t0 = self._clock()
            ep, stats = run_with_watchdog(
                _device_call, self._watchdog_budget(rids),
                label=spec.short())
        except BaseException as e:
            if isinstance(e, WatchdogTimeout):
                self.metrics.inc("watchdog_timeouts")
            self._breaker.record_failure(key)
            raise
        else:
            self._breaker.record_success(key)
        wall = self._clock() - t0
        for t in trs:
            t.mark("execute", self._clock(), solve_ms=round(wall * 1e3, 3))
        B_real = n_batch
        # grow-on-overflow through the same helper (and the same registry)
        # fit_path_batched(working_set="auto") uses
        if ws_key is not None and stats is not None:
            grow_ws_bucket(ws_key, stats.ws_size[:B_real],
                           stats.fell_back[:B_real], W, P,
                           two_tier=key.ws_tiers != 1)
        occupancy = B_real / self.slots
        plan_summary = spec.plan().summary()
        with self._lock:
            self.metrics.inc("batches")
            self.metrics.inc("plans", plan=plan_summary)
            self.metrics.observe("batch_occupancy", occupancy)
            self.metrics.inc("flush", trigger=trigger)
            for i, pending in enumerate(batch):
                item = pending.item
                n_i, p_i = item.X.shape
                betas = ep.betas[i][:, :p_i, :]
                if m == 1:
                    betas = betas[:, :, 0]
                unrep = ep.kkt_unrepaired[i]
                pad_ratio = (N * P) / (n_i * p_i)
                resp = PathResponse(
                    rid=pending.rid, betas=betas, sigmas=item.sigmas,
                    lam=item.lam, n_samples=n_i,
                    n_active=ep.n_active[i], n_screened=ep.n_screened[i],
                    n_violations=ep.n_violations[i], refits=ep.refits[i],
                    solver_iters=ep.solver_iters[i],
                    deviance=ep.deviance[i], kkt_unrepaired=unrep,
                    kkt_ok=not bool(unrep.any()), working_set=W,
                    working_set_top=W2,
                    ws_size=None if stats is None else stats.ws_size[i],
                    ws_tier=None if stats is None else stats.tier[i],
                    compact_fallback=(None if stats is None
                                      else stats.fell_back[i]),
                    queue_s=max(0.0, now - pending.submitted), solve_s=wall,
                    batch_size=B_real, batch_occupancy=occupancy,
                    padding_ratio=pad_ratio, cache_hit=hit,
                    health=ep.health[i])
                self.metrics.observe("padding_ratio", pad_ratio)
                if trs:
                    t = self._traces.get(pending.rid)
                    if t is not None:
                        t.mark("harvest", self._clock(),
                               padding_ratio=round(pad_ratio, 3))
                self._deliver(pending.rid, resp)

    def _record_latency(self, rid: int, resp: PathResponse) -> None:
        """Queue+solve latency, routed to the user-facing or the internal
        (CV-fold-fit) window — percentiles must measure what a caller sees."""
        lat = resp.queue_s + resp.solve_s
        internal = rid in self._cv_fold_rids or rid in self._rs_member_rids
        self.metrics.observe("latency_s", lat,
                             scope="internal" if internal else "user")

    def _finish_trace(self, rid: int, resp: PathResponse) -> None:
        """Close and attach the request's trace (the final "deliver" span)."""
        if not self._traces:
            return
        tr = self._traces.pop(rid, None)
        if tr is not None:
            tr.mark("deliver", self._clock())
            resp.trace = tr

    def _deliver(self, rid: int, resp: PathResponse) -> None:
        """Hand one finished response over for collection (``poll`` here;
        the async subclass overrides this to resolve the request's future).
        Caller holds ``self._lock``."""
        self.metrics.inc("completed")
        self.metrics.inc("kkt_violations", int(resp.n_violations.sum()))
        self._record_latency(rid, resp)
        self._finish_trace(rid, resp)
        self._solve_timeouts.pop(rid, None)
        if rid in self._cv_fold_rids:
            self._store(self._cv_hold, rid, resp)
        elif rid in self._rs_member_rids:
            self._store(self._rs_hold, rid, resp)
        else:
            self._store(self._done, rid, resp)

    def _store(self, table: OrderedDict, rid: int, resp) -> None:
        table[rid] = resp
        while len(table) > self.max_unclaimed:
            old, _ = table.popitem(last=False)
            # an evicted fold orphans its CV request; drop the membership
            # so the set cannot grow unboundedly with abandoned folds
            self._cv_fold_rids.discard(old)
            self._rs_member_rids.discard(old)
            self.metrics.inc("results_evicted")

    # -- collection ---------------------------------------------------------

    def poll(self, rid: int, *, flush: bool = False):
        """Collect a finished request (None while still pending).

        ``flush=True`` force-flushes first — the synchronous way to say
        "I need this result now" without waiting for fill or deadline.
        Responses are handed out once; polling again returns None.
        """
        if flush:
            self.flush()
        with self._lock:
            self._flush_due(self._clock())
            if rid in self._cv:
                return self._collect_cv(rid)
            if rid in self._rs:
                return self._collect_rs(rid)
            return self._done.pop(rid, None)

    def _collect_cv(self, rid: int):
        cv = self._cv[rid]
        if not all(r in self._cv_hold for r in cv.fold_rids):
            return None
        del self._cv[rid]
        folds = [self._cv_hold.pop(r) for r in cv.fold_rids]
        self._cv_fold_rids.difference_update(cv.fold_rids)
        betas = np.stack([f.betas for f in folds])
        val_dev = cv_val_deviance(cv.X, cv.y, cv.val_indices, betas,
                                  cv.family)
        mean, se, best_min, best_1se = cv_select(val_dev)
        best = best_1se if cv.selection == "1se" else best_min
        self.metrics.inc("completed")
        return CvResponse(
            rid=rid, sigmas=cv.sigmas, lam=cv.lam, val_deviance=val_dev,
            mean_val_deviance=mean, se_val_deviance=se, best_index=best,
            best_sigma=float(cv.sigmas[best]), best_index_min=best_min,
            best_index_1se=best_1se, selection=cv.selection,
            fold_responses=folds)

    def _collect_rs(self, rid: int):
        rp = self._rs[rid]
        if not all(r in self._rs_hold for r in rp.member_rids):
            return None
        del self._rs[rid]
        members = [self._rs_hold.pop(r) for r in rp.member_rids]
        self._rs_member_rids.difference_update(rp.member_rids)
        self.metrics.inc("completed")
        track_in_flight(rp.resample.kind, -len(members))
        return ResampleResponse(
            rid=rid, betas=np.stack([f.betas for f in members]),
            sigmas=rp.sigmas, lam=rp.lam, weights=rp.weights,
            resample=rp.resample, member_responses=members)

    # -- warmup & telemetry -------------------------------------------------

    def warmup(self, shapes, *, family: Family = ols, path_length: int = 100,
               screening: str = "strong", solver_tol: float = 1e-8,
               max_iter: int = 5000, kkt_tol: float = 1e-4,
               max_refits: int = 32,
               working_set: int | str | None = None,
               ws_tiers: int | str = DEFAULT_WS_TIERS,
               dtype: str | None = None,
               y_dtype: str | None = None) -> dict:
        """Pre-compile the programs a list of native ``(n, p)`` shapes will
        need, so the first live request pays no XLA latency."""
        specs = []
        for n, p in shapes:
            N, P = self.policy.shape_bucket(n, p, family.name)
            W = W2 = None
            if working_set is not None:
                ws_key = (N, P, family.n_classes, family.name, screening)
                W, W2 = resolve_ws_tiers(working_set, ws_tiers, N, P, ws_key)
            specs.append(ProgramSpec(
                family=family, batch=self.slots, n_rows=N, n_cols=P,
                path_length=path_length, screening=screening,
                solver_tol=solver_tol, max_iter=max_iter, kkt_tol=kkt_tol,
                max_refits=max_refits, working_set=W, working_set_top=W2,
                dtype=dtype, y_dtype=y_dtype))
        return self.cache.warmup(specs)

    def stats(self) -> dict:
        """Service-level telemetry: throughput, occupancy, latency
        percentiles, cache and bucket-registry counters.

        A read-through view over :attr:`metrics` (the unified
        :class:`repro.obs.MetricsRegistry`) — the key schema is pinned by
        ``tests/test_obs.py`` and the async override is a strict superset."""
        m = self.metrics
        with self._lock:
            lat = m.histogram("latency_s", scope="user")
            lat_int = m.histogram("latency_s", scope="internal")
            occ = m.histogram("batch_occupancy")
            pads = m.histogram("padding_ratio")
            return {
                "submitted": m.value("submitted"),
                "completed": m.value("completed"),
                "pending": (self._batcher.pending() + len(self._cv)
                            + len(self._rs)),
                "unclaimed": (len(self._done) + len(self._cv_hold)
                              + len(self._rs_hold)),
                "results_evicted": m.value("results_evicted"),
                "batches": m.value("batches"),
                "flush_fill": m.value("flush", trigger="fill"),
                "flush_deadline": m.value("flush", trigger="deadline"),
                "flush_forced": m.value("flush", trigger="forced"),
                "flush_retry": m.value("flush", trigger="retry"),
                "rejected": m.value("rejected"),
                "validation_rejected": m.value("validation_rejected"),
                "shed": m.value("shed"),
                "watchdog_timeouts": m.value("watchdog_timeouts"),
                "breaker": {**self._breaker.stats(),
                            "rejected": m.value("breaker_rejected")},
                "kkt_violations": m.value("kkt_violations"),
                "max_queue": self._batcher.max_queue,
                "faults": self._faults.stats() if self._faults.active()
                          else None,
                "slots": self.slots,
                "occupancy_mean": occ.mean(),
                "padding_ratio_mean": pads.mean(),
                # user-facing requests only — internal CV fold fits are
                # reported apart so SLO rows measure what a caller sees
                "latency_ms_p50": lat.percentile(50) * 1e3,
                "latency_ms_p95": lat.percentile(95) * 1e3,
                "latency_count": lat.retained,
                "internal_latency_ms_p50": lat_int.percentile(50) * 1e3,
                "internal_latency_ms_p95": lat_int.percentile(95) * 1e3,
                "internal_latency_count": lat_int.retained,
                "cache": self.cache.stats(),
                # executed ExecutionPlan summaries → batch counts: the
                # planner/program decisions behind the numbers above
                "plans": m.label_values("plans", "plan"),
                "ws_buckets": _WS_BUCKETS.summary(),
                # the resampling subsystem's registry (ns=resample) — one
                # read-through dict, shared with direct execution
                "resample": resample_stats(),
            }
