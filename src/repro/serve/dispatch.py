"""`AsyncPathService` — the asynchronous, continuously-batched front end.

The synchronous :class:`~repro.serve.service.PathService` enforces flush
deadlines *on the next service call*: an idle queue can hold a request past
its deadline forever (ROADMAP open item 2).  This subclass closes that gap
with a worker thread and changes the submit contract:

* ``submit`` returns a :class:`concurrent.futures.Future` instead of a
  request id (``future.rid`` carries the id; ``poll`` is disabled).
* A dispatcher thread sleeps until the earliest flush deadline
  (:meth:`~repro.serve.batcher.MicroBatcher.next_deadline`) and flushes on
  time even when no further calls arrive — deadline enforcement is
  timer-driven, not call-driven.
* Admission is bounded: past ``max_queue`` queued requests, ``submit``
  resolves the future immediately with a :class:`Rejection` status (the
  caller sees backpressure in microseconds, not a deadline miss later).
* Masked-engine groups run with **continuous batching**: the grid advances
  in ``step_chunk``-step compiled chunks
  (:func:`repro.core.engine.chunk_path_engine`) with per-slot carried
  state, so a path that early-stops frees its batch slot at the next chunk
  boundary and the next queued same-bucket request joins the *running*
  cohort — seeded mid-flight by :func:`repro.core.engine.path_init_engine`
  with bitwise the state a from-scratch run starts from.  Compact groups
  keep the whole-grid program (compact carried state is not
  slot-swappable).

Bit-identity is preserved end to end: the chunked step body is the SAME
traced body the monolithic engines scan, dead chunk steps hold the carry
exactly, and batch slots are member-invariant — an async-served result
equals the synchronous served result (and the direct padded call) at
tolerance 0.  ``tests/test_serve_async.py`` pins this.

Failure isolation (PR 7): a worker exception fails only the **implicated
cohort** — the requests the failing serve had actually taken — never the
whole outstanding future set.  The cohort is retried with exponential
backoff + jitter (``retry_limit`` attempts); a cohort that keeps failing
is **bisected** until the poison request is isolated — only it gets the
exception, and the innocent members re-dispatch through the normal
execution path, so their results are bit-identical to an unfaulted run
(same program, same padded operands).  Requests the engine quarantines
in-graph (non-finite inputs under ``validate="quarantine"``) resolve
normally with ``PathResponse.quarantined`` set — sick data is a *flagged
result*, not an exception, and never stalls the cohort.

Crash safety (PR 10): :meth:`AsyncPathService.checkpoint` pauses the
dispatcher at a chunk boundary and snapshots every admitted-but-undelivered
request — untaken queue entries plus each live slot's carried engine state
(the same ``(beta, grad, active, L, health)`` host carry the chunk rounds
already round-trip) — into a picklable :class:`ServiceCheckpoint`;
:meth:`AsyncPathService.restore` on a fresh process re-admits the queued
requests and resumes the in-flight ones from their carry, completing them
**bit-identical** to an uninterrupted run.  A ``solve_timeout_ms`` budget
(service-wide or per request) runs each chunk round under a watchdog, so a
hung device dispatch fails only its cohort through the retry/bisect path;
repeated compile/execute failures open a per-program circuit breaker and
latency pressure against request deadlines sheds the lowest-priority
admissions (both reject with a structured :class:`Rejection`).  Pair with
``store=DurableProgramStore(...)`` and a restarted server also skips every
recompile its predecessor already paid for.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time
from concurrent.futures import Future

import numpy as np

from ..core.engine import cv_fold_indices, cv_select, cv_val_deviance, \
    null_sigma_grid
from ..core.losses import Family, ols
from ..core.path import _stop_triggered
from ..core.solver import DEFAULT_WS_TIERS
from .batcher import Pending, QueueFull, Rejection
from .buckets import pad_batch
from .cache import ProgramSpec
from .durable import (
    InflightSlot,
    ServiceCheckpoint,
    WatchdogTimeout,
    run_with_watchdog,
    snapshot_queued,
)
from .service import (
    CvResponse,
    PathResponse,
    PathService,
    ResampleResponse,
    _GroupKey,
)

__all__ = ["AsyncPathService", "Rejection", "ServiceCheckpoint"]


@dataclasses.dataclass
class _Slot:
    """One occupied batch slot in a continuous run (host-side bookkeeping;
    the device carry lives in the run's persistent buffers)."""

    pending: Pending
    grid: np.ndarray       # native σ grid in the program dtype, length L
    n: int                 # native rows
    p: int                 # native cols
    inserted: float        # service clock at slot insertion
    batch_size: int        # occupied slots when this one joined
    cache_hit: bool
    early_stop: bool = True  # False for CV fold fits: the aggregation
    #   needs every fold on the full shared grid (sync parity)
    null_dev: float = 0.0
    prev_dev: float = 0.0  # early-stop carry across chunk boundaries
    cursor: int = 1        # next σ index to produce; done at cursor == L
    take: int = 0          # live steps requested from the current chunk
    solve_s: float = 0.0   # accumulated chunk walls while this slot ran
    finished: bool = False
    health0: int = 0       # init-time health word (nonzero: quarantined
    #   on admission — the slot delivers its flagged null head and frees)
    steps: list = dataclasses.field(default_factory=list)
    # each entry: (beta (p, m), n_active, n_screened, n_violations,
    #              refits, solver_iters, deviance, kkt_unrepaired, health)


class AsyncPathService(PathService):
    """Worker-thread path service: futures, SLOs, continuous batching.

    ``step_chunk`` is the continuous-batching granularity: slots can be
    recycled every ``step_chunk`` σ-steps (smaller = faster recycling, more
    host round-trips).  ``max_queue`` bounds queued depth for admission
    control.  ``autostart=False`` leaves the dispatcher stopped (useful for
    testing admission without execution); :meth:`start` launches it.
    """

    def __init__(self, *, max_batch: int | None = None,
                 max_delay: float = 0.02, step_chunk: int | None = None,
                 max_queue: int | None = 64,
                 retry_limit: int = 2, retry_backoff: float = 0.02,
                 retry_jitter: float = 0.25,
                 autostart: bool = True, policy=None, cache=None,
                 canonicalizer=None, clock=time.perf_counter, faults=None,
                 tracing: bool = False, store=None,
                 solve_timeout_ms: float | None = None,
                 breaker_threshold: int = 5, breaker_cooldown: float = 5.0,
                 shed_threshold: float = 0.9, shed_priority: int = 0,
                 shed_window: int = 8):
        super().__init__(max_batch=max_batch, max_delay=max_delay,
                         max_queue=max_queue, policy=policy, cache=cache,
                         canonicalizer=canonicalizer, clock=clock,
                         faults=faults, tracing=tracing, store=store,
                         solve_timeout_ms=solve_timeout_ms,
                         breaker_threshold=breaker_threshold,
                         breaker_cooldown=breaker_cooldown,
                         shed_threshold=shed_threshold,
                         shed_priority=shed_priority,
                         shed_window=shed_window)
        if step_chunk is None:
            step_chunk = self.policy.step_chunk
        if step_chunk < 1:
            raise ValueError(f"step_chunk must be ≥ 1, got {step_chunk}")
        if retry_limit < 0:
            raise ValueError(f"retry_limit must be ≥ 0, got {retry_limit}")
        if retry_backoff < 0 or retry_jitter < 0:
            raise ValueError("retry_backoff and retry_jitter must be ≥ 0")
        self.step_chunk = step_chunk
        # transient-failure policy: attempt k sleeps
        # retry_backoff · 2^(k-1) · (1 + retry_jitter·U[0,1)) seconds
        self.retry_limit = retry_limit
        self.retry_backoff = retry_backoff
        self.retry_jitter = retry_jitter
        self._jitter_rng = random.Random(0)  # deterministic under test
        self._futures: dict[int, Future] = {}
        # slot_recycles / chunk_batches / retries / bisections / poisoned
        # live on the inherited MetricsRegistry (self.metrics) — stats()
        # reads them back through the same registry the sync service uses
        self._current_cohort: list[Pending] = []
        self._last_error: BaseException | None = None
        self._cond = threading.Condition()
        self._stop_flag = False
        self._worker: threading.Thread | None = None
        # crash-safety state (PR 10): the continuous runner keeps, per
        # in-flight rid, a copy of the slot's carried engine state at its
        # last chunk boundary (checkpoint() collects these), and restore()
        # parks resumed carries here until the runner inserts them
        self._inflight_state: dict[int, InflightSlot] = {}
        self._resume_state: dict[int, InflightSlot] = {}
        self._ckpt_request = False
        if autostart:
            self.start()

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        """Launch the dispatcher thread (idempotent)."""
        with self._cond:
            if self._worker is not None and self._worker.is_alive():
                return
            self._stop_flag = False
            self._worker = threading.Thread(
                target=self._run, name="repro-serve-dispatch", daemon=True)
            self._worker.start()

    def close(self, *, flush: bool = True, timeout: float = 10.0) -> None:
        """Stop the dispatcher; ``flush=True`` then serves anything still
        queued synchronously so no admitted future is left unresolved.

        A fault raised during the close-time drain must not leave futures
        permanently pending: whatever the flush could not deliver is failed
        explicitly before returning — every admitted future resolves.
        """
        with self._cond:
            self._stop_flag = True
            self._cond.notify_all()
        w = self._worker
        if w is not None:
            w.join(timeout=timeout)
        drain_error: BaseException | None = None
        if flush:
            try:
                self.flush()
            except BaseException as e:
                self._last_error = drain_error = e
        with self._lock:
            leftovers = list(self._futures.items())
            self._futures.clear()
            self._traces.clear()
            self._cv_fold_rids.clear()
            self._rs_member_rids.clear()
            self._solve_timeouts.clear()
            self._resume_state.clear()
            self._inflight_state.clear()
        for rid, fut in leftovers:
            if not fut.done():
                fut.set_exception(RuntimeError(
                    f"service closed with request {rid} undelivered")
                    if drain_error is None else drain_error)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def drain(self, timeout: float | None = None) -> bool:
        """Block until every admitted request has been delivered (or
        ``timeout`` seconds passed; returns False on timeout).

        Waits on the dispatcher's condition variable — every delivery
        notifies it — instead of polling on a sleep loop.  The idle
        predicate is read without ``self._lock`` (deliverers hold it while
        notifying, so taking it here would be an ABBA ordering); a stale
        read only costs one extra wait-and-recheck, never a wrong answer.
        """
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self._futures or self._batcher.pending():
                if deadline is None:
                    self._cond.wait()
                else:
                    left = deadline - time.monotonic()
                    if left <= 0 or not self._cond.wait(timeout=left):
                        return not (self._futures
                                    or self._batcher.pending())
            return True

    # -- checkpoint / restore -----------------------------------------------

    def checkpoint(self, *, timeout: float = 60.0) -> ServiceCheckpoint:
        """Pause serving at the next chunk boundary and snapshot every
        admitted-but-undelivered request.

        The dispatcher is signalled, joined, and the snapshot assembled
        from the batcher queue (untaken requests, non-destructively) plus
        the continuous runner's shadowed per-slot carry (in-flight
        requests at their last chunk boundary).  The service is left
        STOPPED — a checkpoint is the prelude to a process exit; call
        :meth:`start` to keep serving in place, or :meth:`restore` the
        snapshot on a fresh service, where every captured request
        completes bit-identical to an uninterrupted run.
        """
        with self._cond:
            self._ckpt_request = True
            self._stop_flag = True
            self._cond.notify_all()
        w = self._worker
        if w is not None:
            w.join(timeout=timeout)
            if w.is_alive():
                self._ckpt_request = False
                raise RuntimeError(
                    f"dispatcher did not reach a chunk boundary within "
                    f"{timeout} s; checkpoint aborted")
        self._ckpt_request = False
        with self._lock:
            queued = snapshot_queued(self._batcher, self._cv_fold_rids,
                                     self._rs_member_rids)
            queued_rids = {q.rid for q in queued}
            inflight = [st for rid, st in self._inflight_state.items()
                        if rid in self._futures and rid not in queued_rids]
            self.metrics.inc("checkpoints")
        return ServiceCheckpoint(queued=queued, inflight=inflight)

    def restore(self, ckpt: ServiceCheckpoint) -> dict:
        """Re-admit every request a :class:`ServiceCheckpoint` captured;
        returns ``{old_rid: Future}`` keyed by the checkpointed process's
        request ids.

        Queued requests re-enter normal admission.  In-flight requests
        re-enter WITH their carried engine state, which the continuous
        runner scatters into a batch slot in place of init seeding — the
        resumed path picks up at the exact chunk boundary the checkpoint
        cut (per-slot σ windows are cursor-driven, so chunk alignment is
        preserved) and its result is bit-identical to an uninterrupted
        run.  Refuses a checkpoint taken under a different jax/jaxlib/
        backend fingerprint: bit-identity cannot be promised across
        version or backend changes.
        """
        from .durable import backend_fingerprint

        here = backend_fingerprint()
        if ckpt.fingerprint != here:
            raise RuntimeError(
                f"checkpoint fingerprint {ckpt.fingerprint!r} does not "
                f"match this process ({here!r}); resumed execution would "
                f"not be bit-identical")
        futures: dict = {}
        for q in ckpt.queued:
            futures[q.rid] = self._admit(
                q.key, q.item, priority=q.priority,
                _cv_fold=q.cv_fold, _rs_member=q.rs_member)
            self.metrics.inc("restored")
        for st in ckpt.inflight:
            futures[st.rid] = self._admit(
                st.key, st.item, priority=st.priority,
                _cv_fold=st.cv_fold, _resume=st)
            self.metrics.inc("restored")
        return futures

    # -- admission (future-returning) ---------------------------------------

    def _admit(self, key: _GroupKey, item, *, deadline_ms=None, priority=0,
               solve_timeout_ms: float | None = None,
               _cv_fold: bool = False, _rs_member: bool = False,
               _resume: InflightSlot | None = None) -> Future:
        fut: Future = Future()
        t_in = self._clock()
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            self.metrics.inc("submitted")
            fut.rid = rid
            verdict = self._admission_control(
                key, rid, priority=priority, deadline_ms=deadline_ms)
            if verdict is not None:
                # async contract: rejection is a resolved future, not an
                # exception — callers see backpressure without waiting
                fut.set_result(verdict)
                return fut
            if _cv_fold:
                self._cv_fold_rids.add(rid)
            if _rs_member:
                self._rs_member_rids.add(rid)
            if solve_timeout_ms is not None:
                self._solve_timeouts[rid] = solve_timeout_ms / 1e3
            if _resume is not None:
                # restore(): the continuous runner scatters this carried
                # state into the slot instead of init-seeding it
                self._resume_state[rid] = _resume
            item = self._maybe_corrupt(rid, item)
            now = self._clock()
            try:
                self._batcher.admit(
                    key, rid, item, now, priority=priority,
                    deadline=self._flush_by(now, deadline_ms))
            except QueueFull as e:
                self.metrics.inc("rejected")
                self._cv_fold_rids.discard(rid)
                self._rs_member_rids.discard(rid)
                self._solve_timeouts.pop(rid, None)
                self._resume_state.pop(rid, None)
                fut.set_result(Rejection(
                    rid=rid, reason=str(e), queued=self._batcher.pending(),
                    max_queue=self._batcher.max_queue))
                return fut
            self._start_trace(rid, t_in)
            self._futures[rid] = fut
        with self._cond:
            self._cond.notify_all()  # wake the dispatcher: new work/deadline
        return fut

    def _deliver(self, rid: int, resp: PathResponse) -> None:
        """Resolve the request's future (caller holds ``self._lock``)."""
        self.metrics.inc("completed")
        self.metrics.inc("kkt_violations", int(resp.n_violations.sum()))
        self._record_latency(rid, resp)   # before dropping fold membership
        self._finish_trace(rid, resp)
        self._cv_fold_rids.discard(rid)
        self._rs_member_rids.discard(rid)
        self._solve_timeouts.pop(rid, None)
        self._inflight_state.pop(rid, None)
        fut = self._futures.pop(rid, None)
        if fut is not None and not fut.done():
            fut.set_result(resp)
        with self._cond:
            self._cond.notify_all()  # drain() waits on delivery

    def poll(self, rid, *, flush: bool = False):
        raise TypeError("AsyncPathService resolves results through the "
                        "futures submit() returns; there is nothing to poll")

    # -- CV: fold futures aggregate through a done-callback -----------------

    def _submit_cv(self, X, y, lam, family, *, n_folds, stratify, selection,
                   sigmas, path_length, sigma_ratio, screening, solver_tol,
                   max_iter, kkt_tol, max_refits, working_set,
                   ws_tiers=DEFAULT_WS_TIERS, deadline_ms=None,
                   priority=0, solve_timeout_ms=None,
                   validate="strict") -> Future:
        if sigmas is None:
            sigmas = null_sigma_grid(X, y, lam, family,
                                     path_length=path_length,
                                     sigma_ratio=sigma_ratio)
        sigmas = np.asarray(sigmas)
        trains, vals = cv_fold_indices(y, n_folds, family=family,
                                       stratify=stratify)
        fold_futs = [
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
        cv_fut: Future = Future()
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            self.metrics.inc("submitted")
        cv_fut.rid = rid
        remaining = [len(fold_futs)]
        agg_lock = threading.Lock()

        def on_fold_done(_):
            with agg_lock:
                remaining[0] -= 1
                if remaining[0]:
                    return
            try:
                folds = [f.result() for f in fold_futs]
                rej = next((r for r in folds if isinstance(r, Rejection)),
                           None)
                if rej is not None:
                    cv_fut.set_result(Rejection(
                        rid=rid,
                        reason=f"CV fold rejected: {rej.reason}",
                        queued=rej.queued, max_queue=rej.max_queue))
                    return
                betas = np.stack([f.betas for f in folds])
                val_dev = cv_val_deviance(X, y, vals, betas, family)
                mean, se, best_min, best_1se = cv_select(val_dev)
                best = best_1se if selection == "1se" else best_min
                self.metrics.inc("completed")
                cv_fut.set_result(CvResponse(
                    rid=rid, sigmas=sigmas, lam=lam, val_deviance=val_dev,
                    mean_val_deviance=mean, se_val_deviance=se,
                    best_index=best, best_sigma=float(sigmas[best]),
                    best_index_min=best_min, best_index_1se=best_1se,
                    selection=selection, fold_responses=folds))
            except BaseException as e:  # pragma: no cover - defensive
                if not cv_fut.done():
                    cv_fut.set_exception(e)

        for f in fold_futs:
            f.add_done_callback(on_fold_done)
        return cv_fut

    # -- resample: member futures aggregate the same way --------------------

    def _register_resample(self, rid, member_futs, W, rs, sigmas,
                           lam) -> Future:
        from ..resample.metrics import track_in_flight

        parent: Future = Future()
        parent.rid = rid
        remaining = [len(member_futs)]
        agg_lock = threading.Lock()

        def on_member_done(_):
            with agg_lock:
                remaining[0] -= 1
                if remaining[0]:
                    return
            try:
                members = [f.result() for f in member_futs]
                track_in_flight(rs.kind, -len(members))
                rej = next((r for r in members if isinstance(r, Rejection)),
                           None)
                if rej is not None:
                    parent.set_result(Rejection(
                        rid=rid,
                        reason=f"replicate member rejected: {rej.reason}",
                        queued=rej.queued, max_queue=rej.max_queue))
                    return
                self.metrics.inc("completed")
                parent.set_result(ResampleResponse(
                    rid=rid, betas=np.stack([f.betas for f in members]),
                    sigmas=sigmas, lam=lam, weights=W, resample=rs,
                    member_responses=members))
            except BaseException as e:  # pragma: no cover - defensive
                if not parent.done():
                    parent.set_exception(e)

        for f in member_futs:
            f.add_done_callback(on_member_done)
        return parent

    # -- the dispatcher -----------------------------------------------------

    def _next_group(self):
        fill = self._batcher.fillable()
        if fill:
            return fill[0], "fill"
        due = self._batcher.due(self._clock())
        if due:
            return due[0], "deadline"
        return None, None

    def _run(self) -> None:
        while True:
            key = trigger = None
            with self._cond:
                while not self._stop_flag:
                    key, trigger = self._next_group()
                    if key is not None:
                        break
                    nd = self._batcher.next_deadline()
                    if nd is None:
                        self._cond.wait()
                    else:
                        # +0.1 ms so the post-sleep clock is past the
                        # deadline and due() actually returns the group
                        self._cond.wait(
                            timeout=max(0.0, nd - self._clock()) + 1e-4)
                if self._stop_flag:
                    return
            self._serve_safely(key, trigger)

    # -- failure isolation: cohort-scoped retry, backoff, bisection ---------

    def _note_taken(self, batch) -> None:
        """Record what the in-flight serve has actually taken — the blast
        radius of a worker exception is exactly this cohort."""
        self._current_cohort.extend(batch)

    def _serve_safely(self, key: _GroupKey, trigger: str) -> None:
        """One dispatcher serve with scoped failure handling.

        On an exception only the implicated cohort (requests this serve
        took) enters recovery; every other outstanding future is untouched.
        A failure *before* anything was taken (e.g. an injected compile
        fault) implicates the queued group, which is popped and recovered
        through the same path so a persistent failure cannot spin the
        dispatcher hot on an undrainable queue.
        """
        self._current_cohort = []
        try:
            self._serve_group(key, trigger)
        except BaseException as e:  # keep serving; recover the cohort
            self._last_error = e
            with self._lock:
                cohort = [p for p in self._current_cohort
                          if p.rid in self._futures]
            if not cohort:
                cohort = self._batcher.take(key)
            self._recover(key, cohort, e)
        finally:
            self._current_cohort = []

    def _sleep_backoff(self, attempt: int) -> None:
        delay = self.retry_backoff * (2 ** (attempt - 1))
        delay *= 1.0 + self.retry_jitter * self._jitter_rng.random()
        if delay > 0:
            time.sleep(delay)

    def _trace_recovery(self, cohort: list[Pending], name: str,
                        **attrs) -> None:
        """Attach a zero-width recovery child span (retry/bisect) to every
        traced cohort member — poison isolation stays visible per request."""
        if not self._traces:
            return
        now = self._clock()
        with self._lock:
            for p in cohort:
                tr = self._traces.get(p.rid)
                if tr is not None:
                    tr.child(name, t0=now, t1=now, **attrs)

    def _recover(self, key: _GroupKey, cohort: list[Pending],
                 exc: BaseException, *, retries: int | None = None) -> None:
        """Retry a failed cohort, then bisect it down to the poison.

        ``retries`` whole-cohort re-serves (exponential backoff + jitter)
        absorb transient faults; a cohort that still fails is split in two
        and each half re-served with zero retries — O(log B) extra serves
        isolate a single poison request, which alone gets the exception.
        Innocent members re-dispatch through the normal execution path, so
        their results are bit-identical to an unfaulted run.  Total work is
        bounded: retries + at most 2·B − 1 bisection serves.
        """
        retries = self.retry_limit if retries is None else retries
        for attempt in range(1, retries + 1):
            with self._lock:
                cohort = [p for p in cohort if p.rid in self._futures]
            if not cohort:
                return
            self._sleep_backoff(attempt)
            self.metrics.inc("retries")
            self._trace_recovery(cohort, "retry", attempt=attempt,
                                 cohort_size=len(cohort))
            try:
                self._serve_cohort(key, cohort)
                return
            except BaseException as e:
                self._last_error = exc = e
        with self._lock:
            cohort = [p for p in cohort if p.rid in self._futures]
        if not cohort:
            return
        if len(cohort) == 1:
            pending = cohort[0]
            with self._lock:
                self.metrics.inc("poisoned")
                self._cv_fold_rids.discard(pending.rid)
                self._solve_timeouts.pop(pending.rid, None)
                self._inflight_state.pop(pending.rid, None)
                fut = self._futures.pop(pending.rid, None)
                tr = self._traces.pop(pending.rid, None)
            if tr is not None:
                # the failed request's timeline rides on the exception so
                # callers can see the retry/bisect history that isolated it
                tr.mark("poisoned", self._clock())
                try:
                    exc.trace = tr
                except Exception:  # exceptions with __slots__
                    pass
            if fut is not None and not fut.done():
                fut.set_exception(exc)
            with self._cond:
                self._cond.notify_all()  # drain() waits on resolution
            return
        self.metrics.inc("bisections")
        self._trace_recovery(cohort, "bisect", cohort_size=len(cohort))
        mid = len(cohort) // 2
        for half in (cohort[:mid], cohort[mid:]):
            try:
                self._serve_cohort(key, half)
            except BaseException as e:
                self._last_error = e
                self._recover(key, half, e, retries=0)

    def _serve_cohort(self, key: _GroupKey, cohort: list[Pending]) -> None:
        """Re-dispatch exactly ``cohort`` (no new queue pulls) through the
        normal execution path — same programs, same padded operands, so a
        successful re-serve is bit-identical to an unfaulted serve."""
        if key.working_set is not None or key.replicates:
            self._execute_batch(key, list(cohort), trigger="retry")
        else:
            self._run_continuous(key, "retry", cohort=list(cohort))

    def _serve_group(self, key: _GroupKey, trigger: str) -> None:
        if key.working_set is not None or key.replicates:
            # compact carried state is not slot-swappable, and replicate
            # chunks already batch continuously over the member axis:
            # whole-grid program, same as the synchronous service (delivery
            # still resolves futures through the _deliver override)
            self._flush_group(key, trigger=trigger)
        else:
            self._run_continuous(key, trigger)

    # -- continuous batching (masked groups) --------------------------------

    def _chunk_specs(self, key: _GroupKey):
        base = dict(
            family=key.family, batch=self.slots, n_rows=key.n_rows,
            n_cols=key.n_cols, path_length=key.path_length,
            screening=key.screening, solver_tol=key.solver_tol,
            max_iter=key.max_iter, kkt_tol=key.kkt_tol,
            max_refits=key.max_refits, dtype=key.dtype, y_dtype=key.y_dtype)
        return (ProgramSpec(**base, variant="init"),
                ProgramSpec(**base, variant="chunk",
                            step_chunk=self.step_chunk))

    def _run_continuous(self, key: _GroupKey, trigger: str,
                        cohort: list[Pending] | None = None) -> None:
        """Breaker-instrumented wrapper around the continuous runner.

        Any failure — injected, device, watchdog timeout — counts one
        consecutive-failure strike against ``key``'s circuit before the
        PR-7 recovery machinery sees it; a clean drain (including the
        innocent halves of a bisection, which re-enter here) resets the
        count, so only a persistent fault opens the circuit.
        """
        try:
            self._run_continuous_impl(key, trigger, cohort=cohort)
        except BaseException:
            if self._breaker.record_failure(key) == "open":
                self._trace_recovery(list(self._current_cohort),
                                     "breaker_open",
                                     threshold=self._breaker.threshold)
            raise
        else:
            self._breaker.record_success(key)

    def _run_continuous_impl(self, key: _GroupKey, trigger: str,
                             cohort: list[Pending] | None = None) -> None:
        """Serve one masked group until it drains, recycling slots.

        Persistent padded operand buffers plus the scan carry round-trip
        through the host between ``step_chunk``-step compiled chunks.  At
        every chunk boundary, finished slots (grid done or early-stopped)
        deliver and free; queued same-group requests take the free slots
        and are seeded by the init program — run on the whole updated batch,
        scattered only into the inserted slots, so standing neighbours'
        state is untouched (bitwise).

        ``cohort`` (retry/bisection re-dispatch) serves exactly those
        pendings and never pulls from the queue — failure recovery must
        not widen its own blast radius.
        """
        family = key.family
        m = family.n_classes
        S, N, P, L = self.slots, key.n_rows, key.n_cols, key.path_length
        C = self.step_chunk
        f = np.dtype(key.dtype)
        init_spec, chunk_spec = self._chunk_specs(key)
        self._faults.fire("compile", rids=(
            () if cohort is None else [p.rid for p in cohort]))
        init_prog, init_hit = self.cache.get(init_spec)
        chunk_prog, chunk_hit = self.cache.get(chunk_spec)
        first_hit = init_hit and chunk_hit

        Xs = np.zeros((S, N, P), f)
        ys = np.zeros((S, N), np.dtype(key.y_dtype))
        lam = np.zeros((S, P * m), f)
        p_valid = np.zeros((S,), np.int32)
        sig_prev = np.ones((S, C), f)
        sig_next = np.ones((S, C), f)
        live = np.zeros((S, C), bool)
        beta = np.zeros((S, P, m), f)
        grad = np.zeros((S, P, m), f)
        active = np.zeros((S, P), bool)
        Lc = np.ones((S,), f)
        Hc = np.zeros((S,), np.int32)
        slots: list[_Slot | None] = [None] * S
        # stable buffer handles for _finish_slot's lane blanking; the chunk
        # outputs below are copied INTO these arrays (np.copyto), never
        # rebound, so this dict cannot go stale
        bufs = dict(Xs=Xs, ys=ys, lam=lam, p_valid=p_valid, beta=beta,
                    grad=grad, active=active, Lc=Lc, Hc=Hc)

        plan_summary = chunk_spec.plan().summary()
        self.metrics.inc("flush", trigger=trigger)
        self.metrics.inc("plans", plan=plan_summary)

        rounds = 0
        while True:
            if self._ckpt_request and cohort is None:
                # checkpoint(): pause at this chunk boundary — untaken work
                # stays queued, live slots' carry is already shadowed in
                # self._inflight_state by the end of the previous round.
                # Recovery cohorts run to completion: their pendings left
                # the queue long ago and re-admission owns no record of
                # them, so pausing mid-recovery would strand futures.
                return
            # refill free slots from the queue (the slot-recycle seam), or —
            # in cohort mode — from the re-dispatched pendings only
            free = [i for i in range(S) if slots[i] is None]
            if cohort is not None:
                taken = [cohort.pop(0)
                         for _ in range(min(len(free), len(cohort)))]
            else:
                taken = (self._batcher.take(key, limit=len(free))
                         if free else [])
                if taken:
                    self._note_taken(taken)
            occupied = S - len(free) + len(taken)
            inserted = []
            resumed = []
            now = self._clock()
            if self._traces and taken:
                with self._lock:
                    for pend in taken:
                        tr = self._traces.get(pend.rid)
                        if tr is not None:
                            tr.mark("queue", now, trigger=trigger)
            for i, pending in zip(free, taken):
                item = pending.item
                pb = pad_batch(
                    [(item.X, item.y, item.lam, item.sigmas)],
                    n_rows=N, n_cols=P, n_slots=1, n_classes=m)
                Xs[i] = pb.Xs[0]
                ys[i] = pb.ys[0]
                lam[i] = pb.lam[0]
                p_valid[i] = pb.p_valid[0]
                with self._lock:
                    es = pending.rid not in self._cv_fold_rids
                    rs = self._resume_state.pop(pending.rid, None)
                slots[i] = _Slot(
                    pending=pending, grid=np.asarray(item.sigmas, f),
                    n=item.X.shape[0], p=item.X.shape[1], inserted=now,
                    batch_size=occupied, early_stop=es,
                    cache_hit=first_hit if rounds == 0 else True)
                if rs is None:
                    inserted.append(i)
                    continue
                # restore(): scatter the checkpointed carry into the lane
                # instead of init-seeding it — the slot continues from the
                # exact chunk boundary the checkpoint cut, so per-slot σ
                # windows (cursor-driven, not round-driven) and every later
                # step are bit-identical to an uninterrupted run
                s = slots[i]
                beta[i] = rs.beta
                grad[i] = rs.grad
                active[i] = rs.active
                Lc[i] = rs.L
                Hc[i] = rs.H
                s.cursor = rs.cursor
                s.steps = list(rs.steps)
                s.null_dev = rs.null_dev
                s.prev_dev = rs.prev_dev
                s.health0 = rs.health0
                s.early_stop = rs.early_stop
                s.solve_s = rs.solve_s
                resumed.append(i)
                if self._traces:
                    with self._lock:
                        tr = self._traces.get(pending.rid)
                    if tr is not None:
                        tr.mark("restore", self._clock(), slot=i,
                                cursor=rs.cursor)
            for i in resumed:
                # a carry checkpointed at the finish line (sick at init, or
                # cursor already past the grid) delivers immediately
                if slots[i].health0 or slots[i].cursor >= L:
                    self._finish_slot(i, slots, key, bufs)
            if inserted:
                if rounds > 0:
                    # joined a cohort already in flight: true recycling
                    self.metrics.inc("slot_recycles", len(inserted))
                # prefill on the WHOLE updated batch, scatter only the new
                # slots — standing neighbours keep their carried state
                g0, nd0, L0, h0 = (np.asarray(a)
                                   for a in init_prog(Xs, ys))
                for i in inserted:
                    beta[i] = 0.0
                    grad[i] = g0[i]
                    active[i] = False
                    Lc[i] = L0[i]
                    Hc[i] = h0[i]
                    slots[i].health0 = int(h0[i])
                    slots[i].null_dev = slots[i].prev_dev = float(nd0[i])
                    if self._traces:
                        with self._lock:
                            tr = self._traces.get(slots[i].pending.rid)
                        if tr is not None:
                            tr.mark("init", self._clock(),
                                    recycled=rounds > 0, slot=i)
                    if L < 2:  # degenerate grid: null model only
                        self._finish_slot(i, slots, key, bufs)
                    elif slots[i].health0:
                        # sick at init (quarantine-mode admission): every
                        # remaining step would be a quarantined no-op —
                        # deliver the flagged null head now, free the slot
                        self._finish_slot(i, slots, key, bufs)
            if all(s is None for s in slots):
                break

            # per-slot chunk inputs from each slot's own grid cursor
            for i in range(S):
                s = slots[i]
                if s is None:
                    sig_prev[i] = 1.0
                    sig_next[i] = 1.0
                    live[i] = False
                    continue
                s.take = min(C, L - s.cursor)
                for c in range(C):
                    if c < s.take:
                        sig_prev[i, c] = s.grid[s.cursor - 1 + c]
                        sig_next[i, c] = s.grid[s.cursor + c]
                        live[i, c] = True
                    else:
                        sig_prev[i, c] = 1.0
                        sig_next[i, c] = 1.0
                        live[i, c] = False

            rids = [s.pending.rid for s in slots if s is not None]

            def _chunk_round():
                # the worker fault site fires INSIDE the watched call, so an
                # injected kind="hang" delay trips the watchdog exactly like
                # a stuck device dispatch would
                self._faults.fire("worker", rids=rids)
                return chunk_prog(
                    Xs, ys, lam, sig_prev, sig_next, live, beta, grad,
                    active, Lc, Hc, p_valid)

            t0 = self._clock()
            try:
                (nb, ng, na, nL, nH), ep = run_with_watchdog(
                    _chunk_round, self._watchdog_budget(rids),
                    label=chunk_spec.short())
            except WatchdogTimeout:
                self.metrics.inc("watchdog_timeouts")
                raise  # cohort-scoped: _serve_safely recovers exactly rids
            # copy INTO the persistent buffers (device outputs view as
            # read-only, and the next insertion scatters into them; copyto
            # keeps the bufs handles above valid)
            np.copyto(beta, nb)
            np.copyto(grad, ng)
            np.copyto(active, na)
            np.copyto(Lc, nL)
            np.copyto(Hc, nH)
            eb = np.asarray(ep.betas)
            edev = np.asarray(ep.deviance)
            scalars = [np.asarray(a) for a in
                       (ep.n_active, ep.n_screened, ep.n_violations,
                        ep.refits, ep.solver_iters)]
            eunrep = np.asarray(ep.kkt_unrepaired)
            ehlth = np.asarray(ep.health)
            wall = self._clock() - t0
            rounds += 1
            n_live = sum(s is not None for s in slots)
            self.metrics.inc("batches")
            self.metrics.inc("chunk_batches")
            self.metrics.observe("batch_occupancy", n_live / S)
            if self._traces:
                t_chunk = self._clock()
                with self._lock:
                    for s in slots:
                        if s is None:
                            continue
                        tr = self._traces.get(s.pending.rid)
                        if tr is not None:
                            tr.mark("chunk", t_chunk, round=rounds,
                                    solve_ms=round(wall * 1e3, 3))

            # harvest: native-width steps, early stop on the growing prefix
            for i in range(S):
                s = slots[i]
                if s is None:
                    continue
                s.solve_s += wall
                for c in range(s.take):
                    b = np.array(eb[i, c, :s.p, :])
                    dev = float(edev[i, c])
                    hw = int(ehlth[i, c])
                    s.steps.append((
                        b, *(int(a[i, c]) for a in scalars), dev,
                        bool(eunrep[i, c]), hw))
                    s.cursor += 1
                    if hw:
                        # quarantined in-graph: the remaining grid would be
                        # no-op placeholder steps (and the NaN-blind stop
                        # predicate below can never fire) — truncate here,
                        # the response carries the sticky health word
                        s.finished = True
                        break
                    # the SAME predicate the sync path applies post-hoc —
                    # it reads only the prefix, so stopping at a chunk
                    # boundary truncates exactly where path_result() would
                    if s.early_stop and _stop_triggered(
                            b, dev, s.prev_dev, s.null_dev, s.n):
                        s.finished = True
                        break
                    s.prev_dev = dev
                if s.finished or s.cursor >= L:
                    self._finish_slot(i, slots, key, bufs)

            # shadow every still-live slot's carry at this chunk boundary —
            # what checkpoint() collects after pausing the runner, and the
            # most a crash can lose per request is the current chunk
            with self._lock:
                for i in range(S):
                    s = slots[i]
                    if s is None:
                        continue
                    self._inflight_state[s.pending.rid] = InflightSlot(
                        rid=s.pending.rid, key=key, item=s.pending.item,
                        priority=s.pending.priority,
                        cv_fold=not s.early_stop,
                        beta=beta[i].copy(), grad=grad[i].copy(),
                        active=active[i].copy(), L=float(Lc[i]),
                        H=int(Hc[i]), cursor=s.cursor,
                        steps=list(s.steps), null_dev=s.null_dev,
                        prev_dev=s.prev_dev, health0=s.health0,
                        early_stop=s.early_stop, solve_s=s.solve_s)

    def _finish_slot(self, i: int, slots: list, key: _GroupKey,
                     bufs: dict) -> None:
        """Assemble the slot's response (null head + harvested steps at
        native shape), deliver its future, and free the slot."""
        s = slots[i]
        m = key.family.n_classes
        f = np.dtype(key.dtype)
        k = 1 + len(s.steps)
        betas = np.zeros((k, s.p, m), f)
        n_act = np.zeros((k,), np.int32)
        n_scr = np.zeros((k,), np.int32)
        viol = np.zeros((k,), np.int32)
        refits = np.zeros((k,), np.int32)
        iters = np.zeros((k,), np.int32)
        dev = np.zeros((k,), f)
        unrep = np.zeros((k,), bool)
        hlth = np.zeros((k,), np.int32)
        dev[0] = s.null_dev
        hlth[0] = s.health0
        for j, st in enumerate(s.steps, start=1):
            (betas[j], n_act[j], n_scr[j], viol[j], refits[j], iters[j],
             dev[j], unrep[j], hlth[j]) = st
        out_betas = betas[:, :, 0] if m == 1 else betas
        item = s.pending.item
        pad_ratio = (key.n_rows * key.n_cols) / (s.n * s.p)
        resp = PathResponse(
            rid=s.pending.rid, betas=out_betas,
            sigmas=np.asarray(item.sigmas)[:k], lam=item.lam, n_samples=s.n,
            n_active=n_act, n_screened=n_scr, n_violations=viol,
            refits=refits, solver_iters=iters, deviance=dev,
            kkt_unrepaired=unrep, kkt_ok=not bool(unrep.any()),
            working_set=None, working_set_top=None, ws_size=None,
            ws_tier=None, compact_fallback=None,
            queue_s=max(0.0, s.inserted - s.pending.submitted),
            solve_s=s.solve_s, batch_size=s.batch_size,
            batch_occupancy=s.batch_size / self.slots,
            padding_ratio=pad_ratio, cache_hit=s.cache_hit, health=hlth)
        self.metrics.observe("padding_ratio", pad_ratio)
        with self._lock:
            if self._traces:
                tr = self._traces.get(s.pending.rid)
                if tr is not None:
                    tr.mark("harvest", self._clock(),
                            padding_ratio=round(pad_ratio, 3))
            self._deliver(s.pending.rid, resp)
        slots[i] = None
        # blank the freed lane EVERYWHERE — operands AND carry: dead lanes
        # still execute in the vmapped chunk program (live=False only gates
        # the results), so a stale non-finite operand or carry (a
        # quarantined member leaves a NaN grad) would spin its lockstep
        # FISTA to max_iter on every remaining chunk.  All-zero lanes
        # converge in one iteration.
        for name in ("Xs", "ys", "lam", "p_valid", "beta", "grad", "Hc"):
            bufs[name][i] = 0
        bufs["active"][i] = False
        bufs["Lc"][i] = 1.0

    # -- warmup & telemetry -------------------------------------------------

    def warmup(self, shapes, *, family: Family = ols, path_length: int = 100,
               screening: str = "strong", solver_tol: float = 1e-8,
               max_iter: int = 5000, kkt_tol: float = 1e-4,
               max_refits: int = 32,
               working_set: int | str | None = None,
               ws_tiers: int | str = DEFAULT_WS_TIERS,
               dtype: str | None = None,
               y_dtype: str | None = None) -> dict:
        """Pre-compile what async serving actually runs: the (init, chunk)
        program pair for masked shapes; compact shapes defer to the base
        whole-grid warmup."""
        if working_set is not None:
            return super().warmup(
                shapes, family=family, path_length=path_length,
                screening=screening, solver_tol=solver_tol,
                max_iter=max_iter, kkt_tol=kkt_tol, max_refits=max_refits,
                working_set=working_set, ws_tiers=ws_tiers, dtype=dtype,
                y_dtype=y_dtype)
        specs = []
        for n, p in shapes:
            N, P = self.policy.shape_bucket(n, p, family.name)
            base = dict(
                family=family, batch=self.slots, n_rows=N, n_cols=P,
                path_length=path_length, screening=screening,
                solver_tol=solver_tol, max_iter=max_iter, kkt_tol=kkt_tol,
                max_refits=max_refits, dtype=dtype, y_dtype=y_dtype)
            specs.append(ProgramSpec(**base, variant="init"))
            specs.append(ProgramSpec(**base, variant="chunk",
                                     step_chunk=self.step_chunk))
        return self.cache.warmup(specs)

    def stats(self) -> dict:
        """Strict superset of :meth:`PathService.stats` — the async-only
        keys are a read-through over the same :attr:`metrics` registry."""
        out = super().stats()
        m = self.metrics
        with self._lock:
            out.update(
                slot_recycles=m.value("slot_recycles"),
                chunk_batches=m.value("chunk_batches"),
                step_chunk=self.step_chunk,
                inflight=len(self._futures),
                retries=m.value("retries"),
                bisections=m.value("bisections"),
                poisoned=m.value("poisoned"),
                checkpoints=m.value("checkpoints"),
                restored=m.value("restored"),
                retry_limit=self.retry_limit,
                retry_backoff=self.retry_backoff,
                worker_alive=bool(self._worker is not None
                                  and self._worker.is_alive()),
            )
        return out
