"""Admission queue and micro-batcher for the path service.

Requests land in per-group priority queues — a *group* is everything that
can legally share one compiled program: same family, same padded bucket
shape, same path length and solver statics.  Within a group, higher
``priority`` pops first; equal priorities keep FIFO order (a stable
sequence number breaks ties), so the default priority-0 stream behaves
exactly like the original FIFO.  A group flushes when it **fills**
(``max_batch`` requests waiting) or when its most urgent request passes
its **flush deadline** (``max_delay`` seconds in the queue, or sooner for
requests carrying their own deadline budget).

Two front-ends drain these queues: the synchronous
:class:`~repro.serve.service.PathService` checks deadlines on the next
``submit``/``poll`` call (no timer thread — the deadline bounds added
latency under load, not wall-clock staleness of an abandoned queue), and
the async :class:`~repro.serve.dispatch.AsyncPathService` runs a worker
thread that sleeps until :meth:`MicroBatcher.next_deadline` and flushes on
time even when no further calls arrive.  ``max_queue`` bounds total queued
depth for admission control: past capacity, :meth:`MicroBatcher.admit`
raises :class:`QueueFull` and the async service rejects-with-status
instead of queueing unboundedly.

λ-sequence canonicalization lives here too: requests that *name* a sequence
(``("bh", q)`` etc.) resolve through one memoised table, so equal specs map
to the same immutable array (one hash, byte-equal padded operands) instead
of freshly generated near-duplicates.  Since PR 4 the declarative
:class:`repro.api.LambdaSpec` is the canonical naming surface — it resolves
through the process-wide shared instance
(:func:`repro.api.shared_canonicalizer`), which is also every
:class:`~repro.serve.service.PathService`'s default, so direct and served
execution share one memo table.
"""

from __future__ import annotations

import dataclasses
import heapq
import threading
from collections import OrderedDict

import numpy as np

from ..core.lambda_seq import (
    bh_sequence,
    float_dtype,
    gaussian_sequence,
    lasso_sequence,
    oscar_sequence,
)

__all__ = ["Pending", "MicroBatcher", "QueueFull", "Rejection",
           "RejectionError", "LambdaCanonicalizer", "lambda_kinds"]


class QueueFull(RuntimeError):
    """Admission rejected: the batcher's bounded queue is at capacity.

    Deprecated alias surface: services raise/convert this into the
    structured :class:`Rejection` form — the synchronous service raises
    :class:`RejectionError` (a ``QueueFull`` subclass, so existing
    ``except QueueFull`` handlers keep working) and the async service
    resolves the future with the :class:`Rejection` value itself.
    """


@dataclasses.dataclass(frozen=True)
class Rejection:
    """Admission-control verdict: the request was NOT queued.

    The ONE structured rejection shape both front-ends speak: the async
    service resolves it into the submit future immediately (callers
    distinguish "rejected now" from "missed its deadline later" without
    waiting), the synchronous service raises it wrapped in
    :class:`RejectionError`.
    """

    rid: int
    reason: str            # queue-capacity text, or the admission-control
    #   verdicts "circuit_open" (per-program circuit breaker is open) and
    #   "shed" (adaptive load shedding under latency pressure)
    queued: int            # queue depth at the rejecting admission
    max_queue: int | None  # the capacity that was hit (None: not a
    #   capacity rejection)


class RejectionError(QueueFull):
    """Synchronous admission rejection carrying the structured verdict.

    Subclasses :class:`QueueFull` so pre-PR-7 ``except QueueFull`` code
    keeps catching capacity rejections; new code should read
    ``err.rejection`` for the structured fields.
    """

    def __init__(self, rejection: Rejection):
        super().__init__(rejection.reason)
        self.rejection = rejection


@dataclasses.dataclass
class Pending:
    """One queued request: opaque payload plus admission bookkeeping."""

    rid: int
    item: object
    submitted: float   # service clock at admission
    deadline: float    # flush-by time (submitted + max_delay, or tighter
    #   when the request carries its own latency budget)
    priority: int = 0  # higher pops first within the group; 0 = default


class MicroBatcher:
    """Per-group priority queues with fill- and deadline-triggered flushing.

    ``max_queue`` (optional) bounds TOTAL queued requests across groups —
    the admission-control knob: at capacity, :meth:`admit` raises
    :class:`QueueFull` instead of queueing (unbounded by default, which is
    the synchronous service's historical behaviour).
    """

    def __init__(self, max_batch: int = 8, max_delay: float = 0.02,
                 max_queue: int | None = None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be ≥ 1, got {max_batch}")
        if max_delay < 0:
            raise ValueError(f"max_delay must be ≥ 0, got {max_delay}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be ≥ 1, got {max_queue}")
        self.max_batch = max_batch
        self.max_delay = max_delay
        self.max_queue = max_queue
        # heap entries (-priority, seq, Pending): priority order, FIFO ties
        self._queues: OrderedDict[object, list] = OrderedDict()
        self._seq = 0
        self._size = 0
        self._lock = threading.Lock()

    def admit(self, key, rid: int, item, now: float, *, priority: int = 0,
              deadline: float | None = None) -> bool:
        """Queue one request; True ⇒ the group just filled and should flush.

        Raises :class:`QueueFull` when ``max_queue`` is set and reached —
        the request is NOT queued and the caller owns the rejection.
        """
        if deadline is None:
            deadline = now + self.max_delay
        with self._lock:
            if self.max_queue is not None and self._size >= self.max_queue:
                raise QueueFull(
                    f"micro-batcher queue at capacity "
                    f"({self._size}/{self.max_queue} queued requests)")
            q = self._queues.get(key)
            if q is None:
                q = []
                self._queues[key] = q
            heapq.heappush(
                q, (-priority, self._seq,
                    Pending(rid, item, now, deadline, priority)))
            self._seq += 1
            self._size += 1
            return len(q) >= self.max_batch

    def due(self, now: float) -> list:
        """Groups holding a request past its flush deadline."""
        with self._lock:
            return [k for k, q in self._queues.items()
                    if q and min(e[2].deadline for e in q) <= now]

    def next_deadline(self) -> float | None:
        """Earliest flush deadline over every queued request (None when
        idle) — what the async worker thread sleeps until."""
        with self._lock:
            deadlines = [e[2].deadline for q in self._queues.values()
                         for e in q]
            return min(deadlines) if deadlines else None

    def fillable(self) -> list:
        """Groups at or above fill capacity (``max_batch`` queued)."""
        with self._lock:
            return [k for k, q in self._queues.items()
                    if len(q) >= self.max_batch]

    def take(self, key, limit: int | None = None) -> list[Pending]:
        """Pop up to ``limit`` (default ``max_batch``) requests — highest
        priority first, FIFO within a priority."""
        limit = self.max_batch if limit is None else limit
        with self._lock:
            q = self._queues.get(key)
            if not q:
                self._queues.pop(key, None)
                return []
            batch = [heapq.heappop(q)[2]
                     for _ in range(min(limit, len(q)))]
            self._size -= len(batch)
            if not q:
                del self._queues[key]
            return batch

    def groups(self) -> list:
        with self._lock:
            return [k for k, q in self._queues.items() if q]

    def snapshot(self) -> list[tuple]:
        """Non-destructive ``(key, Pending)`` view of everything queued, in
        pop order per group — what a service checkpoint records without
        disturbing admission state."""
        with self._lock:
            return [(k, e[2]) for k, q in self._queues.items()
                    for e in sorted(q)]

    def pending(self) -> int:
        with self._lock:
            return self._size


_SEQUENCES = {
    "bh": bh_sequence,
    "gaussian": gaussian_sequence,
    "oscar": oscar_sequence,
    "lasso": lasso_sequence,
}


def lambda_kinds() -> tuple[str, ...]:
    """The named λ-sequence recipes (the single source of truth shared with
    ``repro.api.LambdaSpec`` validation)."""
    return tuple(sorted(_SEQUENCES))


class LambdaCanonicalizer:
    """Memoised named-λ-sequence table: ``(kind, q, size) → one array``.

    The returned arrays are read-only — every request naming the same spec
    shares the same bytes, so padded batches built from them are byte-equal
    and the program inputs (not just the program) are canonical.
    """

    def __init__(self):
        self._memo: dict[tuple, np.ndarray] = {}
        self._lock = threading.Lock()

    def get(self, kind: str, q: float, size: int,
            n: int | None = None, dtype=None) -> np.ndarray:
        """The sequence in :func:`~repro.core.lambda_seq.float_dtype` of
        ``dtype`` — the operands' dtype, JAX's default float if None."""
        dtype = float_dtype(dtype)
        # n parameterizes only the gaussian recursion; keying every other
        # kind on it would duplicate byte-identical arrays per problem size
        key = (kind, float(q), int(size), n if kind == "gaussian" else None,
               dtype.name)
        with self._lock:
            lam = self._memo.get(key)
            if lam is None:
                fn = _SEQUENCES.get(kind)
                if fn is None:
                    raise ValueError(
                        f"unknown λ sequence {kind!r}; choose from "
                        f"{sorted(_SEQUENCES)}")
                if kind == "lasso":
                    lam = np.asarray(fn(size, dtype=dtype), dtype)
                elif kind == "gaussian":
                    if n is None:
                        raise ValueError("gaussian sequences need n")
                    lam = np.asarray(fn(size, n, q, dtype=dtype), dtype)
                else:
                    lam = np.asarray(fn(size, q, dtype=dtype), dtype)
                lam.flags.writeable = False
                self._memo[key] = lam
            return lam

    def __len__(self) -> int:
        with self._lock:
            return len(self._memo)
