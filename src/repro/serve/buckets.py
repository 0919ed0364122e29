"""Shape bucketing for the path service: canonical execution shapes.

The serving problem: a stream of heterogeneous ``(n, p)`` fit requests must
share compiled device programs, or XLA compilation dominates wall time.  The
policy here rounds every incoming problem up to a power-of-two bucket and
pads with inert zeros, so the whole stream funnels into a handful of
compiled shapes.

Two properties make the padding *canonical* rather than merely tolerable:

* **Inertness.**  Zero columns are inert for every GLM family (a zero
  column never moves the linear predictor, and its gradient entry
  ``x_jᵀr`` is identically zero), and padded λ entries are zero, so padded
  coefficients stay *exactly* 0 through screening, prox and KKT repair.
  Zero **rows** are inert only for OLS (residual ``z − y = 0 − 0``); other
  families keep their exact row count in the bucket key.
* **Bit-identity by construction.**  XLA programs of different shapes are
  not bitwise-interchangeable (gemm tiling changes with shape), so the
  repo's rule is: one bucket → ONE execution shape, shared by the direct
  ``fit_path_batched(pad="bucket")`` entry point and the
  :class:`repro.serve.service.PathService` micro-batcher.  A request padded
  into a bucket by the service returns bit-identical coefficients to an
  unpadded direct call because both run the *same* compiled program on the
  *same* padded operands.  (Batch slots are bitwise member-invariant for
  B ≥ 2 on this backend — verified in ``tests/test_serve.py`` — which is
  why :meth:`ShapeBucketPolicy.batch_bucket` floors the batch at 2.)

This module is dependency-free (NumPy only): :mod:`repro.core.engine`
imports it for the working-set :class:`BucketRegistry`, so it must be
importable before ``repro.core`` finishes initialising.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict

import numpy as np

from ..obs import MetricsRegistry

__all__ = [
    "next_pow2",
    "BucketRegistry",
    "ShapeBucketPolicy",
    "default_policy",
    "PaddedBatch",
    "pad_batch",
]

_MISSING = object()


def next_pow2(x: int) -> int:
    """Smallest power of two ≥ x (1 for x ≤ 1)."""
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


class BucketRegistry:
    """Thread-safe, bounded, introspectable ``key → bucket`` memory.

    Promoted out of ``repro.core.engine``'s module-level ``_WS_BUCKETS``
    dict: the grow-on-overflow working-set memory is now shared between the
    batched engine and the path service (both resolve compact widths through
    the same instance, so a service batch that overflows grows the bucket
    the next direct call sees, and vice versa).

    Correctness never depends on the registry — overflow steps fall back to
    the masked solve in-graph — it only stops the next same-shape call from
    paying the fallback again.  Eviction (LRU, ``capacity`` entries) is
    therefore always safe.

    The mapping interface is dict-like (``reg[key]``, ``key in reg``,
    ``reg.pop(key, default)``) so existing callers and tests keep working;
    :meth:`stats` exposes hit/miss/update/eviction counters plus a snapshot
    of the current entries.
    """

    def __init__(self, name: str = "buckets", capacity: int = 512):
        if capacity < 1:
            raise ValueError(f"capacity must be ≥ 1, got {capacity}")
        self.name = name
        self.capacity = capacity
        self._data: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        # counters live on the unified registry (repro.obs); stats() reads
        # back through it so this module stays NumPy+stdlib importable
        self.metrics = MetricsRegistry(f"buckets.{name}")

    def get(self, key, default=None):
        with self._lock:
            if key in self._data:
                self._data.move_to_end(key)
                self.metrics.inc("hits")
                return self._data[key]
            self.metrics.inc("misses")
            return default

    def __getitem__(self, key):
        value = self.get(key, _MISSING)
        if value is _MISSING:
            raise KeyError(key)
        return value

    def __setitem__(self, key, value) -> None:
        with self._lock:
            self._data[key] = value
            self._data.move_to_end(key)
            self.metrics.inc("updates")
            while len(self._data) > self.capacity:
                self._data.popitem(last=False)
                self.metrics.inc("evictions")

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._data

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def grow(self, key, value: int, cap: int | None = None) -> bool:
        """Monotonic, idempotent growth: raise ``key``'s bucket to at least
        ``value`` (clipped to ``cap``), never shrink it.

        This is the ONE write path for grow-on-overflow working-set entries:
        plain ``__setitem__`` is last-write-wins, so two concurrent
        overflowing runs (service flush + direct call) could overwrite a
        larger grown bucket with a smaller one and re-pay the fallback the
        larger run already learned to avoid.  ``cap`` bounds the stored
        bucket at the native column count — a bucket wider than ``p`` is
        wasted compaction (the gather would cover every column and the
        compact solve degenerates to the masked one plus gather overhead).
        Returns True iff the stored value changed.
        """
        if cap is not None:
            value = min(int(value), int(cap))
        with self._lock:
            current = self._data.get(key)
            if current is not None and current >= value:
                self._data.move_to_end(key)
                self.metrics.inc("hits")
                return False
            self._data[key] = value
            self._data.move_to_end(key)
            self.metrics.inc("updates")
            while len(self._data) > self.capacity:
                self._data.popitem(last=False)
                self.metrics.inc("evictions")
            return True

    def pop(self, key, default=None):
        with self._lock:
            return self._data.pop(key, default)

    def clear(self) -> None:
        with self._lock:
            self._data.clear()

    def stats(self) -> dict:
        m = self.metrics
        with self._lock:
            return {
                "name": self.name,
                "size": len(self._data),
                "capacity": self.capacity,
                "hits": m.value("hits"),
                "misses": m.value("misses"),
                "updates": m.value("updates"),
                "evictions": m.value("evictions"),
                "entries": dict(self._data),
            }

    def summary(self) -> dict:
        """:meth:`stats` with JSON-safe entry keys — what
        ``PathService.stats()`` and the ``BENCH_ci.json`` serve rows embed
        so registry growth is visible in the perf trajectory."""
        st = self.stats()
        st["entries"] = {repr(k): v for k, v in st["entries"].items()}
        return st

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"BucketRegistry({self.name!r}, size={len(self)}, "
                f"capacity={self.capacity})")


@dataclasses.dataclass(frozen=True)
class ShapeBucketPolicy:
    """Power-of-two padding policy for incoming ``(n, p)`` problems.

    * columns: always padded to ``max(min_cols, 2^⌈log₂ p⌉)`` — zero columns
      are inert for every family;
    * rows: padded the same way for OLS only (zero rows change the loss for
      logistic/Poisson/multinomial, so those keep their exact ``n``);
    * batch slots: padded to ``max(min_batch, 2^⌈log₂ B⌉)`` with all-zero
      dummy problems (``p_valid = 0``) in unused slots.  The floor of 2
      matters: B = 1 programs lower to a different gemm than B ≥ 2 and are
      not bitwise-interchangeable with them.

    Floors bound the number of distinct compiled shapes a mixed stream can
    produce; raise them if a deployment sees too many tiny odd shapes.

    ``serve_slots`` and ``step_chunk`` are the services' default batch
    width and the async service's continuous-batching chunk length.  A
    direct ``pad="bucket"`` fit runs at the same width (:meth:`direct_slots`)
    and, when masked, in the same chunks, so it runs the very programs the
    default service runs: on a TPU, programs of another batch width or loop
    structure round differently, and only the same programs are bitwise.
    """

    min_rows: int = 16
    min_cols: int = 32
    min_batch: int = 2
    serve_slots: int = 8
    step_chunk: int = 8

    def shape_bucket(self, n: int, p: int, family_name: str = "ols"):
        """Execution shape ``(N, P)`` for a native ``(n, p)`` problem."""
        P = max(self.min_cols, next_pow2(p))
        N = max(self.min_rows, next_pow2(n)) if family_name == "ols" else n
        return N, P

    def batch_bucket(self, b: int) -> int:
        """Execution batch width for ``b`` live requests."""
        return max(self.min_batch, next_pow2(b))

    def direct_slots(self, b: int) -> int:
        """Batch width of a direct ``pad="bucket"`` fit of ``b`` problems:
        the default services' width, unused slots holding inert dummies."""
        return self.batch_bucket(max(b, self.serve_slots))


_DEFAULT_POLICY = ShapeBucketPolicy()


def default_policy() -> ShapeBucketPolicy:
    """The policy shared by ``fit_path_batched(pad="bucket")`` and the
    service default — one policy, one set of execution shapes."""
    return _DEFAULT_POLICY


@dataclasses.dataclass
class PaddedBatch:
    """Stacked, padded device operands for one engine dispatch."""

    Xs: np.ndarray        # (B_slots, N, P)
    ys: np.ndarray        # (B_slots, N[, ...])
    lam: np.ndarray       # (B_slots, P·m) per-member λ, zero-padded tail
    sigmas: np.ndarray    # (B_slots, L); dummy slots hold a flat grid of 1s
    p_valid: np.ndarray   # (B_slots,) int32 native p per slot (0 = dummy)
    n_batch: int          # leading slots holding real problems

    @property
    def shape(self):
        return self.Xs.shape


def pad_batch(problems, *, n_rows: int, n_cols: int, n_slots: int,
              n_classes: int = 1) -> PaddedBatch:
    """Pad native problems into one ``(n_slots, n_rows, n_cols)`` batch.

    ``problems`` is a sequence of ``(X, y, lam, sigmas)`` tuples at native
    shapes; every ``n_i ≤ n_rows``, ``p_i ≤ n_cols``, and all σ grids share
    one length.  X/λ are padded with zeros (inert — see the module
    docstring), unused batch slots hold all-zero dummy problems with
    ``p_valid = 0`` so screening keeps nothing and their solves freeze
    immediately.  The caller promises zero-row inertness when ``n_i <
    n_rows`` (i.e. rows are only padded for OLS).
    """
    if not problems:
        raise ValueError("pad_batch needs at least one problem")
    if len(problems) > n_slots:
        raise ValueError(f"{len(problems)} problems exceed {n_slots} slots")
    m = n_classes
    L = len(problems[0][3])
    X0, y0 = problems[0][0], problems[0][1]
    dtype = X0.dtype
    Xs = np.zeros((n_slots, n_rows, n_cols), dtype)
    ys = np.zeros((n_slots,) + (n_rows,) + y0.shape[1:], y0.dtype)
    lam = np.zeros((n_slots, n_cols * m), dtype)
    sigmas = np.ones((n_slots, L), dtype)
    p_valid = np.zeros((n_slots,), np.int32)
    for i, (X, y, lam_i, sig_i) in enumerate(problems):
        n_i, p_i = X.shape
        if n_i > n_rows or p_i > n_cols:
            raise ValueError(
                f"problem {i} shape {(n_i, p_i)} exceeds bucket "
                f"{(n_rows, n_cols)}")
        if len(sig_i) != L:
            raise ValueError("all σ grids in a batch must share one length")
        Xs[i, :n_i, :p_i] = X
        ys[i, :n_i] = y
        lam[i, : p_i * m] = np.asarray(lam_i)[: p_i * m]
        sigmas[i] = sig_i
        p_valid[i] = p_i
    return PaddedBatch(Xs=Xs, ys=ys, lam=lam, sigmas=sigmas,
                       p_valid=p_valid, n_batch=len(problems))
