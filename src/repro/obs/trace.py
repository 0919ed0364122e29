"""Request tracing: gap-free span timelines for served path fits.

A :class:`Trace` is a per-request timeline built from **cursor-based**
spans: :meth:`Trace.mark` closes a span from the trace's internal cursor to
the given end time and advances the cursor, so consecutive top-level spans
are contiguous *by construction* — the admit → queue → flush → compile →
execute → harvest → deliver chain can have no gaps, which is what lets a
trace account for every microsecond of a request's latency budget.

Out-of-band events (a retry attempt, a bisection split, a slot recycle)
ride as **child spans** via :meth:`Trace.child`: they carry a ``parent``
span name, never move the cursor, and so annotate the timeline without
perturbing its contiguity.

Span vocabulary used by the serving stack (see the README "Observability"
section and ``examples/serve_paths.py`` for a rendered timeline):

========== ==========================================================
``admit``   validation, λ/σ canonicalization, queue insertion
``queue``   waiting in the micro-batcher for fill/deadline
``flush``   batch take + host-side padding (attrs: trigger, slots)
``compile`` program-cache fetch (attrs: ``hit``, ``program``)
``execute`` the compiled whole-grid device program
``init``    async: slot insertion + prefill (attr: ``recycled``)
``chunk``   async: one ``step_chunk``-step compiled slice (attr: round)
``harvest`` unpadding + response assembly
``deliver`` future/poll-table handoff (always the last span)
``retry``/``bisect``/``poisoned`` recovery events (children of the
            span named by their ``parent`` attr / cursor position)
========== ==========================================================

The host path driver (``core/path.py``) keeps one trace per path, marked
phase by phase with :meth:`Trace.phase`, which also writes each phase as
a ``repro.path.<name>`` profiler annotation on the device trace's clock:

============ ========================================================
``setup``    null gradient, null deviance, σ grid (step 0)
``screen``   strong rule and screened mask, once per σ-step
``gather``   host column gather, λ slice and warm start (per refit)
``solve``    FISTA sub-solve through the fetch of β (attrs ``width``,
             ``bucket``, and ``prox_live`` / ``prox_full``: FISTA's Σ of
             the prox's live bound and of its width)
``gradient`` full gradient, with its upload of X and its fetch
``kkt``      KKT check of the full gradient
``loss``     the step's deviance (the step's last span)
============ ========================================================

Every span of a path trace carries ``step``, ``refit``, ``h2d_bytes``
(host arrays turned into device arrays), ``fetches`` (blocking
device→host reads) and ``d2h_bytes``.  Each finished path trace is kept
in a bounded flight recorder, :func:`keep` / :func:`recent`: the last
:data:`RECENT_PATHS` paths of the process.

Threading contract: one request's trace is only ever mutated by the thread
currently driving that request (submit thread through admission, dispatcher
thread afterwards — handoff sequenced by the service lock), so spans need
no lock of their own.  stdlib-only at import (:mod:`.profile` reaches
jax lazily).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time

from .profile import annotate

__all__ = ["Span", "Trace", "keep", "recent", "RECENT_PATHS"]

RECENT_PATHS = 64
_recent: collections.deque = collections.deque(maxlen=RECENT_PATHS)
_recent_lock = threading.Lock()


def keep(trace: "Trace") -> None:
    """Add a finished trace to the flight recorder (the oldest of more
    than :data:`RECENT_PATHS` is dropped)."""
    with _recent_lock:
        _recent.append(trace)


def recent() -> list:
    """The recorder's traces, oldest first."""
    with _recent_lock:
        return list(_recent)


@dataclasses.dataclass
class Span:
    """One named interval; ``parent`` is set on child (event) spans."""

    name: str
    t0: float
    t1: float
    parent: str | None = None
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> dict:
        return {"name": self.name, "t0": self.t0, "t1": self.t1,
                "duration_ms": round(self.duration_s * 1e3, 4),
                "parent": self.parent, "attrs": dict(self.attrs)}


class Trace:
    """One request's span timeline (see module docstring)."""

    __slots__ = ("rid", "t0", "cursor", "spans")

    def __init__(self, rid: int | None = None, t0: float | None = None):
        self.rid = rid
        self.t0 = time.perf_counter() if t0 is None else float(t0)
        self.cursor = self.t0
        self.spans: list[Span] = []

    # -- construction -------------------------------------------------------

    def mark(self, name: str, t_end: float | None = None, **attrs) -> Span:
        """Close a top-level span from the cursor to ``t_end`` (now when
        omitted) and advance the cursor — contiguity by construction."""
        t_end = time.perf_counter() if t_end is None else float(t_end)
        t_end = max(t_end, self.cursor)  # clock monotonicity guard
        span = Span(name=name, t0=self.cursor, t1=t_end, attrs=attrs)
        self.spans.append(span)
        self.cursor = t_end
        return span

    @contextlib.contextmanager
    def phase(self, name: str, **attrs):
        """``with tr.phase("solve", step=3) as span:`` — run the body under
        the profiler annotation ``repro.path.<name>`` and :meth:`mark` it on
        exit.  Yields the span's attribute dict, so the body can add its
        counts before the span closes."""
        with annotate(f"repro.path.{name}"):
            yield attrs
        self.mark(name, **attrs)

    def child(self, name: str, t0: float | None = None,
              t1: float | None = None, *, parent: str | None = None,
              **attrs) -> Span:
        """Attach a child/event span without moving the cursor.  ``parent``
        defaults to the most recent top-level span's name."""
        if parent is None:
            parent = self.spans[-1].name if self.spans else "admit"
        t0 = time.perf_counter() if t0 is None else float(t0)
        t1 = t0 if t1 is None else float(t1)
        span = Span(name=name, t0=t0, t1=t1, parent=parent, attrs=attrs)
        self.spans.append(span)
        return span

    # -- introspection ------------------------------------------------------

    def top(self) -> list[Span]:
        """Top-level (cursor-advancing) spans in timeline order."""
        return [s for s in self.spans if s.parent is None]

    def children(self) -> list[Span]:
        return [s for s in self.spans if s.parent is not None]

    def span_names(self) -> list[str]:
        return [s.name for s in self.top()]

    @property
    def total_s(self) -> float:
        return self.cursor - self.t0

    def contiguous(self) -> bool:
        """True when the top-level chain covers admit→deliver with no gaps
        (each span starts exactly where the previous one ended)."""
        tops = self.top()
        if not tops:
            return False
        if tops[0].t0 != self.t0:
            return False
        return all(b.t0 == a.t1 for a, b in zip(tops, tops[1:]))

    def well_parented(self) -> bool:
        """Every child span names a parent that appears earlier in the
        span list — the ordering invariant the async stress test pins."""
        seen: set[str] = set()
        for s in self.spans:
            if s.parent is None:
                seen.add(s.name)
            elif s.parent not in seen:
                return False
        return True

    # -- export -------------------------------------------------------------

    def to_events(self, **extra) -> list[dict]:
        """JSON-safe event list (relative times) for the JSONL exporter."""
        return [
            {"rid": self.rid, **extra, **s.to_dict(),
             "t0": round(s.t0 - self.t0, 6), "t1": round(s.t1 - self.t0, 6)}
            for s in self.spans
        ]

    def render(self, width: int = 40) -> str:
        """Human-readable timeline (the example prints this)."""
        total = max(self.total_s, 1e-12)
        lines = [f"trace rid={self.rid}  total={total * 1e3:.3f} ms"]
        for s in self.spans:
            off = int((s.t0 - self.t0) / total * width)
            bar = max(1, int(s.duration_s / total * width))
            bar = min(bar, width - min(off, width - 1))
            indent = "  " if s.parent is not None else ""
            gutter = " " * min(off, width - 1) + "#" * bar
            attrs = (" " + ",".join(f"{k}={v}" for k, v in s.attrs.items())
                     if s.attrs else "")
            name = s.name if s.parent is None else f"{s.name}<{s.parent}"
            lines.append(f"  {indent}{name:<18}{gutter:<{width + 2}}"
                         f"{s.duration_s * 1e3:9.3f} ms{attrs}")
        return "\n".join(lines)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Trace(rid={self.rid}, spans={self.span_names()}, "
                f"total_ms={self.total_s * 1e3:.3f})")
