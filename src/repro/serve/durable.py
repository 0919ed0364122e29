"""Crash-safety primitives for the serve layer (PR 10).

Four cooperating pieces, all optional and inert by default:

* :class:`DurableProgramStore` — serialized AOT executables on disk, keyed
  by :class:`~repro.serve.cache.ProgramSpec`.  A restarted server loads a
  previously-compiled program in milliseconds instead of re-lowering and
  re-compiling it (seconds per shape).  Entries carry a spec hash, a
  jax/jaxlib/backend fingerprint and a payload checksum; anything corrupt
  or mismatched is discarded and rebuilt — a stored entry is never
  trusted.  A **warmup manifest** (JSONL, appended on every build) records
  the specs live traffic actually compiled, so :meth:`replay` at boot
  warms exactly the programs the previous process served.
* :class:`CircuitBreaker` — per-program-group failure gate: K consecutive
  compile/execute faults open the circuit (admissions rejected with
  ``Rejection(reason="circuit_open")``), a cooldown later one probe
  admission is let through (half-open), and its outcome closes or
  re-opens the circuit.  Stops a persistent fault from burning the
  retry/bisection budget on every new admission.
* :class:`LoadShedGovernor` — adaptive admission shedding: when the
  rolling user-scope latency p95 approaches a request's ``deadline_ms``,
  lowest-priority admissions are rejected with
  ``Rejection(reason="shed")`` instead of queueing work already doomed to
  miss its SLO.  The decision is a pure function of (p95 window, deadline,
  priority) — deterministic given the metrics window.
* :func:`run_with_watchdog` — bounded device dispatch: runs a call on a
  sacrificial thread and raises :class:`WatchdogTimeout` after
  ``solve_timeout_ms``, so a hung XLA call fails only its cohort (through
  the PR-7 retry/bisect path) instead of stalling the dispatcher forever.
  The abandoned call finishes (or hangs) on its daemon thread; its result
  is discarded.

:class:`ServiceCheckpoint` is the picklable snapshot
``AsyncPathService.checkpoint()`` produces and ``restore()`` consumes:
admitted-but-undelivered requests plus per-slot carried engine state at a
chunk boundary, so resumed requests complete **bit-identical** to an
uninterrupted run (the chunk carry already round-trips through host
buffers — see :mod:`repro.serve.dispatch`).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import threading
import time

import numpy as np

import jax

from ..core.losses import Family, logistic, ols, poisson
from .batcher import Pending

__all__ = [
    "DurableProgramStore", "CircuitBreaker", "LoadShedGovernor",
    "WatchdogTimeout", "run_with_watchdog", "ServiceCheckpoint",
    "QueuedRequest", "InflightSlot",
]

# family registry for manifest round-trips: specs serialize the family by
# name and reconstruct through here (families are code, not data)
_FAMILIES: dict[str, Family] = {f.name: f for f in (ols, logistic, poisson)}

_ENTRY_VERSION = 1


def _spec_token(spec) -> str:
    """Canonical string over every ProgramSpec field (family by name) —
    the integrity token stored with (and checked against) each entry."""
    parts = []
    for f in dataclasses.fields(spec):
        v = getattr(spec, f.name)
        if isinstance(v, Family):
            v = v.name
        parts.append(f"{f.name}={v!r}")
    return ";".join(parts)


def _spec_to_json(spec) -> dict:
    out = {}
    for f in dataclasses.fields(spec):
        v = getattr(spec, f.name)
        out[f.name] = v.name if isinstance(v, Family) else v
    return out


def _spec_from_json(d: dict):
    from .cache import ProgramSpec

    d = dict(d)
    fam = _FAMILIES.get(d.pop("family", None))
    if fam is None:
        return None
    known = {f.name for f in dataclasses.fields(ProgramSpec)}
    if set(d) - known:
        return None
    return ProgramSpec(family=fam, **d)


def backend_fingerprint() -> str:
    """What a serialized executable's validity depends on: jax + jaxlib
    versions, the backend and the device kind it was compiled for (a TPU
    executable built for one chip generation never loads on another)."""
    import jaxlib

    return (f"jax={jax.__version__}|jaxlib={jaxlib.__version__}"
            f"|backend={jax.default_backend()}"
            f"|device={jax.devices()[0].device_kind}")


class DurableProgramStore:
    """Directory-backed store of serialized AOT executables + a warmup
    manifest.

    ``save``/``load`` serialize through
    :mod:`jax.experimental.serialize_executable` (true skip-compile
    restore).  A program the backend refuses to serialize is counted
    (``unserializable``) and left to rebuild on the next boot; the
    manifest still records it.  Integrity: every entry stores the spec
    token, the jax/jaxlib/backend/device fingerprint and a payload
    checksum; any mismatch or unpickling error discards the entry
    (counted, file unlinked) — a corrupt store can cost a rebuild, never a
    wrong program.  The store places no XLA compilation cache: that is the
    entry point's business (``repro.compile_cache``).
    """

    def __init__(self, path: str | os.PathLike):
        self.path = os.fspath(path)
        os.makedirs(self.path, exist_ok=True)
        self._lock = threading.Lock()
        self.counters = {"saved": 0, "loaded": 0, "discarded": 0,
                         "replayed": 0, "unserializable": 0}

    # -- keying -------------------------------------------------------------

    def _entry_path(self, spec) -> str:
        digest = hashlib.sha256(
            f"{_spec_token(spec)}|{backend_fingerprint()}".encode()
        ).hexdigest()
        return os.path.join(self.path, f"{digest}.prog")

    @property
    def _manifest_path(self) -> str:
        return os.path.join(self.path, "manifest.jsonl")

    # -- entries ------------------------------------------------------------

    def save(self, spec, prog) -> bool:
        """Serialize one :class:`~repro.serve.cache.CompiledProgram` and
        append the spec to the warmup manifest.  Returns False (and still
        records the manifest entry) when the backend refuses to serialize
        the executable."""
        from jax.experimental import serialize_executable as se

        self._append_manifest(spec)
        try:
            payload, in_tree, out_tree = se.serialize(prog._compiled)
        except jax.errors.JaxRuntimeError:
            # e.g. XLA:CPU refuses an executable that has already run a sort
            with self._lock:
                self.counters["unserializable"] += 1
            return False
        entry = {
            "version": _ENTRY_VERSION,
            "token": _spec_token(spec),
            "fingerprint": backend_fingerprint(),
            "sha256": hashlib.sha256(payload).hexdigest(),
            "payload": payload,
            "in_tree": in_tree,
            "out_tree": out_tree,
            "build_seconds": prog.build_seconds,
        }
        target = self._entry_path(spec)
        tmp = f"{target}.tmp.{os.getpid()}"
        with self._lock:
            with open(tmp, "wb") as fh:
                pickle.dump(entry, fh)
            os.replace(tmp, target)  # atomic: never a half-written entry
            self.counters["saved"] += 1
        return True

    def load(self, spec):
        """Deserialize the stored executable for ``spec`` (or None).

        Every integrity check failure — unreadable pickle, token mismatch,
        fingerprint mismatch, payload checksum mismatch, deserialization
        error — discards the entry and returns None: the caller rebuilds
        from source, which is always safe."""
        from .cache import CompiledProgram

        target = self._entry_path(spec)
        if not os.path.exists(target):
            return None
        try:
            with open(target, "rb") as fh:
                entry = pickle.load(fh)
            if (entry["version"] != _ENTRY_VERSION
                    or entry["token"] != _spec_token(spec)
                    or entry["fingerprint"] != backend_fingerprint()
                    or entry["sha256"]
                    != hashlib.sha256(entry["payload"]).hexdigest()):
                raise ValueError("integrity check failed")
            from jax.experimental import serialize_executable as se

            compiled = se.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"])
            prog = CompiledProgram(spec, compiled,
                                   float(entry["build_seconds"]))
            with self._lock:
                self.counters["loaded"] += 1
            return prog
        except BaseException:
            with self._lock:
                self.counters["discarded"] += 1
            try:
                os.unlink(target)
            except OSError:  # pragma: no cover - racing unlink
                pass
            return None

    # -- warmup manifest ----------------------------------------------------

    def _append_manifest(self, spec) -> None:
        line = json.dumps(_spec_to_json(spec), sort_keys=True)
        with self._lock:
            with open(self._manifest_path, "a") as fh:
                fh.write(line + "\n")

    def manifest_specs(self) -> list:
        """The deduped spec list live traffic has compiled (admission
        order), reconstructed from the manifest; undecodable lines and
        unknown families are skipped — the manifest is advisory, never
        load-bearing for correctness."""
        specs, seen = [], set()
        try:
            with open(self._manifest_path) as fh:
                lines = fh.readlines()
        except OSError:
            return []
        for line in lines:
            try:
                d = json.loads(line)
            except ValueError:
                continue
            if not isinstance(d, dict):
                continue
            try:
                spec = _spec_from_json(d)
            except (TypeError, ValueError):
                continue
            if spec is not None and spec not in seen:
                seen.add(spec)
                specs.append(spec)
        return specs

    def replay(self, cache) -> int:
        """Warm ``cache`` with every manifest spec (boot-time warmup).

        Specs resident in the store load without compiling; anything
        missing or discarded rebuilds — and re-saves — on the spot.
        Returns the number of programs warmed."""
        n = 0
        for spec in self.manifest_specs():
            cache.get(spec)
            n += 1
        with self._lock:
            self.counters["replayed"] += n
        return n

    def stats(self) -> dict:
        with self._lock:
            entries = sum(1 for f in os.listdir(self.path)
                          if f.endswith(".prog"))
            return {"path": self.path, "entries": entries, **self.counters}


# -- watchdog ---------------------------------------------------------------


class WatchdogTimeout(RuntimeError):
    """A watched device call exceeded its ``solve_timeout_ms`` budget."""


def run_with_watchdog(fn, timeout_s: float | None, *, label: str = ""):
    """Run ``fn()`` with a wall-clock budget.

    ``timeout_s=None`` calls inline (zero overhead — the default path).
    Otherwise ``fn`` runs on a sacrificial daemon thread; past the budget a
    :class:`WatchdogTimeout` is raised to the caller and the stuck call is
    abandoned (an XLA computation cannot be cancelled — the thread finishes
    or hangs on its own, its result discarded).  A per-call thread, not a
    pooled one, so one hung call can never block the next watched call.
    """
    if timeout_s is None:
        return fn()
    box: dict = {}
    done = threading.Event()

    def _target():
        try:
            box["result"] = fn()
        except BaseException as e:
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=_target, daemon=True,
                         name=f"repro-serve-watchdog/{label}")
    t.start()
    if not done.wait(timeout_s):
        raise WatchdogTimeout(
            f"device dispatch exceeded solve_timeout "
            f"({timeout_s * 1e3:.0f} ms){f' [{label}]' if label else ''}")
    if "error" in box:
        raise box["error"]
    return box["result"]


# -- circuit breaker --------------------------------------------------------


@dataclasses.dataclass
class _BreakerState:
    failures: int = 0
    state: str = "closed"      # closed | open | half_open
    opened_at: float = 0.0
    probing: bool = False      # half-open probe admitted, outcome pending


class CircuitBreaker:
    """Per-key consecutive-failure gate with a half-open probe.

    ``record_failure``/``record_success`` are called per compile/execute
    attempt by the serving worker; ``allow`` gates admissions.  K
    (``threshold``) *consecutive* failures open the circuit — interleaved
    successes (e.g. the innocent halves of a bisection) reset the count, so
    only a genuinely persistent fault opens it.  After ``cooldown``
    seconds, ONE admission is let through as the half-open probe; its
    outcome closes (success) or re-opens (failure) the circuit.
    """

    def __init__(self, *, threshold: int = 5, cooldown: float = 5.0,
                 clock=time.perf_counter):
        if threshold < 1:
            raise ValueError(f"threshold must be ≥ 1, got {threshold}")
        if cooldown < 0:
            raise ValueError(f"cooldown must be ≥ 0, got {cooldown}")
        self.threshold = threshold
        self.cooldown = cooldown
        self._clock = clock
        self._states: dict = {}
        self._opens = 0
        self._lock = threading.Lock()

    def allow(self, key) -> bool:
        """Admission gate: False ⇒ reject with ``reason="circuit_open"``."""
        with self._lock:
            st = self._states.get(key)
            if st is None or st.state == "closed":
                return True
            if st.state == "open":
                if self._clock() - st.opened_at < self.cooldown:
                    return False
                st.state = "half_open"
                st.probing = True
                return True  # this admission is the probe
            # half_open: one probe at a time
            if st.probing:
                return False
            st.probing = True
            return True

    def record_success(self, key) -> str:
        with self._lock:
            st = self._states.get(key)
            if st is not None:
                st.failures = 0
                st.state = "closed"
                st.probing = False
            return "closed"

    def record_failure(self, key) -> str:
        """Returns the post-failure state ("open" on a fresh trip)."""
        with self._lock:
            st = self._states.setdefault(key, _BreakerState())
            st.failures += 1
            if st.state == "half_open" or st.failures >= self.threshold:
                freshly = st.state != "open"
                st.state = "open"
                st.opened_at = self._clock()
                st.probing = False
                if freshly:
                    self._opens += 1
                return "open"
            return st.state

    def state(self, key) -> str:
        with self._lock:
            st = self._states.get(key)
            return "closed" if st is None else st.state

    def stats(self) -> dict:
        with self._lock:
            return {
                "tracked": len(self._states),
                "open": sum(1 for s in self._states.values()
                            if s.state == "open"),
                "half_open": sum(1 for s in self._states.values()
                                 if s.state == "half_open"),
                "opens": self._opens,
                "threshold": self.threshold,
                "cooldown_s": self.cooldown,
            }


# -- adaptive load shedding -------------------------------------------------


class LoadShedGovernor:
    """Deterministic admission shedding against the rolling latency window.

    A request is shed when (a) it carries a ``deadline_ms`` budget, (b) the
    user-scope latency window holds at least ``min_window`` observations,
    (c) the window's p95 is at or past ``threshold`` × deadline, and (d)
    the request's priority is at or below ``priority_cutoff`` — so under
    overload the lowest-priority tier is shed first and higher-priority
    admissions are never touched.  A pure function of its inputs: the same
    metrics window and request always produce the same verdict.
    """

    def __init__(self, *, threshold: float = 0.9, priority_cutoff: int = 0,
                 min_window: int = 8):
        if not threshold > 0:
            raise ValueError(f"threshold must be > 0, got {threshold}")
        if min_window < 1:
            raise ValueError(f"min_window must be ≥ 1, got {min_window}")
        self.threshold = threshold
        self.priority_cutoff = priority_cutoff
        self.min_window = min_window

    def should_shed(self, p95_s: float, deadline_ms: float | None,
                    priority: int, window: int) -> bool:
        if deadline_ms is None or window < self.min_window:
            return False
        if priority > self.priority_cutoff:
            return False
        return p95_s * 1e3 >= self.threshold * deadline_ms


# -- checkpoint / restore ---------------------------------------------------


@dataclasses.dataclass
class QueuedRequest:
    """One admitted-but-untaken request in a checkpoint."""

    rid: int                  # rid in the checkpointed service (old process)
    key: object               # _GroupKey (picklable: Family is pure data)
    item: object              # _Item — canonicalized native operands
    priority: int
    cv_fold: bool = False
    rs_member: bool = False


@dataclasses.dataclass
class InflightSlot:
    """One occupied batch slot at its last chunk boundary: the host-side
    ``(beta, grad, active, L, health)`` carry plus harvest bookkeeping —
    everything a resumed run needs to continue bit-identically."""

    rid: int
    key: object
    item: object
    priority: int
    cv_fold: bool
    beta: np.ndarray          # (P, m) padded carry row
    grad: np.ndarray          # (P, m)
    active: np.ndarray        # (P,) bool
    L: float                  # FISTA Lipschitz carry
    H: int                    # in-graph health word carry
    cursor: int               # next σ index to produce
    steps: list               # harvested per-step tuples so far
    null_dev: float
    prev_dev: float
    health0: int
    early_stop: bool
    solve_s: float


@dataclasses.dataclass
class ServiceCheckpoint:
    """Picklable snapshot of every admitted-but-undelivered request.

    Produced by ``AsyncPathService.checkpoint()`` at a chunk boundary;
    consumed by ``restore()`` on a fresh service (same code + backend
    versions), which re-admits the queued requests and resumes the
    in-flight slots from their carried state.
    """

    queued: list      # [QueuedRequest]
    inflight: list    # [InflightSlot]
    fingerprint: str = dataclasses.field(default_factory=backend_fingerprint)

    def save(self, path: str | os.PathLike) -> None:
        target = os.fspath(path)
        tmp = f"{target}.tmp.{os.getpid()}"
        with open(tmp, "wb") as fh:
            pickle.dump(self, fh)
        os.replace(tmp, target)

    @classmethod
    def load(cls, path: str | os.PathLike) -> "ServiceCheckpoint":
        with open(os.fspath(path), "rb") as fh:
            ckpt = pickle.load(fh)
        if not isinstance(ckpt, cls):
            raise TypeError(f"{path!r} does not hold a ServiceCheckpoint")
        return ckpt

    def __len__(self) -> int:
        return len(self.queued) + len(self.inflight)


def snapshot_queued(batcher, cv_fold_rids, rs_member_rids) -> list:
    """Build :class:`QueuedRequest` records from a batcher snapshot
    (non-destructive; caller holds the service lock)."""
    out = []
    for key, pend in batcher.snapshot():
        assert isinstance(pend, Pending)
        out.append(QueuedRequest(
            rid=pend.rid, key=key, item=pend.item, priority=pend.priority,
            cv_fold=pend.rid in cv_fold_rids,
            rs_member=pend.rid in rs_member_rids))
    return out
