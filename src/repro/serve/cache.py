"""Keyed compiled-executable cache for the path service.

The engine's jitted entry points already memoise compilations inside JAX,
but a serving layer needs more than a hidden dispatch cache: it needs to
**warm** programs before traffic arrives, **account** for compile time and
hit rates, and **bound** resident executables with real eviction.  So this
cache compiles ahead-of-time — ``jit(engine).lower(shapes...).compile()``
on :class:`jax.ShapeDtypeStruct` specs, no example data needed — and owns
the resulting executables outright (AOT executables bypass JAX's dispatch
cache, so evicting an entry actually frees the program).

AOT-compiled and jit-dispatched runs of the same program are bitwise
identical (same HLO, same pipeline — asserted in ``tests/test_serve.py``),
which is what lets the service guarantee served results match direct
``fit_path_batched(pad="bucket")`` calls exactly.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict

import numpy as np

import jax

from ..core.lambda_seq import float_dtype
from ..core.losses import Family
from ..obs import MetricsRegistry
from ..obs.profile import annotate

__all__ = ["ProgramSpec", "CompiledProgram", "ProgramCache"]


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """Static description of one compiled path program (the cache key).

    ``working_set=None`` selects the masked full-width engine; an int is the
    *resolved* static compact width W (power-of-two, resolution happens in
    the service/engine, not here).  ``working_set_top`` is the resolved
    second-tier width (None: single tier) — part of the key because the
    two-tier engine is a different compiled program.  ``n_rows``/``n_cols``
    are the padded bucket shape, ``batch`` the padded slot count.

    ``variant`` keys the slot-recycling program family the async dispatcher
    uses: ``"path"`` is the whole-grid program, ``"chunk"`` advances carried
    state by ``step_chunk`` σ-steps per call
    (:func:`repro.core.engine.chunk_path_engine` — masked engine only), and
    ``"init"`` is the batched prefill that seeds a newly inserted slot
    (:func:`repro.core.engine.path_init_engine`).  ``"replicate"`` is the
    weight-fused resample program: ``batch`` row-reweighted members against
    ONE shared ``(n_rows, n_cols)`` design
    (:func:`repro.core.engine.replicate_path_engine`, or the compact
    variant when ``working_set`` is set) — the resident operands are
    O(n·p + B·n), never a (B, n, p) stack.
    """

    family: Family
    batch: int
    n_rows: int
    n_cols: int
    path_length: int
    screening: str = "strong"
    solver_tol: float = 1e-8
    max_iter: int = 5000
    kkt_tol: float = 1e-4
    max_refits: int = 32
    working_set: int | None = None
    working_set_top: int | None = None
    dtype: str | None = None
    y_dtype: str | None = None
    variant: str = "path"
    step_chunk: int | None = None

    def __post_init__(self):
        # unset dtypes are JAX's default float: f64 only under x64
        for f in ("dtype", "y_dtype"):
            if getattr(self, f) is None:
                object.__setattr__(self, f, float_dtype().name)
        if self.variant not in ("path", "chunk", "init", "replicate"):
            raise ValueError(f"variant must be 'path', 'chunk', 'init' or "
                             f"'replicate', got {self.variant!r}")
        if self.variant == "chunk":
            if self.step_chunk is None or self.step_chunk < 1:
                raise ValueError("variant='chunk' needs step_chunk ≥ 1, got "
                                 f"{self.step_chunk!r}")
            if self.working_set is not None:
                raise ValueError(
                    "continuous chunk programs run the masked engine only "
                    "(compact carried state is not slot-swappable); "
                    "working_set must be None for variant='chunk'")
        elif self.step_chunk is not None:
            raise ValueError(
                f"step_chunk only applies to variant='chunk', got "
                f"variant={self.variant!r}")

    def short(self) -> str:
        w = f"W{self.working_set}" if self.working_set else "masked"
        if self.working_set and self.working_set_top:
            w += f"+{self.working_set_top}"
        s = (f"{self.family.name}/B{self.batch}n{self.n_rows}"
             f"p{self.n_cols}L{self.path_length}/{w}")
        if self.variant == "chunk":
            s += f"/chunk{self.step_chunk}"
        elif self.variant == "init":
            s += "/init"
        elif self.variant == "replicate":
            s += "/replicate"
        return s

    def plan(self):
        """The :class:`repro.api.plan.ExecutionPlan` this compiled program
        realises — how the serving layer exposes its (pinned) execution
        choices through the same introspection surface the planner uses."""
        from ..api.plan import ExecutionPlan

        if self.working_set is None:
            tiers = None
        elif self.working_set_top is None:
            tiers = (self.working_set,)
        else:
            tiers = (self.working_set, self.working_set_top)
        reason = f"pinned by compiled program group {self.short()}"
        if self.variant == "chunk":
            reason += (f" (continuous batching: {self.step_chunk}-step "
                       f"chunks, slots recycled at chunk boundaries)")
        elif self.variant == "replicate":
            reason += (f" (weight-fused replicates: {self.batch} members "
                       f"share ONE {self.n_rows}×{self.n_cols} design via "
                       f"per-member row weights)")
        return ExecutionPlan(
            backend="serve",
            mode="compact" if self.working_set else "masked",
            batch=self.batch, n=self.n_rows, p=self.n_cols,
            working_set=self.working_set, ws_tiers=tiers, pad="bucket",
            exec_shape=(self.batch, self.n_rows, self.n_cols),
            screening=self.screening,
            device=jax.default_backend(),
            reasons=(reason,),
        )


class CompiledProgram:
    """One AOT-compiled engine executable plus its call convention.

    ``"path"`` programs take ``(Xs, ys, lam, sigmas, p_valid)``; ``"chunk"``
    programs take ``(Xs, ys, lam, sig_prev, sig_next, live, beta, grad,
    active, L, health, p_valid)``; ``"init"`` programs take ``(Xs, ys)``;
    ``"replicate"`` programs take ``(X, ys, lam, sigmas, weights, p_valid)``
    with one shared (N, P) design, (B, N) member responses/weights and a
    scalar ``p_valid``.  Operands
    are converted as-is — AOT executables demand exact dtypes, so callers
    own them — except the trailing int32 ``p_valid``, which is cast for
    convenience on the variants that end with it.
    """

    def __init__(self, spec: ProgramSpec, compiled, build_seconds: float):
        self.spec = spec
        self.build_seconds = build_seconds
        self.calls = 0
        self._compiled = compiled

    def __call__(self, *operands):
        import jax.numpy as jnp

        self.calls += 1
        args = [jnp.asarray(a) for a in operands]
        if self.spec.variant in ("path", "chunk", "replicate"):
            args[-1] = jnp.asarray(args[-1], jnp.int32)  # p_valid
        return self._compiled(*args)


def _build(spec: ProgramSpec) -> tuple:
    """Lower + compile the engine for ``spec`` from shape specs alone."""
    from ..core.engine import (
        batched_path_engine,
        chunk_path_engine,
        compact_path_engine,
        path_init_engine,
        replicate_compact_path_engine,
        replicate_path_engine,
    )

    m = spec.family.n_classes
    f = np.dtype(spec.dtype)
    B, N, P, L = spec.batch, spec.n_rows, spec.n_cols, spec.path_length
    sds = jax.ShapeDtypeStruct
    data = (
        sds((B, N, P), f),                      # Xs
        sds((B, N), np.dtype(spec.y_dtype)),    # ys
    )
    lam = sds((B, P * m), f)                    # per-member λ
    pv = sds((B,), np.int32)
    kw = dict(screening=spec.screening, max_iter=spec.max_iter,
              tol=spec.solver_tol, kkt_tol=spec.kkt_tol,
              max_refits=spec.max_refits)
    t0 = time.perf_counter()
    if spec.variant == "replicate":
        # ONE shared (N, P) design, (B, N) member responses and row
        # weights, one shared λ/σ grid, scalar p_valid
        rdata = (
            sds((N, P), f),                         # shared X
            sds((B, N), np.dtype(spec.y_dtype)),    # per-member y
            sds((P * m,), f),                       # shared λ
            sds((L,), f),                           # shared σ grid
            sds((B, N), f),                         # per-member row weights
        )
        rpv = sds((), np.int32)
        if spec.working_set is None:
            lowered = replicate_path_engine.lower(*rdata, spec.family, rpv,
                                                  **kw)
        else:
            lowered = replicate_compact_path_engine.lower(
                *rdata, spec.family, rpv, width=spec.working_set,
                width2=spec.working_set_top, **kw)
    elif spec.variant == "init":
        lowered = path_init_engine.lower(*data, spec.family)
    elif spec.variant == "chunk":
        C = spec.step_chunk
        lowered = chunk_path_engine.lower(
            *data, lam,
            sds((B, C), f), sds((B, C), f), sds((B, C), bool),  # σ pairs, live
            sds((B, P, m), f), sds((B, P, m), f),               # beta, grad
            sds((B, P), bool), sds((B,), f),                    # active, L
            sds((B,), np.int32),                                # health
            spec.family, pv, **kw)
    elif spec.working_set is None:
        lowered = batched_path_engine.lower(*data, lam, sds((B, L), f),
                                            spec.family, pv, **kw)
    else:
        lowered = compact_path_engine.lower(*data, lam, sds((B, L), f),
                                            spec.family, pv,
                                            width=spec.working_set,
                                            width2=spec.working_set_top,
                                            **kw)
    with annotate(f"repro.compile/{spec.short()}"):
        compiled = lowered.compile()
    return compiled, time.perf_counter() - t0


class ProgramCache:
    """Bounded LRU cache of :class:`CompiledProgram` executables.

    ``get`` compiles on miss (slow — seconds) and returns ``(program,
    hit)``; ``warmup`` pre-compiles a list of specs so the first real
    request never pays XLA latency.  All mutation happens under one lock;
    compilation itself holds the lock too (simpler, and the service flushes
    batches from one thread — concurrent builders would just duplicate
    work).

    ``store`` (optional, a :class:`repro.serve.DurableProgramStore`) makes
    misses crash-safe: a miss first tries the store's serialized
    executable (milliseconds) before compiling from source (seconds), and
    every fresh build is saved back plus appended to the store's warmup
    manifest — so a restarted process replays the manifest at boot and
    compiles nothing it has already seen.  ``misses`` counts cache misses
    regardless of where the program came from; ``builds`` counts actual
    XLA compilations (a warm-store boot shows misses > 0, builds == 0).
    """

    def __init__(self, capacity: int = 32, store=None):
        if capacity < 1:
            raise ValueError(f"capacity must be ≥ 1, got {capacity}")
        self.capacity = capacity
        self.store = store
        self._data: OrderedDict[ProgramSpec, CompiledProgram] = OrderedDict()
        self._lock = threading.Lock()
        # hits/misses/evictions/build_seconds live on the unified registry;
        # stats() below is a read-through view preserving the legacy keys
        self.metrics = MetricsRegistry("cache")

    def get(self, spec: ProgramSpec) -> tuple[CompiledProgram, bool]:
        with self._lock:
            prog = self._data.get(spec)
            if prog is not None:
                self._data.move_to_end(spec)
                self.metrics.inc("hits")
                return prog, True
            self.metrics.inc("misses")
            prog = None if self.store is None else self.store.load(spec)
            if prog is None:
                compiled, dt = _build(spec)
                prog = CompiledProgram(spec, compiled, dt)
                self.metrics.inc("builds")
                self.metrics.inc("build_seconds", dt)
                self.metrics.observe("build_s", dt)
                if self.store is not None:
                    self.store.save(spec, prog)
            self._data[spec] = prog
            while len(self._data) > self.capacity:
                self._data.popitem(last=False)
                self.metrics.inc("evictions")
            return prog, False

    def warmup(self, specs) -> dict[str, float]:
        """Compile every spec now; returns ``{spec.short(): build_seconds}``
        (0.0 for specs that were already resident)."""
        out = {}
        for spec in specs:
            prog, hit = self.get(spec)
            out[spec.short()] = 0.0 if hit else prog.build_seconds
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def __contains__(self, spec: ProgramSpec) -> bool:
        with self._lock:
            return spec in self._data

    def stats(self) -> dict:
        m = self.metrics
        with self._lock:
            hits = m.value("hits")
            misses = m.value("misses")
            total = hits + misses
            return {
                "size": len(self._data),
                "capacity": self.capacity,
                "hits": hits,
                "misses": misses,
                "hit_rate": hits / total if total else 0.0,
                "evictions": m.value("evictions"),
                "builds": m.value("builds"),
                "build_seconds": round(m.value("build_seconds", 0.0), 3),
                "programs": {s.short(): p.calls for s, p in self._data.items()},
                "store": None if self.store is None else self.store.stats(),
            }
