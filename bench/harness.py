"""The benchmark harness: one run of one cell, driven by data.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix.  The harness finds each by its name:

* ``bench/configs/<config>.json``   sizes, design and penalty of a deployment
* ``bench/traffic/<traffic>.json``  the mix: its ``mode`` (``path``, ``cv``
                                    or ``closed``) and its parameters
* ``bench/limits/<workload>.json``  the limits of the correctness check
* ``bench/metrics/<metric>.py``     one reader per per-layer metric

A seed changes no work: the list of shapes, the bucket each request
lands in, the number of σ-steps of every path and the problems themselves
are fixed by the cell.  The seed draws the order of each design's rows
and columns and the signs of its columns, which leave the solution path
as it is.  Set-up generates the pool of designs and runs the traffic over
the whole pool until one pass compiles nothing; the window then runs the
same traffic for ``--seconds``.  After the window,
every answer (or a seeded sample, where the mix says so) is held to the
plain reference in ``reference.py``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

import numpy as np

import reference as ref
import trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))


class SetupError(RuntimeError):
    """The cell cannot run here (no accelerator, too few chips, ...)."""


# -- the manifest and the files it names ------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: str) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve(root: str, workload: str) -> dict:
    """Everything one cell needs, found by the names in the manifest."""
    man = manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise SetupError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in man["configs"]}
    cfg = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    limits = load_json(os.path.join(BENCH, "limits", workload + ".json"))

    def reports(metric):
        return workload in metric.get("workloads", [workload])

    return dict(cell=cell, cfg=cfg, traffic=traffic, limits=limits,
                end_to_end=[m for m in man["end_to_end"] if reports(m)],
                per_layer=[m for m in man["per_layer"] if reports(m)])


def load_reader(name: str):
    """The ``read(run)`` function of per-layer metric ``name``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str, path: str | None = None) -> dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    table = load_json(path or os.path.join(BENCH, "peaks.json"))
    if kind not in table["devices"]:
        raise SetupError(f"device kind {kind!r} is not in the peaks table "
                         f"({sorted(table['devices'])})")
    return table["devices"][kind]


# -- compile accounting -------------------------------------------------------

class CompileCounter:
    """Counts the executables JAX builds or loads from its compile cache,
    and apart from them its traces, as they happen."""

    BUILDS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.BUILDS:
            self.count += 1
        elif event == self.TRACE:
            self.traces += 1


# -- the data of one seed -----------------------------------------------------

@dataclasses.dataclass
class Design:
    X: np.ndarray          # float32 (n, p): what the program is given
    y: np.ndarray
    lam: np.ndarray        # reference λ (float64)
    sigmas: np.ndarray     # reference σ grid of this design's path
    _X64: np.ndarray | None = None

    @property
    def X64(self) -> np.ndarray:
        if self._X64 is None:
            self._X64 = self.X.astype(np.float64)
        return self._X64


def path_length(cfg, traffic) -> int:
    return int(traffic.get("path_length", cfg["path_length"]))


def grid_ratio(cfg, traffic) -> float:
    """σ(L)/σ(1) of the traffic's path: the top of the config's grid."""
    return ref.top_ratio(cfg["sigma_ratio"], cfg["path_length"],
                         path_length(cfg, traffic))


def make_design(cfg, traffic, seed: int, index: int, n: int,
                p: int) -> Design:
    """Design ``index`` of the deployment's data (drawn from the config's
    ``data_seed``), put in the order that the run's ``seed`` draws."""
    base = ref.rng_for(cfg["data_seed"], n, p, index)
    X = ref.design(base, n, p, cfg["design"], cfg["rho"])
    y = ref.regression(base, X, cfg["k"], cfg["noise"])
    X, y = ref.orient(ref.rng_for(seed, index), X, y,
                      rows=traffic["mode"] != "cv")
    lam = ref.bh_lambda(p, cfg["lambda"]["q"])
    sig = ref.sigma_grid(X, y, lam, path_length(cfg, traffic),
                         grid_ratio(cfg, traffic))
    return Design(X, y, lam, sig)


def request_table(traffic) -> list[tuple[int, int]]:
    """The (n, p) shapes of a closed-loop mix, in table order: n evenly
    over its range, p evenly over each of the mix's p ranges in turn."""
    shapes = []
    n_lo, n_hi = traffic["n_range"]
    ns = np.rint(np.linspace(n_lo, n_hi, traffic["table_size"])).astype(int)
    ps = []
    for lo, hi, count in traffic["p_ranges"]:
        ps.extend(np.rint(np.linspace(lo, hi, count)).astype(int).tolist())
    if len(ps) != len(ns):
        raise ValueError(f"p ranges give {len(ps)} shapes, n range "
                         f"{len(ns)}")
    for n, p in zip(ns.tolist(), ps):
        shapes.append((int(n), int(p)))
    return shapes


def make_pool(cfg, traffic, seed: int) -> list[Design]:
    if traffic["mode"] == "closed":
        return [make_design(cfg, traffic, seed, i, n, p)
                for i, (n, p) in enumerate(request_table(traffic))]
    return [make_design(cfg, traffic, seed, i, cfg["n"], cfg["p"])
            for i in range(traffic["pool"])]


# -- the system under test ----------------------------------------------------

def path_spec(cfg, traffic, **kw):
    from repro.api import LambdaSpec, PathSpec

    lam = cfg["lambda"]
    return PathSpec(LambdaSpec(lam["kind"], q=lam["q"]),
                    path_length=path_length(cfg, traffic),
                    sigma_ratio=grid_ratio(cfg, traffic),
                    early_stop=False, **kw)


@dataclasses.dataclass
class Answer:
    index: int             # pool index of the design it answers
    t0: float
    t1: float
    betas: np.ndarray | None = None   # (L, p), or (K, L, p) for a CV
    error: str | None = None
    info: dict = dataclasses.field(default_factory=dict)

    @property
    def latency(self) -> float:
        return self.t1 - self.t0


class Sequential:
    """``path`` and ``cv`` mixes: whole fits back to back, cycling the pool."""

    def __init__(self, cfg, traffic, pool, dtype):
        from repro.api import Problem, SolverPolicy

        self.cfg, self.traffic, self.pool = cfg, traffic, pool
        kw = {}
        if traffic["mode"] == "cv":
            kw["cv_folds"] = traffic["folds"]
        self.spec = path_spec(cfg, traffic, **kw)
        self.policy = SolverPolicy()
        self.problems = [Problem(d.X.astype(dtype), d.y.astype(dtype))
                         for d in pool]
        self.next = 0

    def one(self) -> Answer:
        from repro import api

        i = self.next % len(self.pool)
        self.next += 1
        t0 = time.perf_counter()
        res = api.slope_path(self.problems[i], self.spec, self.policy)
        t1 = time.perf_counter()
        if self.traffic["mode"] == "cv":
            fp = res.fold_paths
            fb = fp.compact_fallback
            info = dict(iters=int(np.sum(fp.solver_iters)),
                        steps=int(fp.betas.shape[0] * fp.betas.shape[1]),
                        fallback=0 if fb is None else int(np.sum(fb)),
                        mode=res.plan.mode if res.plan else None)
            betas = np.asarray(fp.betas)
        else:
            info = dict(iters=int(sum(s.solver_iters for s in res.steps)),
                        screened=[s.n_screened for s in res.steps],
                        p=self.problems[i].X.shape[1],
                        mode=res.plan.mode if res.plan else None)
            betas = np.asarray(res.betas)
        return Answer(i, t0, t1, betas=betas, info=info)

    def warm_pass(self) -> list[Answer]:
        self.next = 0
        out = [self.one() for _ in self.pool]
        self.next = 0
        return out

    def window(self, seconds: float) -> tuple[list[Answer], float, float]:
        """Fits back to back while the window is open; every fit started
        in it is counted, with all of its time."""
        out = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            out.append(self.one())
        return out, t0, time.perf_counter()

    def close(self):
        pass


class ClosedLoop:
    """``closed`` mixes: clients that each wait for a reply before the
    next request, cycling the request table from their own offsets."""

    def __init__(self, cfg, traffic, pool, dtype, tracing=False):
        from repro.api import Problem, SolverPolicy
        from repro.serve import AsyncPathService

        self.cfg, self.traffic, self.pool = cfg, traffic, pool
        self.spec = path_spec(cfg, traffic)
        self.policy = SolverPolicy(working_set=None)  # continuous batching
        self.problems = [Problem(d.X.astype(dtype), d.y.astype(dtype))
                         for d in pool]
        self.svc = AsyncPathService(tracing=tracing)
        self.clients = int(traffic["clients"])

    def _request(self, i: int) -> Answer:
        from repro.serve import Rejection

        t0 = time.perf_counter()
        try:
            r = self.svc.submit(problem=self.problems[i], path=self.spec,
                                policy=self.policy).result(
                timeout=self.traffic["request_timeout_s"])
        except Exception as e:  # a request that fails is a failed answer
            return Answer(i, t0, time.perf_counter(),
                          error=f"{type(e).__name__}: {e}")
        t1 = time.perf_counter()
        if isinstance(r, Rejection):
            return Answer(i, t0, t1, error=f"rejected: {r}")
        info = dict(iters=int(np.sum(r.solver_iters)), steps=len(r.sigmas))
        if r.trace is not None:
            info["spans"] = [(s.name, s.duration_s) for s in r.trace.top()]
            info["trace_total"] = r.trace.total_s
        return Answer(i, t0, t1, betas=np.asarray(r.betas), info=info)

    def _run(self, per_client: int | None, seconds: float | None):
        """Each client's loop; ``per_client`` requests, or until the
        window of ``seconds`` closes."""
        n = len(self.pool)
        results = [[] for _ in range(self.clients)]
        t0 = time.perf_counter()

        crashed = []

        def client(c):
            try:
                j = 0
                while True:
                    if per_client is not None and j >= per_client:
                        return
                    if (seconds is not None
                            and time.perf_counter() - t0 >= seconds):
                        return
                    results[c].append(self._request((c + j) % n))
                    j += 1
            except BaseException as e:  # re-raised below, in the caller
                crashed.append(e)

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(self.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if crashed:
            raise crashed[0]
        return [a for r in results for a in r], t0

    def warm_pass(self) -> list[Answer]:
        # every client one request: each table shape once, all at a time
        return self._run(per_client=max(1, len(self.pool) // self.clients),
                         seconds=None)[0]

    def window(self, seconds: float):
        out, t0 = self._run(per_client=None, seconds=seconds)
        return out, t0, t0 + seconds

    def close(self):
        self.svc.close()


# -- the check ------------------------------------------------------------------

def check(cfg, traffic, limits, pool, answers, seed) -> dict:
    """Hold the window's answers to the reference.  Returns the numbers
    compared, each with its limit, and the count of failed answers."""
    mode = traffic["mode"]
    L = path_length(cfg, traffic)
    sample = traffic.get("check_sample")
    order = list(range(len(answers)))
    if sample is not None and len(order) > sample:
        order = sorted(ref.rng_for(seed, 99).choice(order, size=sample,
                                                    replace=False).tolist())
    failed, worst, steps = 0, 0.0, 0
    for a in answers:
        if a.error is not None:
            failed += 1
        elif not np.all(np.isfinite(a.betas)):
            failed += 1
            a.error = "non-finite coefficients"
    for j in order:
        a = answers[j]
        if a.error is not None:
            continue
        d = pool[a.index]
        if mode == "cv":
            K = traffic["folds"]
            want = (K, L, d.X.shape[1])
            if a.betas.shape != want:
                failed += 1
                a.error = f"fold paths {a.betas.shape}, want {want}"
                continue
            trains, _ = ref.cv_folds(d.X.shape[0], K)
            for f, tr in enumerate(trains):
                # index 0 is the null head at the full-data σ(1): β = 0
                if np.any(a.betas[f, 0] != 0):
                    failed += 1
                    a.error = f"fold {f} step 0 is not the null head"
                    break
                lv = ref.path_levels(d.X64[tr], d.y[tr].astype(np.float64),
                                     a.betas[f, 1:], d.sigmas[1:], d.lam)
                worst = max(worst, float(np.max(lv)))
                steps += len(lv)
        else:
            want = (L, d.X.shape[1])
            if a.betas.shape != want:
                failed += 1
                a.error = f"path {a.betas.shape}, want {want}"
                continue
            lv = ref.path_levels(d.X64, d.y.astype(np.float64), a.betas,
                                 d.sigmas, d.lam)
            worst = max(worst, float(np.max(lv)))
            steps += len(lv)
    return dict(
        failed=failed, steps=steps, checked=len(order),
        numbers={
            "kkt_worst": (worst, limits["kkt_worst"]),
            "failed_answers": (failed, 0),
        })


# -- metrics --------------------------------------------------------------------

def end_to_end(mode, answers, t0, t1, seconds) -> dict:
    if mode in ("path", "cv"):
        per = (answers[-1].t1 - t0) / len(answers)
        return {"path_s" if mode == "path" else "cv_s": per}
    done = [a for a in answers if a.error is None]
    in_window = sum(1 for a in done if a.t1 <= t1)
    lat = sorted(a.latency for a in done)
    p90 = statistics.quantiles(lat, n=10)[-1] if len(lat) >= 2 else lat[0]
    return {"req_per_s": in_window / seconds, "req_p90_s": p90}


@dataclasses.dataclass
class RunResult:
    correct: bool
    attempted: int
    failed: int
    metrics: dict
    device: dict
    checks: dict
    breakdown: dict | None = None
    notes: dict = dataclasses.field(default_factory=dict)

    def line(self) -> str:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["notes"] = self.notes
        out["checks"] = {k: {"value": v, "limit": lim}
                         for k, (v, lim) in self.checks.items()}
        return json.dumps(out)


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    dev = devs[0]
    if require_chip and dev.platform == "cpu":
        raise SetupError("JAX finds no accelerator (platform cpu)")
    if require_chip and len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run(root: str, workload: str, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, resolved: dict | None = None,
        dtype=np.float32, t_start: float | None = None) -> RunResult:
    """One run of one cell.  ``resolved`` (the cell's files, as
    :func:`resolve` gives them) and ``dtype`` (what the program is fed)
    let tests drive a run at a small size and in another precision."""
    t_start = time.perf_counter() if t_start is None else t_start
    r = resolved or resolve(root, workload)
    cell, cfg, traffic, limits = r["cell"], r["cfg"], r["traffic"], r["limits"]
    chips = int(cell["chips"])

    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SetupError(f"the system under test is not at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    device = device_info(chips, require_chip)
    if require_chip:
        peaks_for(device["kind"])
    if jax.default_backend() != "cpu":
        # a fixed directory inside the checkout, whatever the environment
        # names: only the first run of a cell in a checkout compiles
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    counter = CompileCounter()

    t_data = time.perf_counter()
    pool = make_pool(cfg, traffic, seed)
    t_warm = time.perf_counter()
    mode = traffic["mode"]
    driver = (ClosedLoop(cfg, traffic, pool, dtype, tracing=trace)
              if mode == "closed" else Sequential(cfg, traffic, pool, dtype))
    trace_dir = None
    try:
        warm = traffic.get("warm_passes", 3)
        for _ in range(warm):
            before = counter.count
            driver.warm_pass()
            if counter.count == before:
                break
        setup_s = time.perf_counter() - t_start
        split = dict(start_s=t_data - t_start, data_s=t_warm - t_data,
                     warm_s=time.perf_counter() - t_warm,
                     warm_builds=counter.count)
        compiles0, traces0 = counter.count, counter.traces
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                answers, t0, t1 = driver.window(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        window_compiles = counter.count - compiles0
        window_traces = counter.traces - traces0
        device["memory_peak_bytes"] = memory_peak(chips)
    finally:
        driver.close()
    driver = None

    reduced = None
    if trace:
        reduced = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    chk = check(cfg, traffic, limits, pool, answers, seed)
    numbers = chk["numbers"]
    correct = all(v <= lim for v, lim in numbers.values())

    if trace:
        run_view = dict(answers=answers, trace=reduced, cfg=cfg,
                        traffic=traffic, pool=pool)
        metrics = {}
        for m in r["per_layer"]:
            v = load_reader(m["name"])(run_view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
    else:
        e2e = end_to_end(mode, answers, t0, t1, seconds)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in r["end_to_end"]}
        breakdown = None
    errors = sorted({a.error for a in answers if a.error})[:5]
    notes = dict(window_compiles=window_compiles,
                 window_traces=window_traces, setup_split=split,
                 iters=[a.info.get("iters") for a in answers
                        ] if mode != "closed" else None,
                 iters_total=sum(a.info.get("iters", 0) for a in answers),
                 answers=len(answers), steps_checked=chk["steps"],
                 answers_checked=chk["checked"], errors=errors)
    return RunResult(correct=correct, attempted=len(answers),
                     failed=chk["failed"], metrics=metrics, device=device,
                     checks=numbers, breakdown=breakdown, notes=notes)
