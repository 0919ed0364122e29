"""repro.obs — metrics registry, request tracing, solver introspection
(ISSUE 8).

Contracts under test:

* `MetricsRegistry` counts exactly under N-thread contention on one
  labeled series (the registry is the single accounting surface for the
  whole serving stack, so a lost increment is a lost request);
* histograms are bounded (one eviction policy for every telemetry window)
  while `total` stays monotonic;
* `Trace` timelines are gap-free by construction and children are
  parented to spans that exist;
* `PathService.stats()` / `AsyncPathService.stats()` key schemas are
  snapshot-pinned, and the async schema is a STRICT superset of the sync
  one (both are read-through views over the same registry — they cannot
  drift independently);
* a traced request carries an admit→deliver timeline with no gaps, and
  tracing stays OFF by default (`resp.trace is None`);
* `SolverPolicy(telemetry=...)` attaches a `PathTrace` whose screened-set
  counts match the fit's own arrays;
* exporters round-trip through JSONL and render Prometheus text;
* the host path driver keeps one gap-free phase trace per path
  (`PathResult.trace`), counts its transfers where they happen, keeps the
  trace in the bounded `recent()` recorder, and writes each phase as a
  `repro.path.<name>` profiler annotation without moving a coefficient.
"""

import glob
import json
import re
import threading

import numpy as np
import pytest

from repro.api import LambdaSpec, PathSpec, Problem, SolverPolicy, slope_path
from repro.core import bh_sequence, ols
from repro.obs import (
    MetricsRegistry,
    PathTrace,
    Trace,
    prometheus_text,
    registry_events,
    trace_events,
    write_jsonl,
)
from repro.obs import trace as obs_trace
from repro.serve import AsyncPathService, PathService, ProgramCache

KW = dict(path_length=6, solver_tol=1e-10, max_iter=20000)


@pytest.fixture(scope="module")
def shared_cache():
    return ProgramCache(capacity=16)


def _problem(n=24, p=20, seed=0, k=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, p))
    beta = np.zeros(p)
    beta[:k] = 2.0
    y = X @ beta + 0.1 * rng.normal(size=n)
    return X, y


# ---------------------------------------------------------------------------
# MetricsRegistry: counters, gauges, histograms, labels
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_histograms():
    m = MetricsRegistry("t")
    assert m.inc("a") == 1
    assert m.inc("a", 4) == 5
    assert m.value("a") == 5
    assert m.value("missing") == 0
    assert m.value("missing", default=-1) == -1
    m.set_gauge("depth", 3.5)
    assert m.gauge("depth").value == 3.5
    for v in (1.0, 2.0, 3.0, 4.0):
        m.observe("lat", v)
    h = m.histogram("lat")
    assert h.retained == 4 and h.total == 4
    assert h.mean() == 2.5
    assert h.percentile(50) == pytest.approx(2.5)
    snap = m.snapshot()
    assert snap["namespace"] == "t"
    assert snap["counters"]["a"] == 5
    assert snap["gauges"]["depth"] == 3.5
    assert snap["histograms"]["lat"]["count"] == 4


def test_registry_labeled_series_are_distinct():
    m = MetricsRegistry("t")
    m.inc("flush", trigger="fill")
    m.inc("flush", 2, trigger="deadline")
    assert m.value("flush", trigger="fill") == 1
    assert m.value("flush", trigger="deadline") == 2
    assert m.value("flush") == 0  # the unlabeled series is its own
    assert m.label_values("flush", "trigger") == {"fill": 1, "deadline": 2}


def test_histogram_window_is_bounded_total_is_not():
    m = MetricsRegistry("t")
    for i in range(100):
        m.observe("w", float(i), maxlen=16)
    h = m.histogram("w", maxlen=16)
    assert h.retained == 16
    assert h.maxlen == 16
    assert h.total == 100          # monotonic despite eviction
    assert min(h.values()) == 84.0  # oldest observations evicted


def test_registry_exact_counts_under_thread_contention():
    m = MetricsRegistry("t")
    n_threads, per_thread = 8, 2500

    def worker():
        for _ in range(per_thread):
            m.inc("hits", op="x")
            m.observe("lat", 1.0, op="x")

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert m.value("hits", op="x") == n_threads * per_thread
    assert m.histogram("lat", op="x").total == n_threads * per_thread


# ---------------------------------------------------------------------------
# Trace: gap-free spans, parented children
# ---------------------------------------------------------------------------

def test_trace_is_contiguous_by_construction():
    tr = Trace(rid=7, t0=100.0)
    tr.mark("admit", 100.5)
    tr.mark("queue", 101.0)
    tr.mark("execute", 103.0, batch=4)
    tr.child("retry", t0=102.0, t1=102.0, attempt=1)
    tr.mark("deliver", 103.25)
    assert tr.span_names() == ["admit", "queue", "execute", "deliver"]
    assert [s.name for s in tr.children()] == ["retry"]
    assert tr.children()[0].parent == "execute"
    assert tr.contiguous()
    assert tr.well_parented()
    assert tr.total_s == pytest.approx(3.25)
    # a non-monotonic clock cannot open a gap: t_end clamps to the cursor
    tr2 = Trace(rid=0, t0=10.0)
    tr2.mark("a", 11.0)
    tr2.mark("b", 10.5)   # behind the cursor
    assert tr2.contiguous()
    assert tr2.top()[-1].duration_s == 0.0


def test_trace_events_and_render():
    tr = Trace(rid=1, t0=0.0)
    tr.mark("admit", 0.25)
    tr.mark("deliver", 1.0)
    evs = trace_events(tr, run="x")
    assert all(e["rid"] == 1 and e["run"] == "x" for e in evs)
    assert [e["name"] for e in evs] == ["admit", "deliver"]
    out = tr.render()
    assert "admit" in out and "deliver" in out


# ---------------------------------------------------------------------------
# stats() schema snapshots: sync pinned, async a strict superset
# ---------------------------------------------------------------------------

SYNC_STATS_KEYS = {
    "submitted", "completed", "pending", "unclaimed", "results_evicted",
    "batches", "flush_fill", "flush_deadline", "flush_forced", "flush_retry",
    "rejected", "validation_rejected", "shed", "watchdog_timeouts",
    "breaker", "kkt_violations", "max_queue",
    "faults", "slots", "occupancy_mean", "padding_ratio_mean",
    "latency_ms_p50", "latency_ms_p95", "latency_count",
    "internal_latency_ms_p50", "internal_latency_ms_p95",
    "internal_latency_count", "cache", "plans", "ws_buckets", "resample",
}

ASYNC_ONLY_KEYS = {
    "slot_recycles", "chunk_batches", "step_chunk", "inflight", "retries",
    "bisections", "poisoned", "checkpoints", "restored",
    "retry_limit", "retry_backoff", "worker_alive",
}


def test_stats_schema_snapshot(shared_cache):
    svc = PathService(cache=shared_cache)
    assert set(svc.stats().keys()) == SYNC_STATS_KEYS
    asvc = AsyncPathService(cache=shared_cache, autostart=False)
    try:
        async_keys = set(asvc.stats().keys())
    finally:
        asvc.close(flush=False)
    # strict superset: every sync key present, plus exactly the async keys
    assert async_keys > SYNC_STATS_KEYS
    assert async_keys - SYNC_STATS_KEYS == ASYNC_ONLY_KEYS


def test_cache_and_bucket_stats_schema(shared_cache):
    assert set(shared_cache.stats().keys()) == {
        "size", "capacity", "hits", "misses", "hit_rate", "evictions",
        "builds", "build_seconds", "store", "programs"}
    from repro.core.engine import _WS_BUCKETS
    assert set(_WS_BUCKETS.stats().keys()) == {
        "name", "size", "capacity", "hits", "misses", "updates",
        "evictions", "entries"}


# ---------------------------------------------------------------------------
# end-to-end: a traced sync request, tracing off by default
# ---------------------------------------------------------------------------

def test_sync_request_trace_covers_admit_to_deliver(shared_cache):
    X, y = _problem()
    svc = PathService(max_batch=2, cache=shared_cache, tracing=True)
    rid = svc.submit(X, y, family=ols, **KW)
    resp = svc.poll(rid, flush=True)
    tr = resp.trace
    assert tr is not None and tr.rid == rid
    names = tr.span_names()
    assert names[0] == "admit" and names[-1] == "deliver"
    assert {"queue", "flush", "compile", "execute", "harvest"} <= set(names)
    assert tr.contiguous()
    assert tr.well_parented()
    # registry agrees with delivery
    assert svc.metrics.value("submitted") == 1
    assert svc.metrics.value("completed") == 1


def test_tracing_off_by_default(shared_cache):
    X, y = _problem(seed=1)
    svc = PathService(max_batch=2, cache=shared_cache)
    resp = svc.poll(svc.submit(X, y, family=ols, **KW), flush=True)
    assert resp.trace is None
    assert not svc._traces  # no per-request state retained


# ---------------------------------------------------------------------------
# solver introspection: SolverPolicy.telemetry → PathTrace
# ---------------------------------------------------------------------------

def test_policy_telemetry_attaches_path_trace():
    rng = np.random.default_rng(3)
    B, n, p = 3, 20, 24
    Xs = rng.normal(size=(B, n, p))
    beta = np.zeros(p)
    beta[:4] = 2.0
    ys = Xs @ beta + 0.1 * rng.normal(size=(B, n))
    lam = np.asarray(bh_sequence(p, q=0.1))
    spec = PathSpec(lam=lam, path_length=6)
    pol = SolverPolicy(backend="compact", working_set=8, pad=None,
                       telemetry="steps", **{"solver_tol": 1e-10})
    res = slope_path(Problem(Xs, ys), spec, pol)
    pt = res.path_trace
    assert isinstance(pt, PathTrace)
    assert pt.mode == "steps"
    assert pt.n_members == B and pt.n_steps == 6 and pt.p == p
    # steps mode retains the raw arrays and they match the result's own
    np.testing.assert_array_equal(pt.n_screened, res.n_screened)
    np.testing.assert_array_equal(pt.n_violations, res.n_violations)
    assert pt.screened_peak.shape == (B,)
    assert pt.tier_steps.shape == (B, 3)
    np.testing.assert_array_equal(
        pt.tier_steps.sum(axis=1), np.full(B, 6))
    assert (0.0 <= pt.screened_occupancy).all()
    assert (pt.screened_occupancy <= 1.0).all()
    assert "screened_occupancy_mean" in pt.summary()
    assert "sigma" in pt.render(0)

    # summary mode drops the per-step arrays; off attaches nothing —
    # and NEITHER perturbs the coefficients
    pol_sum = SolverPolicy(backend="compact", working_set=8, pad=None,
                           telemetry="summary", solver_tol=1e-10)
    res_sum = slope_path(Problem(Xs, ys), spec, pol_sum)
    assert res_sum.path_trace.mode == "summary"
    assert res_sum.path_trace.n_screened is None
    pol_off = SolverPolicy(backend="compact", working_set=8, pad=None,
                           solver_tol=1e-10)
    res_off = slope_path(Problem(Xs, ys), spec, pol_off)
    assert res_off.path_trace is None
    np.testing.assert_array_equal(res.betas, res_off.betas)
    np.testing.assert_array_equal(res_sum.betas, res_off.betas)


def test_path_trace_mode_validation():
    with pytest.raises(ValueError, match="telemetry"):
        SolverPolicy(telemetry="everything")
    with pytest.raises(ValueError):
        PathTrace.from_arrays(
            mode="off", p=4, sigmas=np.ones((1, 2)),
            n_screened=np.ones((1, 2)), n_active=np.ones((1, 2)),
            n_violations=np.zeros((1, 2)), refits=np.zeros((1, 2)),
            solver_iters=np.ones((1, 2)), health=np.zeros((1, 2)))


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_jsonl_export_roundtrip(tmp_path):
    m = MetricsRegistry("exp")
    m.inc("reqs", 3, route="a")
    m.set_gauge("depth", 2.0)
    m.observe("lat", 0.5)
    tr = Trace(rid=9, t0=0.0)
    tr.mark("admit", 0.5)
    tr.mark("deliver", 1.0)
    path = tmp_path / "metrics.jsonl"
    n = write_jsonl(str(path), registry_events(m, run="ci"))
    n += write_jsonl(str(path), trace_events(tr), append=True)
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == n == 5
    kinds = {ln["kind"] for ln in lines if "kind" in ln}
    assert kinds == {"counter", "gauge", "histogram"}
    assert lines[0]["run"] == "ci"
    span_lines = [ln for ln in lines if "rid" in ln]
    assert [s["name"] for s in span_lines] == ["admit", "deliver"]


def test_prometheus_text_exposition():
    m = MetricsRegistry("serve")
    m.inc("completed", 7)
    m.inc("flush", 2, trigger="fill")
    m.inc("flush", 1, trigger="deadline")
    m.observe("latency_s", 0.25, scope="user")
    text = prometheus_text(m)
    assert "# TYPE serve_completed counter" in text
    assert "serve_completed 7" in text
    assert 'serve_flush{trigger="fill"} 2' in text
    assert 'serve_flush{trigger="deadline"} 1' in text
    # one TYPE line per metric name even with several labeled series
    assert text.count("# TYPE serve_flush counter") == 1
    assert 'quantile="0.95"' in text
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# host path driver: phase spans, transfer counters, flight recorder
# ---------------------------------------------------------------------------

HOST_SPEC = PathSpec(lam=LambdaSpec("bh", q=0.1), path_length=10,
                     early_stop=False)
HOST_POLICY = SolverPolicy(backend="host")


def _host_problem():
    X, y = _problem(n=40, p=300, seed=11, k=5)
    return Problem(X, y, family=ols)


@pytest.fixture(scope="module")
def host_fit():
    prob = _host_problem()
    return prob, slope_path(prob, HOST_SPEC, HOST_POLICY)


def test_trace_phase_marks_on_exit_with_the_body_s_counts():
    tr = Trace()
    with tr.phase("solve", step=1, fetches=0) as span:
        span["fetches"] += 2
        assert tr.spans == []  # marked on exit, not before
    (s,) = tr.top()
    assert s.name == "solve" and s.attrs == {"step": 1, "fetches": 2}
    assert tr.contiguous() and s.t1 == tr.cursor


def test_host_path_trace_is_gap_free_and_times_the_path(host_fit):
    _, res = host_fit
    tr = res.trace
    assert isinstance(tr, Trace)
    assert tr.contiguous()
    assert res.total_time == tr.total_s
    # a step's wall time is its spans (step 0's is the set-up)
    for i, st in enumerate(res.steps):
        spans = [s for s in tr.top() if s.attrs["step"] == i]
        assert st.wall_time == pytest.approx(
            sum(s.duration_s for s in spans), rel=1e-9, abs=1e-12)
    assert sum(st.wall_time for st in res.steps) == pytest.approx(
        res.total_time, rel=1e-9)


def test_host_path_spans_follow_the_driver_s_phases(host_fit):
    _, res = host_fit
    tops = res.trace.top()
    names = " ".join(s.name for s in tops)
    assert re.fullmatch(
        r"setup( screen( gather solve gradient kkt)+ loss){9}", names), names
    solves = [s for s in tops if s.name == "solve"]
    assert len(solves) == sum(st.refits + 1 for st in res.steps[1:])
    for s in tops:
        assert {"step", "refit", "h2d_bytes", "fetches",
                "d2h_bytes"} <= set(s.attrs)
    for s in solves:
        assert s.attrs["width"] <= s.attrs["bucket"] <= 300
        # FISTA's uploads (Xs, y, λ, warm start), then iters and β back
        assert s.attrs["fetches"] == 2
    for st, loss in zip(res.steps[1:],
                        [s for s in tops if s.name == "loss"]):
        assert loss.attrs["refit"] == st.refits


def test_host_path_counts_each_gradient_s_transfers(host_fit):
    prob, res = host_fit
    X, y = np.asarray(prob.X), np.asarray(prob.y)
    beta_bytes = X.shape[1] * X.dtype.itemsize
    grads = [s for s in res.trace.top() if s.name == "gradient"]
    assert grads
    for s in grads:
        assert s.attrs["h2d_bytes"] == X.nbytes + y.nbytes + beta_bytes
        assert s.attrs["fetches"] == 1
        assert s.attrs["d2h_bytes"] == beta_bytes
    for s in res.trace.top():
        if s.name == "gather":  # host work only
            assert s.attrs["h2d_bytes"] == s.attrs["fetches"] == 0
        if s.name in ("kkt", "screen", "loss", "setup"):
            assert s.attrs["fetches"] >= 1 and s.attrs["h2d_bytes"] > 0


def test_recent_keeps_the_last_path_traces_within_its_bound(host_fit):
    _, res = host_fit
    assert any(t is res.trace for t in obs_trace.recent())
    for _ in range(obs_trace.RECENT_PATHS + 5):
        obs_trace.keep(Trace())
    kept = obs_trace.recent()
    assert len(kept) == obs_trace.RECENT_PATHS
    assert not any(t is res.trace for t in kept)
    # device backends keep no host trace
    other = slope_path(_host_problem(), HOST_SPEC,
                       SolverPolicy(backend="masked"))
    assert other.trace is None


def test_host_path_is_bitwise_under_a_profiler_and_annotated(host_fit,
                                                             tmp_path):
    import jax

    prob, res = host_fit
    with jax.profiler.trace(str(tmp_path)):
        traced = slope_path(prob, HOST_SPEC, HOST_POLICY)
    np.testing.assert_array_equal(traced.betas, res.betas)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes for line in plane.lines
             for e in line.events}
    for phase in ("setup", "screen", "gather", "solve", "gradient", "kkt",
                  "loss"):
        assert f"repro.path.{phase}" in names
