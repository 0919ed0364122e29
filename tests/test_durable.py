"""Crash-safe serving (ISSUE 10).

The contracts under test, in dependency order:

1. **Durable program store.**  A saved executable loads without compiling
   (``builds == 0`` on the warm path) and executes bit-identically to a
   freshly-built program; corrupt or fingerprint-mismatched entries are
   discarded — never trusted — and the caller rebuilds.
2. **Manifest replay.**  A second boot against the same store replays the
   warmup manifest and compiles ZERO programs before serving traffic.
3. **Checkpoint/restore.**  A service killed mid-chunk (checkpoint) and
   restored on a fresh process completes every captured request
   bit-identical to an uninterrupted run (maxdiff == 0).
4. **Watchdog.**  An injected ``kind="hang"`` past ``solve_timeout_ms``
   trips the watchdog; the cohort recovers through retry/bisection and
   every result stays bit-identical.
5. **Circuit breaker.**  K consecutive compile faults open the circuit
   (``Rejection(reason="circuit_open")``); after the cooldown a half-open
   probe closes it again.
6. **Load shedding.**  The shed verdict is a deterministic function of the
   latency window: lowest-priority deadline-carrying admissions shed,
   higher priorities and budget-less requests never.
"""

import os
import pickle

import numpy as np
import pytest

from repro.serve import (
    AsyncPathService,
    CircuitBreaker,
    DurableProgramStore,
    FaultPlan,
    FaultSpec,
    InjectedFault,
    PathService,
    ProgramCache,
    Rejection,
    RejectionError,
    ServiceCheckpoint,
)
from repro.serve.cache import ProgramSpec
from repro.serve.durable import LoadShedGovernor, backend_fingerprint
from repro.core import ols

L = 6
C = 2
SVC_KW = dict(path_length=L, solver_tol=1e-10, max_iter=20000)


def _problem(n, p, seed=0, k=4):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, p))
    beta = np.zeros(p)
    beta[:k] = rng.normal(size=k) * 2.0
    y = X @ beta + 0.1 * rng.normal(size=n)
    return X, y


PROBLEMS = [_problem(18 + 2 * i, 22 + i, seed=70 + i) for i in range(6)]


def _asvc(cache=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_delay", 0.005)
    kw.setdefault("step_chunk", C)
    return AsyncPathService(cache=cache, **kw)


def _result(fut, timeout=180):
    resp = fut.result(timeout=timeout)
    assert not isinstance(resp, Rejection), resp
    return resp


@pytest.fixture(scope="module")
def reference():
    """The uninterrupted run every crash scenario is compared against."""
    svc = _asvc(ProgramCache(capacity=16))
    try:
        futs = [svc.submit(X, y, **SVC_KW) for X, y in PROBLEMS]
        return [_result(f) for f in futs]
    finally:
        svc.close()


@pytest.fixture
def unrun_programs():
    """XLA:CPU refuses to serialize an executable that has already run a
    sort, and a process hands every compile of one program the same
    executable: empty JAX's caches so the store sees programs compiled
    afresh, as on a cold boot."""
    import jax

    jax.clear_caches()


# ---------------------------------------------------------------------------
# 1. durable store: skip-compile load, bitwise execution, integrity checks
# ---------------------------------------------------------------------------

def test_store_round_trip_skips_compile_bitwise(tmp_path, unrun_programs):
    X, y = PROBLEMS[0]
    store = DurableProgramStore(tmp_path / "store")
    svc = _asvc(store=store)
    try:
        cold = _result(svc.submit(X, y, **SVC_KW))
        cold_stats = svc.stats()["cache"]
    finally:
        svc.close()
    assert cold_stats["builds"] == cold_stats["misses"] > 0
    assert store.stats()["saved"] == cold_stats["builds"]

    # fresh cache, same store: loads, zero compiles, bitwise-equal result
    svc2 = _asvc(store=DurableProgramStore(tmp_path / "store"))
    try:
        warm = _result(svc2.submit(X, y, **SVC_KW))
        warm_stats = svc2.stats()["cache"]
    finally:
        svc2.close()
    assert warm_stats["builds"] == 0
    assert warm_stats["store"]["loaded"] > 0
    np.testing.assert_array_equal(cold.betas, warm.betas)
    np.testing.assert_array_equal(cold.deviance, warm.deviance)


def test_store_discards_corrupt_and_mismatched_entries(tmp_path, unrun_programs):
    X, y = PROBLEMS[1]
    store = DurableProgramStore(tmp_path / "store")
    svc = _asvc(store=store)
    try:
        ref = _result(svc.submit(X, y, **SVC_KW))
    finally:
        svc.close()
    entries = [f for f in os.listdir(store.path) if f.endswith(".prog")]
    assert entries

    # corrupt one entry's payload bytes; tamper another's fingerprint
    first = os.path.join(store.path, entries[0])
    with open(first, "rb") as fh:
        entry = pickle.load(fh)
    entry["payload"] = b"garbage" + entry["payload"][7:]
    with open(first, "wb") as fh:
        pickle.dump(entry, fh)
    if len(entries) > 1:
        second = os.path.join(store.path, entries[1])
        with open(second, "rb") as fh:
            entry2 = pickle.load(fh)
        entry2["fingerprint"] = "jax=0.0.0|jaxlib=0.0.0|backend=nope"
        with open(second, "wb") as fh:
            pickle.dump(entry2, fh)

    store2 = DurableProgramStore(tmp_path / "store")
    svc2 = _asvc(store=store2)
    try:
        again = _result(svc2.submit(X, y, **SVC_KW))
        cache_stats = svc2.stats()["cache"]
    finally:
        svc2.close()
    # tampered entries were discarded and rebuilt from source — the result
    # is still bitwise-correct and the store is repopulated
    assert store2.stats()["discarded"] >= 1
    assert cache_stats["builds"] >= 1
    np.testing.assert_array_equal(ref.betas, again.betas)


def test_store_counts_a_refused_serialization(tmp_path, monkeypatch):
    """A backend that refuses to serialize an executable costs a rebuild
    on the next boot, never the service: ``save`` returns False and counts
    the refusal, the manifest still records the spec, and the request is
    served."""
    import jax
    from jax.experimental import serialize_executable as se

    def refuse(_compiled):
        raise jax.errors.JaxRuntimeError("UNIMPLEMENTED: not serializable")

    monkeypatch.setattr(se, "serialize", refuse)
    X, y = PROBLEMS[2]
    store = DurableProgramStore(tmp_path / "store")
    svc = _asvc(store=store)
    try:
        resp = _result(svc.submit(X, y, **SVC_KW))
        stats = svc.stats()["cache"]
    finally:
        svc.close()
    assert np.all(np.isfinite(resp.betas))
    assert stats["builds"] > 0
    assert stats["store"]["unserializable"] == stats["builds"]
    assert stats["store"]["saved"] == 0
    assert len(store.manifest_specs()) == stats["builds"]
    assert not [f for f in os.listdir(store.path) if f.endswith(".prog")]


def test_backend_fingerprint_names_the_device_kind():
    import jax

    assert f"device={jax.devices()[0].device_kind}" in backend_fingerprint()


def test_store_load_rejects_unpicklable_garbage(tmp_path):
    store = DurableProgramStore(tmp_path / "store")
    spec = ProgramSpec(family=ols, batch=1, n_rows=32, n_cols=32,
                       path_length=L, screening="strong", solver_tol=1e-10,
                       max_iter=200, kkt_tol=1e-4, max_refits=32,
                       dtype="float64", y_dtype="float64")
    target = store._entry_path(spec)
    with open(target, "wb") as fh:
        fh.write(b"\x00not a pickle at all")
    assert store.load(spec) is None
    assert store.stats()["discarded"] == 1
    assert not os.path.exists(target)


# ---------------------------------------------------------------------------
# 2. manifest replay: second boot compiles zero programs
# ---------------------------------------------------------------------------

def test_manifest_replay_second_boot_compiles_nothing(tmp_path, unrun_programs):
    store = DurableProgramStore(tmp_path / "store")
    svc = _asvc(store=store)
    try:
        for X, y in PROBLEMS[:3]:
            _result(svc.submit(X, y, **SVC_KW))
    finally:
        svc.close()
    manifest = store.manifest_specs()
    assert manifest  # live traffic recorded what it compiled

    # boot a fresh service: __init__ replays the manifest through the store
    store2 = DurableProgramStore(tmp_path / "store")
    svc2 = _asvc(store=store2)
    try:
        boot = svc2.stats()["cache"]
        assert boot["builds"] == 0          # zero XLA compiles at boot
        assert boot["misses"] == len(manifest)
        assert store2.stats()["loaded"] == len(manifest)
        assert store2.stats()["replayed"] == len(manifest)
        # traffic after boot is all cache hits — still zero compiles
        for X, y in PROBLEMS[:3]:
            _result(svc2.submit(X, y, **SVC_KW))
        assert svc2.stats()["cache"]["builds"] == 0
    finally:
        svc2.close()


def test_manifest_skips_undecodable_lines(tmp_path):
    store = DurableProgramStore(tmp_path / "store")
    with open(store._manifest_path, "w") as fh:
        fh.write("not json\n")
        fh.write('{"family": "martian"}\n')
        fh.write('{"family": "ols", "no_such_field": 1}\n')
        fh.write("[1, 2, 3]\n")
    assert store.manifest_specs() == []


# ---------------------------------------------------------------------------
# 3. checkpoint/restore: kill mid-chunk, restore, maxdiff == 0
# ---------------------------------------------------------------------------

def test_checkpoint_restore_bit_identical(reference):
    cache = ProgramCache(capacity=16)
    svc = _asvc(cache)
    futs = [svc.submit(X, y, **SVC_KW) for X, y in PROBLEMS]
    # checkpoint races the dispatcher: with 6 requests on 4 slots some are
    # typically mid-chunk and some still queued — both capture paths run
    ckpt = svc.checkpoint(timeout=180)
    undelivered = {f.rid for f in futs if not f.done()}
    assert {q.rid for q in ckpt.queued} | {s.rid for s in ckpt.inflight} \
        == undelivered
    assert ckpt.fingerprint == backend_fingerprint()
    assert svc.stats()["checkpoints"] == 1
    # the checkpointed process is abandoned (no close-flush: that would
    # serve the leftovers and defeat the point)

    results = {}
    for i, f in enumerate(futs):
        if f.done():
            results[i] = _result(f)
    rid_to_index = {f.rid: i for i, f in enumerate(futs)}
    svc2 = _asvc(cache)
    try:
        restored = svc2.restore(ckpt)
        assert set(restored) == undelivered
        for old_rid, fut in restored.items():
            results[rid_to_index[old_rid]] = _result(fut)
        assert svc2.stats()["restored"] == len(undelivered)
    finally:
        svc2.close()

    assert len(results) == len(PROBLEMS)
    for i, want in enumerate(reference):
        got = results[i]
        np.testing.assert_array_equal(got.betas, want.betas)
        np.testing.assert_array_equal(got.deviance, want.deviance)
        np.testing.assert_array_equal(got.sigmas, want.sigmas)


def test_checkpoint_pickles_through_disk(reference, tmp_path):
    cache = ProgramCache(capacity=16)
    svc = _asvc(cache)
    futs = [svc.submit(X, y, **SVC_KW) for X, y in PROBLEMS]
    ckpt = svc.checkpoint(timeout=180)
    ckpt.save(tmp_path / "svc.ckpt")
    loaded = ServiceCheckpoint.load(tmp_path / "svc.ckpt")
    assert len(loaded) == len(ckpt)

    results = {}
    for i, f in enumerate(futs):
        if f.done():
            results[i] = _result(f)
    rid_to_index = {f.rid: i for i, f in enumerate(futs)}
    svc2 = _asvc(cache)
    try:
        for old_rid, fut in svc2.restore(loaded).items():
            results[rid_to_index[old_rid]] = _result(fut)
    finally:
        svc2.close()
    for i, want in enumerate(reference):
        np.testing.assert_array_equal(results[i].betas, want.betas)


def test_restore_refuses_foreign_fingerprint():
    ckpt = ServiceCheckpoint(queued=[], inflight=[],
                             fingerprint="jax=0.0.0|jaxlib=0.0.0|backend=x")
    svc = _asvc(ProgramCache(capacity=4), autostart=False)
    try:
        with pytest.raises(RuntimeError, match="fingerprint"):
            svc.restore(ckpt)
    finally:
        svc.close(flush=False)


# ---------------------------------------------------------------------------
# 4. watchdog: a hung chunk fails only its cohort, recovery is bitwise
# ---------------------------------------------------------------------------

def test_watchdog_recovers_hung_cohort_bit_identical(reference):
    plan = FaultPlan([FaultSpec(site="worker", kind="hang", delay_s=3.0,
                                times=1)])
    svc = _asvc(ProgramCache(capacity=16), faults=plan,
                solve_timeout_ms=500.0, retry_backoff=0.001)
    try:
        futs = [svc.submit(X, y, **SVC_KW) for X, y in PROBLEMS]
        got = [_result(f) for f in futs]
        stats = svc.stats()
    finally:
        svc.close()
    # the hang tripped the watchdog (not the sleep) and retry recovered
    assert stats["watchdog_timeouts"] >= 1
    assert stats["retries"] >= 1
    assert stats["poisoned"] == 0
    assert stats["completed"] == len(PROBLEMS)
    for got_r, want in zip(got, reference):
        np.testing.assert_array_equal(got_r.betas, want.betas)
        np.testing.assert_array_equal(got_r.deviance, want.deviance)


def test_solve_timeout_validation():
    with pytest.raises(ValueError, match="solve_timeout_ms"):
        PathService(solve_timeout_ms=0.0)
    svc = PathService()
    X, y = PROBLEMS[0]
    with pytest.raises(ValueError, match="solve_timeout_ms"):
        svc.submit(X, y, solve_timeout_ms=-5.0, **SVC_KW)


# ---------------------------------------------------------------------------
# 5. circuit breaker: open -> reject -> half-open probe -> closed
# ---------------------------------------------------------------------------

def test_breaker_opens_after_consecutive_faults_and_recloses():
    t = [0.0]

    def clock():
        t[0] += 1e-4
        return t[0]

    plan = FaultPlan([FaultSpec(site="compile", kind="error", times=3)])
    svc = PathService(max_batch=1, max_delay=0.0, faults=plan, clock=clock,
                      breaker_threshold=3, breaker_cooldown=10.0)
    X, y = PROBLEMS[0]
    for _ in range(3):
        # max_batch=1: admission fill-flushes synchronously, so the
        # injected compile fault surfaces from submit itself
        with pytest.raises(InjectedFault):
            svc.submit(X, y, **SVC_KW)
    assert svc.stats()["breaker"]["open"] == 1
    assert svc.stats()["breaker"]["opens"] == 1

    # open: admission rejected with the structured verdict
    with pytest.raises(RejectionError) as ei:
        svc.submit(X, y, **SVC_KW)
    assert ei.value.rejection.reason == "circuit_open"
    assert ei.value.rejection.max_queue is None
    assert svc.stats()["breaker"]["rejected"] == 1
    assert svc.stats()["rejected"] == 1

    # past the cooldown: ONE probe admission is let through; the fault plan
    # is exhausted so it succeeds and closes the circuit
    t[0] += 20.0
    rid = svc.submit(X, y, **SVC_KW)
    resp = svc.poll(rid, flush=True)
    assert resp is not None
    assert svc.stats()["breaker"]["open"] == 0
    rid2 = svc.submit(X, y, **SVC_KW)   # closed again: normal admission
    assert svc.poll(rid2, flush=True) is not None


def test_breaker_unit_semantics():
    t = [0.0]
    br = CircuitBreaker(threshold=2, cooldown=5.0, clock=lambda: t[0])
    key = "g"
    assert br.allow(key)
    assert br.record_failure(key) == "closed"   # 1 of 2
    br.record_success(key)                       # interleaved success resets
    assert br.record_failure(key) == "closed"   # consecutive count restarts
    assert br.record_failure(key) == "open"
    assert not br.allow(key)                     # open, inside cooldown
    t[0] += 6.0
    assert br.allow(key)                         # half-open probe
    assert not br.allow(key)                     # one probe at a time
    assert br.record_failure(key) == "open"     # probe failed: re-open
    t[0] += 6.0
    assert br.allow(key)
    assert br.record_success(key) == "closed"
    assert br.allow(key)
    assert br.stats()["opens"] == 2


# ---------------------------------------------------------------------------
# 6. load shedding: deterministic, priority-ordered, fault-injectable
# ---------------------------------------------------------------------------

def test_shed_deterministic_under_fixed_latency_window():
    svc = PathService(max_batch=8, max_delay=10.0, shed_window=8)
    X, y = PROBLEMS[0]
    # fixed window: p95 == 1 s, well past 90% of a 500 ms budget
    for _ in range(20):
        svc.metrics.observe("latency_s", 1.0, scope="user")
    for _ in range(3):  # deterministic: same window -> same verdict
        with pytest.raises(RejectionError) as ei:
            svc.submit(X, y, deadline_ms=500.0, **SVC_KW)
        assert ei.value.rejection.reason == "shed"
    # higher priority is never shed; no deadline -> no shed basis
    assert isinstance(svc.submit(X, y, deadline_ms=500.0, priority=1,
                                 **SVC_KW), int)
    assert isinstance(svc.submit(X, y, **SVC_KW), int)
    # a budget the window comfortably meets is admitted
    assert isinstance(svc.submit(X, y, deadline_ms=60_000.0, **SVC_KW), int)
    assert svc.stats()["shed"] == 3


def test_shed_needs_min_window():
    gov = LoadShedGovernor(threshold=0.9, priority_cutoff=0, min_window=8)
    assert not gov.should_shed(10.0, 100.0, 0, window=7)   # window too small
    assert gov.should_shed(10.0, 100.0, 0, window=8)
    assert not gov.should_shed(10.0, 100.0, 1, window=8)   # priority exempt
    assert not gov.should_shed(10.0, None, 0, window=8)    # no budget
    assert not gov.should_shed(0.05, 100.0, 0, window=8)   # p95 under bar


def test_overload_fault_forces_shed_async():
    plan = FaultPlan([FaultSpec(site="overload", kind="error", times=1)])
    svc = _asvc(ProgramCache(capacity=4), faults=plan, autostart=False)
    X, y = PROBLEMS[0]
    try:
        fut = svc.submit(X, y, **SVC_KW)
        verdict = fut.result(timeout=5)
        assert isinstance(verdict, Rejection)
        assert verdict.reason == "shed"
        assert svc.stats()["shed"] == 1
        # the next admission (spec exhausted) queues normally
        fut2 = svc.submit(X, y, **SVC_KW)
        assert not fut2.done()
    finally:
        svc.close(flush=False)
