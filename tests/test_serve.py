"""repro.serve — shape-bucketed path service (ISSUE 3).

The contract under test: a request padded into a bucket and served through
`PathService` returns BIT-IDENTICAL coefficients to an unpadded direct
`fit_path_batched(..., pad="bucket")` call (tolerance 0, masked and compact
backends, including an all-zero-column edge case), because both resolve
execution shapes through the same policy and batch slots are bitwise
member-invariant.  Around that: registry/batcher/cache unit behavior,
padding semantics vs the native-shape engine, CV-through-the-service
equivalence with `cv_path`, and the telemetry surface.
"""

import threading

import numpy as np
import pytest

from repro.core import (
    bh_sequence,
    cv_path,
    fit_path,
    fit_path_batched,
    logistic,
    ols,
)
from repro.core.engine import _WS_BUCKETS, cv_fold_indices
from repro.data import make_classification, make_regression
from repro.serve.buckets import default_policy
from repro.serve import (
    BucketRegistry,
    LambdaCanonicalizer,
    MicroBatcher,
    PathService,
    ProgramCache,
    ProgramSpec,
    ShapeBucketPolicy,
    next_pow2,
    pad_batch,
)

# small problems + short dense paths: every compiled program in this module
# is shape (32, 32) or (32, 64) so the AOT builds stay countable and the
# jit cache carries the direct-call arms across tests
KW = dict(path_length=6, solver_tol=1e-10, max_iter=20000, kkt_tol=1e-4)
SVC_KW = dict(path_length=6, solver_tol=1e-10, max_iter=20000)


@pytest.fixture(scope="module")
def shared_cache():
    """One ProgramCache for every service in this module — AOT builds are
    seconds each, so tests share residency like a real deployment would."""
    return ProgramCache(capacity=16)


def _svc(shared_cache, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_delay", 1000.0)  # flush explicitly unless testing it
    return PathService(cache=shared_cache, **kw)


def _problem(n, p, seed=0, k=4):
    X, y, _ = make_regression(n, p, k=k, rho=0.2, seed=seed)
    return X, y, np.asarray(bh_sequence(p, q=0.1))


# ---------------------------------------------------------------------------
# buckets: policy, registry, padding
# ---------------------------------------------------------------------------

def test_next_pow2():
    assert [next_pow2(x) for x in (0, 1, 2, 3, 8, 9, 1000)] == \
        [1, 1, 2, 4, 8, 16, 1024]


def test_shape_policy_buckets():
    pol = ShapeBucketPolicy()
    assert pol.shape_bucket(20, 24, "ols") == (32, 32)
    assert pol.shape_bucket(16, 100, "ols") == (16, 128)
    # non-OLS families keep their exact row count (zero rows change the
    # loss for logistic/Poisson/multinomial)
    assert pol.shape_bucket(20, 24, "logistic") == (20, 32)
    assert pol.batch_bucket(1) == 2   # B=1 programs are not bitwise
    assert pol.batch_bucket(5) == 8   # member-invariant with B>=2 ones


def test_bucket_registry_mapping_and_stats():
    reg = BucketRegistry(name="t", capacity=3)
    reg["a"] = 64
    assert "a" in reg and reg["a"] == 64
    assert reg.get("missing") is None
    with pytest.raises(KeyError):
        reg["missing"]
    reg["b"], reg["c"] = 128, 256
    reg.get("a")                      # refresh a's recency
    reg["d"] = 512                    # evicts b (LRU)
    assert "b" not in reg and "a" in reg and len(reg) == 3
    st = reg.stats()
    assert st["evictions"] == 1 and st["updates"] == 4
    assert st["hits"] >= 2 and st["misses"] >= 2
    assert st["entries"] == {"a": 64, "c": 256, "d": 512}
    assert reg.pop("a") == 64 and reg.pop("a", "gone") == "gone"


def test_bucket_registry_grow_monotonic_capped():
    """The grow-on-overflow write path (satellite regression): growth is
    monotonic (a racing smaller grower can never shrink a learned bucket),
    idempotent, and capped at the native column count — a bucket wider
    than p is wasted compaction."""
    reg = BucketRegistry(name="g", capacity=4)
    assert reg.grow("k", 48, cap=256)
    assert reg["k"] == 48
    assert not reg.grow("k", 32, cap=256)   # smaller: no shrink
    assert reg["k"] == 48
    assert not reg.grow("k", 48, cap=256)   # idempotent re-apply
    assert reg.grow("k", 4096, cap=256)     # capped at native p
    assert reg["k"] == 256
    assert not reg.grow("k", 4096, cap=256)


def test_bucket_registry_grow_concurrent_idempotent():
    """Racing growers converge on the maximum, never a last-writer value."""
    reg = BucketRegistry(capacity=8)

    def hammer(v):
        for _ in range(200):
            reg.grow("x", v, cap=1024)

    threads = [threading.Thread(target=hammer, args=(v,))
               for v in (64, 256, 128, 32)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert reg["x"] == 256


def test_grow_ws_bucket_caps_at_native_p():
    """The engine-level growth helper honours the native-p cap and the
    monotonic registry semantics."""
    from repro.core.engine import _WS_BUCKETS, grow_ws_bucket

    key = ("grow-cap-test",)
    _WS_BUCKETS.pop(key, None)
    # peak demand 1500 → next_pow2 = 2048 would overshoot native p = 1500
    assert grow_ws_bucket(key, np.array([1500]), np.array([True]), 64, 1500)
    assert _WS_BUCKETS[key] == 1500
    # a later, smaller overflow must not shrink the learned bucket
    assert not grow_ws_bucket(key, np.array([700]), np.array([True]), 64,
                              1500)
    assert _WS_BUCKETS[key] == 1500
    # no overflow, or an already-maximal W: no write
    assert not grow_ws_bucket(key, np.array([90]), np.array([False]), 64,
                              1500)
    assert not grow_ws_bucket(key, np.array([1500]), np.array([True]), 1500,
                              1500)
    _WS_BUCKETS.pop(key, None)


def test_grow_ws_bucket_two_tier_learns_half_peak():
    """A two-tier run only needs the HALF-peak bucket — its 2W tier covers
    the rest — where single-tier growth stores the full next-pow2 peak."""
    from repro.core.engine import _WS_BUCKETS, grow_ws_bucket

    key = ("grow-half-peak-test",)
    _WS_BUCKETS.pop(key, None)
    assert grow_ws_bucket(key, np.array([42]), np.array([True]), 16, 2048,
                          two_tier=True)
    assert _WS_BUCKETS[key] == 32        # next_pow2(42) / 2
    _WS_BUCKETS.pop(key, None)
    assert grow_ws_bucket(key, np.array([42]), np.array([True]), 16, 2048)
    assert _WS_BUCKETS[key] == 64        # single tier: the full pow2 peak
    # at the cap the halved bucket would get no 2× tier and overflow again
    # — keep the full width there
    _WS_BUCKETS.pop(key, None)
    assert grow_ws_bucket(key, np.array([256]), np.array([True]), 64, 256,
                          two_tier=True)
    assert _WS_BUCKETS[key] == 256
    _WS_BUCKETS.pop(key, None)


def test_bucket_registry_thread_safety():
    reg = BucketRegistry(capacity=64)

    def hammer(t):
        for i in range(200):
            reg[(t, i % 32)] = i
            reg.get((t, (i + 1) % 32))

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(reg) <= 64
    assert reg.stats()["updates"] == 8 * 200


def test_pad_batch_layout():
    X, y, lam = _problem(20, 24)
    sig = np.linspace(1.0, 0.1, 6)
    pb = pad_batch([(X, y, lam, sig)], n_rows=32, n_cols=32, n_slots=4)
    assert pb.shape == (4, 32, 32)
    assert pb.p_valid.tolist() == [24, 0, 0, 0]
    np.testing.assert_array_equal(pb.Xs[0, :20, :24], X)
    assert pb.Xs[0, 20:, :].max() == 0 and pb.Xs[0, :, 24:].max() == 0
    assert pb.Xs[1:].max() == 0           # dummy slots all-zero
    assert pb.lam[0, 24:].max() == 0      # λ tail zero-padded
    np.testing.assert_array_equal(pb.sigmas[1], np.ones(6))
    with pytest.raises(ValueError):
        pad_batch([(X, y, lam, sig)], n_rows=16, n_cols=32, n_slots=4)


# ---------------------------------------------------------------------------
# batcher + λ canonicalization
# ---------------------------------------------------------------------------

def test_microbatcher_fill_and_deadline():
    mb = MicroBatcher(max_batch=3, max_delay=0.5)
    assert not mb.admit("g", 0, "a", now=0.0)
    assert not mb.admit("g", 1, "b", now=0.1)
    assert mb.admit("g", 2, "c", now=0.2)        # fill trigger
    assert mb.due(now=0.3) == []                 # not yet overdue
    assert mb.due(now=0.51) == ["g"]             # oldest passed deadline
    batch = mb.take("g")
    assert [p.rid for p in batch] == [0, 1, 2]   # FIFO
    assert mb.pending() == 0 and mb.take("g") == []


def test_lambda_canonicalizer_shares_arrays():
    canon = LambdaCanonicalizer()
    a = canon.get("bh", 0.1, 50)
    b = canon.get("bh", 0.1, 50)
    assert a is b and not a.flags.writeable
    assert canon.get("bh", 0.2, 50) is not a
    np.testing.assert_array_equal(a, np.asarray(bh_sequence(50, q=0.1)))
    np.testing.assert_array_equal(canon.get("lasso", 0.0, 8), np.ones(8))
    with pytest.raises(ValueError):
        canon.get("nope", 0.1, 50)
    with pytest.raises(ValueError):
        canon.get("gaussian", 0.1, 50)  # needs n
    assert len(canon.get("gaussian", 0.1, 50, n=40)) == 50


# ---------------------------------------------------------------------------
# compiled-program cache
# ---------------------------------------------------------------------------

def test_program_cache_aot_matches_jit_and_evicts():
    from repro.core.engine import batched_path_engine
    import jax.numpy as jnp

    cache = ProgramCache(capacity=1)
    spec = ProgramSpec(family=ols, batch=2, n_rows=16, n_cols=16,
                       path_length=4, solver_tol=1e-9, max_iter=2000)
    prog, hit = cache.get(spec)
    assert not hit and prog.build_seconds > 0
    _, hit = cache.get(spec)
    assert hit
    # AOT executable == jit dispatch, bitwise
    probs = [_problem(12, 14, seed=s) for s in range(2)]
    pb = pad_batch([(X, y, lam, np.linspace(1, 0.3, 4)) for X, y, lam in probs],
                   n_rows=16, n_cols=16, n_slots=2)
    aot = prog(pb.Xs, pb.ys, pb.lam, pb.sigmas, pb.p_valid)
    jit_out = batched_path_engine(
        jnp.asarray(pb.Xs), jnp.asarray(pb.ys), jnp.asarray(pb.lam),
        jnp.asarray(pb.sigmas), ols, jnp.asarray(pb.p_valid),
        screening="strong", max_iter=2000, tol=1e-9, kkt_tol=1e-4,
        max_refits=32)
    np.testing.assert_array_equal(np.asarray(aot.betas),
                                  np.asarray(jit_out.betas))
    # capacity 1: a second spec evicts the first
    spec2 = ProgramSpec(family=ols, batch=2, n_rows=16, n_cols=16,
                        path_length=5, solver_tol=1e-9, max_iter=2000)
    cache.get(spec2)
    assert spec not in cache and spec2 in cache
    st = cache.stats()
    assert st["evictions"] == 1 and st["hits"] == 1 and st["misses"] == 2
    # warmup: one resident, one fresh
    out = cache.warmup([spec2, spec])
    assert out[spec2.short()] == 0.0 and out[spec.short()] > 0


# ---------------------------------------------------------------------------
# the tentpole contract: served == direct padded call, bit for bit
# ---------------------------------------------------------------------------

def test_served_bit_identical_masked(shared_cache):
    """Mixed native widths co-batched in one bucket: every response must be
    bit-identical to its own unpadded fit_path_batched(pad='bucket') call,
    and to serving the same request alone (batch composition must not leak
    into results)."""
    reqs = [_problem(20, 24, seed=0), _problem(18, 30, seed=1),
            _problem(20, 24, seed=2)]
    svc = _svc(shared_cache)
    rids = [svc.submit(X, y, lam=lam, **SVC_KW) for X, y, lam in reqs]
    svc.flush()
    resps = [svc.poll(r) for r in rids]
    assert all(r is not None for r in resps)
    assert resps[0].batch_size == 3
    assert resps[0].batch_occupancy == pytest.approx(3 / 4)
    for (X, y, lam), resp in zip(reqs, resps):
        direct = fit_path_batched(X[None], y[None], lam, ols,
                                  pad="bucket", **KW)
        assert resp.betas.shape == direct.betas[0].shape  # native, unpadded
        np.testing.assert_array_equal(resp.betas, direct.betas[0])
        np.testing.assert_array_equal(resp.n_screened, direct.n_screened[0])
        np.testing.assert_array_equal(resp.n_violations,
                                      direct.n_violations[0])
        assert resp.kkt_ok
    # solo submission: same program, dummy-filled slots -> identical bits
    solo = _svc(shared_cache)
    rid = solo.submit(reqs[1][0], reqs[1][1], lam=reqs[1][2], **SVC_KW)
    resp = solo.poll(rid, flush=True)
    np.testing.assert_array_equal(resp.betas, resps[1].betas)
    assert resp.batch_size == 1 and resp.cache_hit  # shared cache residency


def test_served_bit_identical_compact(shared_cache):
    """Same contract through the compact working-set backend (no overflow:
    fallback coupling across co-batched members is the documented exception
    to bit-identity, so the test uses p ≫ n sparse problems with a shallow
    σ grid, where W=32 sits above the peak demand)."""
    def sparse(n, p, seed):
        X, y, _ = make_regression(n, p, k=3, rho=0.2, seed=seed, noise=0.3)
        return X, y, np.asarray(bh_sequence(p, q=0.05))

    kw = dict(KW, sigma_ratio=0.5)
    svc_kw = dict(SVC_KW, sigma_ratio=0.5)
    reqs = [sparse(16, 60, seed=3), sparse(14, 55, seed=4)]
    svc = _svc(shared_cache)
    rids = [svc.submit(X, y, lam=lam, working_set=32, **svc_kw)
            for X, y, lam in reqs]
    svc.flush()
    resps = [svc.poll(r) for r in rids]
    for (X, y, lam), resp in zip(reqs, resps):
        assert resp.working_set == 32
        assert not resp.compact_fallback.any()
        assert resp.ws_size.max() > 0
        direct = fit_path_batched(X[None], y[None], lam, ols, working_set=32,
                                  pad="bucket", **kw)
        assert not direct.compact_fallback.any()
        np.testing.assert_array_equal(resp.betas, direct.betas[0])


def test_served_two_tier_compact_matches_direct(shared_cache):
    """A two-tier compact request resolves (W, 2W) through the shared tier
    recipe, compiles a two-tier program (working_set_top in the spec), and
    stays bit-identical to the direct padded call at the same widths."""
    X, y, _ = make_regression(16, 60, k=3, rho=0.2, seed=5, noise=0.3)
    lam = np.asarray(bh_sequence(60, q=0.05))
    svc = _svc(shared_cache)
    rid = svc.submit(X, y, lam=lam, working_set=8, sigma_ratio=0.5,
                     **SVC_KW)
    resp = svc.poll(rid, flush=True)
    assert (resp.working_set, resp.working_set_top) == (8, 16)
    assert resp.ws_tier is not None and resp.ws_tier.shape == resp.ws_size.shape
    direct = fit_path_batched(X[None], y[None], lam, ols, working_set=8,
                              pad="bucket", sigma_ratio=0.5, **KW)
    assert (direct.working_set, direct.working_set_top) == (8, 16)
    np.testing.assert_array_equal(resp.betas, direct.betas[0])
    np.testing.assert_array_equal(resp.ws_tier, direct.ws_tier[0])


def test_served_bit_identical_all_zero_column(shared_cache):
    """Degenerate user data: a request whose X already contains all-zero
    columns must unpad cleanly (real zero columns are not confused with
    bucket padding) and stay bit-identical to the direct padded call."""
    X, y, lam = _problem(20, 24, seed=5)
    X = X.copy()
    X[:, [3, 17]] = 0.0
    svc = _svc(shared_cache)
    rid = svc.submit(X, y, lam=lam, **SVC_KW)
    resp = svc.poll(rid, flush=True)
    direct = fit_path_batched(X[None], y[None], lam, ols, pad="bucket", **KW)
    np.testing.assert_array_equal(resp.betas, direct.betas[0])
    assert resp.betas.shape == (6, 24)
    assert np.abs(resp.betas[:, [3, 17]]).max() == 0.0  # inert, exactly


def test_served_logistic_exact_rows(shared_cache):
    """Non-OLS families must NOT get row padding (zero rows shift their
    loss): the bucket keeps the exact n, columns still pad, and the served
    result stays bit-identical to the direct padded call and tolerance-close
    to the native-shape engine."""
    X, y, _ = make_classification(20, 24, k=3, rho=0.1, seed=17)
    lam = np.asarray(bh_sequence(24, q=0.1))
    svc = _svc(shared_cache)
    rid = svc.submit(X, y, family=logistic, lam=lam, **SVC_KW)
    resp = svc.poll(rid, flush=True)
    direct = fit_path_batched(X[None], y[None], lam, logistic,
                              pad="bucket", **KW)
    # rows exact, columns padded, the default service's slots
    assert direct.pad_shape == (default_policy().serve_slots, 20, 32)
    np.testing.assert_array_equal(resp.betas, direct.betas[0])
    native = fit_path_batched(X[None], y[None], lam, logistic, **KW)
    np.testing.assert_allclose(resp.betas, native.betas[0], atol=5e-3)
    np.testing.assert_array_equal(resp.n_violations, native.n_violations[0])


def test_padded_semantics_match_native_engine():
    """pad='bucket' is a different execution shape, not different math:
    screening decisions and violation counts must be identical to the
    native-shape engine, coefficients within solver tolerance."""
    X, y, lam = _problem(20, 24, seed=6)
    native = fit_path_batched(X[None], y[None], lam, ols, **KW)
    padded = fit_path_batched(X[None], y[None], lam, ols, pad="bucket", **KW)
    assert padded.pad_shape == (default_policy().serve_slots, 32, 32)
    assert native.pad_shape is None
    np.testing.assert_array_equal(native.n_screened, padded.n_screened)
    np.testing.assert_array_equal(native.n_violations, padded.n_violations)
    np.testing.assert_allclose(native.betas, padded.betas, atol=5e-3)


def test_fit_path_device_pad_bucket():
    X, y, lam = _problem(20, 24, seed=7)
    host = fit_path(X, y, lam, ols, engine="host", early_stop=False, **KW)
    dev = fit_path(X, y, lam, ols, engine="device", pad="bucket",
                   early_stop=False, **KW)
    np.testing.assert_allclose(host.betas, dev.betas, atol=5e-3)
    assert len(dev.steps) == len(host.steps)
    with pytest.raises(ValueError):
        fit_path(X, y, lam, ols, engine="host", pad="bucket", **KW)


def test_per_member_lambda_batched():
    """fit_path_batched with a (B, p·m) λ stack: each member must match the
    same member fitted in a batch where that λ is shared (member results
    cannot depend on a neighbour's λ)."""
    (X0, y0, lamA), (X1, y1, _) = _problem(20, 24, seed=8), _problem(20, 24,
                                                                     seed=9)
    lamB = np.asarray(bh_sequence(24, q=0.02))
    Xs = np.stack([X0, X1])
    ys = np.stack([y0, y1])
    mixed = fit_path_batched(Xs, ys, np.stack([lamA, lamB]), ols, **KW)
    sharedA = fit_path_batched(Xs, ys, lamA, ols, **KW)
    sharedB = fit_path_batched(Xs, ys, lamB, ols, **KW)
    np.testing.assert_array_equal(mixed.betas[0], sharedA.betas[0])
    np.testing.assert_array_equal(mixed.betas[1], sharedB.betas[1])
    with pytest.raises(ValueError):
        fit_path_batched(Xs, ys, np.stack([lamA]), ols, **KW)


# ---------------------------------------------------------------------------
# service mechanics: deadlines, telemetry, registry sharing
# ---------------------------------------------------------------------------

def test_service_deadline_flush(shared_cache):
    clock = {"t": 0.0}
    svc = PathService(max_batch=4, max_delay=0.5, cache=shared_cache,
                      clock=lambda: clock["t"])
    X, y, lam = _problem(20, 24, seed=10)
    rid = svc.submit(X, y, lam=lam, **SVC_KW)
    assert svc.poll(rid) is None            # queued: not full, not overdue
    clock["t"] = 0.6
    resp = svc.poll(rid)                    # deadline passed -> flushed
    assert resp is not None and resp.queue_s >= 0.5
    assert svc.stats()["flush_deadline"] == 1
    assert svc.poll(rid) is None            # responses hand out once


def test_service_fill_flush_and_stats(shared_cache):
    svc = _svc(shared_cache)
    probs = [_problem(20, 24, seed=20 + s) for s in range(4)]
    rids = [svc.submit(X, y, lam=lam, **SVC_KW) for X, y, lam in probs]
    st = svc.stats()
    assert st["flush_fill"] == 1            # 4 submits filled max_batch=4
    assert st["pending"] == 0
    resps = [svc.poll(r) for r in rids]
    assert all(r is not None for r in resps)
    assert resps[0].batch_occupancy == 1.0
    assert {r.rid for r in resps} == set(rids)
    assert st["occupancy_mean"] > 0 and st["latency_ms_p95"] >= 0
    assert st["cache"]["hits"] >= 0 and st["ws_buckets"]["capacity"] == 256


def test_service_validates_requests(shared_cache):
    svc = _svc(shared_cache)
    X, y, lam = _problem(20, 24)
    with pytest.raises(ValueError):
        svc.submit(X[0], y, lam=lam)                 # 1-D X
    with pytest.raises(ValueError):
        svc.submit(X, y, lam=lam[:-1])               # wrong λ length
    with pytest.raises(ValueError):
        svc.submit(X, y, lam=lam, working_set="big")  # bad working_set
    with pytest.raises(ValueError):
        svc.submit(X, y, lam=lam, working_set=0)      # direct path parity


def test_service_grows_shared_ws_registry(shared_cache):
    """An overflowing service batch must grow the SAME registry direct
    calls use (the satellite contract: one BucketRegistry, engine + serve)."""
    X, y, _ = make_regression(20, 40, k=15, rho=0.3, seed=12, noise=0.05)
    lam = np.asarray(bh_sequence(40, q=0.1))
    key = (32, 64, 1, "ols", "strong")  # padded bucket of (20, 40)
    _WS_BUCKETS.pop(key, None)
    svc = _svc(shared_cache)
    rid = svc.submit(X, y, lam=lam, working_set="auto", path_length=10,
                     solver_tol=1e-9, max_iter=8000)
    resp = svc.poll(rid, flush=True)
    if resp.compact_fallback.any():     # overflow happened -> registry grew
        assert key in _WS_BUCKETS
        assert _WS_BUCKETS[key] > 0


# ---------------------------------------------------------------------------
# CV through the service == cv_path (stratified folds, 1-SE selection)
# ---------------------------------------------------------------------------

def test_cv_fold_indices_stratified_balance():
    y = np.array([0] * 15 + [1] * 9)
    trains, vals = cv_fold_indices(y, 3, family=logistic, stratify="auto")
    for tr, va in zip(trains, vals):
        assert len(va) == 8 and len(tr) == 16
        # each fold sees both classes at the full-data ratio (5:3)
        assert (y[va] == 0).sum() == 5 and (y[va] == 1).sum() == 3
        assert np.intersect1d(tr, va).size == 0
    # OLS keeps the contiguous unstratified layout
    trains, vals = cv_fold_indices(y, 3, family=ols, stratify="auto")
    np.testing.assert_array_equal(vals[0], np.arange(8))


def test_cv_path_1se_selection():
    X, y, _ = make_regression(40, 30, k=4, rho=0.0, seed=13, noise=0.3)
    lam = np.asarray(bh_sequence(30, q=0.1))
    cv_min = cv_path(X, y, lam, ols, n_folds=4, path_length=15,
                     solver_tol=1e-9, max_iter=5000)
    cv_1se = cv_path(X, y, lam, ols, n_folds=4, path_length=15,
                     solver_tol=1e-9, max_iter=5000, selection="1se")
    assert cv_min.selection == "min" and cv_1se.selection == "1se"
    np.testing.assert_array_equal(cv_min.val_deviance, cv_1se.val_deviance)
    assert cv_1se.best_index == cv_1se.best_index_1se
    # 1-SE picks the sparser side (larger σ = smaller index) within 1 SE
    assert cv_1se.best_index_1se <= cv_1se.best_index_min
    mean, se = cv_1se.mean_val_deviance, cv_1se.se_val_deviance
    assert mean[cv_1se.best_index_1se] <= (mean[cv_1se.best_index_min]
                                           + se[cv_1se.best_index_min])


def test_cv_stratified_logistic_runs():
    X, y, _ = make_classification(36, 20, k=3, rho=0.1, seed=14)
    lam = np.asarray(bh_sequence(20, q=0.1))
    cv = cv_path(X, y, lam, logistic, n_folds=3, path_length=8,
                 solver_tol=1e-9, max_iter=5000)
    assert cv.val_deviance.shape == (3, 8)
    assert np.isfinite(cv.val_deviance).all()


def test_service_cv_matches_cv_path(shared_cache):
    """A cv_folds request served fold-by-fold through the batcher must
    reproduce cv_path(pad='bucket') exactly: same fold splits, same held-out
    deviances (bit-identical), same min/1-SE selection."""
    X, y, _ = make_regression(30, 24, k=4, rho=0.0, seed=15, noise=0.3)
    lam = np.asarray(bh_sequence(24, q=0.1))
    svc = _svc(shared_cache)
    rid = svc.submit(X, y, lam=lam, cv_folds=3, selection="1se", **SVC_KW)
    assert svc.poll(rid) is None            # folds still queued
    resp = svc.poll(rid, flush=True)
    assert resp is not None
    ref = cv_path(X, y, lam, ols, n_folds=3, pad="bucket", selection="1se",
                  **KW)
    np.testing.assert_array_equal(resp.val_deviance, ref.val_deviance)
    assert resp.best_index == ref.best_index
    assert resp.best_index_min == ref.best_index_min
    assert resp.best_index_1se == ref.best_index_1se
    assert resp.best_sigma == ref.best_sigma
    assert len(resp.fold_responses) == 3
    for fold in resp.fold_responses:
        assert fold.kkt_ok


def test_service_cv_survives_mid_submission_flush(shared_cache):
    """Regression: the K-th fold submit can FILL the group and flush it
    synchronously, before _submit_cv finishes — fold responses must still
    route to the CV aggregation, not leak into the plain-results table."""
    X, y, _ = make_regression(30, 24, k=4, rho=0.0, seed=18, noise=0.3)
    lam = np.asarray(bh_sequence(24, q=0.1))
    svc = PathService(max_batch=3, max_delay=1000.0, cache=shared_cache)
    rid = svc.submit(X, y, lam=lam, cv_folds=3, **SVC_KW)
    assert svc.stats()["flush_fill"] == 1   # folds filled the group inline
    resp = svc.poll(rid)                    # no force flush needed
    assert resp is not None
    assert resp.val_deviance.shape == (3, 6)
    assert len(resp.fold_responses) == 3


def test_response_path_result_view(shared_cache):
    X, y, lam = _problem(20, 24, seed=16)
    svc = _svc(shared_cache)
    rid = svc.submit(X, y, lam=lam, **SVC_KW)
    resp = svc.poll(rid, flush=True)
    pr = resp.path_result(early_stop=False)
    np.testing.assert_array_equal(pr.betas, resp.betas)
    assert len(pr.steps) == 6
    assert pr.total_violations == resp.total_violations


# ---------------------------------------------------------------------------
# compare_sweeps --bench: clean first-run summary (CI satellite)
# ---------------------------------------------------------------------------

def test_compare_sweeps_bench_no_previous(tmp_path, capsys):
    import json

    from benchmarks.compare_sweeps import main_bench

    new = tmp_path / "BENCH_ci.json"
    new.write_text(json.dumps([{"name": "serve/x", "us_per_call": 12.5,
                                "derived": "rps=1"}]))
    rc = main_bench(str(tmp_path / "missing.json"), str(new))
    out = capsys.readouterr().out
    assert rc == 0
    assert "No previous artifact" in out and "serve/x" in out
    # corrupt previous artifact: same clean path
    prev = tmp_path / "prev.json"
    prev.write_text("{not json")
    rc = main_bench(str(prev), str(new))
    assert rc == 0
    assert "baseline recorded" in capsys.readouterr().out
    # healthy diff still works and flags new rows
    prev.write_text(json.dumps([{"name": "serve/x", "us_per_call": 10.0}]))
    rc = main_bench(str(prev), str(new))
    out = capsys.readouterr().out
    assert rc == 0 and "+25%" in out
