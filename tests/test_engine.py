"""Device-resident batched path engine vs the host driver.

The contract under test (ISSUE 1): ``fit_path_batched`` over B independent
problems agrees with per-problem ``fit_path`` — same betas within solver
tolerance, same violation counts — and the masked screening scan equals the
paper's Algorithm 2 run on the unmasked prefix alone.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal image: fall back to seeded random fuzzing
    from _hypothesis_fallback import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import (
    algorithm_2_oracle,
    bh_sequence,
    cv_path,
    fit_path,
    fit_path_batched,
    get_family,
    ols,
    screen_masked,
)
from repro.data import make_multinomial, make_regression

# tight solves, default-width KKT guard: the violation check must sit well
# clear of fp noise so host and device flag identical sets
KW = dict(path_length=10, solver_tol=1e-12, max_iter=30000, kkt_tol=1e-4)


def _batch_problems(B, n, p, *, k=5, rho=0.2, noise=1.0):
    probs = [make_regression(n, p, k=k, rho=rho, seed=s, noise=noise)[:2]
             for s in range(B)]
    return np.stack([X for X, _ in probs]), np.stack([y for _, y in probs])


@pytest.mark.parametrize("screening", ["strong", "previous", "none"])
def test_batched_agrees_with_fit_path(screening):
    B, n, p = 3, 40, 60
    Xs, ys = _batch_problems(B, n, p)
    lam = np.asarray(bh_sequence(p, q=0.1))
    batched = fit_path_batched(Xs, ys, lam, ols, screening=screening, **KW)
    assert not batched.kkt_unrepaired.any()  # repair loop always finished
    for b in range(B):
        single = fit_path(Xs[b], ys[b], lam, ols, screening=screening,
                          engine="host", early_stop=False, **KW)
        np.testing.assert_allclose(batched.betas[b], single.betas, atol=5e-3)
        assert int(batched.total_violations[b]) == single.total_violations
        # screened/active sets may flip by a coefficient sitting exactly at
        # the zero boundary between two tol-accurate solutions
        np.testing.assert_allclose(
            batched.n_screened[b], [s.n_screened for s in single.steps], atol=2)
        np.testing.assert_allclose(
            batched.n_active[b], [s.n_active for s in single.steps], atol=2)


def test_device_engine_matches_host_single_problem():
    """fit_path(engine='device') is a drop-in for the host backend."""
    n, p = 40, 60
    X, y, _ = make_regression(n, p, k=5, rho=0.3, seed=9)
    lam = np.asarray(bh_sequence(p, q=0.1))
    host = fit_path(X, y, lam, ols, engine="host", early_stop=False, **KW)
    dev = fit_path(X, y, lam, ols, engine="device", early_stop=False, **KW)
    np.testing.assert_allclose(host.betas, dev.betas, atol=5e-3)
    assert host.total_violations == dev.total_violations
    assert len(host.steps) == len(dev.steps)
    for hs, ds in zip(host.steps, dev.steps):
        assert abs(hs.n_screened - ds.n_screened) <= 2
        assert abs(hs.n_active - ds.n_active) <= 2


def test_device_engine_early_stop_truncates_like_host():
    n, p = 25, 50
    X, y, _ = make_regression(n, p, k=20, rho=0.0, seed=5, noise=0.01)
    lam = np.ones(p)
    r = fit_path(X, y, lam, ols, engine="device", path_length=100,
                 solver_tol=1e-10, max_iter=5000)
    assert len(r.sigmas) < 100  # saturation rules applied post-hoc


def test_multinomial_engine_agrees_with_host():
    """Engine-vs-host on the multinomial family: the (p, m) mask-broadcast
    logic in fista_masked/_engine (every class column of a screened
    predictor shares the mask row) must reproduce the host driver's
    gathered sub-problems, violations included."""
    B, n, p, m = 2, 30, 36, 3
    probs = [make_multinomial(n, p, k=4, m=m, rho=0.2, seed=s)[:2]
             for s in range(B)]
    Xs = np.stack([X for X, _ in probs])
    ys = np.stack([y for _, y in probs])
    fam = get_family("multinomial", m)
    lam = np.asarray(bh_sequence(p * m, q=0.1))
    kw = dict(path_length=8, solver_tol=1e-11, max_iter=20000, kkt_tol=1e-4)
    batched = fit_path_batched(Xs, ys, lam, fam, screening="strong", **kw)
    assert not batched.kkt_unrepaired.any()
    for b in range(B):
        single = fit_path(Xs[b], ys[b], lam, fam, screening="strong",
                          engine="host", early_stop=False, **kw)
        assert single.betas.shape == (8, p, m)
        np.testing.assert_allclose(batched.betas[b], single.betas, atol=5e-3)
        assert int(batched.total_violations[b]) == single.total_violations
        np.testing.assert_allclose(
            batched.n_screened[b], [s.n_screened for s in single.steps], atol=2)
        np.testing.assert_allclose(
            batched.n_active[b], [s.n_active for s in single.steps], atol=2)


# ---------------------------------------------------------------------------
# compact working-set engine (ISSUE 2 tentpole)
# ---------------------------------------------------------------------------

def test_compact_engine_matches_masked():
    """With W above the peak working set the compact engine must follow the
    masked engine step for step — same betas, same violation accounting —
    while solving at (n, W) instead of (n, p)."""
    B, n, p = 3, 40, 96
    Xs, ys = _batch_problems(B, n, p)
    lam = np.asarray(bh_sequence(p, q=0.1))
    masked = fit_path_batched(Xs, ys, lam, ols, **KW)
    compact = fit_path_batched(Xs, ys, lam, ols, working_set=64, **KW)
    assert compact.working_set == 64
    assert compact.ws_size is not None and compact.ws_size.max() > 0
    np.testing.assert_allclose(compact.betas, masked.betas, atol=1e-8)
    np.testing.assert_array_equal(compact.n_violations, masked.n_violations)
    np.testing.assert_array_equal(compact.n_screened, masked.n_screened)
    # every non-fallback step honoured the bucket
    honored = ~compact.compact_fallback
    assert (compact.ws_size[honored] <= 64).all()


def test_compact_engine_overflow_falls_back():
    """A bucket below the peak working set must flip the scalar lax.cond to
    the masked full-width solve — flagged per step, results identical."""
    B, n, p = 3, 40, 96
    Xs, ys = _batch_problems(B, n, p)
    lam = np.asarray(bh_sequence(p, q=0.1))
    masked = fit_path_batched(Xs, ys, lam, ols, **KW)
    over = fit_path_batched(Xs, ys, lam, ols, working_set=4, **KW)
    assert over.compact_fallback.any()  # overflow demonstrably happened
    np.testing.assert_allclose(over.betas, masked.betas, atol=1e-8)
    np.testing.assert_array_equal(over.n_violations, masked.n_violations)
    # overflow recorded the true demand so the bucket cache can grow
    assert over.ws_size.max() > 4


# ---------------------------------------------------------------------------
# two-tier working sets (ISSUE 5 tentpole)
# ---------------------------------------------------------------------------

def test_resolve_ws_tiers_recipe():
    """The ONE tier recipe: 2W second tier when it fits under p, single
    tier when pinned or when 2W would span p (the masked fallback IS the
    top tier there)."""
    from repro.core.engine import _WS_BUCKETS, resolve_ws_tiers

    key = ("tier-recipe-test",)
    _WS_BUCKETS.pop(key, None)
    assert resolve_ws_tiers(16, "auto", 40, 256, key) == (16, 32)
    assert resolve_ws_tiers(16, 2, 40, 256, key) == (16, 32)
    assert resolve_ws_tiers(16, 1, 40, 256, key) == (16, None)
    # 2W ≥ p degenerates to single tier under every policy
    assert resolve_ws_tiers(16, "auto", 40, 32, key) == (16, None)
    assert resolve_ws_tiers(16, 2, 40, 32, key) == (16, None)
    with pytest.raises(ValueError):
        resolve_ws_tiers(16, 3, 40, 256, key)
    with pytest.raises(ValueError):
        resolve_ws_tiers(16, "both", 40, 256, key)


def test_two_tier_per_member_promotion_and_fallback_cut():
    """The two-tier contract on one p ≫ n batch, single vs two tier:

    * a member whose screened set outgrows W (but fits 2W) is served at
      tier 2 while another member of the SAME step stays at tier 1;
    * steps whose peak demand lands in (W, 2W] stop falling back, so the
      two-tier fallback count is strictly below the single-tier one;
    * both engines match the masked solve, violations included.
    """
    from repro.core.engine import _fit_path_batched

    B, n, p = 4, 40, 256
    probs = [make_regression(n, p, k=5, rho=0.0, seed=s, noise=0.3)[:2]
             for s in range(B)]
    Xs = np.stack([X for X, _ in probs])
    ys = np.stack([y for _, y in probs])
    lam = np.asarray(bh_sequence(p, q=0.05))
    kw = dict(path_length=20, solver_tol=1e-12, max_iter=30000,
              kkt_tol=1e-4, sigma_ratio=0.5)
    masked = _fit_path_batched(Xs, ys, lam, ols, **kw)
    single = _fit_path_batched(Xs, ys, lam, ols, working_set=8, ws_tiers=1,
                               **kw)
    two = _fit_path_batched(Xs, ys, lam, ols, working_set=8,
                            ws_tiers="auto", **kw)
    assert (two.working_set, two.working_set_top) == (8, 16)
    assert single.working_set_top is None
    fb_single = int(single.compact_fallback.any(axis=0).sum())
    fb_two = int(two.compact_fallback.any(axis=0).sum())
    assert fb_single > fb_two  # the second tier absorbed real steps
    np.testing.assert_allclose(single.betas, masked.betas, atol=1e-9)
    np.testing.assert_allclose(two.betas, masked.betas, atol=1e-9)
    np.testing.assert_array_equal(two.n_violations, masked.n_violations)
    # some step promoted only part of the batch: one member runs at tier 2
    # while another member of the same step is served at tier 1
    mixed = (two.ws_tier == 2).any(axis=0) & (two.ws_tier == 1).any(axis=0)
    assert mixed.any()
    # tier accounting is consistent with demand: tier-1 steps fit W,
    # tier-2 steps need (W, 2W], fallback (tier 0) only past the top tier
    assert (two.ws_size[two.ws_tier == 1] <= 8).all()
    assert (two.ws_size[two.ws_tier == 2] <= 16).all()
    assert (two.ws_size[two.ws_tier == 2] > 8).all()
    fb_cols = two.compact_fallback.any(axis=0)
    assert ((two.ws_tier == 0) == fb_cols[None, :].repeat(B, axis=0)).all()
    assert (two.ws_size.max(axis=0)[fb_cols] > 16).all()


def test_two_tier_overflow_past_top_falls_back_whole_batch():
    """Demand beyond the top tier still sends the WHOLE batch to the
    masked solve — flagged in CompactStats.fell_back / tier 0 — and the
    forced per-member overflow reproduces the masked results."""
    from repro.core.engine import _fit_path_batched

    B, n, p = 3, 40, 96
    Xs, ys = _batch_problems(B, n, p)
    lam = np.asarray(bh_sequence(p, q=0.1))
    masked = fit_path_batched(Xs, ys, lam, ols, **KW)
    over = _fit_path_batched(Xs, ys, lam, ols, working_set=2, ws_tiers=2,
                             **KW)
    assert (over.working_set, over.working_set_top) == (2, 4)
    assert over.compact_fallback.any()
    # fallback steps are tier 0 for every member (the fallback is batch-
    # wide by construction — the scalar gate is what keeps it a real branch)
    fb = over.compact_fallback.any(axis=0)
    assert (over.ws_tier[:, fb] == 0).all()
    assert (over.ws_size.max(axis=0)[fb] > 4).all()
    # demand exceeds the top tier at EVERY fitted step here, so the whole
    # trajectory ran the masked solve — the forced per-member overflow is
    # BIT-identical to the masked engine, not merely tolerance-close
    assert over.compact_fallback[:, 1:].all()
    np.testing.assert_array_equal(over.betas, masked.betas)
    np.testing.assert_array_equal(over.n_violations, masked.n_violations)


def test_compact_engine_multinomial():
    """Compact gather/scatter through the (p, m) coefficient block."""
    B, n, p, m = 2, 30, 40, 3
    probs = [make_multinomial(n, p, k=4, m=m, rho=0.2, seed=s)[:2]
             for s in range(B)]
    Xs = np.stack([X for X, _ in probs])
    ys = np.stack([y for _, y in probs])
    fam = get_family("multinomial", m)
    lam = np.asarray(bh_sequence(p * m, q=0.1))
    kw = dict(path_length=6, solver_tol=1e-10, max_iter=10000)
    masked = fit_path_batched(Xs, ys, lam, fam, **kw)
    compact = fit_path_batched(Xs, ys, lam, fam, working_set=16, **kw)
    np.testing.assert_allclose(compact.betas, masked.betas, atol=1e-7)


def test_compact_auto_bucket_grows_on_overflow():
    """working_set='auto' starts at min(2^⌈log₂ max(2n, 64)⌉, p); an
    overflowing auto run writes the grown bucket to the cache and the next
    same-shape auto call picks it up.  Explicit-int runs never touch the
    cache (an undersized overflow probe must not shrink auto's default)."""
    from repro.core.engine import _WS_BUCKETS, _ws_bucket

    B, n, p = 2, 20, 256
    # dense signal + a σ grid deep enough that screening keeps ≥ p/2 and
    # the engine widens E to full-p: guaranteed overflow of the 64 bucket
    probs = [make_regression(n, p, k=20, rho=0.3, seed=s, noise=0.05)[:2]
             for s in range(B)]
    Xs = np.stack([X for X, _ in probs])
    ys = np.stack([y for _, y in probs])
    lam = np.asarray(bh_sequence(p, q=0.1))
    key = (n, p, 1, "ols", "strong")
    _WS_BUCKETS.pop(key, None)
    assert _ws_bucket("auto", n, p, key) == 64  # 2^⌈log₂ max(40, 64)⌉
    kw = dict(path_length=12, solver_tol=1e-9, max_iter=5000)
    res = fit_path_batched(Xs, ys, lam, ols, working_set="auto", **kw)
    assert res.working_set == 64
    assert res.compact_fallback.any()          # the 64 bucket overflowed
    grown = _WS_BUCKETS[key]                   # ... and the cache grew
    assert grown > 64
    assert grown == min(2 ** (int(res.ws_size.max()) - 1).bit_length(), p)
    # the next same-shape auto call starts from the grown bucket
    res2 = fit_path_batched(Xs, ys, lam, ols, working_set="auto", **kw)
    assert res2.working_set == grown
    np.testing.assert_allclose(res2.betas, res.betas, atol=1e-8)
    # explicit ints are pow-2 bucketed, capped at p, and never write the cache
    _WS_BUCKETS.pop(key, None)
    fit_path_batched(Xs, ys, lam, ols, working_set=4, **kw)
    assert key not in _WS_BUCKETS
    assert _ws_bucket(48, n, p, key) == 64
    assert _ws_bucket(1024, n, p, key) == p


def test_batched_multinomial_runs():
    B, n, p, m = 3, 30, 40, 3
    probs = [make_multinomial(n, p, k=4, m=m, rho=0.2, seed=s)[:2]
             for s in range(B)]
    Xs = np.stack([X for X, _ in probs])
    ys = np.stack([y for _, y in probs])
    fam = get_family("multinomial", m)
    lam = np.asarray(bh_sequence(p * m, q=0.1))
    res = fit_path_batched(Xs, ys, lam, fam, path_length=6,
                           solver_tol=1e-9, max_iter=5000)
    assert res.betas.shape == (B, 6, p, m)
    assert np.isfinite(res.betas).all()


def test_batched_path_results_views():
    B, n, p = 3, 30, 40
    Xs, ys = _batch_problems(B, n, p)
    lam = np.asarray(bh_sequence(p, q=0.1))
    res = fit_path_batched(Xs, ys, lam, ols, path_length=8,
                           solver_tol=1e-9, max_iter=5000)
    paths = res.path_results(early_stop=False)
    assert len(paths) == B
    for b, pr in enumerate(paths):
        np.testing.assert_array_equal(pr.betas, res.betas[b])
        assert len(pr.steps) == 8
        assert pr.total_violations == int(res.total_violations[b])
    # the default view applies the early-stopping rules post-hoc
    for pr in res.path_results():
        assert 1 <= len(pr.steps) <= 8


def test_cv_path_selects_signal_recovering_sigma():
    n, p = 60, 50
    X, y, _ = make_regression(n, p, k=4, rho=0.0, seed=2, noise=0.3)
    lam = np.asarray(bh_sequence(p, q=0.1))
    cv = cv_path(X, y, lam, ols, n_folds=4, path_length=25,
                 solver_tol=1e-9, max_iter=5000)
    assert cv.val_deviance.shape == (4, 25)
    assert np.isfinite(cv.mean_val_deviance).all()
    # with real signal, some amount of fitting must beat the null model
    assert cv.best_index > 0
    assert cv.mean_val_deviance[cv.best_index] < cv.mean_val_deviance[0]


# ---------------------------------------------------------------------------
# screen_masked == Algorithm 2 on the unmasked prefix (satellite property)
# ---------------------------------------------------------------------------

@st.composite
def masked_screen_case(draw):
    """Dyadic-grid inputs (exact in f64) plus a random mask."""
    p = draw(st.integers(1, 60))
    c = draw(st.lists(st.integers(-320, 320), min_size=p, max_size=p))
    raw = draw(st.lists(st.integers(0, 256), min_size=p, max_size=p))
    keep = draw(st.lists(st.integers(0, 1), min_size=p, max_size=p))
    lam = np.sort(np.asarray(raw, np.float64))[::-1] / 64.0
    return (np.asarray(c, np.float64) / 64.0, lam,
            np.asarray(keep, bool))


@settings(max_examples=200, deadline=None)
@given(masked_screen_case())
def test_screen_masked_equals_oracle_on_unmasked_prefix(case):
    c, lam, mask = case
    p = len(c)
    # pad to one fixed jit shape; padded entries are masked out, which is
    # exactly the property under test
    pad = 60 - p
    cp = jnp.asarray(np.concatenate([c, np.zeros(pad)]))
    lamp = jnp.asarray(np.concatenate([lam, np.zeros(pad)]))
    maskp = jnp.asarray(np.concatenate([mask, np.zeros(pad, bool)]))
    keep, k = screen_masked(cp, lamp, maskp, jnp.zeros_like(cp))
    keep = np.asarray(keep)[:p]
    k = int(k)
    # oracle: run Algorithm 2 on the unmasked entries alone (sorted), with
    # the leading λ entries — masking must be exactly problem truncation
    sub = np.sort(c[mask])[::-1]
    k_oracle = algorithm_2_oracle(sub, lam[: len(sub)])
    assert k == k_oracle
    assert keep.sum() == k
    assert not keep[~mask].any()
    # kept set = k largest unmasked magnitudes
    if k:
        kept_vals = np.sort(c[keep])[::-1]
        np.testing.assert_array_equal(kept_vals, sub[:k])


@pytest.mark.parametrize("working_set", [None, 16])
def test_engines_pool_in_the_kernel_on_a_tpu(monkeypatch, working_set):
    """On a TPU the batched engines' f32 solves take the Pallas pooling
    kernel, one member at a time (run here in the interpreter), in the
    masked and in the compact engine; every step of the path is
    KKT-certified at the level of the sweep-merging prox's path."""
    import jax

    from repro.core import kkt_optimal
    from repro.kernels import ops

    B, n, p = 2, 20, 40
    Xs, ys = _batch_problems(B, n, p, rho=0.5)
    Xs, ys = Xs.astype(np.float32), ys.astype(np.float32)
    lam = np.asarray(bh_sequence(p, q=0.1), np.float32)
    kw = dict(path_length=6, solver_tol=1e-8, max_iter=5000,
              working_set=working_set)
    traced = []
    pool = ops.prox_pool

    def counted(w, **k):
        traced.append(w.shape)
        return pool(w, **k)

    jax.clear_caches()  # the engines pick their prox while they trace
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "_interpret", lambda: True)  # no chip here
    monkeypatch.setattr(ops, "prox_pool", counted)
    try:
        got = fit_path_batched(Xs, ys, lam, ols, **kw)
    finally:
        jax.clear_caches()
    assert traced and all(s == (p,) for s in traced if working_set is None)
    assert got.betas.dtype == np.float32
    assert np.asarray(got.solver_iters).max() < 5000
    for b in range(B):
        X64, y64 = Xs[b].astype(np.float64), ys[b].astype(np.float64)
        for beta, s in zip(got.betas[b], got.sigmas[b]):
            beta = beta.astype(np.float64).ravel()
            grad = X64.T @ (X64 @ beta - y64)
            assert kkt_optimal(grad, beta, float(s) * lam, atol=0.0,
                               rtol=1e-2)
