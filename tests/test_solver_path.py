"""FISTA solver + path drivers: optimality, screening-invariance, stopping."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    bh_sequence,
    fista,
    fista_compact,
    fista_masked,
    fit_path,
    get_family,
    kkt_optimal,
    lasso_sequence,
    ols,
    prox_sorted_l1,
    sorted_l1_norm,
)
from repro.data import (
    make_classification,
    make_multinomial,
    make_poisson,
    make_regression,
)


def test_fista_orthonormal_closed_form(rng):
    """X orthonormal ⇒ β̂ = prox(Xᵀy; λ) exactly."""
    n, p = 60, 40
    Q, _ = np.linalg.qr(rng.normal(size=(n, p)))
    X = Q
    y = rng.normal(size=n)
    lam = np.sort(np.abs(rng.normal(size=p)))[::-1] * 0.5
    res = fista(jnp.asarray(X), jnp.asarray(y), jnp.asarray(lam),
                jnp.zeros(p), ols, max_iter=20000, tol=1e-15)
    want = np.asarray(prox_sorted_l1(jnp.asarray(X.T @ y), jnp.asarray(lam)))
    np.testing.assert_allclose(np.asarray(res.beta), want, atol=1e-7)


@pytest.mark.parametrize("family_name,maker", [
    ("ols", make_regression),
    ("logistic", make_classification),
    ("poisson", make_poisson),
])
def test_fista_kkt_optimal(family_name, maker):
    n, p = 80, 60
    X, y, _ = maker(n, p, k=5, rho=0.2, seed=1)
    fam = get_family(family_name)
    lam = np.asarray(bh_sequence(p, q=0.2)) * (2.0 if family_name != "poisson" else 5.0)
    res = fista(jnp.asarray(X), jnp.asarray(y), jnp.asarray(lam),
                jnp.zeros(p), fam, max_iter=30000, tol=1e-15)
    beta = np.asarray(res.beta)
    grad = np.asarray(fam.gradient(jnp.asarray(X), jnp.asarray(y), jnp.asarray(beta)))
    assert kkt_optimal(grad, beta, lam, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("screening", ["strong", "previous"])
def test_path_screening_invariance(screening):
    """Screened and unscreened paths reach the same objectives."""
    n, p = 50, 200
    X, y, _ = make_regression(n, p, k=8, rho=0.3, seed=7)
    lam = np.asarray(bh_sequence(p, q=0.1))
    # kkt_tol bounds how far a guarded-but-accepted solution may sit from
    # the unscreened optimum — tighten it to make invariance testable
    kw = dict(path_length=20, solver_tol=1e-12, max_iter=20000, kkt_tol=1e-7)
    r_scr = fit_path(X, y, lam, ols, screening=screening, **kw)
    r_ref = fit_path(X, y, lam, ols, screening="none", **kw)
    # early stopping can trigger one step apart at fp noise of the threshold
    assert abs(len(r_scr.steps) - len(r_ref.steps)) <= 1
    for i, (s1, s2) in enumerate(zip(r_scr.steps, r_ref.steps)):
        o1 = s1.deviance + float(sorted_l1_norm(jnp.asarray(r_scr.betas[i]),
                                                jnp.asarray(s1.sigma * lam)))
        o2 = s2.deviance + float(sorted_l1_norm(jnp.asarray(r_ref.betas[i]),
                                                jnp.asarray(s2.sigma * lam)))
        assert abs(o1 - o2) <= 1e-5 * max(1.0, abs(o2)), (i, o1, o2)
    L = min(len(r_scr.betas), len(r_ref.betas))
    np.testing.assert_allclose(r_scr.betas[:L], r_ref.betas[:L], atol=2e-3)


def test_path_multinomial_runs():
    n, p, m = 40, 60, 3
    X, y, _ = make_multinomial(n, p, k=5, m=m, rho=0.2, seed=2)
    fam = get_family("multinomial", m)
    lam = np.asarray(bh_sequence(p * m, q=0.1))
    r = fit_path(X, y, lam, fam, screening="strong", path_length=8,
                 solver_tol=1e-9, max_iter=4000)
    assert r.betas.shape[1:] == (p, m)
    assert np.isfinite(r.betas).all()


def test_path_screened_set_contains_active():
    n, p = 50, 400
    X, y, _ = make_regression(n, p, k=6, rho=0.0, seed=11)
    lam = np.asarray(bh_sequence(p, q=0.05))
    r = fit_path(X, y, lam, ols, screening="strong", path_length=15,
                 solver_tol=1e-11, max_iter=10000)
    # efficiency ≥ 1 whenever anything is active and no violation occurred
    for s in r.steps[1:]:
        if s.n_active and not s.n_violations:
            assert s.n_screened + 1e-9 >= 0  # screened count recorded
    assert r.total_violations <= 2  # rare by Fig. 3


@pytest.mark.parametrize("family_name,m", [("ols", 1), ("multinomial", 3)])
def test_fista_masked_zero_invariant(family_name, m, rng):
    """Masked coordinates come back EXACTLY 0 with no exit re-mask: zeroed
    columns have identically-zero gradient and the sorted-ℓ1 prox preserves
    exact zeros, so the solver never perturbs them (the re-mask this
    replaces was a redundant (p, m) multiply per solve)."""
    n, p = 40, 80
    if family_name == "ols":
        X, y, _ = make_regression(n, p, k=5, rho=0.3, seed=3)
    else:
        X, y, _ = make_multinomial(n, p, k=5, m=m, rho=0.3, seed=3)
    fam = get_family(family_name, m)
    # weak penalty so the unmasked columns actually activate
    lam = np.asarray(bh_sequence(p * m, q=0.1)) * 0.05
    mask = rng.random(p) < 0.15
    mask[0] = True  # keep the working set non-empty
    beta0 = np.zeros(p) if m == 1 else np.zeros((p, m))
    res = fista_masked(jnp.asarray(X), jnp.asarray(y), jnp.asarray(lam),
                       jnp.asarray(beta0), jnp.asarray(mask), fam,
                       max_iter=5000, tol=1e-12)
    beta = np.asarray(res.beta)
    assert (beta[~mask] == 0.0).all()  # exact, not just small
    assert np.abs(beta[mask]).max() > 0  # the solve did something


def test_fista_compact_matches_masked(rng):
    """The compact (n, W) gather solve equals the masked full-width solve;
    padding columns beyond |mask| stay inert."""
    n, p, W = 40, 150, 16
    X, y, _ = make_regression(n, p, k=5, rho=0.2, seed=9)
    lam = np.asarray(bh_sequence(p, q=0.1)) * 1.5
    mask = np.zeros(p, bool)
    mask[rng.choice(p, size=9, replace=False)] = True
    args = (jnp.asarray(X), jnp.asarray(y), jnp.asarray(lam),
            jnp.zeros(p), jnp.asarray(mask), ols)
    kw = dict(max_iter=20000, tol=1e-14)
    r_masked = fista_masked(*args, **kw)
    r_compact = fista_compact(*args, width=W, **kw)
    beta_c = np.asarray(r_compact.beta)
    assert beta_c.shape == (p,)
    assert (beta_c[~mask] == 0.0).all()
    np.testing.assert_allclose(beta_c, np.asarray(r_masked.beta), atol=1e-9)
    np.testing.assert_allclose(float(r_compact.objective),
                               float(r_masked.objective), rtol=1e-10)


def test_path_early_stop_on_saturation():
    n, p = 25, 50
    X, y, _ = make_regression(n, p, k=20, rho=0.0, seed=5, noise=0.01)
    lam = np.asarray(lasso_sequence(p)) * 1.0
    r = fit_path(X, y, lam, ols, screening="strong", path_length=100,
                 solver_tol=1e-10, max_iter=5000)
    assert len(r.sigmas) < 100  # stopped early (rules 1–3)


def test_f32_path_converges_on_equicorrelated_design():
    """An f32 device path on the paper's equicorrelated design (ρ = 0.5,
    p ≫ n): every solve ends on its stopping tests well before max_iter,
    and every step certifies at the KKT level an f64 path reaches under
    the same tolerances (rtol 1e-2).  Judged on function values alone, the
    line search let f32 rounding pass steps with L far too small near
    σ_max, and solves there ran to max_iter."""
    from repro.core.engine import null_sigma_grid, path_engine

    n, p, L = 100, 2000, 100
    X, y, _ = make_regression(n, p, 20, rho=0.5, seed=1, design="equi")
    X, y = X.astype(np.float32), y.astype(np.float32)
    lam = np.asarray(bh_sequence(p, q=0.1), np.float32)
    sig = null_sigma_grid(X, y, lam, ols, path_length=L, sigma_ratio=None)
    res = path_engine(jnp.asarray(X), jnp.asarray(y), jnp.asarray(lam),
                      jnp.asarray(sig), ols, max_iter=5000)
    assert np.asarray(res.betas).dtype == np.float32
    iters = np.asarray(res.solver_iters)
    assert iters.max() < 1000, iters.tolist()
    X64, y64 = X.astype(np.float64), y.astype(np.float64)
    for b, s in zip(np.asarray(res.betas)[1:, :, 0], sig[1:]):
        b = b.astype(np.float64)
        grad = X64.T @ (X64 @ b - y64)
        assert kkt_optimal(grad, b, float(s) * lam.astype(np.float64),
                           atol=0.0, rtol=1e-2)


def test_host_path_pools_in_the_kernel_on_a_tpu(monkeypatch):
    """On a TPU the host driver's f32 sub-solves take the Pallas pooling
    kernel (run here in the interpreter); the path is the one the XLA stack
    prox gives, bit for bit.  f64 keeps the XLA stack."""
    import jax

    from repro.core.sorted_l1 import pool_prox_applies
    from repro.kernels import ops

    X, y, _ = make_regression(30, 120, 5, rho=0.5, seed=3, design="equi")
    X, y = X.astype(np.float32), y.astype(np.float32)
    lam = np.asarray(bh_sequence(120, q=0.1), np.float32)
    kw = dict(screening="strong", path_length=12, max_iter=2000)
    want = fit_path(X, y, lam, ols, **kw)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "_interpret", lambda: True)  # no chip here
    assert pool_prox_applies(np.float32)
    assert not pool_prox_applies(np.float64)
    got = fit_path(X, y, lam, ols, **kw)
    np.testing.assert_array_equal(got.betas, want.betas)
    assert [s.solver_iters for s in got.steps] == [
        s.solver_iters for s in want.steps]
