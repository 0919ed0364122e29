"""λ sequences (paper §3.1.1) and the dry-run input-spec machinery."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    bh_sequence,
    gaussian_sequence,
    lasso_sequence,
    oscar_sequence,
    path_start_sigma,
    sigma_grid,
)


def test_bh_sequence_shape_and_monotonicity():
    lam = np.asarray(bh_sequence(500, q=0.1))
    assert lam.shape == (500,)
    assert np.all(np.diff(lam) <= 0) and lam[-1] >= 0
    # λ_1 = Φ⁻¹(1 − q/(2p)) — scipy is a [test] extra; the minimal install
    # still runs every other assertion in this module
    norm = pytest.importorskip("scipy.stats").norm

    np.testing.assert_allclose(lam[0], norm.ppf(1 - 0.1 / (2 * 500)), rtol=1e-10)


def test_gaussian_sequence_truncates_when_increasing():
    """Paper §3.1.1: λG is set to the previous value once it increases, and
    for small q/p it reduces to (nearly) the BH sequence start."""
    lam = np.asarray(gaussian_sequence(100, n=50, q=0.1))
    assert np.all(np.diff(lam) <= 1e-12)
    # the adjustment never lifts λ above λ_1
    assert lam.max() == lam[0]


def test_oscar_and_lasso_sequences():
    osc = np.asarray(oscar_sequence(10, q=0.5))
    np.testing.assert_allclose(osc, 0.5 * (10 - np.arange(1, 11)) + 1)
    las = np.asarray(lasso_sequence(7))
    np.testing.assert_allclose(las, np.ones(7))


@pytest.mark.parametrize("kind", ["bh", "gaussian", "oscar", "lasso"])
def test_named_lambda_follows_design_dtype(kind):
    """An f32 design gets an f32 λ and σ grid even under x64, so no f64
    op enters an f32 program; f64 designs keep f64."""
    from repro.api import LambdaSpec
    from repro.core import ols
    from repro.core.engine import null_sigma_grid

    rng = np.random.default_rng(0)
    X = rng.normal(size=(30, 50))
    y = rng.normal(size=30)
    for dt in (np.float32, np.float64):
        lam = LambdaSpec(kind, q=0.1).resolve(50, n=30, dtype=dt)
        assert lam.dtype == dt
        sig = null_sigma_grid(X.astype(dt), y.astype(dt), lam, ols,
                              path_length=5, sigma_ratio=None)
        assert sig.dtype == dt


def test_sigma_grid_paper_ratios():
    g1 = sigma_grid(2.0, length=10, n=50, p=100)   # n < p → ratio 1e-2
    assert g1[0] == 2.0 and np.isclose(g1[-1], 2.0 * 1e-2)
    g2 = sigma_grid(2.0, length=10, n=100, p=50)   # n ≥ p → ratio 1e-4
    assert np.isclose(g2[-1], 2.0 * 1e-4)


def test_path_start_sigma_zeroes_the_first_step(rng):
    """σ(1) is the smallest σ with β̂ = 0 (checked via the dual gauge)."""
    from repro.core import fista, ols
    from repro.data import make_regression

    X, y, _ = make_regression(40, 80, k=5, seed=0)
    lam = np.asarray(bh_sequence(80, 0.1))
    grad0 = X.T @ (0 - y)
    s1 = float(path_start_sigma(jnp.asarray(grad0), jnp.asarray(lam)))
    res = fista(jnp.asarray(X), jnp.asarray(y), jnp.asarray(s1 * lam * 1.0001),
                jnp.zeros(80), ols, max_iter=5000, tol=1e-14)
    assert np.abs(np.asarray(res.beta)).max() < 1e-10


def test_lambda_spec_paths_match_legacy_arrays():
    """ISSUE 4 satellite: bh / gaussian / oscar sequences produce IDENTICAL
    paths through LambdaSpec vs the legacy explicit-array kwargs, on both
    the host and device backends (the spec resolves through the shared
    canonicalizer to the same bytes the legacy recipe functions return)."""
    import warnings

    from repro.api import LambdaSpec, PathSpec, Problem, SolverPolicy, slope_path
    from repro.core import fit_path, ols
    from repro.data import make_regression

    n, p = 25, 30
    X, y, _ = make_regression(n, p, k=3, rho=0.2, seed=7)
    kw = dict(path_length=5, solver_tol=1e-10, max_iter=20000)
    legacy_arrays = {
        ("bh", 0.1): np.asarray(bh_sequence(p, 0.1)),
        ("gaussian", 0.1): np.asarray(gaussian_sequence(p, n=n, q=0.1)),
        ("oscar", 0.05): np.asarray(oscar_sequence(p, 0.05)),
    }
    for (kind, q), lam in legacy_arrays.items():
        spec = PathSpec(lam=LambdaSpec(kind, q=q), path_length=5,
                        early_stop=False)
        resolved = spec.lam.resolve(p, n=n)
        np.testing.assert_array_equal(resolved, lam)

        host_legacy = fit_path(X, y, lam, ols, early_stop=False, **kw)
        host_spec = slope_path(Problem(X, y), spec,
                               SolverPolicy(backend="host",
                                            solver_tol=1e-10,
                                            max_iter=20000))
        np.testing.assert_array_equal(host_legacy.betas, host_spec.betas)

        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            dev_legacy = fit_path(X, y, lam, ols, engine="device",
                                  early_stop=False, **kw)
        dev_spec = slope_path(Problem(X, y), spec,
                              SolverPolicy(backend="masked",
                                           solver_tol=1e-10,
                                           max_iter=20000))
        np.testing.assert_array_equal(dev_legacy.betas, dev_spec.betas)


def test_lambda_spec_validation_and_sharing():
    from repro.api import LambdaSpec, shared_canonicalizer

    a = LambdaSpec("bh", q=0.1).resolve(50)
    b = LambdaSpec("bh", q=0.1).resolve(50)
    assert a is b and not a.flags.writeable  # one shared memoised array
    assert shared_canonicalizer().get("bh", 0.1, 50) is a

    import pytest

    with pytest.raises(ValueError):
        LambdaSpec("nope")
    with pytest.raises(ValueError):
        LambdaSpec("explicit")               # explicit needs values
    with pytest.raises(ValueError):
        LambdaSpec.explicit(np.ones(7)).resolve(9)
    lam2 = LambdaSpec.explicit(np.ones((3, 9))).resolve(9)  # (B, p·m) stack
    assert lam2.shape == (3, 9)

    # ... but a per-problem stack needs a batched (B, n, p) problem
    from repro.api import PathSpec, Problem, slope_path
    from repro.data import make_regression

    X, y, _ = make_regression(12, 9, k=2, seed=0)
    with pytest.raises(ValueError, match="batched"):
        slope_path(Problem(X, y),
                   PathSpec(lam=LambdaSpec.explicit(np.ones((3, 9))),
                            path_length=4))


def test_input_specs_cover_all_cells():
    from repro.configs import ARCH_NAMES, get_config
    from repro.launch.specs import SHAPES, input_specs, skip_reason

    n_skip = 0
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        for shape in SHAPES:
            if skip_reason(cfg, shape):
                n_skip += 1
                continue
            spec = input_specs(cfg, shape)
            if spec["kind"] in ("train", "prefill"):
                toks = spec["batch"]["tokens"]
                assert toks.shape[0] == SHAPES[shape].global_batch
                total = toks.shape[1] + (cfg.n_patches or 0)
                assert total == SHAPES[shape].seq_len
                if cfg.encdec:
                    assert "frames" in spec["batch"]
            else:
                assert spec["token"].shape == (SHAPES[shape].global_batch, 1)
                assert len(jax.tree.leaves(spec["cache"])) > 0
    # exactly the 7 full-attention archs skip long_500k
    assert n_skip == 7


import jax  # noqa: E402  (used in the spec test above)
