"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal image: fall back to seeded random fuzzing
    from _hypothesis_fallback import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import prox_sorted_l1
from repro.core.screening import algorithm_2_oracle
from repro.kernels import (
    compact_gemv_stats,
    prox_pool,
    prox_sorted_l1_kernel,
    screen_scan,
    slope_gradient,
    slope_gradient_compact,
    slope_gradient_masked,
    slope_loss_residual,
    slope_loss_residual_compact,
    slope_residual,
    slope_residual_compact,
    slope_residual_masked,
)
from repro.kernels import ref as R
from repro.kernels.prox_sorted_l1 import prox_pool_kernel_call

SHAPES = [(7, 13, 1), (64, 128, 3), (33, 257, 4), (256, 512, 1), (129, 1025, 2)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_xt_matmul_kernel(shape, dtype, rng):
    n, p, m = shape
    X = jnp.asarray(rng.normal(size=(n, p)), dtype)
    Rm = jnp.asarray(rng.normal(size=(n, m)), dtype)
    got = np.asarray(slope_gradient(X, Rm), np.float32)
    want = np.asarray(R.xt_matmul_ref(X, Rm), np.float32)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("family", ["none", "ols", "logistic", "poisson", "multinomial"])
def test_xb_residual_kernel(shape, family, rng):
    n, p, m = shape
    X = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(p, m)) / np.sqrt(p), jnp.float32)
    Y = jnp.asarray(rng.integers(0, 2, size=(n, m)), jnp.float32)
    got = np.asarray(slope_residual(X, B, Y, family=family))
    want = np.asarray(R.xb_residual_ref(X, B, Y, family))
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("shape", SHAPES)
def test_xt_matmul_masked_kernel(shape, rng):
    """Mask-aware gradient GEMV: block-skip must not change the result, and
    masked columns' gradient rows must be exactly 0."""
    n, p, m = shape
    X = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    Rm = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    # sparse mask leaves whole (bn × bp) blocks dead — the skip path
    mask = np.zeros(p, bool)
    mask[rng.choice(p, size=max(1, p // 8), replace=False)] = True
    got = np.asarray(slope_gradient_masked(X, Rm, jnp.asarray(mask)))
    want = np.asarray(R.xt_matmul_masked_ref(X, Rm, jnp.asarray(mask)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert (got[~mask] == 0.0).all()
    # all-masked and all-alive extremes
    dead = np.asarray(slope_gradient_masked(X, Rm, jnp.zeros(p, bool)))
    assert (dead == 0.0).all()
    alive = np.asarray(slope_gradient_masked(X, Rm, jnp.ones(p, bool)))
    np.testing.assert_allclose(alive, np.asarray(slope_gradient(X, Rm)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("family", ["none", "ols", "logistic", "multinomial"])
def test_xb_residual_masked_kernel(shape, family, rng):
    n, p, m = shape
    X = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(p, m)) / np.sqrt(p), jnp.float32)
    Y = jnp.asarray(rng.integers(0, 2, size=(n, m)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, size=p).astype(bool))
    got = np.asarray(slope_residual_masked(X, B, Y, mask, family=family))
    want = np.asarray(R.xb_residual_masked_ref(X, B, Y, mask, family))
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("family", ["ols", "logistic", "poisson", "multinomial"])
def test_fused_loss_residual_kernel(shape, family, rng):
    """One X pass must reproduce the separate loss + residual oracles."""
    from repro.core import get_family

    n, p, m = shape
    X = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(p, m)) / np.sqrt(p), jnp.float32)
    Y = jnp.asarray(rng.integers(0, 2, size=(n, m)), jnp.float32)
    loss, r = slope_loss_residual(X, B, Y, family=family)
    want_r, want_rows = R.xb_loss_residual_ref(X, B, Y, family)
    np.testing.assert_allclose(np.asarray(r), np.asarray(want_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(float(loss), float(jnp.sum(want_rows)),
                               rtol=2e-4, atol=2e-4)
    if family != "multinomial" and m == 1:
        # cross-check against the Family value/residual pair the solver uses
        fam = get_family(family)
        z = X @ B[:, 0]
        np.testing.assert_allclose(float(loss),
                                   float(fam.value(z, Y[:, 0])),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# block-compacted GEMVs (ISSUE 5): live-block grid remap via scalar prefetch
# ---------------------------------------------------------------------------

def _block_mask(p: int, bp: int, pattern: str, rng) -> np.ndarray:
    """Column mask whose per-block liveness follows ``pattern`` (blocks of
    width ``bp``): all live, every other block live, or all dead.  Live
    blocks keep a random sparse interior so the in-block mask multiply is
    exercised too."""
    n_blocks = (p + bp - 1) // bp
    mask = np.zeros(p, bool)
    live = {"all_live": range(n_blocks),
            "half_live": range(0, n_blocks, 2),
            "all_dead": ()}[pattern]
    for b in live:
        lo, hi = b * bp, min((b + 1) * bp, p)
        cols = rng.choice(np.arange(lo, hi), size=max(1, (hi - lo) // 4),
                          replace=False)
        mask[cols] = True
    return mask


@pytest.mark.parametrize("pattern", ["all_live", "half_live", "all_dead"])
def test_compact_gemv_patterns(pattern, rng):
    """Compact == masked == oracle at every block-liveness pattern, and the
    remapped grid covers exactly the live blocks (dead-block DMA cannot
    happen when the grid never visits the block)."""
    n, p, m = 24, 512, 2
    bp = 128
    n_blocks = p // bp
    expect_live = {"all_live": n_blocks, "half_live": n_blocks // 2,
                   "all_dead": 0}[pattern]
    X = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    Rm = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(p, m)) / np.sqrt(p), jnp.float32)
    Y = jnp.asarray(rng.integers(0, 2, size=(n, m)), jnp.float32)
    mask = jnp.asarray(_block_mask(p, bp, pattern, rng))

    got = np.asarray(slope_gradient_compact(X, Rm, mask, bp=bp))
    st = compact_gemv_stats("gradient")
    assert (st.blocks_total, st.blocks_live) == (n_blocks, expect_live)
    assert st.grid[0] == st.blocks_live  # the remapped grid == live blocks
    np.testing.assert_array_equal(
        got, np.asarray(slope_gradient_masked(X, Rm, mask, bp=bp)))
    want = np.asarray(R.xt_matmul_compact_ref(X, Rm, mask))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert (got[~np.asarray(mask)] == 0.0).all()

    for family in ("ols", "logistic"):
        got_r = np.asarray(slope_residual_compact(X, B, Y, mask,
                                                  family=family, bp=bp))
        st = compact_gemv_stats("residual")
        assert st.blocks_live == expect_live
        assert st.grid[1] == st.blocks_live
        np.testing.assert_array_equal(
            got_r, np.asarray(slope_residual_masked(X, B, Y, mask,
                                                    family=family, bp=bp)))
        want_r = np.asarray(R.xb_residual_compact_ref(X, B, Y, mask, family))
        np.testing.assert_allclose(got_r, want_r, rtol=3e-5, atol=3e-5)

    loss, r = slope_loss_residual_compact(X, B, Y, mask, family="logistic",
                                          bp=bp)
    st = compact_gemv_stats("loss_residual")
    assert st.blocks_live == expect_live and st.grid[1] == st.blocks_live
    want_r, want_rows = R.xb_loss_residual_compact_ref(X, B, Y, mask,
                                                       "logistic")
    np.testing.assert_allclose(np.asarray(r), np.asarray(want_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(float(loss), float(jnp.sum(want_rows)),
                               rtol=2e-4, atol=2e-4)


def test_compact_gemv_odd_shapes_and_1d(rng):
    """Padding/squeeze parity with the masked wrappers at non-block shapes."""
    n, p = 33, 257
    X = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    r = jnp.asarray(rng.normal(size=n), jnp.float32)
    b = jnp.asarray(rng.normal(size=p) / np.sqrt(p), jnp.float32)
    y = jnp.asarray(rng.normal(size=n), jnp.float32)
    mask = np.zeros(p, bool)
    mask[rng.choice(p, size=9, replace=False)] = True
    mj = jnp.asarray(mask)
    g = slope_gradient_compact(X, r, mj)
    assert g.shape == (p,)
    np.testing.assert_array_equal(
        np.asarray(g), np.asarray(slope_gradient_masked(X, r, mj)))
    z = slope_residual_compact(X, b, y, mj, family="ols")
    assert z.shape == (n,)
    np.testing.assert_array_equal(
        np.asarray(z),
        np.asarray(slope_residual_masked(X, b, y, mj, family="ols")))


def test_compact_gemv_traced_mask_degrades_to_masked(rng):
    """Under jit the mask is a tracer — no static live list exists, so the
    compact wrappers must fall back to the (semantically identical) masked
    kernels instead of failing."""
    import jax

    n, p = 16, 256
    X = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(n, 1)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, size=p).astype(bool))

    @jax.jit
    def traced(m):
        return slope_gradient_compact(X, r, m)

    from repro.kernels.ops import COMPACT_METRICS

    before = COMPACT_METRICS.value("fallbacks", op="gradient",
                                   reason="traced_mask")
    np.testing.assert_allclose(
        np.asarray(traced(mask)),
        np.asarray(slope_gradient_masked(X, r, mask)), rtol=2e-5, atol=2e-5)
    # the fallback is counted (once, when the caller's program traces)
    assert COMPACT_METRICS.value("fallbacks", op="gradient",
                                 reason="traced_mask") == before + 1


def test_gemv_1d_paths(rng):
    X = jnp.asarray(rng.normal(size=(50, 70)), jnp.float32)
    r = jnp.asarray(rng.normal(size=50), jnp.float32)
    b = jnp.asarray(rng.normal(size=70) / 8, jnp.float32)
    y = jnp.asarray(rng.normal(size=50), jnp.float32)
    g = slope_gradient(X, r)
    assert g.shape == (70,)
    np.testing.assert_allclose(np.asarray(g), np.asarray(X).T @ np.asarray(r),
                               rtol=2e-5, atol=2e-5)
    z = slope_residual(X, b, y, family="ols")
    assert z.shape == (50,)
    np.testing.assert_allclose(np.asarray(z), np.asarray(X) @ np.asarray(b) - np.asarray(y),
                               rtol=2e-5, atol=2e-5)


@st.composite
def screen_case(draw):
    """Dyadic-grid inputs (multiples of 1/64, bounded): every partial sum is
    exact in f32, so block-wise (kernel), parallel-prefix (ref) and
    sequential (Algorithm 2) summation orders all agree exactly — the tests
    check the algorithms, not float association on constructed ties."""
    p = draw(st.integers(1, 600))
    c = draw(st.lists(st.integers(-320, 320), min_size=p, max_size=p))
    raw = draw(st.lists(st.integers(0, 256), min_size=p, max_size=p))
    lam = np.sort(np.asarray(raw, np.float32))[::-1] / 64.0
    return np.asarray(c, np.float32) / 64.0, lam


@pytest.mark.slow
@settings(max_examples=60, deadline=None)
@given(screen_case())
def test_screen_kernel_matches_f32_ref(case):
    c, lam = case
    k_ref = int(R.screen_scan_ref(jnp.asarray(c), jnp.asarray(lam)))
    k_kernel = int(screen_scan(jnp.asarray(c), jnp.asarray(lam), block=128))
    assert k_ref == k_kernel


def test_screen_kernel_fixed_cases(rng):
    """Fast-tier screen-kernel coverage: one compile, deterministic data."""
    p = 256
    lam = np.sort(np.abs(rng.normal(size=p)).astype(np.float32))[::-1].copy()
    for scale in (0.1, 1.0, 3.0):
        c = (rng.normal(size=p) * scale).astype(np.float32)
        k_ref = algorithm_2_oracle(c, lam)
        k_kernel = int(screen_scan(jnp.asarray(c), jnp.asarray(lam), block=128))
        assert k_ref == k_kernel


@pytest.mark.slow
def test_screen_kernel_matches_algorithm_2_random(rng):
    """Kernel vs the paper's Algorithm 2 on generic (non-adversarial) data.

    Slow tier: 200 interpret-mode pallas calls across ~200 distinct padded
    shapes recompile per shape."""
    for trial in range(200):
        p = int(rng.integers(1, 2000))
        c = (rng.normal(size=p) * 3).astype(np.float32)
        lam = np.sort(np.abs(rng.normal(size=p)).astype(np.float32))[::-1].copy()
        k1 = algorithm_2_oracle(c, lam)
        k2 = int(screen_scan(jnp.asarray(c), jnp.asarray(lam), block=256))
        assert k1 == k2, (trial, p, k1, k2)


@pytest.mark.slow
@settings(max_examples=60, deadline=None)
@given(st.integers(1, 400), st.integers(0, 2**31 - 1))
def test_prox_kernel_matches_core(p, seed):
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.normal(size=p) * 3, jnp.float32)
    lam = jnp.asarray(np.sort(np.abs(rng.normal(size=p)))[::-1], jnp.float32)
    got = np.asarray(prox_sorted_l1_kernel(v, lam))
    want = np.asarray(prox_sorted_l1(v, lam))
    np.testing.assert_allclose(got, want, atol=3e-6)


def test_prox_pool_monotone_output(rng):
    for trial in range(12):
        p = (1, 7, 120, 500)[trial % 4]
        w = jnp.asarray(np.sort(rng.normal(size=p))[::-1] + rng.normal(size=p) * 0.3,
                        jnp.float32)
        out = np.asarray(prox_pool(w))
        assert np.all(np.diff(out) <= 1e-5)
        assert np.all(out >= 0)


def test_prox_pool_kernel_is_named():
    # the device trace names the kernel after the pallas_call's name, and
    # the benchmark's prox share finds it by that name
    jaxpr = jax.make_jaxpr(
        lambda w: prox_pool_kernel_call(w, interpret=True))(
        jnp.ones(16, jnp.float32))
    (eqn,) = [e for e in jaxpr.jaxpr.eqns
              if e.primitive.name == "pallas_call"]
    assert eqn.params["name"] == "prox_pool"
