"""Sorted-ℓ1 norm + prox: oracle comparisons and subdifferential certificates."""

import numpy as np

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal image: fall back to seeded random fuzzing
    from _hypothesis_fallback import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import (
    bh_sequence,
    dual_sorted_l1_gauge,
    in_subdifferential,
    isotonic_decreasing,
    prox_sorted_l1,
    sorted_l1_norm,
)


def numpy_pava_prox(v, lam):
    """Stack-based FastProxSL1 reference in pure NumPy (float64)."""
    v = np.asarray(v, float)
    lam = np.asarray(lam, float)
    sign = np.sign(v)
    mag = np.abs(v)
    order = np.argsort(-mag)
    w = mag[order] - lam
    stack = []
    for s in w:
        stack.append([s, 1])
        while len(stack) > 1 and stack[-1][0] * stack[-2][1] >= stack[-2][0] * stack[-1][1]:
            b = stack.pop()
            stack[-1][0] += b[0]
            stack[-1][1] += b[1]
    x = np.concatenate([[b[0] / b[1]] * int(b[1]) for b in stack])
    x = np.maximum(x, 0)
    out = np.zeros_like(v)
    out[order] = x
    return sign * out


@st.composite
def prox_case(draw):
    # allow_subnormal=False: XLA flushes denormals to zero (FTZ), which is
    # a hardware semantic, not a prox property
    p = draw(st.integers(1, 64))
    v = draw(st.lists(st.floats(-10, 10, allow_nan=False, allow_subnormal=False),
                      min_size=p, max_size=p))
    raw = draw(st.lists(st.floats(0, 5, allow_nan=False, allow_subnormal=False),
                        min_size=p, max_size=p))
    lam = np.sort(np.asarray(raw))[::-1]
    return np.asarray(v), lam


def _pad_prox_case(v, lam, width=64):
    """Zero-pad (v, λ) to a fixed width so every drawn case shares ONE jit
    shape (a fresh compile per random size turns the property test into a
    compile benchmark).  Exact: padded v entries are 0 with λ = 0, so they
    sort to the tail, pool only into non-positive blocks, and emit 0."""
    pad = width - len(v)
    return (np.concatenate([v, np.zeros(pad)]),
            np.concatenate([lam, np.zeros(pad)]))


@settings(max_examples=200, deadline=None)
@given(prox_case())
def test_prox_matches_numpy_pava(case):
    v, lam = case
    p = len(v)
    vp, lamp = _pad_prox_case(v, lam)
    got = np.asarray(prox_sorted_l1(jnp.asarray(vp), jnp.asarray(lamp)))[:p]
    want = numpy_pava_prox(v, lam)
    np.testing.assert_allclose(got, want, atol=1e-10)


@settings(max_examples=100, deadline=None)
@given(prox_case())
def test_prox_optimality_certificate(case):
    """v − prox(v) ∈ ∂J(prox(v); λ)  — Theorem 1 as a prox certificate."""
    v, lam = case
    p = len(v)
    vp, lamp = _pad_prox_case(v, lam)
    x = np.asarray(prox_sorted_l1(jnp.asarray(vp), jnp.asarray(lamp)))[:p]
    assert in_subdifferential(v - x, x, lam, atol=1e-8)


def test_prox_certificate_accepts_rounding_level_gradient():
    """λ ≡ 0 makes the prox the identity, but pooling the tie
    [0.4, 0.4, 0.4] rounds its mean up: v − x = −5.6e-17 has the wrong
    sign on an active coefficient.  It lies within tol of the admissible
    0, so the certificate accepts it."""
    v, lam = np.array([0.4, 0.4, 0.4]), np.zeros(3)
    vp, lamp = _pad_prox_case(v, lam)
    x = np.asarray(prox_sorted_l1(jnp.asarray(vp), jnp.asarray(lamp)))[:3]
    assert in_subdifferential(v - x, x, lam, atol=1e-8)


def test_prox_is_projection_when_lam_zero(rng):
    v = rng.normal(size=50)
    lam = np.zeros(50)
    np.testing.assert_allclose(np.asarray(prox_sorted_l1(jnp.asarray(v), jnp.asarray(lam))), v)


def test_prox_shrinks_toward_zero(rng):
    v = rng.normal(size=100) * 3
    lam = np.sort(np.abs(rng.normal(size=100)))[::-1]
    x = np.asarray(prox_sorted_l1(jnp.asarray(v), jnp.asarray(lam)))
    assert np.all(np.abs(x) <= np.abs(v) + 1e-12)
    assert np.all(np.sign(x[x != 0]) == np.sign(v[x != 0]))


def test_isotonic_decreasing_is_monotone(rng):
    # sizes from a fixed palette: each new length recompiles the lax loop,
    # so free-form random sizes turn this into a compile-time benchmark
    for p in (1, 2, 17, 200) * 8:
        y = rng.normal(size=p)
        x = np.asarray(isotonic_decreasing(jnp.asarray(y)))
        assert np.all(np.diff(x) <= 1e-12)


def test_isotonic_parallel_and_minimax_match_stack(rng):
    """The engine's sweep-merging form and the minimax form are exact."""
    from repro.core import isotonic_decreasing_parallel
    from repro.core.sorted_l1 import isotonic_decreasing_minimax

    iso_par = jax.jit(isotonic_decreasing_parallel)
    iso_mm = jax.jit(isotonic_decreasing_minimax)
    for trial in range(24):
        p = (1, 2, 17, 200)[trial % 4]
        kind = rng.integers(0, 3)
        if kind == 0:
            y = np.sort(rng.normal(size=p))          # fully violating
        elif kind == 1:
            y = rng.integers(-3, 3, size=p).astype(float)  # heavy ties
        else:
            y = rng.normal(size=p) * 3
        want = np.asarray(isotonic_decreasing(jnp.asarray(y)))
        np.testing.assert_allclose(np.asarray(iso_par(jnp.asarray(y))), want,
                                   atol=1e-10)
        if p == 200:  # minimax builds p×p intermediates; one shape suffices
            np.testing.assert_allclose(np.asarray(iso_mm(jnp.asarray(y))),
                                       want, atol=1e-10)


def test_isotonic_parallel_f32_matches_stack_at_path_width():
    """In f32 at the paper's width the sweep-merging form keeps the stack
    form's accuracy: block sums come from a prefix sum that restarts at each
    block, not from differences of one global cumsum (≈1e-3 off here)."""
    from repro.core import isotonic_decreasing_parallel

    # its own stream: draws from the session rng would shift later tests'
    rng = np.random.default_rng(12)
    p = 20000
    lam = np.asarray(bh_sequence(p, q=0.1), np.float64)
    w = np.sort(np.abs(rng.normal(size=p)) * 3)[::-1] - 0.9 * lam
    w32 = jnp.asarray(w, jnp.float32)
    want = np.asarray(isotonic_decreasing(w32))
    got = np.asarray(jax.jit(isotonic_decreasing_parallel)(w32))
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_array_equal(got > 0, want > 0)


def test_prox_pool_method_matches_stack_f32():
    """The Pallas pooling kernel runs the stack PAVA's arithmetic: in f32
    the ``pool`` prox returns the ``stack`` prox's values and norm."""
    from repro.core.sorted_l1 import prox_sorted_l1_with_norm

    rng = np.random.default_rng(5)
    p = 512
    lam = jnp.asarray(bh_sequence(p, q=0.1), jnp.float32)
    for scale in (0.5, 3.0):
        v = jnp.asarray(rng.normal(size=p) * scale, jnp.float32)
        x_s, j_s = prox_sorted_l1_with_norm(v, lam, method="stack")
        x_p, j_p = prox_sorted_l1_with_norm(v, lam, method="pool")
        assert x_p.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(x_p), np.asarray(x_s))
        np.testing.assert_array_equal(np.asarray(j_p), np.asarray(j_s))


def test_norm_properties(rng):
    p = 64
    lam = np.sort(np.abs(rng.normal(size=p)))[::-1]
    a = rng.normal(size=p)
    b = rng.normal(size=p)
    Ja = float(sorted_l1_norm(jnp.asarray(a), jnp.asarray(lam)))
    Jb = float(sorted_l1_norm(jnp.asarray(b), jnp.asarray(lam)))
    Jab = float(sorted_l1_norm(jnp.asarray(a + b), jnp.asarray(lam)))
    assert Jab <= Ja + Jb + 1e-9  # triangle inequality
    J2a = float(sorted_l1_norm(jnp.asarray(2 * a), jnp.asarray(lam)))
    np.testing.assert_allclose(J2a, 2 * Ja, rtol=1e-10)


def test_dual_gauge_certifies_zero_solution(rng):
    """gauge(g/σ) ≤ 1 ⇔ g ∈ ∂J(0; σλ): σ(1) is the smallest σ giving β̂=0."""
    p = 40
    lam = np.sort(np.abs(rng.normal(size=p)))[::-1] + 0.1
    g = rng.normal(size=p)
    sigma = float(dual_sorted_l1_gauge(jnp.asarray(g), jnp.asarray(lam)))
    assert in_subdifferential(g, np.zeros(p), sigma * lam * (1 + 1e-9))
    # exact test: the default tolerance (1e-6 + 1e-6·max λ) is as wide as
    # the 1e-6 shrink, so with it the answer would depend on the draw
    assert not in_subdifferential(g, np.zeros(p), sigma * lam * (1 - 1e-6),
                                  rtol=0.0, atol=0.0)
