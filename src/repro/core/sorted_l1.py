"""Sorted-ℓ1 norm, its proximal operator and dual gauge.

This is the mathematical heart of SLOPE (paper §1, eq. (1)):

    J(β; λ) = Σ_j λ_j |β|_(j),   λ_1 ≥ … ≥ λ_p ≥ 0.

The prox follows the FastProxSL1 construction (Bogdan et al. 2015, used by
the paper's reference implementation): sort |v| in decreasing order, subtract
λ, project onto the non-increasing cone (PAVA), clip at zero, undo the sort
and restore signs.  The PAVA pooling is implemented with a fixed-shape stack
driven by ``lax.fori_loop``/``lax.while_loop`` so it jits with static shapes;
``repro.kernels.prox_sorted_l1`` provides the blocked Pallas version of the
pooling loop and ``repro.kernels.ref`` the pure-jnp oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "sorted_l1_norm",
    "prox_sorted_l1",
    "prox_sorted_l1_with_norm",
    "dual_sorted_l1_gauge",
    "isotonic_decreasing",
    "isotonic_decreasing_minimax",
    "isotonic_decreasing_parallel",
    "isotonic_decreasing_pool",
    "pool_prox_applies",
    "clusters",
]


def sorted_l1_norm(beta: jax.Array, lam: jax.Array) -> jax.Array:
    """J(β; λ) = Σ λ_j |β|_(j) with |β|_(1) ≥ |β|_(2) ≥ …"""
    beta = jnp.ravel(beta)
    mag = jnp.sort(jnp.abs(beta))[::-1]
    return jnp.dot(mag, lam.astype(mag.dtype), precision=lax.Precision.HIGHEST)


def isotonic_decreasing(y: jax.Array) -> jax.Array:
    """Project ``y`` onto the non-increasing cone {w : w_1 ≥ … ≥ w_p}.

    Pool-adjacent-violators with an explicit block stack.  O(p): every
    element is pushed once and merged at most once.
    """
    p = y.shape[0]
    dtype = y.dtype

    def push(i, state):
        sums, counts, top = state
        sums = sums.at[top].set(y[i])
        counts = counts.at[top].set(1)

        def violated(s):
            sm, ct, t = s
            # mean(block t) >= mean(block t-1): pool them.
            return (t > 0) & (sm[t] * ct[t - 1] >= sm[t - 1] * ct[t])

        def pool(s):
            sm, ct, t = s
            sm = sm.at[t - 1].add(sm[t])
            ct = ct.at[t - 1].add(ct[t])
            return sm, ct, t - 1

        sums, counts, top = lax.while_loop(violated, pool, (sums, counts, top))
        return sums, counts, top + 1

    sums0 = jnp.zeros((p,), dtype)
    counts0 = jnp.zeros((p,), jnp.int32)
    sums, counts, top = lax.fori_loop(0, p, push, (sums0, counts0, 0))

    # Expand block means back to element positions.  Block j covers
    # positions [cumsum(counts)[j-1], cumsum(counts)[j]).
    ends = jnp.cumsum(counts)
    idx = jnp.searchsorted(ends, jnp.arange(p, dtype=ends.dtype), side="right")
    safe_counts = jnp.maximum(counts, 1)
    means = sums / safe_counts.astype(dtype)
    return means[idx]


def _segmented_add(a, b):
    """Associative combine of a prefix sum that restarts at flagged
    positions: ``(flag, value)`` pairs, right operand's flag resets."""
    fa, va = a
    fb, vb = b
    return fa | fb, jnp.where(fb, vb, va + vb)


def isotonic_decreasing_parallel(y: jax.Array) -> jax.Array:
    """Project onto the non-increasing cone by parallel block merging.

    Each sweep merges EVERY violating adjacent block pair at once — safe
    because a violating adjacent pair must share a block in the optimum, so
    simultaneous merging keeps the partition a refinement of the optimal
    one (the classic PAVA invariant).  A sweep is ~10 dense vectorized ops
    (segment sums + a cumsum), with no data-dependent inner loop: unlike
    the sequential stack PAVA this form vmaps with near-perfect batch
    efficiency, which is why the batched device engine uses it.  Typical
    sweep counts are O(log p); worst case O(p) sweeps (still exact).
    """
    p = y.shape[0]
    dtype = y.dtype
    idx = jnp.arange(p)
    if dtype == jnp.float64:
        S = jnp.concatenate([jnp.zeros((1,), dtype), jnp.cumsum(y)])

    def block_means(start):
        # scatter-free segment means: a block is [begin, end) where begin is
        # the last start flag at-or-before i (cummax) and end the first one
        # after i (reverse cummin) — scatters are pathological under vmap on
        # CPU, cumulative scans are not
        begin = lax.cummax(jnp.where(start, idx, 0))
        nxt = lax.cummin(jnp.where(start, idx, p), reverse=True)
        end = jnp.concatenate([nxt[1:], jnp.full((1,), p, idx.dtype)])
        # block sums from a prefix sum that restarts at every block start:
        # differences of ONE global cumsum lose everything below eps·|Σy|,
        # which in f32 turns zero-clipped blocks into spurious nonzeros.
        # In f64 that loss sits below every tolerance of the solver, and
        # f64 keeps the plain global form.
        if dtype == jnp.float64:
            return (S[end] - S[begin]) / (end - begin).astype(dtype)
        _, seg = lax.associative_scan(_segmented_add, (start, y))
        return seg[end - 1] / (end - begin).astype(dtype)

    def violations(start):
        mean = block_means(start)
        prev = jnp.roll(mean, 1)
        # pool when mean(block) ≥ mean(previous block) — ties merge, which
        # leaves the projected values unchanged (equal means pool to equal)
        return start & (mean >= prev) & (idx > 0)

    def cond(state):
        start, viol = state
        return viol.any()

    def body(state):
        start, viol = state
        start = start & ~viol
        return start, violations(start)

    start0 = jnp.ones((p,), bool)
    start, _ = lax.while_loop(cond, body, (start0, violations(start0)))
    return block_means(start)


def isotonic_decreasing_minimax(y: jax.Array) -> jax.Array:
    """Project onto the non-increasing cone via the minimax formula
    (Robertson et al.):  x_i = min_{a ≤ i} max_{b ≥ i} mean(y[a..b]).

    O(p²) work but O(log p) depth with NO sequential data dependence.
    Reference/benchmark alternative: the batched engine uses the
    sweep-merging form above (cheaper on CPU); this closed form is kept as
    an independently-derived oracle and for accelerator experiments, where
    its depth-parallelism may win despite the p × p intermediates.
    """
    p = y.shape[0]
    dtype = y.dtype
    S = jnp.concatenate([jnp.zeros((1,), dtype), jnp.cumsum(y)])
    a = jnp.arange(p)[:, None]
    b = jnp.arange(p)[None, :]
    valid = b >= a
    means = (S[b + 1] - S[a]) / jnp.where(valid, b - a + 1, 1).astype(dtype)
    means = jnp.where(valid, means, -jnp.inf)
    # R[a, i] = max_{b ≥ i} mean(y[a..b]);  x_i = min_{a ≤ i} R[a, i]
    R = lax.cummax(means, axis=1, reverse=True)
    R = jnp.where(valid, R, jnp.inf)
    return jnp.diagonal(lax.cummin(R, axis=0))


@jax.custom_batching.custom_vmap
def isotonic_decreasing_pool(y: jax.Array) -> jax.Array:
    """:func:`isotonic_decreasing` clipped at 0, by the Pallas kernel
    (:func:`repro.kernels.ops.prox_pool`): the same stack PAVA, run on the
    TPU core's scalar unit out of its scalar memory, over the first q
    elements only (q = 1 + the last index where ``y`` > 0; past it the
    result is 0).  f32 only.  Under ``vmap`` the rows go through the
    kernel one after another, each with its own q."""
    from ..kernels.ops import prox_pool  # the kernels import this module

    return prox_pool(y)


@isotonic_decreasing_pool.def_vmap
def _pool_rows(axis_size, in_batched, y):
    # the kernel holds one whole row in scalar memory, and Mosaic takes no
    # batched block there; a row at a time also keeps the stack PAVA's
    # O(p) cost where the sweep-merging form needs O(p) sweeps (one
    # cluster spanning most coordinates, as SLOPE gives near σ_max on an
    # equicorrelated design)
    (batched,) = in_batched
    if not batched:
        return isotonic_decreasing_pool(y), False
    return lax.map(isotonic_decreasing_pool, y), True


def pool_prox_applies(dtype) -> bool:
    """Whether solves of this dtype take the ``pool`` prox: on a TPU, in
    f32.  Elsewhere the kernel would run in the Pallas interpreter, and it
    computes in f32 only."""
    return (jax.default_backend() == "tpu"
            and jnp.dtype(dtype) == jnp.float32)


@functools.partial(jax.jit, static_argnames=("method",))
def prox_sorted_l1_with_norm(v: jax.Array, lam: jax.Array, *,
                             method: str = "stack"):
    """(prox_{J(·;λ)}(v), J(prox; λ)) in one pass.

    The prox works on |v| sorted decreasing, and its sorted output IS the
    sorted magnitude vector of the result — so J(x; λ) = ⟨x_sorted, λ⟩ falls
    out for free, saving the solver a per-iteration sort.
    """
    return _prox_sorted_l1_with_norm_live(v, lam, method=method)[:2]


@functools.partial(jax.jit, static_argnames=("method",))
def _prox_sorted_l1_with_norm_live(v, lam, *, method):
    """:func:`prox_sorted_l1_with_norm` and the live bound q of its sorted
    input (:func:`repro.kernels.prox_sorted_l1.live_bound`), whatever the
    ``method``: the pool kernel loops over the first q elements only."""
    from ..kernels.prox_sorted_l1 import live_bound

    shape = v.shape
    v = jnp.ravel(v)
    lam = jnp.ravel(lam).astype(v.dtype)
    sign = jnp.sign(v)
    mag = jnp.abs(v)
    order = jnp.argsort(-mag)  # decreasing |v|
    w = mag[order] - lam
    iso = {
        "stack": isotonic_decreasing,
        "parallel": isotonic_decreasing_parallel,
        "minimax": isotonic_decreasing_minimax,
        "pool": isotonic_decreasing_pool,
    }[method](w)
    x_sorted = jnp.maximum(iso, 0)
    x = jnp.zeros_like(v).at[order].set(x_sorted)
    return ((sign * x).reshape(shape),
            jnp.dot(x_sorted, lam, precision=lax.Precision.HIGHEST),
            live_bound(w))


@functools.partial(jax.jit, static_argnames=("method",))
def prox_sorted_l1(v: jax.Array, lam: jax.Array, *, method: str = "stack") -> jax.Array:
    """prox_{J(·;λ)}(v) = argmin_x ½‖x − v‖² + J(x; λ).

    ``method='stack'`` is the lax.while_loop PAVA here; ``method='parallel'``
    the sweep-merging form (:func:`isotonic_decreasing_parallel`) the
    batched device engine uses; ``method='minimax'`` the O(p²)-work
    depth-parallel form; ``method='pool'`` the stack PAVA as a Pallas
    kernel (:func:`isotonic_decreasing_pool`, f32).
    """
    return prox_sorted_l1_with_norm(v, lam, method=method)[0]


def dual_sorted_l1_gauge(g: jax.Array, lam: jax.Array) -> jax.Array:
    """Gauge of the dual ball of J: max_i cumsum(|g|↓)_i / cumsum(λ)_i.

    ``gauge ≤ 1``  ⇔  g ∈ ∂J(0; λ)  (Theorem 1, case β = 0).  The path
    start σ(1) (paper §3.1.2) is exactly this gauge evaluated at ∇f(0).
    """
    g = jnp.ravel(g)
    mag = jnp.sort(jnp.abs(g))[::-1]
    num = jnp.cumsum(mag)
    den = jnp.cumsum(lam.astype(mag.dtype))
    den = jnp.where(den <= 0, jnp.inf, den)
    return jnp.max(num / den)


def clusters(beta: jax.Array, *, atol: float = 0.0):
    """Cluster indices A_i of equal-magnitude coefficients (paper eq. (2)).

    Host-side helper (NumPy semantics) used by tests and the KKT check;
    returns a list of index arrays, magnitudes strictly decreasing.
    """
    import numpy as np

    beta = np.asarray(beta).ravel()
    mag = np.abs(beta)
    out = []
    for m in np.unique(mag)[::-1]:
        members = np.nonzero(np.abs(mag - m) <= atol)[0]
        out.append(members)
    return out
