"""KKT / subdifferential checks for SLOPE (paper Theorem 1 + §2.2.4).

Two flavours:

* :func:`in_subdifferential` — exact Theorem-1 membership test for
  ``g ∈ ∂J(β; λ)`` (cluster-wise cumsum + equality conditions).  Used by
  tests to certify prox correctness and solver optimality.
* :func:`kkt_violations` — the operational check both path algorithms use:
  run Proposition 1 (Algorithm 1 with the current full gradient); any
  predictor the rule keeps that is outside the working set E is a violation
  and must be added to E (Algorithms 3 and 4).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .screening import screen_k, screen_masked

__all__ = [
    "in_subdifferential",
    "kkt_violations",
    "kkt_violations_masked",
    "kkt_optimal",
]


def in_subdifferential(g, beta, lam, *, rtol: float = 1e-6, atol: float = 1e-6) -> bool:
    """Exact Theorem-1 test: is g ∈ ∂J(β; λ)?  Host-side (NumPy).

    Clusters A_i of equal |β| are checked independently (the subdifferential
    factorises over clusters); within a cluster the conditions are
      cumsum(|g_A|↓ − λ_A) ≤ 0, and, if the cluster is non-zero,
      Σ_{j∈A}(|g_j| − λ_j) = 0 together with sign(g_j) = sign(β_j).
    λ slots are allocated to clusters by the global magnitude order of β
    (inactive cluster gets the tail), matching Theorem 1's R(s) indexing.
    """
    g = np.asarray(g, dtype=np.float64).ravel()
    beta = np.asarray(beta, dtype=np.float64).ravel()
    lam = np.asarray(lam, dtype=np.float64).ravel()
    scale = max(1.0, float(np.max(lam, initial=0.0)))
    tol = atol + rtol * scale

    mag = np.abs(beta)
    order = np.argsort(-mag, kind="stable")
    # walk clusters in decreasing |β|; slot λ entries in order.  Clusters
    # are EXACT equality classes (paper eq. (2)) — prox/FISTA pool ties and
    # zeros exactly, and any absolute merge tolerance would misclassify
    # tiny-but-nonzero coefficients into the zero cluster.
    pos = 0
    i = 0
    while i < len(order):
        j = i
        while j < len(order) and mag[order[j]] == mag[order[i]]:
            j += 1
        members = order[i:j]
        lam_slot = lam[pos: pos + len(members)]
        gs = g[members]
        active = mag[members[0]] > 0
        if active and np.any((np.sign(gs) != np.sign(beta[members]))
                             & (np.abs(gs) > tol)):
            # sign condition binds where β ≠ 0 AND |g| exceeds the
            # tolerance (g_j = 0 is always admissible — e.g. λ ≡ 0 gives
            # ∂J = {0} regardless of signs — and a g_j within tol of 0 is
            # within tol of an admissible value)
            return False
        c = np.sort(np.abs(gs))[::-1]
        if np.any(np.cumsum(c - lam_slot) > tol):
            return False
        if active and abs(np.sum(np.abs(gs) - lam_slot)) > tol * max(1, len(members)):
            return False
        pos = j
        i = j
    return True


def kkt_optimal(grad, beta, lam, **kw) -> bool:
    """Stationarity (7): 0 ∈ ∇f(β) + ∂J(β;λ)  ⇔  −∇f(β) ∈ ∂J(β;λ)."""
    return in_subdifferential(-np.asarray(grad), beta, lam, **kw)


@functools.partial(jax.jit, static_argnames=("tol",))
def kkt_violations_masked(grad, lam, ever_mask, subset_mask, *, tol: float = 1e-6):
    """Device-resident form of :func:`kkt_violations` (no dynamic shapes).

    Same semantics — Proposition 1 over ``subset_mask | ever_mask``, minus
    the working set — but expressed through :func:`screen_masked` so the
    whole check stays inside one jit scope (the path engine's ``lax.scan``
    step).  ``grad`` is the *flattened* coefficient gradient; both masks are
    coordinate-space booleans of the same length.
    """
    grad = jnp.ravel(grad)
    ever_mask = jnp.ravel(ever_mask).astype(bool)
    consider = jnp.ravel(subset_mask).astype(bool) | ever_mask
    mag = jnp.abs(grad)
    shift = jnp.full(grad.shape, -tol, mag.dtype)
    keep, _ = screen_masked(mag, jnp.ravel(lam), consider, shift)
    return keep & ~ever_mask


def kkt_violations(grad, lam, ever_mask, *, subset_mask=None, tol: float = 1e-6):
    """Operational violation check used by Algorithms 3 and 4.

    Runs Proposition 1 on |grad| restricted to ``subset_mask`` (default: the
    full predictor set) and returns the boolean mask of predictors that the
    rule keeps but which are *not* in the working set ``ever_mask``.

    Host-side orchestration (the path drivers are NumPy-driven); the scan
    itself is the jit'd :func:`repro.core.screening.screen_k`.
    """
    grad = np.asarray(grad)
    p = grad.size
    ever_mask = np.asarray(ever_mask, dtype=bool).ravel()
    if subset_mask is None:
        subset_mask = np.ones(p, dtype=bool)
    else:
        subset_mask = np.asarray(subset_mask, dtype=bool).ravel()
    consider = subset_mask | ever_mask
    idx = np.nonzero(consider)[0]
    mag = np.abs(grad.ravel())[idx]
    order = np.argsort(-mag, kind="stable")
    # pad to the full length so screen_k sees ONE shape per problem (the
    # padded tail c−λ = −1e12 can never host the rightmost argmax) — keeps
    # the KKT check recompile-free along the whole path
    c_pad = np.full(p, -1e12)
    c_pad[: len(idx)] = mag[order] - tol
    lam_pad = np.zeros(p)
    lam_pad[: len(idx)] = np.asarray(lam)[: len(idx)]
    k = int(screen_k(jnp.asarray(c_pad), jnp.asarray(lam_pad)))
    k = min(k, len(idx))
    kept = idx[order[:k]]
    viol = np.zeros(p, dtype=bool)
    viol[kept] = True
    viol &= ~ever_mask
    return viol
