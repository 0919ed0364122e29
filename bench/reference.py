"""The plain reference: data recipes and the SLOPE optimality test.

Written from the paper (Larsson, Bogdan & Wallin 2020, Theorem 1 and
§3.1) in NumPy float64, and independent of the system under test: it
imports nothing from ``src/`` and takes nothing the program computed
except the coefficients it judges.

* designs: equicorrelated (factor form) and AR(1) chains, columns centred
  and scaled to unit norm, ``y = Xβ + ε`` centred (paper §3.1), and the
  same problem in another order (:func:`orient`);
* the BH penalty sequence and the σ grid (σ(1) from the dual gauge of the
  null gradient, geometric decay);
* the KKT level of one path step: the smallest ``rtol`` at which
  ``−∇f(β) ∈ ∂J(β; σλ)`` holds with tolerance ``rtol · max(1, max σλ)``;
* the K-fold split of a CV request (contiguous validation folds of
  ⌊n/K⌋ rows, remainder rows always in training).
"""

from __future__ import annotations

import numpy as np
from scipy.special import ndtri


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """The generator of one stream of one seed (any non-negative int)."""
    return np.random.default_rng([int(seed), *map(int, stream)])


def design(rng, n: int, p: int, kind: str, rho: float) -> np.ndarray:
    """An (n, p) float32 design with centred, unit-norm columns."""
    if kind == "equi":
        z = rng.standard_normal((n, 1), dtype=np.float32)
        X = rng.standard_normal((n, p), dtype=np.float32)
        X *= np.float32(np.sqrt(1.0 - rho))
        X += np.float32(np.sqrt(rho)) * z
    elif kind == "ar":
        # built column by column as rows of the transpose (contiguous)
        Xt = rng.standard_normal((p, n), dtype=np.float32)
        for j in range(1, p):
            Xt[j] += np.float32(rho) * Xt[j - 1]
        X = np.ascontiguousarray(Xt.T)
    else:
        raise ValueError(f"unknown design {kind!r}")
    X -= X.mean(axis=0, dtype=np.float64).astype(np.float32)
    norms = np.sqrt(np.einsum("ij,ij->j", X, X, dtype=np.float64))
    norms[norms == 0] = 1.0
    X *= (1.0 / norms).astype(np.float32)
    return X


def orient(rng, X: np.ndarray, y: np.ndarray, rows: bool = True):
    """The same problem in another order: columns permuted and their signs
    flipped, rows permuted with y where ``rows``.  SLOPE's solution path
    follows exactly (the sorted-ℓ1 norm and the squared loss do not see
    the order), so the work stays what it was."""
    p = X.shape[1]
    cols = rng.permutation(p)
    signs = rng.choice(np.array([-1.0, 1.0], np.float32), size=p)
    if rows:
        r = rng.permutation(X.shape[0])
        X, y = X[r], y[r]
    return np.ascontiguousarray(X[:, cols] * signs), np.ascontiguousarray(y)


def regression(rng, X: np.ndarray, k: int, noise: float) -> np.ndarray:
    """``y = Xβ + ε`` (first k coefficients ±2), centred, float32."""
    beta = np.zeros(X.shape[1])
    beta[:k] = rng.choice([-2.0, 2.0], size=k)
    y = X[:, :k].astype(np.float64) @ beta[:k] + noise * rng.standard_normal(
        X.shape[0])
    return (y - y.mean()).astype(np.float32)


def bh_lambda(p: int, q: float) -> np.ndarray:
    """Benjamini–Hochberg sequence λ_i = Φ⁻¹(1 − q·i/(2p)), clipped at 0."""
    i = np.arange(1, p + 1, dtype=np.float64)
    return np.maximum(ndtri(1.0 - q * i / (2.0 * p)), 0.0)


def sigma_grid(X, y, lam, length: int, ratio: float) -> np.ndarray:
    """σ(1) = max_k cumsum(|Xᵀy|↓)_k / cumsum(λ)_k, then ``length`` points
    decaying geometrically to ``ratio · σ(1)``."""
    g = np.sort(np.abs(np.asarray(X, np.float64).T
                       @ np.asarray(y, np.float64)))[::-1]
    s1 = float(np.max(np.cumsum(g) / np.maximum(np.cumsum(lam), 1e-300)))
    return s1 * np.logspace(0.0, np.log10(ratio), num=length)


def top_ratio(ratio: float, full_length: int, length: int) -> float:
    """The ratio that makes a ``length``-point grid the top of the
    ``full_length``-point grid with ``ratio`` (the same spacing)."""
    return ratio ** ((length - 1) / (full_length - 1))


def ols_gradient(X64, y64, beta) -> np.ndarray:
    """∇ of ½‖Xβ − y‖² at β (float64)."""
    b = np.asarray(beta, np.float64).ravel()
    nz = np.flatnonzero(b)
    r = X64[:, nz] @ b[nz] - y64
    return X64.T @ r


def kkt_level(grad, beta, lam_sigma) -> float:
    """The smallest rtol at which −grad ∈ ∂J(β; λσ) (paper Theorem 1).

    Clusters are the exact equality classes of |β|, taken in decreasing
    order and given λ slots in that order (the zero cluster takes the
    tail).  Per cluster: the partial sums of |g|↓ − λ may not exceed 0; a
    non-zero cluster's sum must equal 0 (held per member) and its signs
    must agree with β's.  The worst excess over ``max(1, max λσ)``.
    """
    u = -np.asarray(grad, np.float64).ravel()
    beta = np.asarray(beta, np.float64).ravel()
    lam = np.asarray(lam_sigma, np.float64).ravel()
    scale = max(1.0, float(np.max(lam, initial=0.0)))
    mag = np.abs(beta)
    order = np.argsort(-mag, kind="stable")
    smag = mag[order]
    # cluster boundaries: where the sorted magnitude changes
    starts = np.flatnonzero(np.r_[True, smag[1:] != smag[:-1]])
    ends = np.r_[starts[1:], len(smag)]
    worst = 0.0
    for a, b in zip(starts, ends):
        members = order[a:b]
        us = u[members]
        slot = lam[a:b]
        c = np.sort(np.abs(us))[::-1]
        worst = max(worst, float(np.max(np.cumsum(c - slot))))
        if smag[a] > 0:
            wrong = np.sign(us) != np.sign(beta[members])
            if wrong.any():
                worst = max(worst, float(np.max(np.abs(us[wrong]))))
            worst = max(worst, abs(float(np.sum(c - slot))) / max(1, b - a))
    return worst / scale


def path_levels(X64, y64, betas, sigmas, lam) -> np.ndarray:
    """KKT level of every step of one OLS path."""
    return np.array([kkt_level(ols_gradient(X64, y64, b), b, s * lam)
                     for b, s in zip(betas, sigmas)])


def cv_folds(n: int, k: int):
    """(train, validation) row indices of each of k contiguous folds."""
    fold = n // k
    vals = [np.arange(i * fold, (i + 1) * fold) for i in range(k)]
    return [np.setdiff1d(np.arange(n), v) for v in vals], vals
