"""Penalty sequences and path parameterization (paper §3.1.1–§3.1.2).

All sequences are returned *unscaled*; the path multiplies them by σ, with
σ(1) chosen so the first path point gives the all-zero solution:

    σ(1) = max( cumsum(|∇f(0)|↓) ⊘ cumsum(λ) )

which is exactly the dual gauge of ∇f(0) (see sorted_l1.dual_sorted_l1_gauge).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtri

from .sorted_l1 import dual_sorted_l1_gauge

__all__ = [
    "bh_sequence",
    "gaussian_sequence",
    "oscar_sequence",
    "lasso_sequence",
    "path_start_sigma",
    "sigma_grid",
    "float_dtype",
]


def float_dtype(dtype=None) -> np.dtype:
    """The float dtype JAX computes an operand of ``dtype`` in: f32 stays
    f32, f64 is f64 only under ``jax_enable_x64``, integers promote, and
    None is JAX's default float.  λ and σ grids are built in it, so no
    f64 enters an f32 program."""
    dtype = np.float64 if dtype is None else dtype
    return np.dtype(jax.dtypes.canonicalize_dtype(
        np.result_type(dtype, np.float32)))


def bh_sequence(p: int, q: float = 0.1, dtype=None) -> jax.Array:
    """Benjamini–Hochberg sequence: λ_i = Φ⁻¹(1 − q·i/(2p))."""
    i = jnp.arange(1, p + 1, dtype=float_dtype(dtype))
    lam = ndtri(1 - q * i / (2 * p))
    return jnp.maximum(lam, 0)


def gaussian_sequence(p: int, n: int, q: float = 0.1, dtype=None):
    """Gaussian-adjusted BH sequence (paper §3.1.1).

    λG_1 = λBH_1;  λG_i = λBH_i · sqrt(1 + Σ_{j<i}(λG_j)² / (n − i)),
    truncated to the previous value once the sequence starts increasing
    (and undefined at i = n, handled by the same truncation).
    Host-side NumPy: the recursion is inherently sequential and tiny.
    """
    bh = np.asarray(bh_sequence(p, q, dtype=dtype), np.float64)
    lam = np.empty(p, dtype=np.float64)
    lam[0] = bh[0]
    acc = 0.0
    for i in range(1, p):
        acc += lam[i - 1] ** 2
        denom = n - (i + 1)  # 1-based i in the paper
        if denom <= 0:
            lam[i:] = lam[i - 1]
            break
        cand = bh[i] * np.sqrt(1 + acc / denom)
        if cand > lam[i - 1]:
            lam[i:] = lam[i - 1]
            break
        lam[i] = cand
    return jnp.asarray(lam, float_dtype(dtype))


def oscar_sequence(p: int, q: float = 0.1, dtype=None) -> jax.Array:
    """OSCAR linear sequence λ_i = q(p − i) + 1 (paper §3.1.1, single-param)."""
    i = jnp.arange(1, p + 1, dtype=float_dtype(dtype))
    return q * (p - i) + 1


def lasso_sequence(p: int, dtype=None) -> jax.Array:
    """Constant sequence — SLOPE degenerates to the lasso (Proposition 3)."""
    return jnp.ones((p,), dtype=float_dtype(dtype))


def path_start_sigma(grad0: jax.Array, lam: jax.Array) -> jax.Array:
    """σ(1): smallest σ with β̂ = 0, i.e. max(cumsum(|∇f(0)|↓) ⊘ cumsum(σλ)) = 1."""
    return dual_sorted_l1_gauge(grad0, lam)


def sigma_grid(sigma_max: float, *, length: int = 100, ratio: float | None = None,
               n: int | None = None, p: int | None = None,
               dtype=None) -> np.ndarray:
    """Geometric grid σ(1) … σ(l), in :func:`float_dtype` of ``dtype``.
    Paper: σ(l) = t·σ(1), t = 1e-2 if n < p else 1e-4 (§3.1.2)."""
    if ratio is None:
        if n is None or p is None:
            ratio = 1e-2
        else:
            ratio = 1e-2 if n < p else 1e-4
    grid = sigma_max * np.logspace(0, np.log10(ratio), num=length)
    return grid.astype(float_dtype(dtype))
