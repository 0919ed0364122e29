"""GLM objectives used in the paper's experiments (§3.2.3): OLS, logistic,
Poisson and multinomial regression.

Every family exposes the loss through its linear predictor z = Xβ:

    f(β) = Σ_i ℓ(z_i, y_i),       ∇f(β) = Xᵀ r(z, y),   r = ∂ℓ/∂z

so the solver and the screening rule only ever need ``value``/``residual``
plus the two matvecs (which are what the Pallas kernels accelerate).
Conventions follow the R SLOPE package: unnormalised sums, centred y for
OLS, y ∈ {0,1} for logistic, y ∈ ℕ for Poisson, integer classes for
multinomial (β ∈ R^{p×m}, penalty on the flattened coefficients).

Per-row sample weights generalize every family without touching X:

    f_w(β) = Σ_i w_i ℓ(z_i, y_i),   ∇f_w(β) = Xᵀ (w ⊙ r(z, y))

which is exactly the loss of the row-duplicated problem when w is an
integer count vector — the representation the resampling engine uses to
solve B bootstrap replicates against ONE shared X.  ``weights=None``
keeps the original (unweighted) code path byte-for-byte.  Zero-weight
rows are guarded with ``jnp.where`` so a w=0 row can never leak a
non-finite z into the sums (0·inf would otherwise NaN the member).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

__all__ = ["Family", "ols", "logistic", "poisson", "multinomial", "get_family",
           "matmul"]


def matmul(a, b):
    """``a @ b`` at full precision.  A TPU runs an f32 product as one bf16
    pass by default, which leaves a path solver's gradient ~1e-3 off; on
    the CPU the full product is what runs anyway."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _row_broadcast(w, a):
    """Broadcast per-row weights (n,) against a row-shaped array: (n,) for
    single-class families, (n, 1) against the (n, m) multinomial block."""
    return w if a.ndim == 1 else w[:, None]


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    value: Callable  # (z, y) -> scalar loss
    residual: Callable  # (z, y) -> dloss/dz, same shape as z
    hess_bound: float | None  # sup of d²ℓ/dz² (None: use backtracking)
    n_classes: int = 1  # >1 → β is (p, m)
    row_value: Callable | None = None  # (z, y) -> (n,) per-row losses

    def weighted_value(self, z, y, weights):
        """Σ wᵢ ℓ(zᵢ, yᵢ) with zero-weight rows exactly inert (a w=0 row
        contributes an exact 0 even when its z is non-finite)."""
        rv = self.row_value(z, y)
        return jnp.sum(jnp.where(weights == 0, jnp.zeros((), rv.dtype),
                                 weights * rv))

    def weighted_residual(self, z, y, weights):
        """w ⊙ r(z, y), zero-weight rows guarded to exact 0."""
        r = self.residual(z, y)
        wb = _row_broadcast(weights, r)
        return jnp.where(wb == 0, jnp.zeros((), r.dtype), wb * r)

    def loss(self, X, y, beta, weights=None):
        if weights is None:
            return self.value(matmul(X, beta), y)
        return self.weighted_value(matmul(X, beta), y, weights)

    def gradient(self, X, y, beta, weights=None):
        """∇f(β) = Xᵀ r(Xβ, y); shape = beta.shape."""
        if weights is None:
            return matmul(X.T, self.residual(matmul(X, beta), y))
        return matmul(X.T, self.weighted_residual(matmul(X, beta), y,
                                                      weights))

    def value_residual(self, z, y, weights=None):
        """(f, w ⊙ r) at the linear predictor z = Xβ: the loss and the
        weighted residual whose Xᵀ-product is the gradient.  A FISTA step
        feeds both from ONE z, so it streams X once for z and once for
        the Xᵀr matvec; the Pallas analogue is
        :func:`repro.kernels.slope_loss_residual`."""
        if weights is None:
            return self.value(z, y), self.residual(z, y)
        return (self.weighted_value(z, y, weights),
                self.weighted_residual(z, y, weights))

    def lipschitz(self, X) -> jax.Array:
        """Upper bound on the gradient Lipschitz constant: c·‖X‖₂²."""
        s = _spectral_norm(X)
        c = self.hess_bound if self.hess_bound is not None else 1.0
        return c * s * s


def _spectral_norm(X, iters: int = 30):
    """Power iteration for ‖X‖₂ (deterministic start)."""
    v = jnp.ones((X.shape[1],), X.dtype) / jnp.sqrt(X.shape[1])

    def body(_, v):
        u = matmul(X, v)
        u = u / (jnp.linalg.norm(u) + 1e-12)
        w = matmul(X.T, u)
        return w / (jnp.linalg.norm(w) + 1e-12)

    v = jax.lax.fori_loop(0, iters, body, v)
    return jnp.linalg.norm(matmul(X, v))


# -- OLS --------------------------------------------------------------------

def _ols_value(z, y):
    return 0.5 * jnp.sum(jnp.square(z - y))


def _ols_residual(z, y):
    return z - y


def _ols_row_value(z, y):
    return 0.5 * jnp.square(z - y)


ols = Family("ols", _ols_value, _ols_residual, hess_bound=1.0,
             row_value=_ols_row_value)


# -- logistic (y ∈ {0,1}) ----------------------------------------------------

def _logit_value(z, y):
    # Σ log(1 + e^z) − y z, numerically stable
    return jnp.sum(jnp.logaddexp(0.0, z) - y * z)


def _logit_residual(z, y):
    return jax.nn.sigmoid(z) - y


def _logit_row_value(z, y):
    return jnp.logaddexp(0.0, z) - y * z


logistic = Family("logistic", _logit_value, _logit_residual, hess_bound=0.25,
                  row_value=_logit_row_value)


# -- Poisson -----------------------------------------------------------------

def _pois_value(z, y):
    return jnp.sum(jnp.exp(z) - y * z)


def _pois_residual(z, y):
    return jnp.exp(z) - y


def _pois_row_value(z, y):
    return jnp.exp(z) - y * z


poisson = Family("poisson", _pois_value, _pois_residual, hess_bound=None,
                 row_value=_pois_row_value)


# -- multinomial (y integer classes, β ∈ R^{p×m}) ----------------------------

def _multi_value(Z, y):
    return jnp.sum(jax.nn.logsumexp(Z, axis=-1) - jnp.take_along_axis(Z, y[:, None], axis=-1)[:, 0])


def _multi_residual(Z, y):
    m = Z.shape[-1]
    return jax.nn.softmax(Z, axis=-1) - jax.nn.one_hot(y, m, dtype=Z.dtype)


def _multi_row_value(Z, y):
    return (jax.nn.logsumexp(Z, axis=-1)
            - jnp.take_along_axis(Z, y[:, None], axis=-1)[:, 0])


def multinomial(m: int) -> Family:
    return Family("multinomial", _multi_value, _multi_residual, hess_bound=0.5,
                  n_classes=m, row_value=_multi_row_value)


def get_family(name: str, n_classes: int = 3) -> Family:
    if name == "multinomial":
        return multinomial(n_classes)
    fam = {"ols": ols, "logistic": logistic, "poisson": poisson}.get(name)
    if fam is None:
        raise ValueError(f"unknown family {name!r}")
    return fam
