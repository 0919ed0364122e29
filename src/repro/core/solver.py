"""FISTA solver for SLOPE (paper §3.1: accelerated proximal gradient).

One jit-compiled ``lax.while_loop`` per (n, p, m) shape; the path driver
buckets sub-problem widths to powers of two so the whole regularization
path reuses a handful of compilations.  Backtracking line search covers the
Poisson family (no global Lipschitz bound); adaptive restart (gradient
scheme) is a strict improvement over plain FISTA and is on by default.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .losses import Family, matmul
from .sorted_l1 import _prox_sorted_l1_with_norm_live, sorted_l1_norm

__all__ = ["fista", "fista_masked", "fista_shared_masked", "fista_compact",
           "default_L0", "FistaResult",
           "DEFAULT_PATH_TOL", "DEFAULT_PATH_MAX_ITER", "DEFAULT_KKT_TOL",
           "DEFAULT_MAX_REFITS", "DEFAULT_WS_TIERS"]

# Path-level solver defaults — the ONE source of truth shared by the host
# driver, the device engines, the serve layer and repro.api.SolverPolicy.
# (fista()'s own max_iter default stays lower: single sub-solves outside a
# path context have no warm start to lean on and callers pass their own.)
DEFAULT_PATH_TOL = 1e-8
DEFAULT_PATH_MAX_ITER = 5000
DEFAULT_KKT_TOL = 1e-4
DEFAULT_MAX_REFITS = 32
# Working-set tier policy for the compact engine: "auto" gives every W
# bucket a second 2W tier (when 2W < p) so a member whose screened set
# creeps just past W promotes its own tier instead of sending the whole
# batch to the masked O(n·p) fallback.  1 pins the single-tier PR-2
# behaviour, 2 demands the second tier (still capped below p).
DEFAULT_WS_TIERS = "auto"
# rounding floors of FISTA's tests, in units of the dtype's machine
# epsilon: the objective-plateau tolerance, and the line search's allowed
# excess.  Both sit below 1e-12 in f64, so f64 solves are unchanged.
_TOL_ULPS = 16
_SLACK_ULPS = 1024


def _vdot(a, b):
    return jnp.vdot(a, b, precision=lax.Precision.HIGHEST)


def default_L0(X: jax.Array, family: Family,
               weights: jax.Array | None = None) -> jax.Array:
    """Initial curvature guess: crude row-norm bound, corrected by
    backtracking.  Shared by :func:`fista` and the path engine's scan carry
    so warm-started device solves seed the same curvature as cold ones.

    With per-row ``weights`` the bound is Σᵢ wᵢ‖xᵢ‖² — computed as a dot
    of the weight vector against the (shared) per-row square norms, so a
    batch of weight vectors against one shared X never materializes a
    per-member copy of X under vmap."""
    if weights is None:
        return jnp.maximum(
            jnp.sum(X * X) * (family.hess_bound or 1.0) / X.shape[1], 1e-3
        )
    row_sq = jnp.sum(X * X, axis=1)  # (n,), loop/batch-invariant for shared X
    total = jnp.sum(jnp.where(weights == 0, jnp.zeros((), row_sq.dtype),
                              weights * row_sq))
    return jnp.maximum(total * (family.hess_bound or 1.0) / X.shape[1], 1e-3)


class FistaResult(NamedTuple):
    beta: jax.Array
    iters: jax.Array
    objective: jax.Array
    converged: jax.Array
    L: jax.Array  # final curvature estimate (warm-start for the next solve)
    # Σ over every prox call, line-search tries included, of the prox
    # input's live bound q (``kernels.prox_sorted_l1.live_bound``) and of
    # its width: the share of the pool kernel's loops that ran
    prox_live: jax.Array
    prox_full: jax.Array


class _State(NamedTuple):
    x: jax.Array
    z: jax.Array
    t: jax.Array
    L: jax.Array
    obj: jax.Array
    it: jax.Array
    done: jax.Array
    prox_live: jax.Array
    prox_full: jax.Array


@functools.partial(
    jax.jit,
    static_argnames=(
        "family", "max_iter", "tol", "restart", "max_backtrack", "prox_method"
    ),
)
def fista(
    X: jax.Array,
    y: jax.Array,
    lam: jax.Array,
    beta0: jax.Array,
    family: Family,
    *,
    max_iter: int = 1000,
    tol: float = 1e-8,
    restart: bool = True,
    max_backtrack: int = 30,
    prox_method: str = "stack",
    L0: jax.Array | None = None,
    weights: jax.Array | None = None,
    col_mask: jax.Array | None = None,
) -> FistaResult:
    """Minimise f(β) + J(β; λ) with FISTA + backtracking + adaptive restart.

    ``lam`` must have ``beta0.size`` entries (flattened coefficients for the
    multinomial family) and be non-increasing.  Zero-padded columns of X are
    self-consistent: their gradient is identically zero so they stay at 0.
    ``L0`` overrides the initial curvature guess — the device path engine
    passes the previous path step's learned L so warm solves skip the
    backtracking ramp-up.

    ``weights`` (optional, (n,)) solves the row-reweighted problem
    Σ wᵢ ℓ(zᵢ, yᵢ) + J(β; λ) — the count-vector representation of a
    bootstrap replicate.  ``col_mask`` (optional, (p,) 0/1) restricts the
    solve to a working set by zeroing the *gradient* of masked columns
    instead of the columns of X themselves: for finite X this is bitwise
    the same fixed point as :func:`fista_masked` (masked coefficients stay
    exactly 0, unmasked gradients are untouched), but it keeps a shared X
    unbatched under vmap — ``X * mask`` with a per-member mask would
    materialize the (B, n, p) stack the resampling engine exists to avoid.

    Convergence requires BOTH an objective plateau (|Δobj| ≤ tol·max(1,|obj|))
    and a prox-gradient fixed-point residual ≤ √tol — coefficient-scale
    accuracy tracks √tol, so tol=1e-14 certifies β to ≈1e-7.
    """
    dtype = X.dtype
    lam = lam.astype(dtype)
    eps = float(jnp.finfo(dtype).eps)
    # in f32 a relative objective change of a few ulps is noise, and so is
    # a line-search excess of that size: without these floors the plateau
    # test never fires and the sufficient-decrease test rejects correct
    # steps (L then doubles until the solve stalls; where the excess is
    # not small against the step, the line search below tests the
    # linear predictors instead).  A floored plateau test no longer
    # bounds the objective at tol, nor certifies small coefficients (the
    # objective carries the constant ½‖y‖², so a change that moves β by
    # 10% can sit under the floor), so the fixed-point test then carries
    # the accuracy alone: x⁺ = z to within the same ulps of the prox
    # step's input z − ∇f(z)/L, near the finest a fixed point can be
    # resolved (√tol relative to max|β| left deep-path steps 10× off of
    # the f64 path's KKT level, and 32 ulps one step in 990 on a TPU).
    plateau_tol = max(tol, _TOL_ULPS * eps)
    floored = plateau_tol > tol
    slack = max(1e-12, _SLACK_ULPS * eps)

    def obj_fn(beta):
        return family.loss(X, y, beta, weights=weights) + sorted_l1_norm(beta, lam)

    if L0 is None:
        L0 = default_L0(X, family, weights)

    def mask_grad(g):
        if col_mask is None:
            return g
        cm = col_mask if g.ndim == 1 else col_mask[:, None]
        # where (not multiply): a masked column's gradient becomes an exact
        # 0 even when non-finite, so a poisoned column cannot leak through
        return jnp.where(cm == 0, jnp.zeros((), g.dtype), g)

    def step(state: _State) -> _State:
        z = state.z
        # fused forward pair: one linear predictor feeds both the loss and
        # the residual for the gradient matvec (X streamed once for z)
        eta_z = matmul(X, z)
        fz, rz = family.value_residual(eta_z, y, weights)
        gz = mask_grad(matmul(X.T, rz))

        def bt_cond(carry):
            L, x_new, fx, J, ok, tries, live = carry
            return (~ok) & (tries < max_backtrack)

        def bt_body(carry):
            L, _, _, _, _, tries, live = carry
            # prox at λ/L; its by-product norm is ⟨x_sorted, λ/L⟩, so scale
            # by L to recover J(x_new; λ) — no extra sort for the objective
            x_new, J_scaled, bound = _prox_sorted_l1_with_norm_live(
                jnp.ravel(z - gz / L), lam / L, method=prox_method
            )
            x_new = x_new.reshape(z.shape)
            diff = x_new - z
            dd = _vdot(diff, diff)
            q = fz + _vdot(gz, diff) + 0.5 * L * dd
            eta_x = matmul(X, x_new)
            fx, rx = family.value_residual(eta_x, y, weights)
            # sufficient decrease: the Bregman gap f(x) − f(z) − ⟨∇f(z), Δ⟩
            # may not exceed ½L‖Δ‖².  From function values it carries
            # ≈ slack·|q| of rounding; once that is no longer small against
            # ½L‖Δ‖² (f32 near σ_max: |β| ~ 1e-3 against f ~ 1e2) the test
            # passes steps with L far too small and the solve never settles.
            # There the gap comes from the linear predictors instead,
            # ½⟨XΔ, r(Xx) − r(Xz)⟩ — the trapezoid rule of its integral,
            # exact for OLS, free of the cancellation.
            by_value = fx <= q + slack * jnp.abs(q)
            by_predictor = (0.5 * _vdot(eta_x - eta_z, rx - rz)
                            <= 0.5 * L * dd)
            ok = jnp.where(slack * jnp.abs(q) <= 0.25 * L * dd,
                           by_value, by_predictor)
            L_next = jnp.where(ok, L, L * 2.0)
            return L_next, x_new, fx, J_scaled * L, ok, tries + 1, live + bound

        L, x_new, fx, J_new, _, tries, live = lax.while_loop(
            bt_cond, bt_body,
            (state.L, z, fz, jnp.zeros_like(fz), jnp.bool_(False), jnp.int32(0),
             jnp.int32(0)),
        )

        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * state.t**2))
        momentum = (state.t - 1.0) / t_new
        z_new = x_new + momentum * (x_new - state.x)
        if restart:
            # Gradient-scheme restart (O'Donoghue & Candès): kill momentum
            # when the update opposes the trajectory.
            bad = _vdot(z - x_new, x_new - state.x) > 0
            t_new = jnp.where(bad, 1.0, t_new)
            z_new = jnp.where(bad, x_new, z_new)

        obj_new = fx + J_new
        # two-part stop: the objective Cauchy test alone can fire while
        # weakly-determined coefficients still drift (flat directions change
        # the objective at O(step²)), so also require the prox-gradient
        # fixed-point residual ‖x⁺ − z‖∞ ≲ √tol — that bounds coefficient
        # error at the same scale the objective test bounds the value
        plateau = (jnp.abs(state.obj - obj_new)
                   <= plateau_tol * jnp.maximum(1.0, jnp.abs(obj_new)))
        resid = jnp.max(jnp.abs(x_new - z))
        if floored:
            stationary = resid <= (_TOL_ULPS * eps
                                   * jnp.max(jnp.abs(z - gz / L)))
        else:
            stationary = resid <= (tol ** 0.5
                                   * jnp.maximum(1.0, jnp.max(jnp.abs(x_new))))
        done = plateau & stationary
        # mild decrease of L lets the step size recover after conservative phases
        return _State(x_new, z_new, t_new, L * 0.95, obj_new, state.it + 1, done,
                      state.prox_live + live, state.prox_full + tries * z.size)

    def cond(state: _State):
        return (~state.done) & (state.it < max_iter)

    init = _State(
        x=beta0.astype(dtype),
        z=beta0.astype(dtype),
        t=jnp.asarray(1.0, dtype),
        L=L0.astype(dtype),
        obj=obj_fn(beta0.astype(dtype)),
        it=jnp.int32(0),
        done=jnp.bool_(False),
        prox_live=jnp.int32(0),
        prox_full=jnp.int32(0),
    )
    final = lax.while_loop(cond, step, init)
    return FistaResult(final.x, final.it, final.obj, final.done, final.L,
                       final.prox_live, final.prox_full)


def fista_masked(
    X: jax.Array,
    y: jax.Array,
    lam: jax.Array,
    beta0: jax.Array,
    mask: jax.Array,
    family: Family,
    **kw,
) -> FistaResult:
    """FISTA restricted to the working set ``mask`` — no column gathers.

    The device-engine analogue of the host driver's bucketed sub-problem:
    masked columns of X are zeroed, so their gradient vanishes and their
    coefficients stay pinned at exactly 0; because those coefficients are 0
    they sort to the tail of |β|, which leaves the working set aligned with
    the *leading* entries of λ — the same rank alignment the host driver
    achieves by slicing ``λ[:|E|·m]`` for the gathered sub-problem.

    ``mask`` is a (p,) predictor mask; for multinomial families it applies
    to every class column of the (p, m) coefficient block.

    Masked coordinates of the result are *exactly* 0 with no exit re-mask:
    their columns of ``Xm`` are zero so their gradient vanishes, momentum
    combines zeros into zeros, and the sorted-ℓ1 prox preserves exact zeros
    (a pooled block containing a zero-magnitude coordinate has mean ≤ 0 and
    clips to 0).  The invariant is asserted in ``tests/test_solver_path.py``.
    """
    mask_col = mask.astype(X.dtype)
    Xm = X * mask_col[None, :]
    beta0 = beta0 * (mask_col if beta0.ndim == 1 else mask_col[:, None])
    return fista(Xm, y, lam, beta0, family, **kw)


def fista_shared_masked(
    X: jax.Array,
    y: jax.Array,
    lam: jax.Array,
    beta0: jax.Array,
    mask: jax.Array,
    family: Family,
    **kw,
) -> FistaResult:
    """:func:`fista_masked` for a *shared* design matrix: identical fixed
    point, but the working set restricts the solve by masking the gradient
    (``fista(col_mask=...)``) instead of materializing ``X * mask``.

    For finite X the two are numerically identical coordinate-for-
    coordinate: unmasked gradients are the same partial sums (×1.0 is
    exact), masked coordinates are exact zeros either way, and the z = Xβ
    products agree term-by-term because masked coefficients are exactly 0.
    What changes is the memory profile under vmap — with ``in_axes=None``
    on X and a per-member mask, ``X * mask`` would batch a (B, n, p)
    intermediate; the gradient mask keeps X a single (n, p) operand, which
    is the whole point of the weight-fused replicate engine.
    """
    mask_col = mask.astype(X.dtype)
    beta0 = beta0 * (mask_col if beta0.ndim == 1 else mask_col[:, None])
    return fista(X, y, lam, beta0, family, col_mask=mask_col, **kw)


def fista_compact(
    X: jax.Array,
    y: jax.Array,
    lam: jax.Array,
    beta0: jax.Array,
    mask: jax.Array,
    family: Family,
    *,
    width: int,
    **kw,
) -> FistaResult:
    """FISTA on the working set *compacted* to a static ``width`` bucket.

    Where :func:`fista_masked` zeroes masked columns and still pays O(n·p)
    per iteration, this gathers the ≤ ``width`` unmasked columns into a
    device-resident (n, width) matrix — no host round-trip, no ``X * mask``
    materialization — solves at width W, and scatters the coefficients back
    to p-space.  Every FISTA iteration then costs O(n·W).

    Correctness leans on the same rank alignment as the host driver's
    gathered sub-problem: unmasked coefficients occupy the leading λ slots
    (λ[:W·m]) because masked coordinates are exactly 0 and sort to the λ
    tail.  Padding columns beyond ``mask.sum()`` are zeroed so they stay
    inert.  **The caller must guarantee** ``mask.sum() <= width`` (the path
    engine guards this with an overflow `lax.cond` falling back to
    :func:`fista_masked`) and that ``support(beta0) ⊆ mask``.

    ``width`` must be static (a Python int) — the path engine buckets it to
    powers of two so a whole path reuses a handful of compilations.  The
    two-tier compact engine (PR 5) composes this primitive at two static
    widths (W and 2W): each batch member's solve is served by the smallest
    tier that fits its screened set, and only demand beyond the top tier
    triggers the batch-wide masked fallback.
    """
    n, p = X.shape
    m = 1 if beta0.ndim == 1 else beta0.shape[1]
    dtype = X.dtype
    mask = mask.astype(bool)
    # stable sort: unmasked columns first, ascending index (matches the
    # host driver's np.nonzero gather order)
    idx = jnp.argsort(~mask)[:width]
    valid = (jnp.arange(width) < mask.sum()).astype(dtype)
    Xc = jnp.take(X, idx, axis=1) * valid[None, :]
    b0 = jnp.take(beta0, idx, axis=0)
    b0 = b0 * (valid if b0.ndim == 1 else valid[:, None])
    lam_c = lax.slice_in_dim(lam, 0, width * m)
    res = fista(Xc, y, lam_c, b0, family, **kw)
    bc = res.beta * (valid if res.beta.ndim == 1 else valid[:, None])
    beta = jnp.zeros(beta0.shape, dtype).at[idx].set(bc)
    return res._replace(beta=beta)
