"""Device-resident batched path engine (Algorithms 3/4 under one jit scope).

The host driver in :mod:`repro.core.path` orchestrates one path step at a
time from NumPy: gather the screened columns, pad to a bucket, dispatch a
FISTA solve, pull the gradient back, check KKT, repeat.  That is the right
trade for a *single* huge p ≫ n problem — the gathers shrink every matvec —
but it round-trips host↔device at every step, and it can only fit one
(X, y) problem at a time.

This module moves the whole per-step loop onto the device:

* the path is a ``lax.scan`` over σ-grid points;
* working sets are *masks*, not gathers — :func:`repro.core.solver.fista_masked`
  zeroes masked columns so the sub-problem keeps one static shape, and
  :func:`repro.core.screening.screen_masked` /
  :func:`repro.core.kkt.kkt_violations_masked` run the strong rule and the
  KKT guard on the same masked representation;
* KKT repair is a bounded ``lax.while_loop`` inside each scan step;
* a ``vmap`` batching layer fits B independent problems — CV folds,
  bootstrap replicates, a batch of user requests — in ONE compiled program.

Shape policy: one compilation per static (B, n, p, m, L, config) bucket.
The batching wrappers stack problems of identical shape; callers with mixed
shapes bucket on the host (pad n with zero rows / p with zero columns) —
zero columns are inert in every family, zero rows are inert for OLS.

Everything here returns the *full* σ grid (a scan cannot truncate); the
host front-end applies the paper's early-stopping rules post-hoc when a
:class:`repro.core.path.PathResult` is requested.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..serve.buckets import BucketRegistry, next_pow2
from .kkt import kkt_violations_masked
from .lambda_seq import path_start_sigma, sigma_grid
from .losses import Family
from .screening import screen_masked
from .sorted_l1 import pool_prox_applies
from .solver import (
    DEFAULT_KKT_TOL,
    DEFAULT_MAX_REFITS,
    DEFAULT_PATH_MAX_ITER,
    DEFAULT_PATH_TOL,
    DEFAULT_WS_TIERS,
    default_L0,
    fista_compact,
    fista_masked,
    fista_shared_masked,
)
from .transfer import get, put

__all__ = [
    "EnginePath",
    "CompactStats",
    "PathHealth",
    "HEALTH_OK",
    "HEALTH_NONFINITE_INPUT",
    "HEALTH_NONFINITE_STATE",
    "HEALTH_DIVERGED",
    "health_causes",
    "path_engine",
    "batched_path_engine",
    "compact_path_engine",
    "chunk_path_engine",
    "path_init_engine",
    "replicate_path_engine",
    "replicate_compact_path_engine",
    "fit_path_batched",
    "grow_ws_bucket",
    "resolve_ws_tiers",
    "second_tier_width",
    "cv_path",
    "cv_fold_indices",
    "cv_val_deviance",
    "cv_select",
    "null_gradient",
    "null_sigma_grid",
    "BatchedPathResult",
    "CvPathResult",
]


class EnginePath(NamedTuple):
    """Raw device arrays for one fitted path (leading axis = path point)."""

    betas: jax.Array          # (L, p, m)
    n_active: jax.Array       # (L,) int32
    n_screened: jax.Array     # (L,) int32
    n_violations: jax.Array   # (L,) int32
    refits: jax.Array         # (L,) int32
    solver_iters: jax.Array   # (L,) int32
    deviance: jax.Array       # (L,)
    kkt_unrepaired: jax.Array  # (L,) bool — repair loop hit max_refits
    #   with violations outstanding; the step's betas are NOT KKT-clean
    health: jax.Array         # (L,) int32 — sticky per-step health word
    #   (HEALTH_* bitmask); nonzero from the first step a member turned
    #   sick — its betas are zeroed and it is quarantined out of
    #   screening/KKT from then on


# Per-member health word bits.  The word rides the scan carry, is sticky
# (monotone OR across steps), and quarantines the member in-graph: its data
# is zeroed, its working set blanked and its KKT repair gated off, so the
# quarantined no-op solve exits in one iteration instead of grinding
# ``max_iter`` on NaN stop criteria and stalling the lockstep batch.
HEALTH_OK = 0
HEALTH_NONFINITE_INPUT = 1   # non-finite X/y/λ/σ reached the engine
HEALTH_NONFINITE_STATE = 2   # solver state (beta/grad/L/deviance) went NaN/Inf
HEALTH_DIVERGED = 4          # objective blew past the divergence bound

# a step's deviance beyond FACTOR·(|null deviance| + 1) marks divergence:
# every family's loss at beta=0 is the natural scale of the objective, and
# a correct prox step can never increase it by six orders of magnitude
_DIVERGENCE_FACTOR = 1e6

_HEALTH_BITS = (
    (HEALTH_NONFINITE_INPUT, "nonfinite_input"),
    (HEALTH_NONFINITE_STATE, "nonfinite_state"),
    (HEALTH_DIVERGED, "diverged"),
)


def health_causes(word: int) -> tuple[str, ...]:
    """Human-readable causes encoded in a health word."""
    return tuple(name for bit, name in _HEALTH_BITS if int(word) & bit)


@dataclasses.dataclass(frozen=True)
class PathHealth:
    """Per-member quarantine verdicts for one batched fit.

    ``word`` is the (B, L) sticky per-step health bitmask an engine run
    emitted (``EnginePath.health`` with the batch axis leading).  Because
    the word is monotone along the path, the last step's word is each
    member's cumulative verdict.
    """

    word: np.ndarray  # (B, L) int32

    @property
    def quarantined(self) -> np.ndarray:
        """(B,) bool — members that turned sick anywhere on the path."""
        return np.asarray(self.word)[:, -1] != 0

    @property
    def first_bad_step(self) -> np.ndarray:
        """(B,) int — first sick path index per member, -1 when healthy."""
        w = np.asarray(self.word)
        sick = w != 0
        return np.where(sick.any(axis=1), sick.argmax(axis=1), -1)

    @property
    def ok(self) -> bool:
        return not bool(self.quarantined.any())

    def causes(self, b: int) -> tuple[str, ...]:
        return health_causes(int(np.asarray(self.word)[b, -1]))


class CompactStats(NamedTuple):
    """Per-step compact-engine telemetry (leading axes = problem, path point)."""

    ws_size: jax.Array    # (B, L) int32 — peak working-set demand |E| per step
    tier: jax.Array       # (B, L) int32 — which tier served the member's
    #   step: 1 = the W bucket, 2 = the 2W top tier, 0 = the step ran the
    #   batch-wide masked fallback (some member's demand exceeded the top
    #   tier; `ws_size` still records every member's own demand)
    fell_back: jax.Array  # (B, L) bool — step ran the masked full-width
    #   fallback because some batch member's |E| exceeded the top tier


# ---------------------------------------------------------------------------
# Per-problem step primitives, shared by the masked and compact engines
# ---------------------------------------------------------------------------

def _valid_masks(p, m, p_valid):
    """Predictor- and coordinate-space validity masks for a (possibly
    bucket-padded) problem.  ``p_valid=None`` means every column is real —
    the masks are all-True constants and fold away at trace time; a traced
    ``p_valid`` scalar marks columns ≥ p_valid as padding, excluded from
    screening, KKT checks and the full-problem widening heuristic (their
    coefficients are inert zeros either way — see repro.serve.buckets)."""
    if p_valid is None:
        return jnp.ones((p,), bool), jnp.ones((p * m,), bool)
    valid_p = jnp.arange(p) < p_valid
    return valid_p, jnp.repeat(valid_p, m)


def _screen_sets(grad, prev_active, sig_prev, sig, lam, *, p, m, screening,
                 p_valid=None):
    """Strong set + initial working set E₀ for one path step (one problem)."""
    pm = p * m
    valid_p, valid_flat = _valid_masks(p, m, p_valid)
    gap = (sig_prev - sig) * lam  # rank-space surrogate shift
    keep_flat, _ = screen_masked(jnp.abs(grad.reshape(pm)), sig * lam,
                                 valid_flat, gap)
    strong_p = keep_flat.reshape(p, m).any(axis=1)
    n_screened = strong_p.sum().astype(jnp.int32)
    if screening == "strong":
        E0 = strong_p | prev_active
    else:  # "previous" (Algorithm 4)
        E0 = jnp.where(prev_active.any(), prev_active, strong_p)
    # mirror the host driver: once screening keeps most predictors
    # (n ≳ p regime) just solve the full problem — keeps violation
    # accounting identical between backends.  "Full" means the valid
    # columns; the threshold counts them, not the padded width.
    p_eff = p if p_valid is None else p_valid
    E0 = jnp.where(E0.sum() >= 0.5 * p_eff, valid_p, E0)
    return strong_p, E0, n_screened


def _kkt_step(grad, lam_next, E, strong_p, checked_full, *, p, m, kkt_tol,
              screening, p_valid=None):
    """KKT violation mask for one problem; see Algorithms 3/4."""
    pm = p * m
    _, valid_flat = _valid_masks(p, m, p_valid)
    gflat = grad.reshape(pm)
    ever = jnp.repeat(E, m)
    viol_full = kkt_violations_masked(gflat, lam_next, ever, valid_flat,
                                      tol=kkt_tol)
    if screening != "previous":
        return viol_full, checked_full
    # Algorithm 4: check the strong set first; only once it is clean,
    # graduate (permanently) to full-set checks.
    subset = jnp.repeat(strong_p, m)
    viol_sub = kkt_violations_masked(gflat, lam_next, ever, subset,
                                     tol=kkt_tol)
    pre = ~checked_full
    sub_has = viol_sub.any()
    viol = jnp.where(pre & sub_has, viol_sub, viol_full)
    return viol, checked_full | (pre & ~sub_has)


def _new_violations(viol_flat, strong_p, prev_active, *, p, m, screening):
    """Count the rule's failures: violations against the *strong* set
    (paper §2.2.3); previous-set warm misses are algorithmic."""
    rows = viol_flat.reshape(p, m).any(axis=1)
    miss = rows & ~strong_p
    if screening == "previous":
        miss = miss & ~prev_active
    return miss.sum().astype(jnp.int32)


def _batched_prox_method(dtype) -> str:
    """The batched engines' sorted-ℓ1 prox.  The stack PAVA as an XLA loop
    is p·m sequential trips, and under vmap every member pays the slowest
    member's pooling in lockstep; the sweep-merging form is a handful of
    dense ops per sweep and batches well — until one cluster spans most
    coordinates (SLOPE near σ_max on an equicorrelated design), where it
    needs O(p) sweeps.  On a TPU, in f32, the Pallas pool kernel runs the
    stack PAVA's O(p) on the scalar unit, one member after another."""
    return "pool" if pool_prox_applies(dtype) else "parallel"


def _step_builder(X, y, lam, family: Family, screening, max_iter, tol,
                  kkt_tol, max_refits, rw=None, shared_x=False):
    """Build the per-σ-point path step for ONE problem.

    Returns ``step(carry, sigs, p_valid) -> (carry, out)`` with carry
    ``(beta, grad, prev_active, L, health)`` — the traced body shared by
    the monolithic scan (:func:`path_engine` / the vmapped batch form) and
    the chunked continuous-batching scan (:func:`chunk_path_engine`).  One
    body, one trace structure: a chunked run must produce bit-identical
    per-step results to the monolithic scan, so the step cannot fork.
    ``p_valid`` is per-call (not closed over) because the chunked engine
    feeds a *dynamic* value: a frozen slot passes 0, which empties the
    screened set and turns the step into a one-iteration no-op solve.

    ``health`` (int32 HEALTH_* bitmask, sticky) is the quarantine word: a
    sick member enters the step with its carry sanitized and its DATA
    zeroed (``jnp.where`` on X/y — value-identity for healthy members, so
    the healthy path stays bitwise what it was before health existed).
    Zeroing the data matters: NaN comparisons are always False, so a
    poisoned X would never trip FISTA's stop criteria and one member would
    grind ``max_iter`` iterations while the whole lockstep batch waits.
    With zeroed data and a blanked working set the quarantined solve exits
    in one iteration — the same blanked-solve trick the two-tier mixed arm
    and the chunked engine's dead steps use.

    ``rw`` (optional, (n,)) is a per-member row-weight vector: the solves
    minimise the reweighted loss Σ wᵢℓᵢ — the count-vector representation
    of a bootstrap replicate, and the row-weight form of OLS sample
    weights.  ``shared_x=True`` marks X as a batch-shared operand (the
    replicate engine vmaps this builder with ``in_axes=None`` on X): the
    quarantine gate then zeroes the member's WEIGHTS instead of the data —
    ``jnp.where`` on a shared X would materialize a per-member copy — and
    the masked solves route through :func:`fista_shared_masked` (gradient
    masking) for the same reason.  Zero weights make every row inert, so
    the blanked-solve quarantine trick carries over unchanged.
    """
    p = X.shape[1]
    m = family.n_classes
    dtype = X.dtype
    lam = lam.astype(dtype)
    if shared_x and rw is None:
        raise ValueError("shared_x=True requires row weights (rw)")
    # loop-invariant health inputs, hoisted by XLA out of the scan: the
    # divergence bound from the null deviance, and whether λ itself is sick
    null_dev_in = family.loss(X, y, jnp.zeros((p,) if m == 1 else (p, m),
                                              dtype), weights=rw)
    dev_bound = _DIVERGENCE_FACTOR * (jnp.abs(null_dev_in) + 1.0)
    lam_bad = ~jnp.all(jnp.isfinite(lam))

    def fam_shape(b):  # (p, m) -> the shape the family callbacks expect
        return b[:, 0] if m == 1 else b

    def lift(b):  # family shape -> (p, m)
        return b[:, None] if m == 1 else b

    prox_method = _batched_prox_method(dtype)

    def solve(Xs, ys, E, lam_next, beta, L, rws=None):
        # L is the curvature estimate carried from the previous solve —
        # device-resident state the host driver cannot keep, which skips
        # the backtracking ramp-up.
        masked = fista_shared_masked if shared_x else fista_masked
        res = masked(Xs, ys, lam_next, fam_shape(beta), E, family,
                     max_iter=max_iter, tol=tol,
                     prox_method=prox_method, L0=L, weights=rws)
        beta_new = lift(res.beta)
        grad = lift(family.gradient(Xs, ys, fam_shape(beta_new),
                                    weights=rws))
        return beta_new, grad, res.iters.astype(jnp.int32), res.L

    count_viol = functools.partial(_new_violations, p=p, m=m,
                                   screening=screening)

    def step(carry, sigs, p_valid):
        beta, grad, prev_active, L_carry, health = carry
        sig_prev, sig = sigs
        lam_next = sig * lam
        kkt_check = functools.partial(_kkt_step, p=p, m=m, kkt_tol=kkt_tol,
                                      screening=screening, p_valid=p_valid)

        # quarantine gate: a member already sick runs this step on zeroed
        # data, zeroed carry and an empty working set — a one-iteration
        # no-op solve.  All selects are value-identity when sick is False.
        # With a shared X the member's row WEIGHTS are zeroed instead of
        # the data (a where() on shared X would materialize a per-member
        # copy under vmap); zero weights make every row inert, so the
        # blanked solve still exits in one iteration.
        sick = health != 0
        Xq = X if shared_x else jnp.where(sick, jnp.zeros((), dtype), X)
        yq = jnp.where(sick, jnp.zeros((), y.dtype), y)
        rwq = (None if rw is None
               else jnp.where(sick, jnp.zeros((), rw.dtype), rw))
        beta = jnp.where(sick, 0, beta)
        grad = jnp.where(sick, 0, grad)
        prev_active = prev_active & ~sick
        L_carry = jnp.where(sick, jnp.ones((), L_carry.dtype), L_carry)

        if screening == "none":
            strong_p, _ = _valid_masks(p, m, p_valid)
            E0 = strong_p
            n_screened = (jnp.int32(p) if p_valid is None
                          else jnp.asarray(p_valid, jnp.int32))
        else:
            strong_p, E0, n_screened = _screen_sets(
                grad, prev_active, sig_prev, sig, lam, p=p, m=m,
                screening=screening, p_valid=p_valid)
        E0 = E0 & ~sick
        strong_p = strong_p & ~sick
        n_screened = jnp.where(sick, 0, n_screened)

        beta1, grad1, it1, L1 = solve(Xq, yq, E0, lam_next, beta, L_carry,
                                      rwq)

        if screening == "none":
            beta_f, grad_f, L_f = beta1, grad1, L1
            viol_count = jnp.int32(0)
            refits = jnp.int32(0)
            iters = it1
            unrepaired = jnp.bool_(False)
        else:
            viol1, checked1 = kkt_check(grad1, lam_next, E0, strong_p,
                                        jnp.bool_(False))
            state = dict(
                beta=beta1, grad=grad1, L=L1,
                E=E0 | viol1.reshape(p, m).any(axis=1),
                checked=checked1, has_viol=viol1.any() & ~sick,
                viol_count=count_viol(viol1, strong_p, prev_active),
                refits=jnp.int32(0), iters=it1,
            )

            def cond(s):
                return s["has_viol"] & (s["refits"] < max_refits)

            def body(s):
                beta2, grad2, it2, L2 = solve(Xq, yq, s["E"], lam_next,
                                              s["beta"], s["L"], rwq)
                viol2, checked2 = kkt_check(grad2, lam_next, s["E"],
                                            strong_p, s["checked"])
                return dict(
                    beta=beta2, grad=grad2, L=L2,
                    E=s["E"] | viol2.reshape(p, m).any(axis=1),
                    checked=checked2, has_viol=viol2.any(),
                    viol_count=s["viol_count"]
                    + count_viol(viol2, strong_p, prev_active),
                    refits=s["refits"] + 1, iters=s["iters"] + it2,
                )

            state = lax.while_loop(cond, body, state)
            beta_f, grad_f, L_f = state["beta"], state["grad"], state["L"]
            viol_count = state["viol_count"]
            refits = state["refits"]
            iters = state["iters"]
            unrepaired = state["has_viol"]  # loop exited on the refit cap

        dev = family.loss(Xq, yq, fam_shape(beta_f), weights=rwq)
        # health detection: non-finite σ/λ inputs, non-finite solver state,
        # objective divergence.  Sticky — once sick, always sick.
        bad_input = lam_bad | ~(jnp.isfinite(sig_prev) & jnp.isfinite(sig))
        bad_state = ~(jnp.all(jnp.isfinite(beta_f))
                      & jnp.all(jnp.isfinite(grad_f))
                      & jnp.isfinite(L_f))
        bad_dev = ~jnp.isfinite(dev) | (dev > dev_bound)
        zero32 = jnp.int32(0)
        health = (health
                  | jnp.where(bad_input, jnp.int32(HEALTH_NONFINITE_INPUT),
                              zero32)
                  | jnp.where(bad_state, jnp.int32(HEALTH_NONFINITE_STATE),
                              zero32)
                  | jnp.where(bad_dev, jnp.int32(HEALTH_DIVERGED), zero32))
        # quarantine newly-sick members' outputs so NaNs cannot escape into
        # the carried state (next step's screen/solve) or the emitted path
        sick_out = health != 0
        beta_f = jnp.where(sick_out, 0, beta_f)
        grad_f = jnp.where(sick_out, 0, grad_f)
        L_f = jnp.where(sick_out, jnp.ones((), L_f.dtype), L_f)

        active = (jnp.abs(beta_f) > 0).any(axis=1)
        out = (beta_f, active.sum().astype(jnp.int32), n_screened, viol_count,
               refits, iters, dev, unrepaired, health)
        return (beta_f, grad_f, active, L_f, health), out

    return step


def _init_state(X, y, family: Family, rw=None):
    """Null-model start state for one problem: ``(beta0, grad0, active0,
    L0, health0)`` plus the null deviance — exactly the pre-scan
    computation :func:`_engine` performs, factored out so the chunked
    engine's prefill is bitwise the same.  ``health0`` is nonzero when the
    inputs are already sick at the null model (non-finite X/y poison the
    null gradient, deviance or Lipschitz estimate) — the member is then
    quarantined from its very first step.  ``rw`` (optional, (n,)) seeds
    the state of the row-reweighted problem (replicates / OLS weights)."""
    p = X.shape[1]
    m = family.n_classes
    dtype = X.dtype
    zeros = jnp.zeros((p, m), dtype)
    fam0 = zeros[:, 0] if m == 1 else zeros
    grad0 = family.gradient(X, y, fam0, weights=rw)
    grad0 = grad0[:, None] if m == 1 else grad0
    null_dev = family.loss(X, y, fam0, weights=rw)
    L_init = default_L0(X, family, rw).astype(dtype)
    finite0 = (jnp.all(jnp.isfinite(grad0)) & jnp.isfinite(null_dev)
               & jnp.isfinite(L_init))
    health0 = jnp.where(finite0, jnp.int32(HEALTH_OK),
                        jnp.int32(HEALTH_NONFINITE_INPUT))
    return zeros, grad0, null_dev, L_init, health0


def _engine(X, y, lam, sigmas, family: Family, screening, max_iter, tol,
            kkt_tol, max_refits, p_valid=None, rw=None,
            shared_x=False) -> EnginePath:
    """Traced body shared by :func:`path_engine` and the vmapped batch form."""
    p = X.shape[1]
    zeros, grad0, null_dev, L_init, health0 = _init_state(X, y, family, rw)
    step = _step_builder(X, y, lam, family, screening, max_iter, tol,
                         kkt_tol, max_refits, rw=rw, shared_x=shared_x)
    carry0 = (zeros, grad0, jnp.zeros((p,), bool), L_init, health0)
    _, outs = lax.scan(lambda c, s: step(c, s, p_valid), carry0,
                       (sigmas[:-1], sigmas[1:]))
    betas, n_act, n_scr, viol, refits, iters, devs, unrep, hlth = outs

    def pre(a, v):
        return jnp.concatenate([jnp.asarray(v, a.dtype)[None], a])

    return EnginePath(
        betas=jnp.concatenate([zeros[None], betas]),
        n_active=pre(n_act, 0),
        n_screened=pre(n_scr, 0),
        n_violations=pre(viol, 0),
        refits=pre(refits, 0),
        solver_iters=pre(iters, 0),
        deviance=pre(devs, null_dev),
        kkt_unrepaired=pre(unrep, False),
        health=pre(hlth, health0),
    )


_ENGINE_STATICS = ("family", "screening", "max_iter", "tol", "kkt_tol",
                   "max_refits")


@functools.partial(jax.jit, static_argnames=_ENGINE_STATICS)
def path_engine(X, y, lam, sigmas, family: Family, p_valid=None, *,
                screening: str = "strong",
                max_iter: int = 5000, tol: float = 1e-8,
                kkt_tol: float = 1e-4, max_refits: int = 32) -> EnginePath:
    """Fit one full SLOPE path entirely on device (fixed σ grid, no early
    stop).  One compilation per (n, p, m, len(sigmas), config).

    ``p_valid`` (optional scalar) marks columns ≥ p_valid as bucket padding:
    inert in the solve and excluded from screening/KKT accounting."""
    return _engine(X, y, lam, sigmas, family, screening, max_iter, tol,
                   kkt_tol, max_refits, p_valid)


@functools.partial(jax.jit, static_argnames=_ENGINE_STATICS)
def batched_path_engine(X, y, lam, sigmas, family: Family, p_valid=None, *,
                        screening: str = "strong", max_iter: int = 5000,
                        tol: float = 1e-8, kkt_tol: float = 1e-4,
                        max_refits: int = 32) -> EnginePath:
    """vmap of :func:`path_engine` over the leading problem axis.

    ``X``: (B, n, p); ``y``: (B, n[, ...]); ``sigmas``: (B, L); ``lam`` is
    either one shared (p·m,) sequence (SLOPE's λ is a rank sequence, not
    per-problem data) or a per-problem (B, p·m) stack — the serve layer
    uses the latter so requests with different native widths can share one
    padded program.  ``p_valid`` (optional, (B,) int32) marks per-member
    bucket padding.  Returns an :class:`EnginePath` whose arrays carry a
    leading batch axis.
    """
    lam_axis = 0 if lam.ndim == 2 else None
    pv_axis = None if p_valid is None else 0

    def one(Xi, yi, si, lami, pvi):
        return _engine(Xi, yi, lami, si, family, screening, max_iter, tol,
                       kkt_tol, max_refits, pvi)

    return jax.vmap(one, in_axes=(0, 0, 0, lam_axis, pv_axis))(
        X, y, sigmas, lam, p_valid)


@functools.partial(jax.jit, static_argnames=_ENGINE_STATICS)
def replicate_path_engine(X, y, lam, sigmas, weights, family: Family,
                          p_valid=None, *, screening: str = "strong",
                          max_iter: int = 5000, tol: float = 1e-8,
                          kkt_tol: float = 1e-4,
                          max_refits: int = 32) -> EnginePath:
    """B row-reweighted SLOPE paths against ONE shared (n, p) design.

    The materialize-free replicate engine (ROADMAP item 4): a bootstrap /
    permutation / subsample replicate is represented as ``(shared X,
    per-member row-weight vector)`` instead of a row-duplicated copy of X,
    so the resident operands are O(n·p + B·n) — the vmap closes over X
    with ``in_axes=None``, which turns every per-member GEMV inside FISTA
    into one shared (n, p) × (p, B) GEMM and never stacks a (B, n, p) X.

    ``X``: (n, p) shared; ``y``: (n,) shared or (B, n) per-member (the
    permutation-null workload permutes y, not X); ``weights``: (B, n)
    per-member row weights (bootstrap count vectors, subsample 0/1 masks,
    OLS sample weights); ``lam``: one shared (p·m,) sequence; ``sigmas``:
    (L,) — replicates share the master problem's σ grid, like CV folds
    share the full-data grid; ``p_valid`` (optional scalar) marks shared
    bucket padding.  An all-zero weight vector is a legal edge member: its
    loss surface is identically 0, every path step solves the blanked
    null problem in one iteration, and its coefficients come back exactly
    0.  Returns an :class:`EnginePath` with a leading (B,) replicate axis.
    """
    y_axis = 0 if y.ndim == 2 else None

    def one(yi, wi):
        return _engine(X, yi, lam, sigmas, family, screening, max_iter, tol,
                       kkt_tol, max_refits, p_valid, rw=wi, shared_x=True)

    return jax.vmap(one, in_axes=(y_axis, 0))(y, weights)


@functools.partial(jax.jit, static_argnames=("family",))
def path_init_engine(X, y, family: Family):
    """Batched prefill: the state a path scan starts from, per member.

    Returns ``(grad0, null_dev, L0, health0)`` with shapes ``(B, p, m)`` /
    ``(B,)`` / ``(B,)`` / ``(B,) int32`` — the same pre-scan computation
    :func:`batched_path_engine` performs internally (one
    :func:`_init_state` per member under vmap), as its own compiled
    program so the continuous-batching dispatcher can initialise a *newly
    inserted* slot mid-flight with bitwise the state a from-scratch run
    would have started with.  ``beta0``/``active0`` are zeros at known
    shapes; the host materialises those itself.  A nonzero ``health0``
    marks a member quarantined before its first step (non-finite inputs).
    """
    def one(Xi, yi):
        _, grad0, null_dev, L0, health0 = _init_state(Xi, yi, family)
        return grad0, null_dev, L0, health0

    return jax.vmap(one)(X, y)


@functools.partial(jax.jit, static_argnames=_ENGINE_STATICS)
def chunk_path_engine(X, y, lam, sig_prev, sig_next, live, beta, grad,
                      active, L, health, family: Family, p_valid, *,
                      screening: str = "strong", max_iter: int = 5000,
                      tol: float = 1e-8, kkt_tol: float = 1e-4,
                      max_refits: int = 32):
    """Advance B carried paths by C σ-grid steps each (continuous batching).

    The slot-swap seam for the async serving layer: instead of one
    monolithic scan over a member's whole grid, the path advances in
    chunks of C steps with the scan carry ``(beta, grad, active, L,
    health)`` round-tripped through the host between chunks — so a member
    that early-stops can free its batch slot and a queued request can join
    the *running* cohort at the next chunk boundary, each slot at its own
    step offset.

    ``sig_prev``/``sig_next``: (B, C) per-slot σ pairs (each slot's own
    grid, wherever its cursor stands); ``live``: (B, C) bool — steps beyond
    a slot's remaining grid (or an empty slot) are dead: the step sees an
    effective ``p_valid`` of 0 (empty screened set → one-iteration blanked
    solve, the same trick the two-tier mixed arm uses) and the carry is
    held, so a dead step costs lockstep time but cannot perturb state.
    ``p_valid``: (B,) int32; ``health``: (B,) int32 sticky quarantine words
    (0 for healthy slots; :func:`path_init_engine` seeds them).  Returns
    ``((beta, grad, active, L, health), EnginePath)`` with EnginePath
    arrays shaped (B, C, ...) — raw chunk steps, no null head (the
    dispatcher owns step 0 via :func:`path_init_engine`).

    Per-step traced body is :func:`_step_builder`'s — the SAME body the
    monolithic engines scan — so chunked execution is bit-identical to
    :func:`batched_path_engine` on the same inputs (pinned in
    ``tests/test_serve_async.py``).
    """
    lam_axis = 0 if lam.ndim == 2 else None

    def one(Xi, yi, lami, spi, sni, lvi, bi, gi, ai, Li, hi, pvi):
        step = _step_builder(Xi, yi, lami, family, screening, max_iter, tol,
                             kkt_tol, max_refits)

        def chunk_step(carry, xs):
            sp, sn, lv = xs
            pv = jnp.where(lv, pvi, 0)
            new_carry, out = step(carry, (sp, sn), pv)
            held = tuple(jnp.where(lv, nw, od)
                         for nw, od in zip(new_carry, carry))
            return held, out

        return lax.scan(chunk_step, (bi, gi, ai, Li, hi), (spi, sni, lvi))

    carry, outs = jax.vmap(one, in_axes=(0, 0, lam_axis, 0, 0, 0, 0, 0, 0,
                                         0, 0, 0))(
        X, y, lam, sig_prev, sig_next, live, beta, grad, active, L, health,
        p_valid)
    return carry, EnginePath(*outs)


def _compact_engine(X, y, lam, sigmas, family: Family, screening, max_iter,
                    tol, kkt_tol, max_refits, width, p_valid=None,
                    width2=None, rw=None, shared_x=False):
    """Natively-batched compact-working-set engine, now two-tier.

    Identical per-step semantics to ``vmap(_engine)`` with one structural
    difference: the batch axis is threaded through the *data* while control
    flow stays **scalar**.  That lets the overflow check reduce over the
    batch (``any(|E| > W_top)``) before the ``lax.cond`` that picks between
    the compact solve and the masked O(n·p) fallback — a per-member cond
    under ``vmap`` would lower to ``lax.select`` and execute BOTH branches,
    erasing the compact win.

    ``width2`` (optional, > ``width``) adds a second tier: inside the
    compact arm a nested scalar gate checks ``any(|E| > W)``; only when it
    fires does the mixed arm run, solving every member at BOTH tiers and
    per-member-selecting each member's own tier's result.  The per-member
    cond is a select by construction — that is exactly what a vmapped cond
    would lower to — but both branches are compact (O(n·W) + O(n·2W) ≈
    3·n·W), so a member whose screened set creeps just past W costs three
    W-solves instead of one O(n·p) masked solve for the whole batch.  The
    batch-wide masked fallback now fires only for demand beyond ``width2``.

    ``rw`` (optional, (B, n)) row-reweights each member's loss; with
    ``shared_x=True`` X is one shared (n, p) design (y then (B, n)), the
    replicate representation: each member's compact gather reads the SAME
    X, so resident memory is O(n·p + B·n·W) — the quarantine gate zeroes a
    sick member's weights instead of the shared data, and the masked
    fallback masks gradients (:func:`fista_shared_masked`) instead of
    columns of X.
    """
    if shared_x:
        if rw is None:
            raise ValueError("shared_x=True requires row weights (rw)")
        n, p = X.shape
        B = rw.shape[0]
    else:
        B, n, p = X.shape
    x_ax = None if shared_x else 0       # vmap axis for the design matrix
    w_ax = None if rw is None else 0     # vmap axis for the row weights
    m = family.n_classes
    dtype = X.dtype
    lam = lam.astype(dtype)
    if lam.ndim == 1:  # shared rank sequence -> per-member view
        lam = jnp.broadcast_to(lam, (B,) + lam.shape)
    pv_axis = None if p_valid is None else 0
    W = width
    W2 = width2
    if W2 is not None and W2 <= W:
        raise ValueError(f"width2 must exceed width, got {W2} <= {W}")
    W_top = W if W2 is None else W2

    def fam_shape(b):  # (p, m) -> the shape the family callbacks expect
        return b[:, 0] if m == 1 else b

    def lift(b):  # family shape -> (p, m)
        return b[:, None] if m == 1 else b

    zeros1 = jnp.zeros((p, m), dtype)

    def grad_one(Xi, yi, beta, wi=None):
        return lift(family.gradient(Xi, yi, fam_shape(beta), weights=wi))

    def dev_one(Xi, yi, beta, wi=None):
        return family.loss(Xi, yi, fam_shape(beta), weights=wi)

    grad0 = jax.vmap(lambda Xi, yi, wi: grad_one(Xi, yi, zeros1, wi),
                     in_axes=(x_ax, 0, w_ax))(X, y, rw)
    null_dev = jax.vmap(lambda Xi, yi, wi: dev_one(Xi, yi, zeros1, wi),
                        in_axes=(x_ax, 0, w_ax))(X, y, rw)
    # health inputs, mirroring _step_builder/_init_state member-for-member
    L_init0 = jax.vmap(lambda Xi, wi: default_L0(Xi, family, wi),
                       in_axes=(x_ax, w_ax))(X, rw).astype(dtype)
    finite0 = (jnp.isfinite(grad0).reshape(B, -1).all(axis=1)
               & jnp.isfinite(null_dev) & jnp.isfinite(L_init0))
    health0 = jnp.where(finite0, jnp.int32(HEALTH_OK),
                        jnp.int32(HEALTH_NONFINITE_INPUT))
    dev_bound = _DIVERGENCE_FACTOR * (jnp.abs(null_dev) + 1.0)  # (B,)
    lam_bad = ~jnp.isfinite(lam).all(axis=1)                    # (B,)

    solver_kw = dict(max_iter=max_iter, tol=tol,
                     prox_method=_batched_prox_method(dtype))

    def solve_masked_one(Xi, yi, wi, lam_next, beta, E, L):
        masked = fista_shared_masked if shared_x else fista_masked
        res = masked(Xi, yi, lam_next, fam_shape(beta), E, family,
                     L0=L, weights=wi, **solver_kw)
        return lift(res.beta), res.iters.astype(jnp.int32), res.L

    def solve_compact_one(width_t):
        def one(Xi, yi, wi, lam_next, beta, E, L):
            res = fista_compact(Xi, yi, lam_next, fam_shape(beta), E, family,
                                width=width_t, L0=L, weights=wi, **solver_kw)
            return lift(res.beta), res.iters.astype(jnp.int32), res.L
        return one

    solve_tier1 = solve_compact_one(W)
    solve_tier2 = None if W2 is None else solve_compact_one(W2)

    # per-member solve axes: the shared-X replicate form broadcasts X
    # (in_axes=None) and batches the weights; the plain form is unchanged
    solve_axes = (x_ax, 0, w_ax, 0, 0, 0, 0)

    def solve_all(Xq, yq, wq, E, lam_next, beta, L):
        need = E.sum(axis=1).astype(jnp.int32)
        # scalar reduction — keeps the fallback cond a real branch
        fell_back = jnp.any(need > W_top)
        args = (lam_next, beta, E, L)

        def tier1_all(a):
            return jax.vmap(solve_tier1, in_axes=solve_axes)(Xq, yq, wq, *a)

        if W2 is None:
            compact_arm = tier1_all
        else:
            over1 = need > W  # (B,) members whose demand needs the top tier

            def mixed(a):
                # both tiers run (a per-member cond would lower to exactly
                # this select); each member keeps its OWN tier's result, so
                # tier-1 members' coefficients come from the same W-width
                # solve a homogeneous batch would have run.  Each member's
                # *other*-tier slot is blanked (empty E, zero warm start):
                # its discarded solve then converges in one iteration
                # instead of grinding a truncated or redundant sub-problem
                # to tolerance — under vmap the solves run in lockstep, so
                # one slow discarded member would stall the whole batch
                lam_next, beta, E, L = a
                # (the solvers already zero each member's warm start through
                # its mask, so blanking E alone blanks the whole problem)
                r1 = jax.vmap(solve_tier1, in_axes=solve_axes)(
                    Xq, yq, wq, lam_next, beta, E & ~over1[:, None], L)
                r2 = jax.vmap(solve_tier2, in_axes=solve_axes)(
                    Xq, yq, wq, lam_next, beta, E & over1[:, None], L)

                def sel(two, one):
                    o = over1.reshape((B,) + (1,) * (two.ndim - 1))
                    return jnp.where(o, two, one)

                return tuple(sel(t2, t1) for t2, t1 in zip(r2, r1))

            def compact_arm(a):
                # nested scalar gate: the all-tier-1 fast path stays a real
                # branch, so homogeneous steps never pay the second gather
                return lax.cond(jnp.any(over1), mixed, tier1_all, a)

        beta1, it1, L1 = lax.cond(
            fell_back,
            lambda a: jax.vmap(solve_masked_one, in_axes=solve_axes)(
                Xq, yq, wq, *a),
            compact_arm,
            args,
        )
        grad1 = jax.vmap(grad_one, in_axes=(x_ax, 0, 0, w_ax))(
            Xq, yq, beta1, wq)
        return beta1, grad1, it1, L1, fell_back, need

    nv_one = functools.partial(_new_violations, p=p, m=m, screening=screening)

    def screen_one(grad_i, prev_i, sp_i, s_i, lam_i, pv_i):
        return _screen_sets(grad_i, prev_i, sp_i, s_i, lam_i, p=p, m=m,
                            screening=screening, p_valid=pv_i)

    def kkt_one(grad_i, lam_i, E_i, strong_i, checked_i, pv_i):
        return _kkt_step(grad_i, lam_i, E_i, strong_i, checked_i, p=p, m=m,
                         kkt_tol=kkt_tol, screening=screening, p_valid=pv_i)

    kkt_all = jax.vmap(kkt_one, in_axes=(0, 0, 0, 0, 0, pv_axis))

    def step(carry, sigs):
        beta, grad, prev_active, L_carry, health = carry
        sig_prev, sig = sigs                      # (B,), (B,)
        lam_next = sig[:, None] * lam             # (B, p·m)

        # quarantine gate, member-for-member what _step_builder applies:
        # sick members run on zeroed data/carry and a blanked working set
        # (shared X stays untouched — the member's weights are zeroed)
        sick = health != 0                        # (B,)
        Xq = (X if shared_x
              else jnp.where(sick[:, None, None], jnp.zeros((), dtype), X))
        yq = jnp.where(sick.reshape((B,) + (1,) * (y.ndim - 1)),
                       jnp.zeros((), y.dtype), y)
        wq = (None if rw is None
              else jnp.where(sick[:, None], jnp.zeros((), rw.dtype), rw))
        beta = jnp.where(sick[:, None, None], 0, beta)
        grad = jnp.where(sick[:, None, None], 0, grad)
        prev_active = prev_active & ~sick[:, None]
        L_carry = jnp.where(sick, jnp.ones((), L_carry.dtype), L_carry)

        if screening == "none":
            if p_valid is None:
                strong_p = jnp.ones((B, p), bool)
                n_screened = jnp.full((B,), p, jnp.int32)
            else:
                strong_p = jnp.arange(p)[None, :] < p_valid[:, None]
                n_screened = jnp.asarray(p_valid, jnp.int32)
            E0 = strong_p
        else:
            strong_p, E0, n_screened = jax.vmap(
                screen_one, in_axes=(0, 0, 0, 0, 0, pv_axis)
            )(grad, prev_active, sig_prev, sig, lam, p_valid)
        E0 = E0 & ~sick[:, None]
        strong_p = strong_p & ~sick[:, None]
        n_screened = jnp.where(sick, 0, n_screened)

        beta1, grad1, it1, L1, fb1, need1 = solve_all(Xq, yq, wq, E0,
                                                      lam_next, beta, L_carry)

        if screening == "none":
            beta_f, grad_f, L_f = beta1, grad1, L1
            viol_count = jnp.zeros((B,), jnp.int32)
            refits = jnp.zeros((B,), jnp.int32)
            iters = it1
            unrepaired = jnp.zeros((B,), bool)
            fell_back = fb1
            ws_max = need1
        else:
            viol1, checked1 = kkt_all(grad1, lam_next, E0, strong_p,
                                      jnp.zeros((B,), bool), p_valid)
            state = dict(
                beta=beta1, grad=grad1, L=L1,
                E=E0 | viol1.reshape(B, p, m).any(axis=2),
                checked=checked1,
                has_viol=viol1.reshape(B, -1).any(axis=1) & ~sick,
                viol_count=jax.vmap(nv_one)(viol1, strong_p, prev_active),
                refits=jnp.zeros((B,), jnp.int32), iters=it1,
                fell_back=fb1, ws_max=need1,
            )

            def cond(s):
                return jnp.any(s["has_viol"] & (s["refits"] < max_refits))

            def body(s):
                # members already KKT-clean keep their state (mirrors the
                # per-member select vmap applies to a batched while_loop).
                # Their E is blanked for this round so only members still
                # repairing count toward the overflow predicate — their
                # (discarded) solve must not force the masked fallback.
                active = s["has_viol"] & (s["refits"] < max_refits)
                beta2, grad2, it2, L2, fb2, need2 = solve_all(
                    Xq, yq, wq, s["E"] & active[:, None], lam_next,
                    s["beta"], s["L"])
                viol2, checked2 = kkt_all(grad2, lam_next, s["E"],
                                          strong_p, s["checked"], p_valid)

                def sel(new, old):
                    a = active.reshape((B,) + (1,) * (new.ndim - 1))
                    return jnp.where(a, new, old)

                viol_rows = viol2.reshape(B, p, m).any(axis=2)
                return dict(
                    beta=sel(beta2, s["beta"]),
                    grad=sel(grad2, s["grad"]),
                    L=sel(L2, s["L"]),
                    E=sel(s["E"] | viol_rows, s["E"]),
                    checked=sel(checked2, s["checked"]),
                    has_viol=sel(viol2.reshape(B, -1).any(axis=1),
                                 s["has_viol"]),
                    viol_count=s["viol_count"] + jnp.where(
                        active, jax.vmap(nv_one)(viol2, strong_p, prev_active),
                        0),
                    refits=s["refits"] + active.astype(jnp.int32),
                    iters=s["iters"] + jnp.where(active, it2, 0),
                    fell_back=s["fell_back"] | fb2,
                    ws_max=jnp.maximum(s["ws_max"], need2),
                )

            state = lax.while_loop(cond, body, state)
            beta_f, grad_f, L_f = state["beta"], state["grad"], state["L"]
            viol_count = state["viol_count"]
            refits = state["refits"]
            iters = state["iters"]
            unrepaired = state["has_viol"]  # loop exited on the refit cap
            fell_back = state["fell_back"]
            ws_max = state["ws_max"]

        dev = jax.vmap(dev_one, in_axes=(x_ax, 0, 0, w_ax))(Xq, yq, beta_f,
                                                            wq)
        # health detection + output quarantine, member-for-member what
        # _step_builder applies (sticky word, NaNs never escape the carry)
        bad_input = lam_bad | ~(jnp.isfinite(sig_prev) & jnp.isfinite(sig))
        bad_state = ~(jnp.isfinite(beta_f).reshape(B, -1).all(axis=1)
                      & jnp.isfinite(grad_f).reshape(B, -1).all(axis=1)
                      & jnp.isfinite(L_f))
        bad_dev = ~jnp.isfinite(dev) | (dev > dev_bound)
        zero32 = jnp.zeros((B,), jnp.int32)
        health = (health
                  | jnp.where(bad_input, jnp.int32(HEALTH_NONFINITE_INPUT),
                              zero32)
                  | jnp.where(bad_state, jnp.int32(HEALTH_NONFINITE_STATE),
                              zero32)
                  | jnp.where(bad_dev, jnp.int32(HEALTH_DIVERGED), zero32))
        sick_out = health != 0
        beta_f = jnp.where(sick_out[:, None, None], 0, beta_f)
        grad_f = jnp.where(sick_out[:, None, None], 0, grad_f)
        L_f = jnp.where(sick_out, jnp.ones((), L_f.dtype), L_f)

        active = (jnp.abs(beta_f) > 0).any(axis=2)
        # which tier served each member this step: 0 on fallback steps (the
        # whole batch ran masked), else the smallest tier covering the
        # member's peak demand across repair rounds
        tier = jnp.where(fell_back, jnp.int32(0),
                         jnp.where(ws_max > W, jnp.int32(2), jnp.int32(1)))
        out = (beta_f, active.sum(axis=1).astype(jnp.int32), n_screened,
               viol_count, refits, iters, dev, unrepaired, health, ws_max,
               tier, fell_back & jnp.ones((B,), bool))
        return (beta_f, grad_f, active, L_f, health), out

    carry0 = (jnp.zeros((B, p, m), dtype), grad0, jnp.zeros((B, p), bool),
              L_init0, health0)
    xs = (sigmas[:, :-1].T, sigmas[:, 1:].T)  # scan over the path axis
    _, outs = lax.scan(step, carry0, xs)
    (betas, n_act, n_scr, viol, refits, iters, devs, unrep, hlth, ws, tiers,
     fb) = outs

    def pre(a, v):
        a = jnp.moveaxis(a, 0, 1)  # (L-1, B, ...) -> (B, L-1, ...)
        v = jnp.broadcast_to(jnp.asarray(v, a.dtype),
                             (a.shape[0],) + a.shape[2:])
        return jnp.concatenate([v[:, None], a], axis=1)

    ep = EnginePath(
        betas=pre(betas, jnp.zeros((p, m), dtype)),
        n_active=pre(n_act, 0),
        n_screened=pre(n_scr, 0),
        n_violations=pre(viol, 0),
        refits=pre(refits, 0),
        solver_iters=pre(iters, 0),
        deviance=jnp.concatenate([null_dev[:, None],
                                  jnp.moveaxis(devs, 0, 1)], axis=1),
        kkt_unrepaired=pre(unrep, False),
        health=jnp.concatenate([health0[:, None],
                                jnp.moveaxis(hlth, 0, 1)], axis=1),
    )
    stats = CompactStats(ws_size=pre(ws, 0), tier=pre(tiers, 1),
                         fell_back=pre(fb, False))
    return ep, stats


_COMPACT_STATICS = _ENGINE_STATICS + ("width", "width2")


@functools.partial(jax.jit, static_argnames=_COMPACT_STATICS)
def compact_path_engine(X, y, lam, sigmas, family: Family, p_valid=None, *,
                        width: int, width2: int | None = None,
                        screening: str = "strong", max_iter: int = 5000,
                        tol: float = 1e-8, kkt_tol: float = 1e-4,
                        max_refits: int = 32):
    """Batched path engine with working sets compacted to a static ``width``
    bucket: the inner solve costs O(n·W) instead of O(n·p), with a batch-wide
    ``lax.cond`` fallback to the masked full-width solve on overflow.

    ``width2`` (optional) adds a second compact tier: members whose screened
    set exceeds ``width`` but fits ``width2`` are served by a wider gather
    instead of dragging the whole batch into the masked fallback (which then
    fires only for demand beyond ``width2``).

    ``X``: (B, n, p); ``y``: (B, n[, ...]); ``sigmas``: (B, L); ``lam``
    shared (p·m,) or per-member (B, p·m); ``p_valid`` (optional, (B,)
    int32) marks bucket padding per member.  Returns ``(EnginePath,
    CompactStats)`` with leading batch axes.  One compilation per
    (B, n, p, m, L, W, W2, config).
    """
    return _compact_engine(X, y, lam, sigmas, family, screening, max_iter,
                           tol, kkt_tol, max_refits, width, p_valid, width2)


@functools.partial(jax.jit, static_argnames=_COMPACT_STATICS)
def replicate_compact_path_engine(X, y, lam, sigmas, weights,
                                  family: Family, p_valid=None, *,
                                  width: int, width2: int | None = None,
                                  screening: str = "strong",
                                  max_iter: int = 5000, tol: float = 1e-8,
                                  kkt_tol: float = 1e-4,
                                  max_refits: int = 32):
    """Compact-working-set replicate engine: B row-reweighted paths against
    ONE shared (n, p) X with per-member W-bucket gathers.

    The compact counterpart of :func:`replicate_path_engine`: each member
    gathers its ≤ W screened columns from the SAME shared design, so the
    resident footprint is O(n·p + B·n·W) — the only per-member matrix ever
    built is the (n, W) compact gather the inner solves run on.  ``X``:
    (n, p); ``y``: (n,) shared or (B, n) per-member; ``weights``: (B, n);
    ``sigmas``: (L,) shared grid; ``lam`` one (p·m,) sequence.  Returns
    ``(EnginePath, CompactStats)`` with leading (B,) replicate axes.
    """
    B = weights.shape[0]
    if y.ndim == 1:
        y = jnp.broadcast_to(y, (B,) + y.shape)
    sig = jnp.broadcast_to(sigmas, (B,) + sigmas.shape)
    if p_valid is not None:  # shared scalar -> the engine's per-member form
        p_valid = jnp.broadcast_to(jnp.asarray(p_valid, jnp.int32), (B,))
    return _compact_engine(X, y, lam, sig, family, screening, max_iter,
                           tol, kkt_tol, max_refits, width, p_valid, width2,
                           rw=weights, shared_x=True)


# ---------------------------------------------------------------------------
# Host-facing wrappers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchedPathResult:
    """B paths fitted by one compiled program (leading axis = problem)."""

    betas: np.ndarray         # (B, L, p) or (B, L, p, m)
    sigmas: np.ndarray        # (B, L)
    lam: np.ndarray
    n_active: np.ndarray      # (B, L)
    n_screened: np.ndarray
    n_violations: np.ndarray
    refits: np.ndarray
    solver_iters: np.ndarray
    deviance: np.ndarray
    kkt_unrepaired: np.ndarray  # (B, L) bool — see EnginePath.kkt_unrepaired
    total_time: float
    n_samples: int            # rows per problem (early-stop rules need it)
    health: np.ndarray | None = None      # (B, L) int32 HEALTH_* words
    working_set: int | None = None        # W bucket (None: masked engine)
    working_set_top: int | None = None    # second-tier bucket (None: one tier)
    ws_size: np.ndarray | None = None     # (B, L) peak |E| per step
    ws_tier: np.ndarray | None = None     # (B, L) serving tier per step
    #   (1 = W, 2 = the top tier, 0 = the step ran the masked fallback)
    compact_fallback: np.ndarray | None = None  # (B, L) masked-fallback steps
    pad_shape: tuple | None = None        # (slots, N, P) executed shape when
    #   pad="bucket" routed the batch through the serve layer's buckets
    plan: object | None = None            # repro.api ExecutionPlan when the
    #   fit was dispatched through slope_path (None for direct impl calls)
    path_trace: object | None = None      # repro.obs.PathTrace when the fit
    #   ran with telemetry="summary"|"steps" (None when "off")

    @property
    def batch(self) -> int:
        return self.betas.shape[0]

    @property
    def total_violations(self) -> np.ndarray:
        return self.n_violations.sum(axis=1)

    @property
    def path_health(self) -> PathHealth | None:
        """Per-member quarantine verdicts (None for pre-health pickles)."""
        return None if self.health is None else PathHealth(word=self.health)

    def path_results(self, *, early_stop: bool = True):
        """Per-problem :class:`repro.core.path.PathResult` views (the same
        contract the unbatched driver returns, early stopping applied
        post-hoc)."""
        from .path import engine_to_path_result  # lazy: avoid import cycle

        per = self.total_time / self.batch
        return [
            engine_to_path_result(
                EnginePath(
                    betas=self.betas[b] if self.betas.ndim == 4
                    else self.betas[b][:, :, None],
                    n_active=self.n_active[b],
                    n_screened=self.n_screened[b],
                    n_violations=self.n_violations[b],
                    refits=self.refits[b],
                    solver_iters=self.solver_iters[b],
                    deviance=self.deviance[b],
                    kkt_unrepaired=self.kkt_unrepaired[b],
                    health=(np.zeros(self.deviance[b].shape, np.int32)
                            if self.health is None else self.health[b]),
                ),
                self.sigmas[b], self.lam, per, early_stop=early_stop,
                n=self.n_samples,
            )
            for b in range(self.batch)
        ]


def null_gradient(X, y, family: Family, span: dict | None = None
                  ) -> np.ndarray:
    """∇f(0) reshaped to (p, m) — the quantity both the σ-grid recipe and
    the first strong-rule step start from.  ``span`` counts its transfers
    (:mod:`repro.core.transfer`)."""
    p = X.shape[1]
    m = family.n_classes
    beta0 = jnp.zeros((p,) if m == 1 else (p, m), X.dtype)
    return get(family.gradient(put(X, span), put(y, span), beta0),
               span).reshape(p, m)


def null_sigma_grid(X, y, lam, family: Family, *, path_length: int,
                    sigma_ratio: float | None,
                    grad0: np.ndarray | None = None,
                    span: dict | None = None) -> np.ndarray:
    """The paper's σ grid for one problem: σ(1) from the null gradient's
    dual gauge, geometric decay per §3.1.2.  The ONE recipe shared by
    fit_path (both backends), fit_path_batched and cv_path."""
    if grad0 is None:
        grad0 = null_gradient(X, y, family, span)
    s1 = float(get(path_start_sigma(put(grad0, span), put(lam, span)),
                   span))
    n, p = X.shape
    return sigma_grid(s1, length=path_length, ratio=sigma_ratio, n=n, p=p,
                      dtype=X.dtype)


def _null_sigma_grids(Xs, ys, lam, family: Family, path_length, sigma_ratio):
    """Per-problem σ grids (stacked :func:`null_sigma_grid`)."""
    lam = np.asarray(lam)
    return np.stack([
        null_sigma_grid(Xs[b], ys[b], lam[b] if lam.ndim == 2 else lam,
                        family, path_length=path_length,
                        sigma_ratio=sigma_ratio)
        for b in range(Xs.shape[0])
    ])


# Grow-on-overflow bucket memory: (n, p, m, family, screening) → last W that
# overflowed, promoted to the next power of two.  Correctness never depends
# on it (overflow steps fall back to the masked solve in-graph); it just
# stops the NEXT same-shape call from paying the fallback again.  A proper
# thread-safe bounded registry (PR 3) shared with repro.serve: the path
# service resolves compact widths through this same instance, so a service
# batch that overflows grows the bucket the next direct call sees.
_WS_BUCKETS = BucketRegistry(name="working_set", capacity=256)

_next_pow2 = next_pow2  # promoted to repro.serve.buckets; alias kept local


def _chunked_path(Xs, ys, lam, sigmas, family: Family, p_valid, *,
                  step_chunk: int, **engine_kw) -> EnginePath:
    """The masked path run the way the async service runs it: the
    :func:`path_init_engine` prefill, then :func:`chunk_path_engine` calls
    of ``step_chunk`` σ-steps with the carry round-tripped through the
    host.  Bitwise the service's result on any backend (same programs,
    same operands); on the CPU also bitwise :func:`batched_path_engine`'s.
    """
    S, _, P = Xs.shape
    m = family.n_classes
    f = Xs.dtype
    L = sigmas.shape[1]
    C = step_chunk
    g0, nd0, L0, h0 = (np.asarray(a) for a in
                       path_init_engine(jnp.asarray(Xs), jnp.asarray(ys),
                                        family))
    carry = (np.zeros((S, P, m), f), g0, np.zeros((S, P), bool), L0, h0)
    parts = []
    for start in range(1, L, C):
        take = min(C, L - start)
        sig_prev = np.ones((S, C), f)
        sig_next = np.ones((S, C), f)
        live = np.zeros((S, C), bool)
        sig_prev[:, :take] = sigmas[:, start - 1:start - 1 + take]
        sig_next[:, :take] = sigmas[:, start:start + take]
        live[:, :take] = True
        out, ep = chunk_path_engine(Xs, ys, lam, sig_prev, sig_next, live,
                                    *carry, family, p_valid, **engine_kw)
        carry = tuple(np.asarray(a) for a in out)
        parts.append([np.asarray(a)[:, :take] for a in ep])
    head = EnginePath(
        betas=np.zeros((S, 1, P, m), f), n_active=np.zeros((S, 1), np.int32),
        n_screened=np.zeros((S, 1), np.int32),
        n_violations=np.zeros((S, 1), np.int32),
        refits=np.zeros((S, 1), np.int32),
        solver_iters=np.zeros((S, 1), np.int32), deviance=nd0[:, None],
        kkt_unrepaired=np.zeros((S, 1), bool), health=h0[:, None])
    return EnginePath(*(np.concatenate([h] + [pt[i] for pt in parts], axis=1)
                        for i, h in enumerate(head)))


def _ws_bucket(working_set, n: int, p: int, key: tuple) -> int:
    """Resolve the static compact width W to a power-of-two bucket ≤ p."""
    if isinstance(working_set, int):
        if working_set < 1:
            raise ValueError(f"working_set must be ≥ 1, got {working_set}")
        return min(_next_pow2(working_set), p)
    if working_set != "auto":
        raise ValueError(
            f"working_set must be None, an int or 'auto', got {working_set!r}")
    grown = _WS_BUCKETS.get(key)
    if grown is not None:
        return min(grown, p)
    # p ≫ n: the screened set tracks the active set, which cannot exceed n
    # useful coefficients by much — 2n is a comfortable first bucket
    return min(_next_pow2(max(2 * n, 64)), p)


def second_tier_width(W: int, ws_tiers, p: int) -> int | None:
    """The second tier for a resolved W bucket: ``2·W`` for ``ws_tiers``
    "auto"/2 whenever ``2·W < p`` (a top tier spanning p would be the
    masked solve with gather overhead on top, so it degenerates to
    single-tier), None otherwise.  Factored out so the planner can derive
    the tier pair from its already-previewed W — one registry read, no
    window for the pair to desynchronize."""
    if ws_tiers not in ("auto", 1, 2):
        raise ValueError(
            f"ws_tiers must be 'auto', 1 or 2, got {ws_tiers!r}")
    if ws_tiers == 1 or 2 * W >= p:
        return None
    return 2 * W


def resolve_ws_tiers(working_set, ws_tiers, n: int, p: int,
                     key: tuple) -> tuple[int, int | None]:
    """Resolve the compact tier widths ``(W, W2)`` for one run.

    ``W`` comes from :func:`_ws_bucket` (explicit int / registry / auto
    recipe); ``W2`` from :func:`second_tier_width`.  The ONE tier recipe,
    shared by the engine, the planner preview and the serve layer so the
    three can never disagree on what shape actually compiles.
    """
    W = _ws_bucket(working_set, n, p, key)
    return W, second_tier_width(W, ws_tiers, p)


def grow_ws_bucket(ws_key: tuple, ws_size, fell_back, W: int,
                   p_cap: int, *, two_tier: bool = False) -> bool:
    """Grow the shared working-set registry after an overflowing "auto" run.

    ``ws_size``/``fell_back`` are the run's CompactStats arrays (real
    members only); ``p_cap`` bounds the promoted bucket (a bucket wider
    than the column count is wasted compaction).  ``two_tier`` marks a run
    whose next same-shape call will carry a 2W second tier: the registry
    then only needs the HALF-peak bucket — tier 2 covers (W, 2W], so
    ``W = 2^⌈log₂ peak⌉ / 2`` already makes the whole observed demand
    compact-servable at half the gather width the single-tier rule would
    store.  The ONE growth rule, shared by :func:`fit_path_batched` and
    the path service so the two front-ends can never desynchronize the
    registry they share.  Growth is monotonic and idempotent
    (:meth:`BucketRegistry.grow`): concurrent overflowing runs can only
    raise the stored bucket, never shrink it.  Returns True if the bucket
    grew.
    """
    if W >= p_cap or not np.asarray(fell_back).any():
        return False
    target = _next_pow2(int(np.asarray(ws_size).max()))
    if two_tier and target < p_cap:
        # the next run's second tier will sit at 2·(target/2) = target and
        # cover the observed peak; fell_back implies peak > 2W, so the
        # half-peak bucket (≥ 2W) still strictly exceeds the current W.
        # (target ≥ p_cap keeps the full width: a halved bucket would get
        # no 2× tier under the cap and just overflow again.)
        target = max(target // 2, 1)
    return _WS_BUCKETS.grow(ws_key, target, cap=p_cap)


def _fit_path_batched(
    Xs, ys, lam, family: Family, *,
    screening: str = "strong",
    path_length: int = 100,
    sigma_ratio: float | None = None,
    sigmas: np.ndarray | None = None,
    solver_tol: float = DEFAULT_PATH_TOL,
    max_iter: int = DEFAULT_PATH_MAX_ITER,
    kkt_tol: float = DEFAULT_KKT_TOL,
    max_refits: int = DEFAULT_MAX_REFITS,
    working_set: int | str | None = None,
    ws_tiers: int | str = DEFAULT_WS_TIERS,
    pad: str | None = None,
    telemetry: str = "off",
) -> BatchedPathResult:
    """Fit B independent SLOPE paths in one compiled device program.

    ``Xs`` is (B, n, p) and ``ys`` (B, n) — problems of identical shape share
    one compilation (the bucketing policy: pad mixed shapes on the host).
    Semantics match ``fit_path(..., engine="device")`` per problem.  Steps
    whose KKT repair hit ``max_refits`` are flagged in ``kkt_unrepaired``
    (and warned about) — raise the cap if that ever fires.

    ``lam`` is one shared (p·m,) rank sequence or a per-problem (B, p·m)
    stack (what the serve layer uses to co-batch requests of different
    native widths inside one padded program).

    ``working_set`` selects the compact engine: an int requests a static
    width bucket W (rounded up to a power of two, capped at p), ``"auto"``
    picks ``min(2^⌈log₂ max(2n, 64)⌉, p)`` with grow-on-overflow memory, and
    ``None`` keeps the masked full-width engine.  Compact solves cost
    O(n·W) per FISTA iteration.  ``ws_tiers`` ("auto"/1/2, see
    :func:`resolve_ws_tiers`) controls the second tier at 2·W: a member
    whose working set outgrows W but fits 2·W is served by the wider
    gather; only demand beyond the top tier falls back — correctly,
    in-graph — to the masked solve for the whole batch and is flagged in
    ``compact_fallback`` (per-member serving tiers in ``ws_tier``).

    ``pad="bucket"`` routes the batch through the serve layer's canonical
    execution shapes (:mod:`repro.serve.buckets`): rows/columns/batch slots
    are padded to power-of-two buckets with inert zeros, screening and KKT
    checks are restricted to the valid prefix (``p_valid``), and results
    come back unpadded.  Problems then share compiled programs across
    nearby shapes — and, because the :class:`~repro.serve.service.PathService`
    resolves shapes through the same policy, a padded direct call is
    bit-identical to the same request served through the service.
    """
    Xs = np.asarray(Xs)
    ys = np.asarray(ys)
    if Xs.ndim != 3:
        raise ValueError(f"Xs must be (B, n, p), got {Xs.shape}")
    if ys.shape[:2] != Xs.shape[:2]:
        raise ValueError(
            f"ys must be (B, n[, ...]) matching Xs {Xs.shape[:2]}, got {ys.shape}")
    if pad not in (None, "bucket"):
        raise ValueError(f"pad must be None or 'bucket', got {pad!r}")
    if telemetry not in ("off", "summary", "steps"):
        raise ValueError(
            f"telemetry must be 'off', 'summary' or 'steps', got "
            f"{telemetry!r}")
    lam = np.asarray(lam)
    B, n, p = Xs.shape
    m = family.n_classes
    if lam.ndim == 2 and lam.shape != (B, p * m):
        raise ValueError(
            f"per-problem lam must be (B, p·m) = {(B, p * m)}, got {lam.shape}")
    if lam.ndim not in (1, 2):
        raise ValueError(f"lam must be (p·m,) or (B, p·m), got {lam.shape}")
    if sigmas is None:
        sigmas = _null_sigma_grids(Xs, ys, lam, family, path_length,
                                   sigma_ratio)
    sigmas = np.asarray(sigmas)
    if sigmas.ndim == 1:  # one shared grid, like fit_path's 1-D sigmas
        sigmas = np.tile(sigmas, (B, 1))
    if sigmas.shape[0] != B or sigmas.ndim != 2:
        raise ValueError(
            f"sigmas must be (L,) shared or (B, L) per-problem; got "
            f"{sigmas.shape} for B={B}")

    p_valid = None
    pad_shape = None
    Xs_run, ys_run, lam_run, sig_run = Xs, ys, lam, sigmas
    n_run, p_run = n, p
    if pad == "bucket":
        from ..serve.buckets import default_policy, pad_batch

        policy = default_policy()
        n_run, p_run = policy.shape_bucket(n, p, family.name)
        slots = policy.direct_slots(B)
        lam2 = lam if lam.ndim == 2 else np.broadcast_to(lam, (B, p * m))
        pb = pad_batch(
            [(Xs[b], ys[b], lam2[b], sigmas[b]) for b in range(B)],
            n_rows=n_run, n_cols=p_run, n_slots=slots, n_classes=m)
        Xs_run, ys_run, lam_run, sig_run = pb.Xs, pb.ys, pb.lam, pb.sigmas
        p_valid = jnp.asarray(pb.p_valid)
        pad_shape = (slots, n_run, p_run)

    engine_kw = dict(screening=screening, max_iter=max_iter, tol=solver_tol,
                     kkt_tol=kkt_tol, max_refits=max_refits)
    t0 = time.perf_counter()
    W = W2 = None
    stats = None
    if working_set is None and pad == "bucket":
        res = _chunked_path(Xs_run, ys_run, lam_run, sig_run, family,
                            p_valid, step_chunk=policy.step_chunk,
                            **engine_kw)
    elif working_set is None:
        res = batched_path_engine(
            jnp.asarray(Xs_run), jnp.asarray(ys_run), jnp.asarray(lam_run),
            jnp.asarray(sig_run), family, p_valid, **engine_kw)
    else:
        ws_key = (n_run, p_run, m, family.name, screening)
        W, W2 = resolve_ws_tiers(working_set, ws_tiers, n_run, p_run, ws_key)
        res, stats = compact_path_engine(
            jnp.asarray(Xs_run), jnp.asarray(ys_run), jnp.asarray(lam_run),
            jnp.asarray(sig_run), family, p_valid, width=W, width2=W2,
            **engine_kw)
    res = EnginePath(*(np.asarray(a) for a in res))
    wall = time.perf_counter() - t0
    if stats is not None:
        stats = CompactStats(*(np.asarray(a) for a in stats))
    if pad_shape is not None:  # drop dummy slots + padded columns
        res = EnginePath(
            betas=res.betas[:B, :, :p, :],
            n_active=res.n_active[:B], n_screened=res.n_screened[:B],
            n_violations=res.n_violations[:B], refits=res.refits[:B],
            solver_iters=res.solver_iters[:B], deviance=res.deviance[:B],
            kkt_unrepaired=res.kkt_unrepaired[:B], health=res.health[:B])
        if stats is not None:
            stats = CompactStats(ws_size=stats.ws_size[:B],
                                 tier=stats.tier[:B],
                                 fell_back=stats.fell_back[:B])
    betas = res.betas  # (B, L, p, m)
    if m == 1:
        betas = betas[:, :, :, 0]
    unrepaired = res.kkt_unrepaired
    _warn_unrepaired(unrepaired, max_refits)
    _warn_quarantined(res.health)
    ws_size = ws_tier = fallback = None
    if stats is not None:
        ws_size = stats.ws_size
        ws_tier = stats.tier
        fallback = stats.fell_back
        # grow the bucket for the next same-shape "auto" call; explicit-int
        # runs (e.g. a deliberately undersized overflow probe) must not
        # seed "auto" with a bucket below its documented default
        if working_set == "auto":
            grow_ws_bucket(ws_key, ws_size, fallback, W, p_run,
                           two_tier=ws_tiers != 1)
    path_trace = None
    if telemetry != "off":
        # built host-side from arrays the transfer above already landed —
        # one per fit, off the compiled program's path entirely
        from ..obs import PathTrace

        path_trace = PathTrace.from_arrays(
            mode=telemetry, p=p, sigmas=sigmas,
            n_screened=res.n_screened, n_active=res.n_active,
            n_violations=res.n_violations, refits=res.refits,
            solver_iters=res.solver_iters, health=res.health,
            working_set=W, working_set_top=W2, ws_size=ws_size,
            ws_tier=ws_tier, compact_fallback=fallback)
    return BatchedPathResult(
        betas=betas,
        sigmas=sigmas,
        lam=lam,
        n_active=res.n_active,
        n_screened=res.n_screened,
        n_violations=res.n_violations,
        refits=res.refits,
        solver_iters=res.solver_iters,
        deviance=res.deviance,
        kkt_unrepaired=unrepaired,
        total_time=wall,
        n_samples=n,
        health=res.health,
        working_set=W,
        working_set_top=W2,
        ws_size=ws_size,
        ws_tier=ws_tier,
        compact_fallback=fallback,
        pad_shape=pad_shape,
        path_trace=path_trace,
    )


def _fit_replicate_batched(
    X, y, lam, family: Family, weights, *,
    screening: str = "strong",
    path_length: int = 100,
    sigma_ratio: float | None = None,
    sigmas: np.ndarray | None = None,
    solver_tol: float = DEFAULT_PATH_TOL,
    max_iter: int = DEFAULT_PATH_MAX_ITER,
    kkt_tol: float = DEFAULT_KKT_TOL,
    max_refits: int = DEFAULT_MAX_REFITS,
    working_set: int | str | None = None,
    ws_tiers: int | str = DEFAULT_WS_TIERS,
    telemetry: str = "off",
) -> BatchedPathResult:
    """Fit B row-reweighted paths against ONE shared (n, p) design.

    The replicate counterpart of :func:`_fit_path_batched`: ``X`` is a
    single (n, p) design shared by every member, ``weights`` a (B, n)
    per-member row-weight matrix (bootstrap counts / subsample masks /
    direct sample weights), ``y`` the shared (n,) response or a (B, n)
    per-member stack (permutation replicates).  Memory stays
    O(n·p + B·n) — no (B, n, p) batch is ever materialized.

    The σ grid is shared across members (computed from the *unweighted*
    problem when not given), so per-grid-point statistics compare like
    with like; a (B, n) ``y`` needs an explicit ``sigmas``.
    """
    X = np.asarray(X)
    lam = np.asarray(lam)
    weights_np = np.asarray(weights)
    if X.ndim != 2:
        raise ValueError(f"X must be one shared (n, p) design, got {X.shape}")
    n, p = X.shape
    if weights_np.ndim != 2 or weights_np.shape[1] != n:
        raise ValueError(
            f"weights must be (B, n) = (B, {n}), got {weights_np.shape}")
    B = weights_np.shape[0]
    m = family.n_classes
    y_np = np.asarray(y)
    if sigmas is None:
        if y_np.ndim != 1:  # per-member (B, n) stack: no canonical grid
            raise ValueError(
                "per-member (B, n) responses need an explicit shared σ "
                "grid (compute it from the original problem first)")
        sigmas = null_sigma_grid(X, y_np, lam, family,
                                 path_length=path_length,
                                 sigma_ratio=sigma_ratio)
    sigmas = np.asarray(sigmas)
    if sigmas.ndim != 1:
        raise ValueError(
            f"replicates share one (L,) σ grid, got {sigmas.shape}")

    engine_kw = dict(screening=screening, max_iter=max_iter, tol=solver_tol,
                     kkt_tol=kkt_tol, max_refits=max_refits)
    t0 = time.perf_counter()
    W = W2 = None
    stats = None
    if working_set is None:
        res = replicate_path_engine(
            jnp.asarray(X), jnp.asarray(y_np), jnp.asarray(lam),
            jnp.asarray(sigmas), jnp.asarray(weights_np), family, **engine_kw)
    else:
        ws_key = (n, p, m, family.name, screening)
        W, W2 = resolve_ws_tiers(working_set, ws_tiers, n, p, ws_key)
        res, stats = replicate_compact_path_engine(
            jnp.asarray(X), jnp.asarray(y_np), jnp.asarray(lam),
            jnp.asarray(sigmas), jnp.asarray(weights_np), family,
            width=W, width2=W2, **engine_kw)
    res = EnginePath(*(np.asarray(a) for a in res))
    wall = time.perf_counter() - t0
    if stats is not None:
        stats = CompactStats(*(np.asarray(a) for a in stats))
    betas = res.betas  # (B, L, p, m)
    if m == 1:
        betas = betas[:, :, :, 0]
    unrepaired = res.kkt_unrepaired
    _warn_unrepaired(unrepaired, max_refits)
    _warn_quarantined(res.health)
    ws_size = ws_tier = fallback = None
    if stats is not None:
        ws_size = stats.ws_size
        ws_tier = stats.tier
        fallback = stats.fell_back
        if working_set == "auto":
            grow_ws_bucket(ws_key, ws_size, fallback, W, p,
                           two_tier=ws_tiers != 1)
    path_trace = None
    if telemetry != "off":
        from ..obs import PathTrace

        path_trace = PathTrace.from_arrays(
            mode=telemetry, p=p, sigmas=np.tile(sigmas, (B, 1)),
            n_screened=res.n_screened, n_active=res.n_active,
            n_violations=res.n_violations, refits=res.refits,
            solver_iters=res.solver_iters, health=res.health,
            working_set=W, working_set_top=W2, ws_size=ws_size,
            ws_tier=ws_tier, compact_fallback=fallback)
    return BatchedPathResult(
        betas=betas,
        sigmas=np.tile(sigmas, (B, 1)),
        lam=lam,
        n_active=res.n_active,
        n_screened=res.n_screened,
        n_violations=res.n_violations,
        refits=res.refits,
        solver_iters=res.solver_iters,
        deviance=res.deviance,
        kkt_unrepaired=unrepaired,
        total_time=wall,
        n_samples=n,
        health=res.health,
        working_set=W,
        working_set_top=W2,
        ws_size=ws_size,
        ws_tier=ws_tier,
        compact_fallback=fallback,
        path_trace=path_trace,
    )


def _warn_unrepaired(unrepaired: np.ndarray, max_refits: int) -> None:
    if unrepaired.any():
        import warnings

        warnings.warn(
            f"{int(unrepaired.sum())} path step(s) hit the KKT repair cap "
            f"(max_refits={max_refits}) with violations outstanding; those "
            "betas are not KKT-clean — raise max_refits",
            RuntimeWarning,
            stacklevel=3,
        )


def _warn_quarantined(health: np.ndarray) -> None:
    word = np.asarray(health)[:, -1]
    if word.any():
        import warnings

        bad = np.nonzero(word)[0]
        causes = sorted({c for w in word[bad] for c in health_causes(int(w))})
        warnings.warn(
            f"{bad.size} batch member(s) were quarantined in-graph "
            f"(members {bad.tolist()}, causes: {', '.join(causes)}); their "
            "betas are zeroed from the first sick step — inspect "
            "result.path_health",
            RuntimeWarning,
            stacklevel=3,
        )


@dataclasses.dataclass
class CvPathResult:
    """K-fold cross-validation over one shared σ grid."""

    sigmas: np.ndarray            # (L,) shared grid
    lam: np.ndarray
    val_deviance: np.ndarray      # (K, L) held-out deviance per fold
    mean_val_deviance: np.ndarray  # (L,)
    best_index: int               # per the requested selection rule
    best_sigma: float
    fold_paths: BatchedPathResult
    total_time: float
    se_val_deviance: np.ndarray | None = None  # (L,) SE over folds
    best_index_min: int = 0       # argmin of the mean deviance
    best_index_1se: int = 0       # sparsest σ within 1 SE of the minimum
    selection: str = "min"
    plan: object | None = None    # repro.api ExecutionPlan (slope_path only)


def cv_fold_indices(y, n_folds: int, *, family: Family | None = None,
                    stratify="auto"):
    """Equal-size fold assignment shared by :func:`cv_path` and the serve
    layer's CV requests.

    Every validation fold has exactly ⌊n/K⌋ rows (remainder rows are always
    in training) so all K training designs share ONE shape and batch into a
    single compiled program.  ``stratify=True`` deals class-sorted rows
    round-robin across folds so each fold sees the full-data class mix —
    essential for binomial/multinomial families, where a contiguous fold
    can end up single-class (its held-out deviance is then degenerate).
    ``"auto"`` stratifies exactly for those families.  Returns
    ``(trains, vals)``: two lists of K index arrays.
    """
    y = np.asarray(y)
    n = y.shape[0]
    if not 2 <= n_folds <= n:
        raise ValueError(f"n_folds must be in [2, {n}], got {n_folds}")
    if stratify == "auto":
        stratify = family is not None and family.name in ("logistic",
                                                          "multinomial")
    fold = n // n_folds
    if not stratify:
        vals = [np.arange(k * fold, (k + 1) * fold) for k in range(n_folds)]
    else:
        classes = np.asarray(np.rint(y), np.int64)
        order = np.argsort(classes, kind="stable")  # group rows by class
        assign = np.empty(n, np.int64)
        assign[order] = np.arange(n) % n_folds      # deal round-robin
        # trim each fold to exactly ⌊n/K⌋ rows; trimmed rows join the
        # always-in-training remainder, same as the contiguous scheme
        vals = [np.nonzero(assign == k)[0][:fold] for k in range(n_folds)]
    trains = [np.setdiff1d(np.arange(n), v) for v in vals]
    return trains, vals


def cv_val_deviance(X, y, val_indices, fold_betas, family: Family):
    """Held-out deviance (K, L) for stacked per-fold path coefficients.

    One batched evaluation of all K × L deviances (the fold and path axes
    share shapes, so this is two nested vmaps, not K·L dispatches).  Shared
    by :func:`cv_path` and the serve layer's CV aggregation so both compute
    bit-identical selection criteria from the same fold fits.
    """
    X = np.asarray(X)
    y = np.asarray(y)
    Xv = jnp.asarray(np.stack([X[v] for v in val_indices]))
    yv = jnp.asarray(np.stack([y[v] for v in val_indices]))

    def fold_devs(Xvk, yvk, betas_k):
        return jax.vmap(lambda b: family.loss(Xvk, yvk, b))(betas_k)

    return np.asarray(jax.vmap(fold_devs)(Xv, yv, jnp.asarray(fold_betas)))


def cv_select(val_dev: np.ndarray):
    """Deviance-based λ selection from a (K, L) held-out deviance table.

    Returns ``(mean, se, best_min, best_1se)``: the fold mean and its
    standard error per path point, the argmin index, and the 1-SE index —
    the *sparsest* grid point (largest σ, smallest index) whose mean
    deviance is within one standard error of the minimum.  The 1-SE rule
    trades a statistically-insignificant deviance increase for a sparser,
    more stable model (the ROADMAP's deviance-based 1-SE rule).
    """
    val_dev = np.asarray(val_dev)
    K = val_dev.shape[0]
    mean = val_dev.mean(axis=0)
    se = val_dev.std(axis=0, ddof=1) / np.sqrt(K)
    best_min = int(np.argmin(mean))
    thresh = mean[best_min] + se[best_min]
    best_1se = int(np.argmax(mean <= thresh))  # first index ⇔ largest σ
    return mean, se, best_min, best_1se


def _cv_path(
    X, y, lam, family: Family, *,
    n_folds: int = 5,
    screening: str = "strong",
    path_length: int = 100,
    sigma_ratio: float | None = None,
    solver_tol: float = DEFAULT_PATH_TOL,
    max_iter: int = DEFAULT_PATH_MAX_ITER,
    kkt_tol: float = DEFAULT_KKT_TOL,
    max_refits: int = DEFAULT_MAX_REFITS,
    working_set: int | str | None = None,
    ws_tiers: int | str = DEFAULT_WS_TIERS,
    stratify="auto",
    selection: str = "min",
    pad: str | None = None,
) -> CvPathResult:
    """K-fold CV: all fold paths fit as ONE batched device program.

    Every validation fold holds exactly ⌊n/K⌋ rows (remainder rows always
    in training) so the K training designs share one shape and batch into a
    single compilation; ``stratify`` controls class-balanced fold
    assignment (``"auto"``: on for binomial/multinomial — see
    :func:`cv_fold_indices`).  The σ grid is computed once from the full
    data and shared, so every fold is evaluated at the same penalty.

    ``selection`` picks the reported ``best_index``: ``"min"`` (lowest mean
    held-out deviance) or ``"1se"`` (sparsest σ within one standard error
    of it); both candidates are always reported.  ``working_set`` selects
    the compact engine exactly as in :func:`fit_path_batched` — the natural
    fit for CV's p ≫ n folds — and ``pad="bucket"`` routes the fold batch
    through the serve layer's canonical execution shapes.
    """
    if selection not in ("min", "1se"):
        raise ValueError(f"selection must be 'min' or '1se', got {selection!r}")
    t0 = time.perf_counter()
    X = np.asarray(X)
    y = np.asarray(y)
    lam = np.asarray(lam)

    sigmas = null_sigma_grid(X, y, lam, family, path_length=path_length,
                             sigma_ratio=sigma_ratio)

    trains, vals = cv_fold_indices(y, n_folds, family=family,
                                   stratify=stratify)
    res = _fit_path_batched(
        np.stack([X[tr] for tr in trains]),
        np.stack([y[tr] for tr in trains]),
        lam, family, screening=screening,
        sigmas=sigmas, solver_tol=solver_tol,  # 1-D grid: shared across folds
        max_iter=max_iter, kkt_tol=kkt_tol, max_refits=max_refits,
        working_set=working_set, ws_tiers=ws_tiers, pad=pad,
    )

    val_dev = cv_val_deviance(X, y, vals, res.betas, family)
    mean_dev, se_dev, best_min, best_1se = cv_select(val_dev)
    best = best_1se if selection == "1se" else best_min
    return CvPathResult(
        sigmas=sigmas,
        lam=lam,
        val_deviance=val_dev,
        mean_val_deviance=mean_dev,
        best_index=best,
        best_sigma=float(sigmas[best]),
        fold_paths=res,
        total_time=time.perf_counter() - t0,
        se_val_deviance=se_dev,
        best_index_min=best_min,
        best_index_1se=best_1se,
        selection=selection,
    )


# ---------------------------------------------------------------------------
# Legacy entry points — thin shims over the declarative repro.api layer
# ---------------------------------------------------------------------------

# "kwarg not passed" sentinel (legacy defaults must not warn).  Local on
# purpose: importing repro.api.compat.UNSET at module level would run
# repro.api/__init__ while repro.core is still initialising (api.plan pulls
# engine attributes) — each shim module only ever compares its own sentinel.
_UNSET = object()


def _legacy_backend(working_set):
    """Map the legacy ``working_set`` knob onto a SolverPolicy backend."""
    if working_set is None:
        return "masked", "auto"
    if working_set == "auto" or (isinstance(working_set, int)
                                 and not isinstance(working_set, bool)):
        return "compact", working_set
    raise ValueError(
        f"working_set must be None, an int or 'auto', got {working_set!r}")


def fit_path_batched(
    Xs, ys, lam, family: Family, *,
    screening: str = "strong",
    path_length: int = 100,
    sigma_ratio: float | None = None,
    sigmas: np.ndarray | None = None,
    solver_tol: float = DEFAULT_PATH_TOL,
    max_iter: int = DEFAULT_PATH_MAX_ITER,
    kkt_tol: float = DEFAULT_KKT_TOL,
    max_refits: int = DEFAULT_MAX_REFITS,
    working_set: int | str | None = _UNSET,
    pad: str | None = _UNSET,
) -> BatchedPathResult:
    """Fit B independent SLOPE paths in one compiled device program.

    Legacy entry point, now a thin shim over :func:`repro.api.slope_path`:
    the kwargs are translated into a ``(Problem, PathSpec, SolverPolicy)``
    triple and dispatch through the same planned layer (results are
    bit-identical to PR-1..3 behaviour).  ``working_set=`` and ``pad=``
    have spec-field replacements and warn once per process — see
    ``docs/MIGRATION.md``.
    """
    from ..api import LambdaSpec, PathSpec, Problem, SolverPolicy, slope_path
    from ..api.compat import warn_legacy

    if working_set is _UNSET:
        working_set = None
    else:
        warn_legacy("fit_path_batched", "working_set",
                    "SolverPolicy(backend='compact', working_set=...)")
    if pad is _UNSET:
        pad = None
    else:
        warn_legacy("fit_path_batched", "pad", "SolverPolicy(pad=...)")
    Xs = np.asarray(Xs)
    ys = np.asarray(ys)
    if Xs.ndim != 3:
        raise ValueError(f"Xs must be (B, n, p), got {Xs.shape}")
    if ys.shape[:2] != Xs.shape[:2]:
        raise ValueError(
            f"ys must be (B, n[, ...]) matching Xs {Xs.shape[:2]}, got {ys.shape}")
    backend, ws = _legacy_backend(working_set)
    return slope_path(
        Problem(Xs, ys, family=family),
        PathSpec(lam=LambdaSpec.explicit(lam), path_length=path_length,
                 sigma_ratio=sigma_ratio, sigmas=sigmas),
        SolverPolicy(backend=backend, working_set=ws, pad=pad,
                     screening=screening, solver_tol=solver_tol,
                     max_iter=max_iter, kkt_tol=kkt_tol,
                     max_refits=max_refits),
    )


def cv_path(
    X, y, lam, family: Family, *,
    n_folds: int = 5,
    screening: str = "strong",
    path_length: int = 100,
    sigma_ratio: float | None = None,
    solver_tol: float = DEFAULT_PATH_TOL,
    max_iter: int = DEFAULT_PATH_MAX_ITER,
    kkt_tol: float = DEFAULT_KKT_TOL,
    max_refits: int = DEFAULT_MAX_REFITS,
    working_set: int | str | None = _UNSET,
    stratify=_UNSET,
    selection: str = _UNSET,
    pad: str | None = _UNSET,
) -> CvPathResult:
    """K-fold CV: all fold paths fit as ONE batched device program.

    Legacy entry point, now a thin shim over :func:`repro.api.slope_path`
    with ``PathSpec(cv_folds=...)`` — results are bit-identical to the
    PR-1..3 implementation.  ``working_set=``, ``stratify=``,
    ``selection=`` and ``pad=`` have spec-field replacements and warn once
    per process — see ``docs/MIGRATION.md``.
    """
    from ..api import LambdaSpec, PathSpec, Problem, SolverPolicy, slope_path
    from ..api.compat import warn_legacy

    if working_set is _UNSET:
        working_set = None
    else:
        warn_legacy("cv_path", "working_set",
                    "SolverPolicy(backend='compact', working_set=...)")
    if stratify is _UNSET:
        stratify = "auto"
    else:
        warn_legacy("cv_path", "stratify", "PathSpec(stratify=...)")
    if selection is _UNSET:
        selection = "min"
    else:
        warn_legacy("cv_path", "selection", "PathSpec(selection=...)")
    if pad is _UNSET:
        pad = None
    else:
        warn_legacy("cv_path", "pad", "SolverPolicy(pad=...)")
    backend, ws = _legacy_backend(working_set)
    return slope_path(
        Problem(X, y, family=family),
        PathSpec(lam=LambdaSpec.explicit(lam), path_length=path_length,
                 sigma_ratio=sigma_ratio, cv_folds=n_folds,
                 stratify=stratify, selection=selection),
        SolverPolicy(backend=backend, working_set=ws, pad=pad,
                     screening=screening, solver_tol=solver_tol,
                     max_iter=max_iter, kkt_tol=kkt_tol,
                     max_refits=max_refits),
    )
