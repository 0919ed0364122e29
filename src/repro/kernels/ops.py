"""jit'd public wrappers around the Pallas kernels.

Responsibilities: pad to block multiples, pick interpret mode, fall back
where a kernel's preconditions don't hold (prox pooling beyond the SMEM
budget, or a block-compacted call whose mask is a tracer), and — for the
``*_compact`` wrappers — build the live-block index list on the host and
record per-call live-block telemetry (:func:`compact_gemv_stats`) so tests
and benchmarks can assert that the remapped grid covers exactly the live
blocks.  Every fallback is counted in :data:`COMPACT_METRICS`
(``fallbacks{op, reason}``, once per trace), so none is silent.

Interpret mode: the kernels run in the Pallas interpreter exactly when the
backend is not a TPU (that is how the CPU test suite validates them); on
a TPU they always compile with Mosaic.
"""

from __future__ import annotations

import dataclasses
import functools
import threading

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import MetricsRegistry
from . import ref as _ref
from .prox_sorted_l1 import SMEM_ELEM_LIMIT, prox_pool_kernel_call
from .screen_scan import DEFAULT_BLOCK, LANES, screen_scan_kernel_call
from .slope_gemv import (
    DEFAULT_BN,
    DEFAULT_BP,
    xb_loss_residual,
    xb_loss_residual_compact,
    xb_loss_residual_replicate,
    xb_residual,
    xb_residual_compact,
    xb_residual_masked,
    xb_residual_replicate,
    xt_matmul,
    xt_matmul_compact,
    xt_matmul_masked,
    xt_matmul_replicate,
)

__all__ = [
    "slope_gradient",
    "slope_gradient_masked",
    "slope_gradient_compact",
    "slope_gradient_replicate",
    "slope_residual",
    "slope_residual_masked",
    "slope_residual_compact",
    "slope_residual_replicate",
    "slope_loss_residual",
    "slope_loss_residual_compact",
    "slope_loss_residual_replicate",
    "screen_scan",
    "prox_pool",
    "prox_sorted_l1_kernel",
    "CompactGemvStats",
    "compact_gemv_stats",
    "COMPACT_METRICS",
]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, mult: int, axis: int, value=0.0) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("bn", "bp", "use_kernel"))
def slope_gradient(X, R, *, bn: int = DEFAULT_BN, bp: int = DEFAULT_BP,
                   use_kernel: bool = True):
    """∇f = Xᵀ R.  X (n, p); R (n,) or (n, m) → matches R's rank."""
    squeeze = R.ndim == 1
    R2 = R[:, None] if squeeze else R
    if not use_kernel:
        out = _ref.xt_matmul_ref(X, R2)
        return out[:, 0] if squeeze else out
    n, p = X.shape
    bn_ = min(bn, _round_up(n, 8))
    bp_ = min(bp, _round_up(p, 128))
    Xp = _pad_to(_pad_to(X, bn_, 0), bp_, 1)
    Rp = _pad_to(_pad_to(R2, bn_, 0), 128, 1)
    out = xt_matmul(Xp, Rp, bn=bn_, bp=bp_, interpret=_interpret())
    out = out[:p, : R2.shape[1]]
    return out[:, 0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("bn", "bp", "use_kernel"))
def slope_gradient_masked(X, R, mask, *, bn: int = DEFAULT_BN,
                          bp: int = DEFAULT_BP, use_kernel: bool = True):
    """∇f = (X ⊙ mask)ᵀ R with fully-masked column blocks skipped.

    ``mask`` is a (p,) column mask (bool or 0/1); masked columns' gradient
    rows are exactly 0.  Zero-padded mask columns keep the padding blocks
    dead, so padding adds no compute.
    """
    squeeze = R.ndim == 1
    R2 = R[:, None] if squeeze else R
    if not use_kernel:
        out = _ref.xt_matmul_masked_ref(X, R2, mask)
        return out[:, 0] if squeeze else out
    n, p = X.shape
    bn_ = min(bn, _round_up(n, 8))
    bp_ = min(bp, _round_up(p, 128))
    Xp = _pad_to(_pad_to(X, bn_, 0), bp_, 1)
    Rp = _pad_to(_pad_to(R2, bn_, 0), 128, 1)
    Mp = _pad_to(mask.astype(X.dtype)[None, :], bp_, 1)
    out = xt_matmul_masked(Xp, Rp, Mp, bn=bn_, bp=bp_, interpret=_interpret())
    out = out[:p, : R2.shape[1]]
    return out[:, 0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("family", "bn", "bp", "use_kernel"))
def slope_residual(X, B, Y, *, family: str = "none", bn: int = DEFAULT_BN,
                   bp: int = DEFAULT_BP, use_kernel: bool = True):
    """r = ∂ℓ/∂z at z = X·B, fused GEMV + GLM epilogue."""
    squeeze = B.ndim == 1
    B2 = B[:, None] if squeeze else B
    Y2 = Y[:, None] if Y.ndim == 1 else Y
    if not use_kernel:
        out = _ref.xb_residual_ref(X, B2, Y2, family)
        return out[:, 0] if squeeze else out
    n, p = X.shape
    m = B2.shape[1]
    bn_ = min(bn, _round_up(n, 8))
    bp_ = min(bp, _round_up(p, 128))
    Xp = _pad_to(_pad_to(X, bn_, 0), bp_, 1)
    Bp = _pad_to(_pad_to(B2, bp_, 0), 128, 1)
    Yp = _pad_to(_pad_to(Y2, bn_, 0), 128, 1)
    out = xb_residual(
        Xp, Bp, Yp, family=family, m_actual=m, bn=bn_, bp=bp_, interpret=_interpret()
    )
    out = out[:n, :m]
    return out[:, 0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("family", "bn", "bp", "use_kernel"))
def slope_residual_masked(X, B, Y, mask, *, family: str = "none",
                          bn: int = DEFAULT_BN, bp: int = DEFAULT_BP,
                          use_kernel: bool = True):
    """r = ∂ℓ/∂z at z = (X ⊙ mask)·B, skipping fully-masked column blocks."""
    squeeze = B.ndim == 1
    B2 = B[:, None] if squeeze else B
    Y2 = Y[:, None] if Y.ndim == 1 else Y
    if not use_kernel:
        out = _ref.xb_residual_masked_ref(X, B2, Y2, mask, family)
        return out[:, 0] if squeeze else out
    n, p = X.shape
    m = B2.shape[1]
    bn_ = min(bn, _round_up(n, 8))
    bp_ = min(bp, _round_up(p, 128))
    Xp = _pad_to(_pad_to(X, bn_, 0), bp_, 1)
    Bp = _pad_to(_pad_to(B2, bp_, 0), 128, 1)
    Yp = _pad_to(_pad_to(Y2, bn_, 0), 128, 1)
    Mp = _pad_to(mask.astype(X.dtype)[None, :], bp_, 1)
    out = xb_residual_masked(
        Xp, Bp, Yp, Mp, family=family, m_actual=m, bn=bn_, bp=bp_,
        interpret=_interpret(),
    )
    out = out[:n, :m]
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# replicate GEMVs: B row-reweighted members against ONE shared X
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("bn", "bp", "use_kernel"))
def slope_gradient_replicate(X, R, W, *, bn: int = DEFAULT_BN,
                             bp: int = DEFAULT_BP, use_kernel: bool = True):
    """G_b = Xᵀ (w_b ⊙ R_b) for B replicate members, one shared X.

    X (n, p); R (B, n) or (B, n, m); W (B, n) per-member row weights
    (bootstrap counts / subsample masks / ones).  Zero-weight rows are
    exactly inert.  X is never materialized per member — the kernel's
    member axis rides the grid with an X index map that ignores it.
    """
    squeeze = R.ndim == 2
    R3 = R[..., None] if squeeze else R
    if not use_kernel:
        out = _ref.xt_matmul_replicate_ref(X, R3, W)
        return out[..., 0] if squeeze else out
    n, p = X.shape
    m = R3.shape[2]
    bn_ = min(bn, _round_up(n, 8))
    bp_ = min(bp, _round_up(p, 128))
    Xp = _pad_to(_pad_to(X, bn_, 0), bp_, 1)
    Rp = _pad_to(_pad_to(R3, bn_, 1), 128, 2)
    Wt = _pad_to(W.astype(X.dtype)[..., None], bn_, 1)  # padded rows w = 0
    out = xt_matmul_replicate(Xp, Rp, Wt, bn=bn_, bp=bp_,
                              interpret=_interpret())
    out = out[:, :p, :m]
    return out[..., 0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("family", "bn", "bp",
                                             "use_kernel"))
def slope_residual_replicate(X, B, Y, W, *, family: str = "none",
                             bn: int = DEFAULT_BN, bp: int = DEFAULT_BP,
                             use_kernel: bool = True):
    """r_b = w_b ⊙ ∂ℓ/∂z at z_b = X·B_b, one shared X, fused epilogue.

    B (Bm, p) or (Bm, p, m) per-member coefficients; Y (Bm, n[, m])
    per-member responses; W (Bm, n).  Returns the already-weighted
    residual stack ready for :func:`slope_gradient_replicate` — note the
    weights must then NOT be applied again there (pass ones), or use this
    pair as (residual: weighted, gradient: plain per-member xt_matmul).
    """
    squeeze = B.ndim == 2
    B3 = B[..., None] if squeeze else B
    Y3 = Y[..., None] if Y.ndim == 2 else Y
    if not use_kernel:
        out = _ref.xb_residual_replicate_ref(X, B3, Y3, W, family)
        return out[..., 0] if squeeze else out
    n, p = X.shape
    m = B3.shape[2]
    bn_ = min(bn, _round_up(n, 8))
    bp_ = min(bp, _round_up(p, 128))
    Xp = _pad_to(_pad_to(X, bn_, 0), bp_, 1)
    Bp = _pad_to(_pad_to(B3, bp_, 1), 128, 2)
    Yp = _pad_to(_pad_to(Y3, bn_, 1), 128, 2)
    Wt = _pad_to(W.astype(X.dtype)[..., None], bn_, 1)
    out = xb_residual_replicate(Xp, Bp, Yp, Wt, family=family, m_actual=m,
                                bn=bn_, bp=bp_, interpret=_interpret())
    out = out[:, :n, :m]
    return out[..., 0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("family", "bn", "bp",
                                             "use_kernel"))
def slope_loss_residual_replicate(X, B, Y, W, *, family: str = "none",
                                  bn: int = DEFAULT_BN, bp: int = DEFAULT_BP,
                                  use_kernel: bool = True):
    """Per-member fused forward pair (weighted loss, weighted residual).

    Returns ``(loss (Bm,), r (Bm, n[, m]))`` — each member's weighted loss
    Σᵢ w_{b,i}·ℓ(z_{b,i}, y_{b,i}) and weighted residual from ONE pass
    over the shared X per member.
    """
    squeeze = B.ndim == 2
    B3 = B[..., None] if squeeze else B
    Y3 = Y[..., None] if Y.ndim == 2 else Y
    if not use_kernel:
        r, rows = _ref.xb_loss_residual_replicate_ref(X, B3, Y3, W, family)
        return jnp.sum(rows, axis=1), (r[..., 0] if squeeze else r)
    n, p = X.shape
    m = B3.shape[2]
    bn_ = min(bn, _round_up(n, 8))
    bp_ = min(bp, _round_up(p, 128))
    Xp = _pad_to(_pad_to(X, bn_, 0), bp_, 1)
    Bp = _pad_to(_pad_to(B3, bp_, 1), 128, 2)
    Yp = _pad_to(_pad_to(Y3, bn_, 1), 128, 2)
    Wt = _pad_to(W.astype(X.dtype)[..., None], bn_, 1)
    r, rows = xb_loss_residual_replicate(
        Xp, Bp, Yp, Wt, family=family, m_actual=m, bn=bn_, bp=bp_,
        interpret=_interpret())
    # padded rows carry w = 0 → their loss rows are exactly 0, but slice
    # the real rows anyway (mirrors the unweighted wrappers' convention)
    loss = jnp.sum(rows[:, :n, 0], axis=1)
    r = r[:, :n, :m]
    return loss, (r[..., 0] if squeeze else r)


# ---------------------------------------------------------------------------
# block-compacted GEMVs: live-block grid remap via scalar prefetch
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompactGemvStats:
    """Telemetry for one block-compacted GEMV dispatch."""

    op: str              # which wrapper ran
    blocks_total: int    # column blocks in the padded (P/bp) grid axis
    blocks_live: int     # blocks with ≥ 1 unmasked column == remapped extent
    grid: tuple          # the Pallas grid actually launched

    @property
    def live_ratio(self) -> float:
        return self.blocks_live / max(self.blocks_total, 1)


# last dispatch per op — the assertion surface for "dead blocks were not
# fetched": tests/benches check stats.grid's column extent == blocks_live.
# Thread-LOCAL so a caller always reads its own dispatch, never another
# thread's interleaved one (e.g. parallel test workers in one process)
_COMPACT_TELEMETRY = threading.local()

# process-wide dispatch accounting (counts + live-ratio histogram, labeled
# by op) — the aggregate view the serving stack's exporters can dump; the
# thread-local table above stays the per-dispatch assertion surface
COMPACT_METRICS = MetricsRegistry("kernels.compact")


def _record_fallback(op: str, reason: str) -> None:
    """Count one fallback off a kernel (at trace time: once per compile of
    the calling program, not per execution)."""
    COMPACT_METRICS.inc("fallbacks", op=op, reason=reason)


def _record_compact(op: str, stats: "CompactGemvStats") -> None:
    table = getattr(_COMPACT_TELEMETRY, "table", None)
    if table is None:
        table = _COMPACT_TELEMETRY.table = {}
    table[op] = stats
    COMPACT_METRICS.inc("dispatches", op=op)
    COMPACT_METRICS.inc("blocks_live", stats.blocks_live, op=op)
    COMPACT_METRICS.inc("blocks_total", stats.blocks_total, op=op)
    COMPACT_METRICS.observe("live_ratio", stats.live_ratio, op=op)


def compact_gemv_stats(op: str | None = None):
    """Live-block telemetry of the calling thread's most recent compact
    dispatch(es).

    ``op`` is one of ``"gradient"`` / ``"residual"`` / ``"loss_residual"``
    (None returns the whole table).  Host-side bookkeeping only — the
    values describe the launched grid, not traced array contents.
    """
    table = getattr(_COMPACT_TELEMETRY, "table", {})
    if op is None:
        return dict(table)
    return table.get(op)


def _live_blocks(mask_np: np.ndarray, P: int, bp: int) -> np.ndarray:
    """Ascending indices of the (bp-wide) column blocks with any survivor."""
    padded = np.zeros(P, bool)
    padded[: mask_np.shape[0]] = mask_np
    return np.flatnonzero(padded.reshape(P // bp, bp).any(axis=1)).astype(
        np.int32)


def _concrete_mask(mask) -> np.ndarray | None:
    """The mask as a host bool array, or None when it is a tracer (a
    traced mask cannot size a static grid — callers fall back to the
    masked kernels, which are semantically identical)."""
    if isinstance(mask, jax.core.Tracer):
        return None
    return np.asarray(mask).astype(bool)


def slope_gradient_compact(X, R, mask, *, bn: int = DEFAULT_BN,
                           bp: int = DEFAULT_BP, use_kernel: bool = True):
    """∇f = (X ⊙ mask)ᵀ R with dead column blocks never DMA'd.

    The live-block list is built host-side from ``mask`` (which must be
    concrete; a traced mask degrades, counted, to
    :func:`slope_gradient_masked` — same results, block-skip without the
    bandwidth saving) and remaps the Pallas grid via scalar prefetch, so
    a working set of W columns streams ⌈W/bp⌉ blocks of X instead of p/bp.
    Bit-identical to the masked kernel; dead columns' gradient rows are
    exactly 0.
    """
    squeeze = R.ndim == 1
    R2 = R[:, None] if squeeze else R
    if not use_kernel:
        out = _ref.xt_matmul_compact_ref(X, R2, mask)
        return out[:, 0] if squeeze else out
    mask_np = _concrete_mask(mask)
    if mask_np is None:
        _record_fallback("gradient", "traced_mask")
        return slope_gradient_masked(X, R, mask, bn=bn, bp=bp)
    n, p = X.shape
    bn_ = min(bn, _round_up(n, 8))
    bp_ = min(bp, _round_up(p, 128))
    P = _round_up(p, bp_)
    live = _live_blocks(mask_np, P, bp_)
    n_live = int(live.shape[0])
    _record_compact("gradient", CompactGemvStats(
        op="gradient", blocks_total=P // bp_, blocks_live=n_live,
        grid=(n_live, _round_up(n, bn_) // bn_)))
    mR = R2.shape[1]
    if n_live == 0:
        out = jnp.zeros((p, mR), X.dtype)
        return out[:, 0] if squeeze else out
    Xp = _pad_to(_pad_to(X, bn_, 0), bp_, 1)
    Rp = _pad_to(_pad_to(R2, bn_, 0), 128, 1)
    Mp = _pad_to(mask_np.astype(X.dtype)[None, :], bp_, 1)
    outc = xt_matmul_compact(Xp, Rp, Mp, jnp.asarray(live), bn=bn_, bp=bp_,
                             interpret=_interpret())
    full = jnp.zeros((P // bp_, bp_, outc.shape[1]), outc.dtype)
    full = full.at[jnp.asarray(live)].set(
        outc.reshape(n_live, bp_, outc.shape[1]))
    out = full.reshape(P, -1)[:p, :mR]
    return out[:, 0] if squeeze else out


def slope_residual_compact(X, B, Y, mask, *, family: str = "none",
                           bn: int = DEFAULT_BN, bp: int = DEFAULT_BP,
                           use_kernel: bool = True):
    """r = ∂ℓ/∂z at z = (X ⊙ mask)·B with dead column blocks never DMA'd.

    Same contract as :func:`slope_residual_masked` (bit-identical results);
    a traced mask degrades to the masked kernel.
    """
    squeeze = B.ndim == 1
    B2 = B[:, None] if squeeze else B
    Y2 = Y[:, None] if Y.ndim == 1 else Y
    if not use_kernel:
        out = _ref.xb_residual_compact_ref(X, B2, Y2, mask, family)
        return out[:, 0] if squeeze else out
    mask_np = _concrete_mask(mask)
    if mask_np is None:
        _record_fallback("residual", "traced_mask")
        return slope_residual_masked(X, B, Y, mask, family=family, bn=bn,
                                     bp=bp)
    n, p = X.shape
    m = B2.shape[1]
    bn_ = min(bn, _round_up(n, 8))
    bp_ = min(bp, _round_up(p, 128))
    P = _round_up(p, bp_)
    live = _live_blocks(mask_np, P, bp_)
    n_live = int(live.shape[0])
    _record_compact("residual", CompactGemvStats(
        op="residual", blocks_total=P // bp_, blocks_live=n_live,
        grid=(_round_up(n, bn_) // bn_, n_live)))
    if n_live == 0:  # z ≡ 0: the epilogue alone decides the residual
        z = jnp.zeros((n, m), jnp.promote_types(X.dtype, jnp.float32))
        out = _ref._epilogue(z, Y2.astype(z.dtype), family).astype(X.dtype)
        return out[:, 0] if squeeze else out
    Xp = _pad_to(_pad_to(X, bn_, 0), bp_, 1)
    Bp = _pad_to(_pad_to(B2, bp_, 0), 128, 1)
    Yp = _pad_to(_pad_to(Y2, bn_, 0), 128, 1)
    Mp = _pad_to(mask_np.astype(X.dtype)[None, :], bp_, 1)
    out = xb_residual_compact(
        Xp, Bp, Yp, Mp, jnp.asarray(live), family=family, m_actual=m,
        bn=bn_, bp=bp_, interpret=_interpret())
    out = out[:n, :m]
    return out[:, 0] if squeeze else out


def slope_loss_residual_compact(X, B, Y, mask, *, family: str = "none",
                                bn: int = DEFAULT_BN, bp: int = DEFAULT_BP,
                                use_kernel: bool = True):
    """(ℓ(z, y), r) at z = (X ⊙ mask)·B in one live-blocks-only pass over X.

    The compact analogue of :func:`slope_loss_residual`.  A traced mask
    degrades to the pure-jnp masked oracle (one ``X ⊙ mask`` pass for both
    halves — there is no fused *masked* Pallas kernel to fall back on,
    unlike the gradient/residual wrappers which degrade to their masked
    kernels).
    """
    squeeze = B.ndim == 1
    B2 = B[:, None] if squeeze else B
    Y2 = Y[:, None] if Y.ndim == 1 else Y
    if not use_kernel:
        r, rows = _ref.xb_loss_residual_compact_ref(X, B2, Y2, mask, family)
        return jnp.sum(rows), (r[:, 0] if squeeze else r)
    mask_np = _concrete_mask(mask)
    if mask_np is None:
        _record_fallback("loss_residual", "traced_mask")
        r, rows = _ref.xb_loss_residual_compact_ref(X, B2, Y2, mask, family)
        return jnp.sum(rows), (r[:, 0] if squeeze else r)
    n, p = X.shape
    m = B2.shape[1]
    bn_ = min(bn, _round_up(n, 8))
    bp_ = min(bp, _round_up(p, 128))
    P = _round_up(p, bp_)
    live = _live_blocks(mask_np, P, bp_)
    n_live = int(live.shape[0])
    _record_compact("loss_residual", CompactGemvStats(
        op="loss_residual", blocks_total=P // bp_, blocks_live=n_live,
        grid=(_round_up(n, bn_) // bn_, n_live)))
    if n_live == 0:
        z = jnp.zeros((n, m), jnp.promote_types(X.dtype, jnp.float32))
        Yz = Y2.astype(z.dtype)
        r = _ref._epilogue(z, Yz, family).astype(X.dtype)
        loss = jnp.sum(_ref._row_loss(z, Yz, family))
        return loss, (r[:, 0] if squeeze else r)
    Xp = _pad_to(_pad_to(X, bn_, 0), bp_, 1)
    Bp = _pad_to(_pad_to(B2, bp_, 0), 128, 1)
    Yp = _pad_to(_pad_to(Y2, bn_, 0), 128, 1)
    Mp = _pad_to(mask_np.astype(X.dtype)[None, :], bp_, 1)
    r, rows = xb_loss_residual_compact(
        Xp, Bp, Yp, Mp, jnp.asarray(live), family=family, m_actual=m,
        bn=bn_, bp=bp_, interpret=_interpret())
    # padded rows see z = 0, y = 0 — nonzero loss for e.g. logistic — so
    # the reduction must slice the real rows first (as in the fused kernel)
    loss = jnp.sum(rows[:n, 0])
    r = r[:n, :m]
    return loss, (r[:, 0] if squeeze else r)


@functools.partial(jax.jit, static_argnames=("family", "bn", "bp", "use_kernel"))
def slope_loss_residual(X, B, Y, *, family: str = "none", bn: int = DEFAULT_BN,
                        bp: int = DEFAULT_BP, use_kernel: bool = True):
    """(ℓ(z, y), r = ∂ℓ/∂z) at z = X·B in ONE pass over X.

    The fused forward pair a FISTA step needs — the loss is the scalar sum
    over rows, the residual feeds the gradient matvec.
    """
    squeeze = B.ndim == 1
    B2 = B[:, None] if squeeze else B
    Y2 = Y[:, None] if Y.ndim == 1 else Y
    if not use_kernel:
        r, rows = _ref.xb_loss_residual_ref(X, B2, Y2, family)
        return jnp.sum(rows), (r[:, 0] if squeeze else r)
    n, p = X.shape
    m = B2.shape[1]
    bn_ = min(bn, _round_up(n, 8))
    bp_ = min(bp, _round_up(p, 128))
    Xp = _pad_to(_pad_to(X, bn_, 0), bp_, 1)
    Bp = _pad_to(_pad_to(B2, bp_, 0), 128, 1)
    Yp = _pad_to(_pad_to(Y2, bn_, 0), 128, 1)
    r, rows = xb_loss_residual(
        Xp, Bp, Yp, family=family, m_actual=m, bn=bn_, bp=bp_,
        interpret=_interpret(),
    )
    # padded rows see z = 0, y = 0 — nonzero loss for e.g. logistic — so the
    # reduction must slice the real rows first
    loss = jnp.sum(rows[:n, 0])
    r = r[:n, :m]
    return loss, (r[:, 0] if squeeze else r)


@functools.partial(jax.jit, static_argnames=("block", "use_kernel"))
def screen_scan(c, lam, *, block: int = DEFAULT_BLOCK, use_kernel: bool = True):
    """Algorithm-2 screen: k = #kept (c, λ in the sorted order)."""
    if not use_kernel:
        return _ref.screen_scan_ref(c, lam)
    (p,) = c.shape
    tile = 8 * LANES  # a block is a whole number of (8, 128) tiles
    blk = _round_up(min(block, _round_up(p, tile)), tile)
    # pad with c − λ = −1: strictly decreasing tail can never host the
    # rightmost argmax, so k is unaffected
    cp = _pad_to(c.astype(jnp.float32), blk, 0, value=-1.0)
    lp = _pad_to(lam.astype(jnp.float32), blk, 0, value=0.0)
    return screen_scan_kernel_call(cp, lp, block=blk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def prox_pool(w, *, use_kernel: bool = True):
    """Non-increasing isotonic projection + clip at 0."""
    if not use_kernel:
        return _ref.prox_pool_ref(w)
    if w.shape[0] > SMEM_ELEM_LIMIT:
        _record_fallback("prox_pool", "smem_limit")
        return _ref.prox_pool_ref(w)
    return prox_pool_kernel_call(w, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def prox_sorted_l1_kernel(v, lam, *, use_kernel: bool = True):
    """Full sorted-ℓ1 prox: XLA sort + Pallas pooling + unsort."""
    shape = v.shape
    v = jnp.ravel(v)
    lam = jnp.ravel(lam).astype(v.dtype)
    sign = jnp.sign(v)
    mag = jnp.abs(v)
    order = jnp.argsort(-mag)
    w = mag[order] - lam
    x_sorted = prox_pool(w, use_kernel=use_kernel)
    x = jnp.zeros_like(v).at[order].set(x_sorted.astype(v.dtype))
    return (sign * x).reshape(shape)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
