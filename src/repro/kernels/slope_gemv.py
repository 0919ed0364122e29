"""Pallas TPU kernels for the two matvecs that dominate SLOPE solves.

Per FISTA iteration the solver reads X twice: once for the linear predictor
z = X·β (+ the GLM residual epilogue, fused here so z never round-trips
through HBM) and once for the gradient ∇f = Xᵀ·r.  With p ≫ n these GEMVs
are memory-bound on X, so the kernels tile X through VMEM in MXU-aligned
(bn × bp) blocks, accumulate in f32, and stream the small operands (r, β,
y) alongside.

Layouts (m = #classes; 1 for scalar GLMs, padded to the lane width by ops.py):
  xt_matmul:    X (n, p), R (n, m)      → G (p, m)     grid (p/bp, n/bn)
  xb_residual:  X (n, p), B (p, m), Y (n, m) → r (n, m) grid (n/bn, p/bp)

Mask-aware variants (``*_masked``) take a (1, p) column mask alongside X and
skip the MXU work of any (bn × bp) block whose bp-wide mask slice is all
zero — the per-block summary is reduced from the mask tile in VMEM, so a
screened working set of W columns costs ⌈W/bp⌉ column blocks of compute
instead of p/bp.  The block DMA still streams every block, dead or alive.

Block-compacted variants (``*_compact``) close that bandwidth gap: they
take a **live-block index list** (the column blocks whose mask slice has
any survivor, computed on the host from the per-block mask summary) as a
scalar-prefetch operand and remap the Pallas grid through it — the grid's
column axis has exactly ``len(live_idx)`` steps and the ``BlockSpec`` index
maps read ``live_idx[pb]``, so dead (bn × bp) blocks are never DMA'd at
all.  Scalar prefetch makes the indices available before the kernel body
runs, which is what lets Mosaic schedule the remapped DMAs on TPU; on CPU
the same kernels execute in interpret mode (how this container validates
them).  Within a live block the mask still zeroes dead columns, so compact
results are bit-identical to the masked kernels.

``xb_loss_residual`` fuses the loss reduction into the residual epilogue so
one pass over X yields both ℓ(z, y) and r = ∂ℓ/∂z — the pair every FISTA
step needs — instead of two separate streams of X.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mosaic import x32

__all__ = [
    "xt_matmul",
    "xt_matmul_masked",
    "xt_matmul_compact",
    "xb_residual",
    "xb_residual_masked",
    "xb_residual_compact",
    "xb_loss_residual",
    "xb_loss_residual_compact",
    "xt_matmul_replicate",
    "xb_residual_replicate",
    "xb_loss_residual_replicate",
    "DEFAULT_BN",
    "DEFAULT_BP",
]

DEFAULT_BN = 256
DEFAULT_BP = 512
# f32 operands get the full f32 product: the TPU default is one bf16 pass
_HIGHEST = jax.lax.Precision.HIGHEST


def _xt_matmul_kernel(x_ref, r_ref, o_ref, acc_ref):
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        r_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),  # Xᵀ·R without transpose copy
        preferred_element_type=jnp.float32,
        precision=_HIGHEST,
    )

    @pl.when(nb == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@x32
def xt_matmul(
    X: jax.Array,
    R: jax.Array,
    *,
    bn: int = DEFAULT_BN,
    bp: int = DEFAULT_BP,
    interpret: bool = False,
) -> jax.Array:
    """G = Xᵀ R; shapes (n, p) × (n, m) → (p, m).  Caller pads to blocks."""
    n, p = X.shape
    m = R.shape[1]
    assert n % bn == 0 and p % bp == 0, (n, p, bn, bp)
    grid = (p // bp, n // bn)
    return pl.pallas_call(
        _xt_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bp), lambda pb, nb: (nb, pb)),
            pl.BlockSpec((bn, m), lambda pb, nb: (nb, 0)),
        ],
        out_specs=pl.BlockSpec((bp, m), lambda pb, nb: (pb, 0)),
        out_shape=jax.ShapeDtypeStruct((p, m), X.dtype),
        scratch_shapes=[pltpu.VMEM((bp, m), jnp.float32)],
        interpret=interpret,
    )(X, R)


def _xt_matmul_masked_kernel(x_ref, r_ref, mask_ref, o_ref, acc_ref):
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mb = mask_ref[...]  # (1, bp) — this column block's mask slice
    # per-block summary: a fully-masked (bn × bp) block contributes nothing,
    # so its MXU pass is skipped outright (the strong rule typically leaves
    # W ≪ p columns alive → ⌈W/bp⌉ blocks of compute instead of p/bp)
    @pl.when(jnp.max(mb) > 0)
    def _acc():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...] * mb,  # zero masked columns inside kept blocks
            r_ref[...],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_HIGHEST,
        )

    @pl.when(nb == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@x32
def xt_matmul_masked(
    X: jax.Array,
    R: jax.Array,
    mask: jax.Array,
    *,
    bn: int = DEFAULT_BN,
    bp: int = DEFAULT_BP,
    interpret: bool = False,
) -> jax.Array:
    """G = (X ⊙ mask)ᵀ R with fully-masked column blocks skipped.

    ``mask`` is a (1, p) column mask in X's dtype (0/1); masked columns'
    gradient rows come back exactly 0.  Caller pads to blocks.
    """
    n, p = X.shape
    m = R.shape[1]
    assert n % bn == 0 and p % bp == 0, (n, p, bn, bp)
    assert mask.shape == (1, p), mask.shape
    grid = (p // bp, n // bn)
    return pl.pallas_call(
        _xt_matmul_masked_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bp), lambda pb, nb: (nb, pb)),
            pl.BlockSpec((bn, m), lambda pb, nb: (nb, 0)),
            pl.BlockSpec((1, bp), lambda pb, nb: (0, pb)),
        ],
        out_specs=pl.BlockSpec((bp, m), lambda pb, nb: (pb, 0)),
        out_shape=jax.ShapeDtypeStruct((p, m), X.dtype),
        scratch_shapes=[pltpu.VMEM((bp, m), jnp.float32)],
        interpret=interpret,
    )(X, R, mask)


def _epilogue(z, y, family: str, m_actual: int):
    if family == "none":
        return z
    if family == "ols":
        return z - y
    if family == "logistic":
        return jax.nn.sigmoid(z) - y
    if family == "poisson":
        return jnp.exp(z) - y
    if family == "multinomial":
        # mask padded class lanes out of the softmax
        lane = jax.lax.broadcasted_iota(jnp.int32, z.shape, dimension=z.ndim - 1)
        zm = jnp.where(lane < m_actual, z, -jnp.inf)
        sm = jax.nn.softmax(zm, axis=-1)
        return jnp.where(lane < m_actual, sm - y, 0.0)
    raise ValueError(f"unknown family {family!r}")


def _xb_residual_kernel(x_ref, b_ref, y_ref, o_ref, acc_ref, *, family, m_actual):
    pb = pl.program_id(1)

    @pl.when(pb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=_HIGHEST)

    @pl.when(pb == pl.num_programs(1) - 1)
    def _flush():
        z = acc_ref[...]
        o_ref[...] = _epilogue(z, y_ref[...].astype(jnp.float32), family, m_actual).astype(
            o_ref.dtype
        )


@x32
def xb_residual(
    X: jax.Array,
    B: jax.Array,
    Y: jax.Array,
    *,
    family: str = "none",
    m_actual: int | None = None,
    bn: int = DEFAULT_BN,
    bp: int = DEFAULT_BP,
    interpret: bool = False,
) -> jax.Array:
    """r = ∂ℓ/∂z at z = X·B, fused.  Shapes (n,p) × (p,m), Y (n,m) → (n,m)."""
    n, p = X.shape
    m = B.shape[1]
    assert n % bn == 0 and p % bp == 0, (n, p, bn, bp)
    m_actual = m if m_actual is None else m_actual
    grid = (n // bn, p // bp)
    kernel = functools.partial(_xb_residual_kernel, family=family, m_actual=m_actual)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bp), lambda nb, pb: (nb, pb)),
            pl.BlockSpec((bp, m), lambda nb, pb: (pb, 0)),
            pl.BlockSpec((bn, m), lambda nb, pb: (nb, 0)),
        ],
        out_specs=pl.BlockSpec((bn, m), lambda nb, pb: (nb, 0)),
        out_shape=jax.ShapeDtypeStruct((n, m), X.dtype),
        scratch_shapes=[pltpu.VMEM((bn, m), jnp.float32)],
        interpret=interpret,
    )(X, B, Y)


def _xb_residual_masked_kernel(x_ref, b_ref, y_ref, mask_ref, o_ref, acc_ref,
                               *, family, m_actual):
    pb = pl.program_id(1)

    @pl.when(pb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mb = mask_ref[...]  # (1, bp)

    @pl.when(jnp.max(mb) > 0)
    def _acc():
        acc_ref[...] += jnp.dot(x_ref[...] * mb, b_ref[...],
                                preferred_element_type=jnp.float32,
                                precision=_HIGHEST)

    @pl.when(pb == pl.num_programs(1) - 1)
    def _flush():
        z = acc_ref[...]
        o_ref[...] = _epilogue(z, y_ref[...].astype(jnp.float32), family,
                               m_actual).astype(o_ref.dtype)


@x32
def xb_residual_masked(
    X: jax.Array,
    B: jax.Array,
    Y: jax.Array,
    mask: jax.Array,
    *,
    family: str = "none",
    m_actual: int | None = None,
    bn: int = DEFAULT_BN,
    bp: int = DEFAULT_BP,
    interpret: bool = False,
) -> jax.Array:
    """r = ∂ℓ/∂z at z = (X ⊙ mask)·B, skipping fully-masked column blocks.

    The masked-FISTA invariant (coefficients of masked columns are exactly
    0) makes the mask multiply redundant for solver calls, but the kernel
    applies it anyway so the contract holds for arbitrary ``B``.
    """
    n, p = X.shape
    m = B.shape[1]
    assert n % bn == 0 and p % bp == 0, (n, p, bn, bp)
    assert mask.shape == (1, p), mask.shape
    m_actual = m if m_actual is None else m_actual
    grid = (n // bn, p // bp)
    kernel = functools.partial(_xb_residual_masked_kernel, family=family,
                               m_actual=m_actual)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bp), lambda nb, pb: (nb, pb)),
            pl.BlockSpec((bp, m), lambda nb, pb: (pb, 0)),
            pl.BlockSpec((bn, m), lambda nb, pb: (nb, 0)),
            pl.BlockSpec((1, bp), lambda nb, pb: (0, pb)),
        ],
        out_specs=pl.BlockSpec((bn, m), lambda nb, pb: (nb, 0)),
        out_shape=jax.ShapeDtypeStruct((n, m), X.dtype),
        scratch_shapes=[pltpu.VMEM((bn, m), jnp.float32)],
        interpret=interpret,
    )(X, B, Y, mask)


def _xt_matmul_compact_kernel(live_ref, x_ref, r_ref, mask_ref, o_ref,
                              acc_ref):
    del live_ref  # consumed by the BlockSpec index maps, not the body
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # every visited block is live by construction (the grid is the
    # live-block list); the mask multiply only zeroes dead columns *inside*
    # live blocks, keeping results bit-identical to the masked kernel
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...] * mask_ref[...],
        r_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_HIGHEST,
    )

    @pl.when(nb == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@x32
def xt_matmul_compact(
    X: jax.Array,
    R: jax.Array,
    mask: jax.Array,
    live_idx: jax.Array,
    *,
    bn: int = DEFAULT_BN,
    bp: int = DEFAULT_BP,
    interpret: bool = False,
) -> jax.Array:
    """G blocks of (X ⊙ mask)ᵀ R for the live column blocks only.

    ``live_idx`` is a static-length (n_live,) int32 list of column-block
    indices (ascending); it rides in as a scalar-prefetch operand and the
    grid's column axis is remapped through it, so dead (bn × bp) blocks of
    X are neither DMA'd nor computed.  Returns the **compacted**
    ``(n_live·bp, m)`` output — block ``k`` holds the gradient rows of
    column block ``live_idx[k]`` (the ops-layer wrapper scatters them back
    to p-space, dead blocks exactly 0).  Caller pads to blocks.
    """
    n, p = X.shape
    m = R.shape[1]
    assert n % bn == 0 and p % bp == 0, (n, p, bn, bp)
    assert mask.shape == (1, p), mask.shape
    n_live = live_idx.shape[0]
    assert n_live >= 1, "use the ops-layer wrapper for all-dead masks"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_live, n // bn),
        in_specs=[
            pl.BlockSpec((bn, bp), lambda pb, nb, live: (nb, live[pb])),
            pl.BlockSpec((bn, m), lambda pb, nb, live: (nb, 0)),
            pl.BlockSpec((1, bp), lambda pb, nb, live: (0, live[pb])),
        ],
        out_specs=pl.BlockSpec((bp, m), lambda pb, nb, live: (pb, 0)),
        scratch_shapes=[pltpu.VMEM((bp, m), jnp.float32)],
    )
    return pl.pallas_call(
        _xt_matmul_compact_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_live * bp, m), X.dtype),
        interpret=interpret,
    )(live_idx, X, R, mask)


def _xb_residual_compact_kernel(live_ref, x_ref, b_ref, y_ref, mask_ref,
                                o_ref, acc_ref, *, family, m_actual):
    del live_ref
    pb = pl.program_id(1)

    @pl.when(pb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...] * mask_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=_HIGHEST)

    @pl.when(pb == pl.num_programs(1) - 1)
    def _flush():
        z = acc_ref[...]
        o_ref[...] = _epilogue(z, y_ref[...].astype(jnp.float32), family,
                               m_actual).astype(o_ref.dtype)


@x32
def xb_residual_compact(
    X: jax.Array,
    B: jax.Array,
    Y: jax.Array,
    mask: jax.Array,
    live_idx: jax.Array,
    *,
    family: str = "none",
    m_actual: int | None = None,
    bn: int = DEFAULT_BN,
    bp: int = DEFAULT_BP,
    interpret: bool = False,
) -> jax.Array:
    """r = ∂ℓ/∂z at z = (X ⊙ mask)·B over the live column blocks only.

    The accumulation axis is remapped through ``live_idx`` (scalar
    prefetch), so z sums exactly the live blocks' contributions — the same
    partial sums, in the same order, the masked kernel accumulates while
    still streaming every block.  Dead blocks contribute exactly 0 there,
    so skipping their DMA leaves the result bit-identical.
    """
    n, p = X.shape
    m = B.shape[1]
    assert n % bn == 0 and p % bp == 0, (n, p, bn, bp)
    assert mask.shape == (1, p), mask.shape
    m_actual = m if m_actual is None else m_actual
    n_live = live_idx.shape[0]
    assert n_live >= 1, "use the ops-layer wrapper for all-dead masks"
    kernel = functools.partial(_xb_residual_compact_kernel, family=family,
                               m_actual=m_actual)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // bn, n_live),
        in_specs=[
            pl.BlockSpec((bn, bp), lambda nb, pb, live: (nb, live[pb])),
            pl.BlockSpec((bp, m), lambda nb, pb, live: (live[pb], 0)),
            pl.BlockSpec((bn, m), lambda nb, pb, live: (nb, 0)),
            pl.BlockSpec((1, bp), lambda nb, pb, live: (0, live[pb])),
        ],
        out_specs=pl.BlockSpec((bn, m), lambda nb, pb, live: (nb, 0)),
        scratch_shapes=[pltpu.VMEM((bn, m), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, m), X.dtype),
        interpret=interpret,
    )(live_idx, X, B, Y, mask)


def _xb_loss_residual_compact_kernel(live_ref, x_ref, b_ref, y_ref, mask_ref,
                                     r_ref, loss_ref, acc_ref, *, family,
                                     m_actual):
    del live_ref
    pb = pl.program_id(1)

    @pl.when(pb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...] * mask_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=_HIGHEST)

    @pl.when(pb == pl.num_programs(1) - 1)
    def _flush():
        z = acc_ref[...]
        y = y_ref[...].astype(jnp.float32)
        r_ref[...] = _epilogue(z, y, family, m_actual).astype(r_ref.dtype)
        rl = _row_loss(z, y, family, m_actual)  # (bn,)
        loss_ref[...] = jnp.broadcast_to(rl[:, None],
                                         loss_ref.shape).astype(loss_ref.dtype)


@x32
def xb_loss_residual_compact(
    X: jax.Array,
    B: jax.Array,
    Y: jax.Array,
    mask: jax.Array,
    live_idx: jax.Array,
    *,
    family: str = "none",
    m_actual: int | None = None,
    bn: int = DEFAULT_BN,
    bp: int = DEFAULT_BP,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused (r, per-row loss) at z = (X ⊙ mask)·B, live blocks only.

    The compact analogue of :func:`xb_loss_residual`: one remapped pass
    over the live blocks of X yields both halves of the FISTA forward
    pair, with dead-block DMA skipped entirely.
    """
    n, p = X.shape
    m = B.shape[1]
    assert n % bn == 0 and p % bp == 0, (n, p, bn, bp)
    assert mask.shape == (1, p), mask.shape
    m_actual = m if m_actual is None else m_actual
    n_live = live_idx.shape[0]
    assert n_live >= 1, "use the ops-layer wrapper for all-dead masks"
    kernel = functools.partial(_xb_loss_residual_compact_kernel,
                               family=family, m_actual=m_actual)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // bn, n_live),
        in_specs=[
            pl.BlockSpec((bn, bp), lambda nb, pb, live: (nb, live[pb])),
            pl.BlockSpec((bp, m), lambda nb, pb, live: (live[pb], 0)),
            pl.BlockSpec((bn, m), lambda nb, pb, live: (nb, 0)),
            pl.BlockSpec((1, bp), lambda nb, pb, live: (0, live[pb])),
        ],
        out_specs=[
            pl.BlockSpec((bn, m), lambda nb, pb, live: (nb, 0)),
            pl.BlockSpec((bn, m), lambda nb, pb, live: (nb, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((bn, m), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, m), X.dtype),
            jax.ShapeDtypeStruct((n, m), jnp.float32),
        ],
        interpret=interpret,
    )(live_idx, X, B, Y, mask)


# ---------------------------------------------------------------------------
# replicate variants: B row-reweighted problems against ONE shared X
# ---------------------------------------------------------------------------
#
# The resampling engine represents a bootstrap/subsample member as a per-row
# weight vector w_b against the shared (n, p) design, so its matvecs are
#
#     G_b = Xᵀ (w_b ⊙ r_b)         r_b = w_b ⊙ ∂ℓ/∂z at z_b = X·β_b
#
# — the X operand is the SAME array for every member.  These kernels put the
# member axis on the grid and give X a BlockSpec index map that ignores it,
# so X is held once in HBM (O(n·p), not O(B·n·p)) while the per-member
# operands stay O(B·n).  Weights ride in as (B, n, 1) so a member's slice
# is a (bn, 1) column block broadcasting against (bn, m) tiles — the
# trailing unit axis keeps the block's last dimension equal to the
# array's, which the TPU block tiling rules require.
# Zero-weight rows are where-guarded to an exact 0 (the same guard as
# ``Family.weighted_residual``), so a w = 0 row can never leak a non-finite
# residual into the sums — and so results are bit-identical to applying the
# guarded weight host-side and calling the unweighted kernels per member.


def _apply_w(w, a):
    """w ⊙ a with zero-weight rows exact 0; w (bn, 1), a (bn, m)."""
    return jnp.where(w == 0, jnp.zeros((), a.dtype), w * a)


def _xt_matmul_replicate_kernel(x_ref, r_ref, w_ref, o_ref, acc_ref):
    nb = pl.program_id(2)

    @pl.when(nb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        _apply_w(w_ref[0], r_ref[0]),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_HIGHEST,
    )

    @pl.when(nb == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@x32
def xt_matmul_replicate(
    X: jax.Array,
    R: jax.Array,
    W: jax.Array,
    *,
    bn: int = DEFAULT_BN,
    bp: int = DEFAULT_BP,
    interpret: bool = False,
) -> jax.Array:
    """G_b = Xᵀ (w_b ⊙ R_b) for all B members against one shared X.

    Shapes: X (n, p) shared, R (B, n, m) per-member residuals, W (B, n, 1)
    row weights → G (B, p, m).  Per member the block schedule
    (and therefore every partial sum) is exactly :func:`xt_matmul`'s on the
    pre-weighted residual, so results are bit-identical to the materialized
    reference.  Caller pads n/p to blocks.
    """
    n, p = X.shape
    B, n_r, m = R.shape
    assert n_r == n and W.shape == (B, n, 1), (X.shape, R.shape, W.shape)
    assert n % bn == 0 and p % bp == 0, (n, p, bn, bp)
    grid = (B, p // bp, n // bn)
    return pl.pallas_call(
        _xt_matmul_replicate_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bp), lambda b, pb, nb: (nb, pb)),  # shared X
            pl.BlockSpec((1, bn, m), lambda b, pb, nb: (b, nb, 0)),
            pl.BlockSpec((1, bn, 1), lambda b, pb, nb: (b, nb, 0)),
        ],
        out_specs=pl.BlockSpec((1, bp, m), lambda b, pb, nb: (b, pb, 0)),
        out_shape=jax.ShapeDtypeStruct((B, p, m), X.dtype),
        scratch_shapes=[pltpu.VMEM((bp, m), jnp.float32)],
        interpret=interpret,
    )(X, R, W)


def _xb_residual_replicate_kernel(x_ref, b_ref, y_ref, w_ref, o_ref, acc_ref,
                                  *, family, m_actual):
    pb = pl.program_id(2)

    @pl.when(pb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], b_ref[0],
                            preferred_element_type=jnp.float32,
                            precision=_HIGHEST)

    @pl.when(pb == pl.num_programs(2) - 1)
    def _flush():
        z = acc_ref[...]
        # cast the epilogue to the output dtype BEFORE weighting, so the
        # result is bit-identical to host-weighting the unweighted kernel's
        # output (w stays in its native dtype, as it would host-side)
        r = _epilogue(z, y_ref[0].astype(jnp.float32), family,
                      m_actual).astype(o_ref.dtype)
        o_ref[0] = _apply_w(w_ref[0], r)


@x32
def xb_residual_replicate(
    X: jax.Array,
    B: jax.Array,
    Y: jax.Array,
    W: jax.Array,
    *,
    family: str = "none",
    m_actual: int | None = None,
    bn: int = DEFAULT_BN,
    bp: int = DEFAULT_BP,
    interpret: bool = False,
) -> jax.Array:
    """r_b = w_b ⊙ ∂ℓ/∂z at z_b = X·B_b, one shared X, fused epilogue.

    Shapes: X (n, p), B (Bm, p, m) per-member coefficients, Y (Bm, n, m)
    per-member responses (permutation replicates differ per member; others
    broadcast), W (Bm, n, 1) → r (Bm, n, m) already weighted for the
    gradient matvec.
    """
    n, p = X.shape
    Bm, p_b, m = B.shape
    assert p_b == p and Y.shape == (Bm, n, m) and W.shape == (Bm, n, 1), (
        X.shape, B.shape, Y.shape, W.shape)
    assert n % bn == 0 and p % bp == 0, (n, p, bn, bp)
    m_actual = m if m_actual is None else m_actual
    grid = (Bm, n // bn, p // bp)
    kernel = functools.partial(_xb_residual_replicate_kernel, family=family,
                               m_actual=m_actual)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bp), lambda b, nb, pb: (nb, pb)),  # shared X
            pl.BlockSpec((1, bp, m), lambda b, nb, pb: (b, pb, 0)),
            pl.BlockSpec((1, bn, m), lambda b, nb, pb: (b, nb, 0)),
            pl.BlockSpec((1, bn, 1), lambda b, nb, pb: (b, nb, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn, m), lambda b, nb, pb: (b, nb, 0)),
        out_shape=jax.ShapeDtypeStruct((Bm, n, m), X.dtype),
        scratch_shapes=[pltpu.VMEM((bn, m), jnp.float32)],
        interpret=interpret,
    )(X, B, Y, W)


def _xb_loss_residual_replicate_kernel(x_ref, b_ref, y_ref, w_ref, r_ref,
                                       loss_ref, acc_ref, *, family,
                                       m_actual):
    pb = pl.program_id(2)

    @pl.when(pb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], b_ref[0],
                            preferred_element_type=jnp.float32,
                            precision=_HIGHEST)

    @pl.when(pb == pl.num_programs(2) - 1)
    def _flush():
        z = acc_ref[...]
        y = y_ref[0].astype(jnp.float32)
        w = w_ref[0]
        # epilogue → output dtype first, then native-dtype weighting: bit-
        # identical to host-weighting the unweighted kernel's outputs
        r = _epilogue(z, y, family, m_actual).astype(r_ref.dtype)
        r_ref[0] = _apply_w(w, r)
        rl = _row_loss(z, y, family, m_actual)[:, None]  # (bn, 1) f32
        loss_ref[0] = jnp.broadcast_to(
            _apply_w(w.astype(jnp.float32), rl),
            loss_ref.shape[1:]).astype(loss_ref.dtype)


@x32
def xb_loss_residual_replicate(
    X: jax.Array,
    B: jax.Array,
    Y: jax.Array,
    W: jax.Array,
    *,
    family: str = "none",
    m_actual: int | None = None,
    bn: int = DEFAULT_BN,
    bp: int = DEFAULT_BP,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused (w ⊙ r, per-row weighted loss) for B members, one shared X.

    The replicate analogue of :func:`xb_loss_residual`: one pass over the
    shared X per member yields both halves of that member's FISTA forward
    pair — ``loss_rows[b, i]`` carries ``w_{b,i}·ℓ(z_{b,i}, y_{b,i})``
    broadcast across lanes (sum lane 0 over un-padded rows for the
    member's weighted loss).
    """
    n, p = X.shape
    Bm, p_b, m = B.shape
    assert p_b == p and Y.shape == (Bm, n, m) and W.shape == (Bm, n, 1), (
        X.shape, B.shape, Y.shape, W.shape)
    assert n % bn == 0 and p % bp == 0, (n, p, bn, bp)
    m_actual = m if m_actual is None else m_actual
    grid = (Bm, n // bn, p // bp)
    kernel = functools.partial(_xb_loss_residual_replicate_kernel,
                               family=family, m_actual=m_actual)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bp), lambda b, nb, pb: (nb, pb)),  # shared X
            pl.BlockSpec((1, bp, m), lambda b, nb, pb: (b, pb, 0)),
            pl.BlockSpec((1, bn, m), lambda b, nb, pb: (b, nb, 0)),
            pl.BlockSpec((1, bn, 1), lambda b, nb, pb: (b, nb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn, m), lambda b, nb, pb: (b, nb, 0)),
            pl.BlockSpec((1, bn, m), lambda b, nb, pb: (b, nb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bm, n, m), X.dtype),
            jax.ShapeDtypeStruct((Bm, n, m), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, m), jnp.float32)],
        interpret=interpret,
    )(X, B, Y, W)


def _row_loss(z, y, family: str, m_actual: int):
    """Per-row loss ℓ(z_i, y_i) from the same z the epilogue consumes.

    Padded class lanes (≥ m_actual) are masked out so ops.py's 128-lane
    padding contributes exactly 0 to the loss.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, z.shape, dimension=z.ndim - 1)
    lm = lane < m_actual
    if family == "none":
        return jnp.zeros(z.shape[:-1], z.dtype)
    if family == "ols":
        per = 0.5 * jnp.square(z - y)
    elif family == "logistic":
        per = jnp.logaddexp(0.0, z) - y * z
    elif family == "poisson":
        per = jnp.exp(z) - y * z
    elif family == "multinomial":
        zm = jnp.where(lm, z, -jnp.inf)
        lse = jax.nn.logsumexp(zm, axis=-1)
        return lse - jnp.sum(jnp.where(lm, y * z, 0.0), axis=-1)
    else:
        raise ValueError(f"unknown family {family!r}")
    return jnp.sum(jnp.where(lm, per, 0.0), axis=-1)


def _xb_loss_residual_kernel(x_ref, b_ref, y_ref, r_ref, loss_ref, acc_ref,
                             *, family, m_actual):
    pb = pl.program_id(1)

    @pl.when(pb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=_HIGHEST)

    @pl.when(pb == pl.num_programs(1) - 1)
    def _flush():
        z = acc_ref[...]
        y = y_ref[...].astype(jnp.float32)
        r_ref[...] = _epilogue(z, y, family, m_actual).astype(r_ref.dtype)
        rl = _row_loss(z, y, family, m_actual)  # (bn,)
        loss_ref[...] = jnp.broadcast_to(rl[:, None],
                                         loss_ref.shape).astype(loss_ref.dtype)


@x32
def xb_loss_residual(
    X: jax.Array,
    B: jax.Array,
    Y: jax.Array,
    *,
    family: str = "none",
    m_actual: int | None = None,
    bn: int = DEFAULT_BN,
    bp: int = DEFAULT_BP,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One pass over X → (r, per-row loss); the FISTA forward pair fused.

    Returns ``(r (n, m), loss_rows (n, m))`` — each row of ``loss_rows``
    carries ℓ(z_i, y_i) broadcast across lanes; callers sum lane 0 over the
    un-padded rows.  Reads X once where loss + gradient previously streamed
    it twice.
    """
    n, p = X.shape
    m = B.shape[1]
    assert n % bn == 0 and p % bp == 0, (n, p, bn, bp)
    m_actual = m if m_actual is None else m_actual
    grid = (n // bn, p // bp)
    kernel = functools.partial(_xb_loss_residual_kernel, family=family,
                               m_actual=m_actual)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bp), lambda nb, pb: (nb, pb)),
            pl.BlockSpec((bp, m), lambda nb, pb: (pb, 0)),
            pl.BlockSpec((bn, m), lambda nb, pb: (nb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, m), lambda nb, pb: (nb, 0)),
            pl.BlockSpec((bn, m), lambda nb, pb: (nb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, m), X.dtype),
            jax.ShapeDtypeStruct((n, m), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, m), jnp.float32)],
        interpret=interpret,
    )(X, B, Y)
