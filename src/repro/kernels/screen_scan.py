"""Pallas TPU kernel for the strong-rule screen (paper Algorithm 2).

Uses the closed form derived in DESIGN.md §1: with s = cumsum(c − λ),
k = rightmost argmax of s when max(s) ≥ 0, else 0.  The kernel streams
(c, λ) through VMEM in blocks of (rows × 128) tiles, carrying three scalars
across the sequential TPU grid: the running total of (c − λ), the best (rightmost-max) cumsum
value, and its global index.  One pass, O(p) HBM traffic — the screen is
bandwidth-bound by construction, matching the paper's "cheaper than one
gradient step" claim.

Caller pads the tail with c − λ = −1 (strictly decreasing ⇒ never the
rightmost argmax) — see ops.screen_scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mosaic import x32

__all__ = ["screen_scan_kernel_call", "DEFAULT_BLOCK", "LANES"]

DEFAULT_BLOCK = 2048


LANES = 128


def _inclusive_scan(x, axis):
    """Inclusive prefix sum along ``axis`` in log₂(len) shifted adds — the
    TPU kernel compiler has no cumsum, but it rotates vectors natively."""
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    shift = 1
    while shift < x.shape[axis]:
        x = x + jnp.where(pos >= shift, pltpu.roll(x, shift, axis), 0.0)
        shift *= 2
    return x


def _screen_kernel(c_ref, lam_ref, o_ref, total_ref, best_ref, idx_ref):
    b = pl.program_id(0)
    rows, lanes = c_ref.shape  # one block = rows × 128, row-major

    @pl.when(b == 0)
    def _init():
        # explicit f32: under jax_enable_x64 bare Python literals are weak
        # f64 and cannot be stored into the f32 SMEM scratch
        total_ref[0] = jnp.float32(0.0)
        best_ref[0] = jnp.float32(-jnp.inf)
        idx_ref[0] = jnp.int32(0)

    d = c_ref[...].astype(jnp.float32) - lam_ref[...].astype(jnp.float32)
    # block-local prefix sums in row-major order: within each row, then
    # the running total of the rows above
    in_row = _inclusive_scan(d, 1)
    row_sum = jnp.broadcast_to(in_row[:, lanes - 1:], in_row.shape)
    s = in_row + (_inclusive_scan(row_sum, 0) - row_sum) + total_ref[0]

    # rightmost local argmax: the largest position holding the maximum
    local_best = jnp.max(s)
    pos = (b * rows * lanes
           + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    local_idx = jnp.max(jnp.where(s == local_best, pos, -1))

    better = local_best >= best_ref[0]  # ≥ keeps the *rightmost* on ties
    best_ref[0] = jnp.where(better, local_best, best_ref[0])
    idx_ref[0] = jnp.where(better, local_idx, idx_ref[0])
    total_ref[0] = total_ref[0] + jnp.sum(d)

    @pl.when(b == pl.num_programs(0) - 1)
    def _finish():
        k = jnp.where(best_ref[0] >= 0, idx_ref[0] + 1, 0)
        o_ref[0] = k.astype(jnp.int32)


@x32
def screen_scan_kernel_call(
    c: jax.Array, lam: jax.Array, *, block: int = DEFAULT_BLOCK, interpret: bool = False
) -> jax.Array:
    """k for pre-padded inputs (length divisible by ``block``, and
    ``block`` by 8·128 — one block is a whole number of (8, 128) tiles)."""
    (p,) = c.shape
    assert p % block == 0 and block % (8 * LANES) == 0, (p, block)
    rows = block // LANES
    spec = pl.BlockSpec((rows, LANES), lambda b: (b, 0))
    return pl.pallas_call(
        _screen_kernel,
        grid=(p // block,),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        scratch_shapes=[
            pltpu.SMEM((1,), jnp.float32),
            pltpu.SMEM((1,), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        interpret=interpret,
    )(c.reshape(-1, LANES), lam.reshape(-1, LANES))[0]
