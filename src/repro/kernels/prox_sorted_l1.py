"""Pallas kernel for the pooling stage of the sorted-ℓ1 prox.

The prox (FastProxSL1) is sort → subtract λ → PAVA (non-increasing) → clip.
The sort stays in XLA (`jax.lax.sort` is already systolic-sort optimal on
TPU); this kernel keeps the PAVA pooling entirely on-core: input, block
stack (sums/counts) and output sit in scalar memory (SMEM), because every
access is one element at a dynamic index.  PAVA is inherently sequential
(each push may pool with earlier blocks), so the kernel is a
single-program scan — its value on TPU is locality, not parallelism — and
SMEM bounds it to p ≤ SMEM_ELEM_LIMIT.  ops.py falls back to the pure-jnp
oracle beyond that, and counts the fallback.

Implementation note: ``lax.while_loop`` *cond* functions must not read Refs
(state discharge evaluates them against a snapshot), so both loops carry a
continue-flag computed inside the body — do-while style.

Both passes stop at the live bound q = 1 + the last index where w > 0
(:func:`live_bound`, scalar-prefetched); the caller writes zeros past it.
Exact: a block that takes in an element past q has a sum ≤ 0, and it
pools only with blocks whose sum is ≤ 0, so every block with a positive
sum is final after q pushes and every other clips to 0.

Pass 1 (stack build):    one push per element, amortised one pool per push.
Pass 2 (expansion):      two-pointer sweep writing block means, clipped at 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mosaic import x32

__all__ = ["prox_pool_kernel_call", "live_bound", "SMEM_ELEM_LIMIT"]

# input, output, block sums and counts all live in the core's 1 MiB scalar
# memory (SMEM), the only memory a TPU kernel can index one element at a
# time: four p-length f32 buffers fit up to p = 32768
SMEM_ELEM_LIMIT = 32 * 1024


def live_bound(w: jax.Array) -> jax.Array:
    """int32 q = 1 + the last index where ``w`` is not ≤ 0 (a NaN counts
    as live), 0 where there is none: the pooled output past q is 0."""
    (p,) = w.shape
    return jnp.max(jnp.where(w <= 0, 0, jnp.arange(1, p + 1, dtype=jnp.int32)))


def _load1(ref, i):
    return ref[i]


def _store1(ref, i, val, dtype=jnp.float32):
    ref[i] = jnp.asarray(val, dtype)


def _prox_pool_kernel(q_ref, w_ref, o_ref, sums_ref, counts_ref):
    q = q_ref[0]

    def push(i, top):
        w_i = _load1(w_ref, i).astype(jnp.float32)

        # current (not yet stored) block rides in the carry; pool downward
        # while it violates monotonicity against the stored block below
        def body(carry):
            t, s, c, _ = carry
            below = jnp.maximum(t - 1, 0)
            s_p = _load1(sums_ref, below)
            c_p = _load1(counts_ref, below)
            do_pool = (t > 0) & (s * c_p >= s_p * c)
            s = jnp.where(do_pool, s + s_p, s)
            c = jnp.where(do_pool, c + c_p, c)
            t = jnp.where(do_pool, t - 1, t)
            return t, s, c, do_pool

        def cond(carry):
            return carry[3]

        t, s, c, _ = lax.while_loop(
            cond, body, (top, w_i, jnp.float32(1.0), jnp.bool_(True))
        )
        _store1(sums_ref, t, s)
        _store1(counts_ref, t, c)
        return t + 1

    lax.fori_loop(0, q, push, 0)

    # Pass 2: expand block means.  (block index b, elements consumed) sweep.
    def emit(i, carry):
        b, consumed = carry

        def body(carry):
            b, consumed, _ = carry
            cnt = _load1(counts_ref, b).astype(jnp.int32)
            adv = i >= consumed + cnt
            b = jnp.where(adv, b + 1, b)
            consumed = jnp.where(adv, consumed + cnt, consumed)
            return b, consumed, adv

        def cond(carry):
            return carry[2]

        b, consumed, _ = lax.while_loop(cond, body, (b, consumed, jnp.bool_(True)))
        val = jnp.maximum(_load1(sums_ref, b) / _load1(counts_ref, b), 0.0)
        _store1(o_ref, i, val, o_ref.dtype)
        return b, consumed

    lax.fori_loop(0, q, emit, (0, 0))


@x32
def prox_pool_kernel_call(w: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Non-increasing isotonic projection of ``w`` clipped at 0."""
    (p,) = w.shape
    q = live_bound(w)
    out = pl.pallas_call(
        _prox_pool_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
            scratch_shapes=[
                pltpu.SMEM((p,), jnp.float32),
                pltpu.SMEM((p,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((p,), w.dtype),
        interpret=interpret,
        # the benchmark's trace reduction finds the kernel by this name
        name="prox_pool",
    )(q[None], w)
    # the kernel writes the first q outputs only
    return jnp.where(jnp.arange(p) < q, out, 0)
