"""Feature-parallel (column-sharded) SLOPE screening with ``shard_map``.

At cluster scale the design matrix X (n × p, p ≫ n) is column-sharded over
the mesh: shard d owns X[:, d·p/D : (d+1)·p/D].  Per path step the strong
rule needs

  1. the full gradient  ∇f = Xᵀ r         — embarrassingly parallel over
     columns once the residual r (length n) is replicated;
  2. the *sorted* surrogate and the cumsum scan — global order matters.

Gathering all p magnitudes defeats the point, so we exploit the paper's own
observation (Table 2: the screened set is a small multiple of the active
set): the screened set S is always a prefix of the global magnitude order,
so S ⊆ top-`cap` as long as card(S) ≤ cap.  Each shard contributes its local
top-`cap ÷ D` … actually its local top-`cap` (safe: global top-cap ⊆ union
of local top-caps), candidates are all-gathered (O(D·cap) ≪ p), sorted, and
screened with the closed-form cumsum-argmax rule.  If the returned k hits
the cap the caller doubles it and retries — exactness is preserved.

The residual r = ∂ℓ/∂z needs z = Xβ = Σ_d X_d β_d: one ``psum`` of an
n-vector per gradient evaluation — the only communication that scales with
data rather than candidates.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .screening import screen_k

__all__ = ["sharded_linear_predictor", "sharded_gradient", "distributed_strong_rule",
           "DistributedScreenResult"]


class DistributedScreenResult(NamedTuple):
    k: jax.Array            # predicted support size (global)
    threshold: jax.Array    # |surrogate| of the k-th kept candidate
    keep_mask: jax.Array    # bool (p,), column-sharded like X
    hit_cap: jax.Array      # True → retry with a larger cap


def sharded_linear_predictor(mesh: Mesh, axis: str):
    """z = Xβ with X and β column/feature-sharded: local matvec + psum."""

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(None, axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    def z_fn(X_local, beta_local):
        return jax.lax.psum(X_local @ beta_local, axis)

    return z_fn


def sharded_gradient(mesh: Mesh, axis: str):
    """∇f shard: Xᵀr needs no communication when X is column-sharded."""

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(None, axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )
    def g_fn(X_local, r):
        return X_local.T @ r

    return g_fn


def distributed_strong_rule(mesh: Mesh, axis: str, *, cap: int, p_total: int):
    """Strong rule for SLOPE over a column-sharded gradient.

    Inputs (to the returned callable):
      grad         — (p,) gradient at the previous solution, sharded over ``axis``
      gap_cap      — (cap·D,) first cap·D entries of λ^(m) − λ^(m+1)
      lam_cap      — (cap·D,) first cap·D entries of λ^(m+1)
      lam_min      — scalar λ^(m+1)_p (smallest penalty)
      gap_tail_max — scalar max_{j>cap·D} (λ^(m)_j − λ^(m+1)_j)

    Only the top-``cap`` magnitudes per shard enter the global screen
    (all-gather payload and sort bounded at cap·D ≪ p).  Truncation is a
    *prefix* of Algorithm 2's input, so the result is certified exact only
    when the un-gathered tail provably cannot raise the running cumsum
    above its current maximum: every un-gathered surrogate value is ≤
    c_bound = max over shards of the shard's cap-th magnitude (+ the λ-gap
    bound), and each tail term contributes ≤ c_bound − λ_min.  When the
    certificate fails the callable reports uncertain=True and the caller
    retries with a doubled cap — exactness is never silently lost.
    """

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(), P(), P(), P()),
        out_specs=(P(), P(), P(axis), P()),
        check_vma=False,
    )
    def screen_fn(grad_local, gap_cap, lam_cap, lam_min, gap_tail_max):
        mag_local = jnp.abs(grad_local)
        top_local, _ = jax.lax.top_k(mag_local, cap)
        cand = jax.lax.all_gather(top_local, axis, tiled=True)  # (cap·D,)
        cand = -jnp.sort(-cand)
        c = cand + gap_cap
        s = jnp.cumsum(c - lam_cap)
        k = screen_k(c, lam_cap)
        # threshold: magnitude of the weakest kept candidate (∞ if none kept)
        thr = jnp.where(k > 0, cand[jnp.maximum(k - 1, 0)], jnp.inf)
        keep_local = mag_local >= thr

        capD = cand.shape[0]
        c_bound = jax.lax.pmax(top_local[-1], axis) + gap_tail_max
        tail = (p_total - capD) * jnp.maximum(c_bound - lam_min, 0.0)
        best = jnp.max(s)
        uncertain = (k >= capD) | (s[-1] + tail >= jnp.maximum(best, 0.0))
        return k, thr, keep_local, uncertain

    return screen_fn
