"""Replicate representation layer: one seed → B resampled problems, no
materialized ``(B, n, p)`` X.

A :class:`ResamplePlan` describes a whole resampling experiment with four
scalars — kind, replicate count, seed, subsample fraction — and expands it
deterministically into per-member *row weights* (and, for permutations,
per-member response orderings) via per-member jax PRNG key derivation:
``fold_in(PRNGKey(seed), b)`` gives replicate b its own key, so member b
of a B=256 plan draws exactly the same replicate as member b of a B=8 plan
with the same seed (prefix stability — the property that makes incremental
B sweeps and served chunking reproducible).

The weight representation is what makes replicates materialize-free:

* ``bootstrap``  — w_b ∈ ℕⁿ is the multinomial count vector of n draws
  with replacement; f_{w_b} is *exactly* the loss of the row-duplicated
  bootstrap sample (``Family.weighted_value``), so the engines solve B
  bootstrap problems against ONE shared ``(n, p)`` X.
* ``subsample``  — w_b ∈ {0,1}ⁿ keeps ⌈fraction·n⌉ rows (complementary
  -pairs-style subsampling for stability selection).
* ``permutation`` — w_b ≡ 1 and the *response* is permuted per member
  (:meth:`permuted_targets`); X never moves, which is what the
  max-|gradient| null calibration in :mod:`repro.resample.select` exploits.

``replicate_indices`` derives the equivalent row-index arrays *from the
same generated draws*, so the materialized row-duplication reference used
by the tests and benchmarks agrees with the weighted path by construction.

Memory: a plan occupies O(B·n) (the weights) next to the O(n·p) shared X —
the ROADMAP item-4 budget — versus O(B·n·p) for materialized replicates.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["ResamplePlan", "RESAMPLE_KINDS"]

RESAMPLE_KINDS = ("bootstrap", "permutation", "subsample")


@dataclasses.dataclass(frozen=True, eq=False)
class ResamplePlan:
    """Declarative description of a B-replicate resampling experiment.

    ``kind`` ∈ ``{"bootstrap", "permutation", "subsample"}``;
    ``n_replicates`` is B; ``seed`` feeds one ``jax.random.PRNGKey`` whose
    B-way split generates every member; ``fraction`` is the subsample
    keep-fraction (ignored by the other kinds).
    """

    kind: str = "bootstrap"
    n_replicates: int = 100
    seed: int = 0
    fraction: float = 0.5

    def __post_init__(self):
        if self.kind not in RESAMPLE_KINDS:
            raise ValueError(
                f"unknown resample kind {self.kind!r}; choose from "
                f"{RESAMPLE_KINDS}")
        if isinstance(self.n_replicates, bool) or not isinstance(
                self.n_replicates, int) or self.n_replicates < 1:
            raise ValueError(
                f"n_replicates must be a positive int, got "
                f"{self.n_replicates!r}")
        if not 0.0 < float(self.fraction) <= 1.0:
            raise ValueError(
                f"fraction must be in (0, 1], got {self.fraction!r}")

    # -- deterministic generation --------------------------------------------

    def keys(self) -> jax.Array:
        """The (B, 2) per-replicate key array.

        ``fold_in(PRNGKey(seed), b)`` rather than ``split(key, B)``: a
        member's key depends only on (seed, b), never on B, which is what
        makes the prefix-stability property above true.
        """
        base = jax.random.PRNGKey(self.seed)
        return jax.vmap(lambda b: jax.random.fold_in(base, b))(
            jnp.arange(self.n_replicates))

    def _subsample_count(self, n: int) -> int:
        return max(1, int(round(float(self.fraction) * n)))

    def row_weights(self, n: int, dtype=jnp.float64) -> jax.Array:
        """Per-member row weights ``(B, n)`` — counts, 0/1 masks or ones.

        This is the array the replicate engines thread through
        ``Family.value_residual``; it is the *only* per-member state of
        O(n) size the fused execution needs.
        """
        keys = self.keys()
        if self.kind == "bootstrap":
            def one(key):
                draws = jax.random.randint(key, (n,), 0, n)
                return jnp.zeros((n,), dtype).at[draws].add(
                    jnp.ones((), dtype))
        elif self.kind == "subsample":
            k = self._subsample_count(n)

            def one(key):
                perm = jax.random.permutation(key, n)
                return jnp.zeros((n,), dtype).at[perm[:k]].set(
                    jnp.ones((), dtype))
        else:  # permutation: the *response* moves, every row keeps weight 1
            def one(key):
                return jnp.ones((n,), dtype)
        return jax.vmap(one)(keys)

    def permutations(self, n: int) -> jax.Array:
        """Per-member row orderings ``(B, n)`` int32 (permutation kind)."""
        if self.kind != "permutation":
            raise ValueError(
                f"permutations are only defined for kind='permutation' "
                f"plans, got {self.kind!r}")
        return jax.vmap(lambda key: jax.random.permutation(key, n))(
            self.keys())

    def permuted_targets(self, y) -> jax.Array:
        """The ``(B, n[, ...])`` stack of per-member permuted responses."""
        y = jnp.asarray(y)
        perms = self.permutations(y.shape[0])
        return jax.vmap(lambda idx: jnp.take(y, idx, axis=0))(perms)

    # -- materialized reference ----------------------------------------------

    def replicate_indices(self, n: int) -> list[np.ndarray]:
        """Host-side row-index arrays equivalent to each member.

        Derived from the *same* device draws as :meth:`row_weights` /
        :meth:`permutations`, so ``X[idx], y[idx]`` is the materialized
        problem whose loss the weighted path reproduces exactly — the
        reference the property tests and the bench baseline fit against.
        """
        if self.kind == "permutation":
            return [np.asarray(p) for p in self.permutations(n)]
        w = np.asarray(self.row_weights(n))
        if self.kind == "bootstrap":
            return [np.repeat(np.arange(n), w[b].astype(np.int64))
                    for b in range(self.n_replicates)]
        return [np.flatnonzero(w[b]) for b in range(self.n_replicates)]


def _register(cls, leaf_fields: tuple[str, ...]):
    # same pytree idiom as repro.api.specs._register (kept local so the
    # resample package never imports the api/serve layers — the services
    # import *us* for the metrics read-through)
    aux_fields = tuple(f.name for f in dataclasses.fields(cls)
                       if f.name not in leaf_fields)

    def flatten(obj):
        return (tuple(getattr(obj, f) for f in leaf_fields),
                tuple(getattr(obj, f) for f in aux_fields))

    def unflatten(aux, children):
        kw = dict(zip(leaf_fields, children))
        kw.update(zip(aux_fields, aux))
        return cls(**kw)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)


# fully static: a plan is four scalars; the arrays it *generates* are
# recomputed on demand, never carried as leaves
_register(ResamplePlan, ())
