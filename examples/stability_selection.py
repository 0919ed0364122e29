"""Stability selection over materialize-free SLOPE replicates.

    PYTHONPATH=src python examples/stability_selection.py

Fits B subsample replicates of one problem as ONE weight-fused device
program (every member shares the single (n, p) design; per-member state
is an (n,) row-weight vector), prints the per-predictor selection
frequencies next to the single-path support, and closes with
permutation p-values for the same predictors.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import use_checkout_cache

use_checkout_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from repro.core import bh_sequence, fit_path, ols
from repro.data import make_regression
from repro.resample import (
    ResamplePlan,
    permutation_pvalues,
    resample_stats,
    stability_selection,
)


def main():
    n, p, k = 200, 400, 8
    B = 64
    print(f"simulating OLS-SLOPE data: n={n}, p={p}, k={k}")
    X, y, beta_true = make_regression(n, p, k=k, rho=0.2, seed=0, noise=0.5)
    lam = np.asarray(bh_sequence(p, q=0.05))
    support = np.flatnonzero(beta_true != 0)

    print("\nsingle path (the baseline selector):")
    res = fit_path(X, y, lam, ols, screening="strong", path_length=40,
                   solver_tol=1e-8, max_iter=5000)
    single = np.flatnonzero(np.abs(np.asarray(res.betas)[-1]).reshape(p, -1)
                            .max(axis=1) > 0)
    print(f"  last-grid-point support: {len(single)} predictors")

    plan = ResamplePlan(kind="subsample", n_replicates=B, seed=1,
                        fraction=0.5)
    print(f"\nstability selection: B={B} half-subsample replicates, "
          f"one shared {n}x{p} design, ({B}, {n}) weight matrix "
          f"({plan.kind!r} plan is deterministic and prefix-stable)")
    sel = stability_selection(X, y, lam, plan, path_length=40,
                              threshold=0.6, solver_tol=1e-8, max_iter=5000)
    picked = np.flatnonzero(sel.selected)

    print(f"\n  {'predictor':>9s}  {'max freq':>8s}  {'single':>6s}  "
          f"{'stable':>6s}  {'truth':>5s}")
    show = sorted(set(support) | set(picked) | set(single[:k]))
    for j in show:
        print(f"  {j:9d}  {sel.max_frequency[j]:8.2f}  "
              f"{'yes' if j in single else '':>6s}  "
              f"{'yes' if sel.selected[j] else '':>6s}  "
              f"{'*' if j in support else '':>5s}")
    tp = len(set(picked) & set(support))
    print(f"\n  threshold={sel.threshold}: {len(picked)} selected, "
          f"{tp}/{k} true predictors recovered")

    print("\npermutation p-values (max-|gradient| null, B=199):")
    pv = permutation_pvalues(X, y, ResamplePlan(kind="permutation",
                                                n_replicates=199, seed=2))
    for j in support:
        print(f"  predictor {j:4d}: p = {pv.pvalues[j]:.3f}")
    print(f"  median null-predictor p = "
          f"{np.median(np.delete(pv.pvalues, support)):.3f}")

    st = resample_stats()
    print(f"\nns=resample telemetry: replicates={st['replicates']}, "
          f"null draws={st['null_calibration_draws']:.0f}")


if __name__ == "__main__":
    main()
