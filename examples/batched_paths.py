"""Batched path engine through the declarative front door.

    PYTHONPATH=src python examples/batched_paths.py

Two workloads the host driver handles one-problem-at-a-time but the device
engine fits in a single ``lax.scan`` × ``vmap`` program:

1. a batch of B independent (X, y) problems (bootstrap replicates here),
2. K-fold cross-validation over one σ grid, with the best σ selected from
   held-out deviance.

Everything goes through ``repro.api.slope_path``: a ``Problem`` +
``PathSpec`` + ``SolverPolicy`` triple, with ``backend="auto"`` resolved by
the planner (``res.plan.explain()`` says what ran and why).
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import use_checkout_cache

use_checkout_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import time

import numpy as np

from repro.api import LambdaSpec, PathSpec, Problem, SolverPolicy, slope_path
from repro.data import make_regression


def main():
    rng = np.random.default_rng(0)
    n, p, k, B = 50, 80, 6, 8
    X, y, beta_true = make_regression(n, p, k=k, rho=0.2, seed=0, noise=0.4)
    lam = LambdaSpec("bh", q=0.1)
    # dense grid over the top decade of the path: the resolution regime
    # model selection explores, and where batching pays off most on CPU
    spec = PathSpec(lam=lam, path_length=40, sigma_ratio=0.1)
    policy = SolverPolicy(solver_tol=1e-9, max_iter=10000)

    # -- 1. bootstrap replicates, fitted as ONE compiled program ------------
    idx = rng.integers(0, n, size=(B, n))
    batch = Problem(X[idx], y[idx])          # (B, n, p) resampled designs
    single = Problem(X, y)
    # warm the compile caches first: both arms are timed steady-state
    slope_path(batch, spec, policy)
    host_spec = PathSpec(lam=lam, path_length=40, sigma_ratio=0.1,
                         early_stop=False)
    slope_path(Problem(X[idx][0], y[idx][0]), host_spec, policy)
    t0 = time.perf_counter()
    res = slope_path(batch, spec, policy)
    t_batched = time.perf_counter() - t0
    print(res.plan.explain())
    t0 = time.perf_counter()
    for b in range(B):
        slope_path(Problem(X[idx][b], y[idx][b]), host_spec, policy)
    t_loop = time.perf_counter() - t0
    print(f"\nbootstrap B={B}: batched {t_batched:.2f}s vs looped "
          f"{t_loop:.2f}s ({t_loop / t_batched:.1f}x)")

    # bootstrap support stability: fraction of replicates selecting each
    # true predictor at the last path point
    support = (np.abs(res.betas[:, -1, :]) > 1e-8)
    stab = support[:, np.nonzero(beta_true)[0]].mean()
    print(f"true-support selection frequency across replicates: {stab:.2f}")

    # -- 2. K-fold CV on a shared sigma grid --------------------------------
    cv = slope_path(single,
                    PathSpec(lam=lam, path_length=40, sigma_ratio=0.1,
                             cv_folds=5),
                    policy)
    print(f"\n5-fold CV in {cv.total_time:.2f}s — "
          f"best sigma {cv.best_sigma:.4f} (index {cv.best_index}, "
          f"mean held-out deviance {cv.mean_val_deviance[cv.best_index]:.3f} "
          f"vs null {cv.mean_val_deviance[0]:.3f}) "
          f"[{cv.plan.summary()}]")

    # -- 3. compact working-set engine at p >> n ----------------------------
    # with p >= 2n the planner picks the compact engine on its own: the
    # masked engine pays O(n*p) per FISTA iteration, the compact engine
    # gathers the screened columns into (n, W) on device and pays O(n*W).
    # Overflowing steps fall back to the masked solve in-graph (flagged in
    # compact_fallback) and the shared bucket registry grows for the next
    # same-shape call.
    n2, p2 = 60, 1024
    X2, y2, _ = make_regression(n2, p2, k=5, rho=0.0, seed=3, noise=0.3)
    idx2 = rng.integers(0, n2, size=(B, n2))
    batch2 = Problem(X2[idx2], y2[idx2])
    spec2 = PathSpec(lam=LambdaSpec("bh", q=0.05), path_length=40,
                     sigma_ratio=0.5)
    masked_policy = SolverPolicy(backend="masked", solver_tol=1e-9,
                                 max_iter=10000)
    auto_policy = SolverPolicy(solver_tol=1e-9, max_iter=10000)
    slope_path(batch2, spec2, masked_policy)
    slope_path(batch2, spec2, auto_policy)
    t0 = time.perf_counter()
    masked = slope_path(batch2, spec2, masked_policy)
    t_masked = time.perf_counter() - t0
    t0 = time.perf_counter()
    compact = slope_path(batch2, spec2, auto_policy)
    t_compact = time.perf_counter() - t0
    diff = np.abs(masked.betas - compact.betas).max()
    print(f"\nplanner chose {compact.plan.summary()} at p={p2}: "
          f"{t_compact:.2f}s vs masked {t_masked:.2f}s "
          f"({t_masked / t_compact:.1f}x), "
          f"peak working set {int(compact.ws_size.max())}, "
          f"fallback steps {int(compact.compact_fallback.sum())}, "
          f"max |beta| diff {diff:.1e}")


if __name__ == "__main__":
    main()
