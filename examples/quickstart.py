"""Quickstart: the strong screening rule for SLOPE on a p ≫ n problem.

    PYTHONPATH=src python examples/quickstart.py

Fits a full SLOPE regularization path twice — with and without the strong
screening rule — and shows (a) identical estimates, (b) the screened-set
sizes, (c) the wall-clock speedup.  This is the paper's headline result.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import use_checkout_cache

use_checkout_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import time

import numpy as np

from repro.core import bh_sequence, fit_path, ols
from repro.data import make_regression


def main():
    n, p, k = 100, 4000, 15
    print(f"simulating OLS-SLOPE data: n={n}, p={p}, k={k} (p >> n)")
    X, y, beta_true = make_regression(n, p, k=k, rho=0.1, seed=0, noise=0.5)
    lam = np.asarray(bh_sequence(p, q=n / (10 * p)))

    runs = {}
    for screening in ("strong", "none"):
        t0 = time.perf_counter()
        res = fit_path(X, y, lam, ols, screening=screening, path_length=60,
                       solver_tol=1e-10, max_iter=10000)
        runs[screening] = (res, time.perf_counter() - t0)
        print(f"  screening={screening:6s}  wall={runs[screening][1]:7.2f}s  "
              f"steps={len(res.steps)}  violations={res.total_violations}")

    scr, t_scr = runs["strong"]
    ref, t_ref = runs["none"]
    # early stopping may trigger one step apart (deviance at 1e-7 of the
    # threshold); compare the common prefix
    L = min(len(scr.betas), len(ref.betas))
    err = np.abs(scr.betas[:L] - ref.betas[:L]).max()
    print(f"\nmax |beta_screened − beta_unscreened| = {err:.2e}  (identical fits)")
    print(f"speedup from the strong rule: {t_ref / t_scr:.1f}x")

    print("\npath profile (every 10th step):")
    print("  step   sigma      active  screened  screened/p")
    for i, s in enumerate(scr.steps):
        if i % 10 == 0 and i > 0:
            print(f"  {i:4d}  {s.sigma:9.4f}  {s.n_active:6d}  {s.n_screened:8d}"
                  f"  {s.n_screened / p:9.3f}")

    hits = max(int(((np.abs(b) > 1e-8)[:k]).sum()) for b in scr.betas)
    print(f"\nbest true-support recovery along the path: {hits}/{k}")


if __name__ == "__main__":
    main()
