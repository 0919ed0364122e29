"""Screening across GLM families (paper §3.2.3): OLS, logistic, Poisson,
multinomial — each fitted with and without the strong rule.

    PYTHONPATH=src python examples/glm_families.py
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import use_checkout_cache

use_checkout_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from repro.core import bh_sequence, fit_path, get_family
from repro.data import (
    make_classification,
    make_multinomial,
    make_poisson,
    make_regression,
)


def main():
    n, p, k = 200, 8000, 20
    cases = {
        "ols": (make_regression, {}),
        "logistic": (make_classification, {}),
        "poisson": (make_poisson, {}),
        "multinomial": (make_multinomial, {"m": 3}),
    }
    print(f"{'family':12s} {'t_screen':>9s} {'t_none':>9s} {'speedup':>8s} "
          f"{'viol':>5s} {'active@end':>10s}")
    for name, (maker, kw) in cases.items():
        X, y, _ = maker(n, p, k=k, rho=0.3, seed=1, **kw)
        fam = get_family(name, 3)
        lam = np.asarray(bh_sequence(p * fam.n_classes, q=n / (10 * p)))
        # warm jit caches so the comparison is steady-state (like the paper's
        # non-JIT R baseline); benchmarks/common.py does the same
        for scr in ("strong", "none"):
            fit_path(X, y, lam, fam, screening=scr, path_length=4,
                     solver_tol=1e-9)
        res_s = fit_path(X, y, lam, fam, screening="strong", path_length=30,
                         solver_tol=1e-9)
        res_n = fit_path(X, y, lam, fam, screening="none", path_length=30,
                         solver_tol=1e-9)
        print(f"{name:12s} {res_s.total_time:9.2f} {res_n.total_time:9.2f} "
              f"{res_n.total_time / res_s.total_time:7.1f}x "
              f"{res_s.total_violations:5d} {res_s.steps[-1].n_active:10d}")


if __name__ == "__main__":
    main()
