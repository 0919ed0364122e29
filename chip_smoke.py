#!/usr/bin/env python3
"""One-chip smoke test: drive the SLOPE path system end to end on a TPU.

    python chip_smoke.py            # on a machine with a TPU
    python chip_smoke.py --tiny     # CPU rehearsal: same phases, tiny sizes

Phases, in order, each printing one line with its wall time split into
compile and run, and its check (any failure raises; nothing is caught):

1. device   — JAX's first device is a TPU (without ``--tiny``).
2. direct   — ``slope_path`` at the paper's size (n=200, p=20 000, 100-step
              BH path): OLS on an equicorrelated design, logistic on an AR
              design; every returned step passes the NumPy f64 KKT test.
3. cv       — 10-fold OLS CV through the planner (compact engine) on the
              same design, over the top 20 points of the 100-point σ
              grid; every fold path past its null head (index 0,
              β = 0 by construction) passes the KKT test on its training
              rows, and so does the full-data refit at the selected σ.
4. served   — ``AsyncPathService`` answers 8 OLS requests of seeded sizes
              (n ∈ [150, 256], p ∈ [12 000, 20 000], equicorrelated
              ρ = 0.5, the top 3 points of the 100-point σ grid); each
              response equals a direct ``pad="bucket"``
              fit bitwise and passes the KKT test.
5. restart  — a second service boots from the durable program store phase
              4 filled, compiles nothing, and serves the same betas bitwise.
6. kernels  — each Pallas kernel once at a real width against its
              ``repro.kernels.ref`` oracle evaluated on the host CPU.
7. x64      — phase 2's OLS fit over the top 4 points of its grid, with
              x64 on, in f64.

Phases 2–6 run in f32 with x64 off, so no f64 op hides in them.  The last
line of standard output is the JSON verdict the chip check reads.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# the KKT certificate every returned step must meet: in_subdifferential's
# tolerance is rtol·max(1, max σλ).  FISTA stops on an objective plateau
# plus a fixed-point test (SolverPolicy defaults), not on a KKT bound: the
# f64 path under the same policy certifies every step at rtol 1e-2, not
# every step at 1e-3 (OLS, n=200, p=20 000, equicorrelated ρ=0.5).  f32
# rounding of β is not what bounds the certificate — an f64 solution
# rounded to f32 keeps its level — so the f32 path is held to the f64
# path's level.
KKT_RTOL = 1e-2
KKT_LEVELS = (1e-3, KKT_RTOL, 1e-1, 1.0)  # reported per step
# kernels vs oracle: max |kernel − oracle| ≤ this × max |oracle| (f32
# accumulation over ≤ 20 480 terms sits near 1e-6; one bf16 pass near 4e-3)
KERNEL_RTOL = 1e-4


class SmokeFailure(AssertionError):
    pass


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums JAX's trace/lower/compile durations while a phase runs."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration


@contextlib.contextmanager
def phase(name, clock):
    start_c, t0 = clock.total, time.perf_counter()
    info = {}
    yield info
    wall = time.perf_counter() - t0
    comp = clock.total - start_c
    print(f"phase {name}: PASS wall={wall:.3f}s compile={comp:.3f}s "
          f"run={wall - comp:.3f}s | {info.get('check', '')}", flush=True)


# -- KKT certificate ----------------------------------------------------------

def _grad(X, y, beta, family):
    z = X @ beta
    r = z - y if family == "ols" else 1.0 / (1.0 + np.exp(-z)) - y
    return X.T @ r


def kkt_levels(X, y, betas, sigmas, lam, family):
    """Per step: the smallest of KKT_LEVELS the step passes (inf: none)."""
    from repro.core.kkt import kkt_optimal

    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    lam = np.asarray(lam, np.float64)
    out = []
    for b, s in zip(betas, sigmas):
        b = np.asarray(b, np.float64).ravel()
        g = _grad(X, y, b, family)
        lvl = np.inf
        for rtol in KKT_LEVELS:
            if kkt_optimal(g, b, float(s) * lam, atol=0.0, rtol=rtol):
                lvl = rtol
                break
        out.append(lvl)
    return np.asarray(out)


def kkt_gate(levels, what):
    check(float(np.max(levels)) <= KKT_RTOL,
          f"{what}: a step fails the KKT test at rtol {KKT_RTOL} "
          f"(levels {levels.tolist()})")
    hist = {f"{t:g}": int(np.sum(levels == t)) for t in KKT_LEVELS}
    return f"kkt {len(levels)} steps ok (steps per rtol {hist})"


# -- phases -------------------------------------------------------------------

def run_direct(sizes, seed, dtype, clock, label="direct"):
    from repro.api import LambdaSpec, PathSpec, Problem, SolverPolicy, slope_path
    from repro.core import logistic, ols
    from repro.data import make_classification, make_regression

    n, p, k, L = sizes["n"], sizes["p"], sizes["k"], sizes["L"]
    spec = PathSpec(LambdaSpec("bh", q=0.1), path_length=L)
    cases = [
        ("ols", ols, make_regression(n, p, k, rho=0.5, seed=seed,
                                     design="equi")),
        ("logistic", logistic, make_classification(n, p, k, rho=0.5,
                                                   seed=seed, design="ar")),
    ]
    for name, fam, (X, y, _) in cases:
        X, y = X.astype(dtype), y.astype(dtype)
        with phase(f"{label}/{name}", clock) as info:
            res = slope_path(Problem(X, y, fam), spec, SolverPolicy())
            check(res.betas.dtype == dtype,
                  f"betas came back {res.betas.dtype}, not {dtype}")
            check(np.all(np.isfinite(res.betas)), "non-finite betas")
            lv = kkt_levels(X, y, res.betas, res.sigmas, res.lam, name)
            print(f"  plan: {res.plan.summary()}")
            print(f"  screened per step: {[s.n_screened for s in res.steps]}")
            print(f"  solver iters per step: "
                  f"{[s.solver_iters for s in res.steps]}")
            print(f"  kkt level per step: {lv.tolist()}")
            info["check"] = (f"n={n} p={p} steps={len(res.steps)} "
                             + kkt_gate(lv, f"{label}/{name}"))


def run_cv(sizes, seed, clock):
    from repro.api import LambdaSpec, PathSpec, Problem, SolverPolicy, slope_path
    from repro.core import ols
    from repro.core.engine import cv_fold_indices
    from repro.data import make_regression

    n, p, k = sizes["n"], sizes["p"], sizes["k"]
    X, y, _ = make_regression(n, p, k, rho=0.5, seed=seed, design="equi")
    X, y = X.astype(np.float32), y.astype(np.float32)
    folds = 10
    with phase("cv", clock) as info:
        cv = slope_path(Problem(X, y, ols),
                        top_of_path(sizes, sizes["L_cv"], cv_folds=folds),
                        SolverPolicy())
        fp = cv.fold_paths
        print(f"  plan: {cv.plan.summary()}")
        check(cv.plan.mode == "compact",
              f"planner chose {cv.plan.mode}, not compact, at p >= 2n")
        fb = (np.zeros(fp.betas.shape[:2], int) if fp.compact_fallback is None
              else np.asarray(fp.compact_fallback))
        print(f"  compact fallback steps per fold: {fb.sum(axis=1).tolist()}")
        best = cv.best_index
        # every fold path past its null head: index 0 emits β = 0 at the
        # full-data σ_max, which a fold's own σ_max may exceed
        trains, _ = cv_fold_indices(y, folds, family=ols)
        fold_lv = [kkt_levels(X[tr], y[tr], fp.betas[f, 1:], cv.sigmas[1:],
                              cv.lam, "ols")
                   for f, tr in enumerate(trains)]
        for f, lv in enumerate(fold_lv):
            print(f"  fold {f}: solver iters "
                  f"{int(fp.solver_iters[f].sum())}, kkt level per step "
                  f"{lv.tolist()}")
        folds_ok = kkt_gate(np.concatenate(fold_lv), "cv folds")
        refit = slope_path(Problem(X, y, ols),
                           PathSpec(LambdaSpec("bh", q=0.1),
                                    sigmas=cv.sigmas[:best + 1],
                                    early_stop=False), SolverPolicy())
        lv = kkt_levels(X, y, refit.betas[-1:], refit.sigmas[-1:],
                        refit.lam, "ols")
        info["check"] = (f"folds {folds_ok}; best σ index {best}/"
                         f"{len(cv.sigmas)}; "
                         f"refit " + kkt_gate(lv, "cv refit"))


def served_requests(sizes, seed):
    """Seeded OLS requests on the direct fits' equicorrelated design."""
    from repro.data import make_regression

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(sizes["requests"]):
        n = int(rng.integers(sizes["n_lo"], sizes["n_hi"] + 1))
        p = int(rng.integers(sizes["p_lo"], sizes["p_hi"] + 1))
        X, y, _ = make_regression(n, p, sizes["k"], rho=0.5,
                                  seed=seed + 1 + i, design="equi")
        reqs.append((X.astype(np.float32), y.astype(np.float32)))
    return reqs


def top_of_path(sizes, Lt, **spec):
    """The first ``Lt`` points of the direct fits' σ grid (the same
    spacing): the path of the cv, served and x64 phases."""
    from repro.api import LambdaSpec, PathSpec

    # 1e-2: the default grid's σ(L)/σ(1) for n < p (paper §3.1.2)
    return PathSpec(LambdaSpec("bh", q=0.1), path_length=Lt,
                    sigma_ratio=1e-2 ** ((Lt - 1) / (sizes["L"] - 1)), **spec)


def serve_all(reqs, store_dir, spec):
    from repro.api import Problem, SolverPolicy
    from repro.serve import AsyncPathService, DurableProgramStore, Rejection

    policy = SolverPolicy(working_set=None)  # continuous batching: masked
    svc = AsyncPathService(store=DurableProgramStore(store_dir))
    try:
        boot = svc.stats()["cache"]
        futs = [svc.submit(problem=Problem(X, y), path=spec, policy=policy)
                for X, y in reqs]
        resps = [f.result(timeout=900) for f in futs]
        stats = svc.stats()["cache"]
    finally:
        svc.close()
    for r in resps:
        check(not isinstance(r, Rejection), f"request rejected: {r}")
    return resps, boot, stats


def run_served(sizes, seed, store_dir, clock):
    from repro.api import Problem, SolverPolicy, slope_path

    reqs = served_requests(sizes, seed)
    spec = top_of_path(sizes, sizes["L_served"])
    with phase("served", clock) as info:
        resps, _, stats = serve_all(reqs, store_dir, spec)
        levels = []
        for (X, y), r in zip(reqs, resps):
            direct = slope_path(Problem(X[None], y[None]), spec,
                                SolverPolicy(working_set=None, pad="bucket"))
            # the async service stops a path where the early-stop rules
            # fire; the direct fit carries the whole grid
            want = direct.betas[0][:len(r.betas)]
            check(np.array_equal(want, r.betas),
                  f"served betas differ from the direct pad='bucket' fit "
                  f"(n={X.shape[0]}, p={X.shape[1]}, max |diff| "
                  f"{np.max(np.abs(want - r.betas))})")
            levels.append(kkt_levels(X, y, r.betas, r.sigmas, r.lam, "ols"))
        shapes = sorted({(X.shape[0], X.shape[1]) for X, _ in reqs})
        info["check"] = (f"{len(reqs)} requests n,p in {shapes[0]}..{shapes[-1]}"
                         f" bitwise == direct pad='bucket'; builds="
                         f"{stats['builds']} saved={stats['store']['saved']}; "
                         + kkt_gate(np.concatenate(levels), "served"))
    return reqs, resps


def run_restart(reqs, first, store_dir, spec, clock):
    with phase("restart", clock) as info:
        resps, boot, stats = serve_all(reqs, store_dir, spec)
        check(stats["builds"] == 0,
              f"restart compiled {stats['builds']} programs (want 0)")
        check(stats["store"]["loaded"] > 0, "nothing loaded from the store")
        for a, b in zip(first, resps):
            check(np.array_equal(a.betas, b.betas),
                  "restarted service betas differ from the first boot")
        info["check"] = (f"boot loaded {boot['store']['loaded']} programs, "
                         f"builds=0, {len(resps)} responses bitwise equal")


def run_kernels(sizes, seed, clock):
    import jax

    from repro.kernels import ops
    from repro.kernels import ref as R

    n, p = sizes["kn"], sizes["kp"]
    rng = np.random.default_rng(seed)
    f32 = np.float32
    X = rng.normal(size=(n, p)).astype(f32)
    r = rng.normal(size=(n,)).astype(f32)
    b = (rng.normal(size=(p,)) * (rng.random(p) < 0.05)).astype(f32)
    y = (rng.random(n) < 0.5).astype(f32)
    mask = np.zeros(p, bool)
    mask[rng.choice(p, size=p // 20, replace=False)] = True
    Bm = 4
    Rb = rng.normal(size=(Bm, n)).astype(f32)
    Bb = (rng.normal(size=(Bm, p)) * (rng.random((Bm, p)) < 0.05)).astype(f32)
    Yb = rng.normal(size=(Bm, n)).astype(f32)
    Wb = rng.integers(0, 3, size=(Bm, n)).astype(f32)
    # integer-valued c − λ: every prefix sum is exact in f32, so the
    # rightmost argmax (ties included) has one right answer
    c = rng.integers(-3, 4, size=p).astype(f32)
    lam = np.zeros(p, f32)
    w = np.sort(rng.normal(size=p).astype(f32))[::-1] - np.linspace(
        0, 1, p, dtype=f32)[::-1]

    cases = [
        ("gradient", lambda: ops.slope_gradient(X, r),
         lambda: R.xt_matmul_ref(X, r[:, None])[:, 0]),
        ("gradient_masked", lambda: ops.slope_gradient_masked(X, r, mask),
         lambda: R.xt_matmul_masked_ref(X, r[:, None], mask)[:, 0]),
        ("gradient_compact", lambda: ops.slope_gradient_compact(X, r, mask),
         lambda: R.xt_matmul_compact_ref(X, r[:, None], mask)[:, 0]),
        ("residual", lambda: ops.slope_residual(X, b, y, family="ols"),
         lambda: R.xb_residual_ref(X, b[:, None], y[:, None], "ols")[:, 0]),
        ("residual_masked",
         lambda: ops.slope_residual_masked(X, b, y, mask, family="ols"),
         lambda: R.xb_residual_masked_ref(X, b[:, None], y[:, None], mask,
                                          "ols")[:, 0]),
        ("residual_compact",
         lambda: ops.slope_residual_compact(X, b, y, mask, family="ols"),
         lambda: R.xb_residual_compact_ref(X, b[:, None], y[:, None], mask,
                                           "ols")[:, 0]),
        ("loss_residual",
         lambda: ops.slope_loss_residual(X, b, y, family="logistic"),
         lambda: _loss_pair(R.xb_loss_residual_ref(
             X, b[:, None], y[:, None], "logistic"))),
        ("loss_residual_compact",
         lambda: ops.slope_loss_residual_compact(X, b, y, mask,
                                                 family="logistic"),
         lambda: _loss_pair(R.xb_loss_residual_compact_ref(
             X, b[:, None], y[:, None], mask, "logistic"))),
        ("gradient_replicate",
         lambda: ops.slope_gradient_replicate(X, Rb, Wb),
         lambda: R.xt_matmul_replicate_ref(X, Rb[..., None], Wb)[..., 0]),
        ("residual_replicate",
         lambda: ops.slope_residual_replicate(X, Bb, Yb, Wb, family="ols"),
         lambda: R.xb_residual_replicate_ref(X, Bb[..., None], Yb[..., None],
                                             Wb, "ols")[..., 0]),
        ("loss_residual_replicate",
         lambda: ops.slope_loss_residual_replicate(X, Bb, Yb, Wb,
                                                   family="ols"),
         lambda: _rep_loss_pair(R.xb_loss_residual_replicate_ref(
             X, Bb[..., None], Yb[..., None], Wb, "ols"))),
        ("screen_scan", lambda: ops.screen_scan(c, lam),
         lambda: R.screen_scan_ref(c, lam)),
        ("prox_pool", lambda: ops.prox_pool(w),
         lambda: R.prox_pool_ref(jax.numpy.asarray(w))),
    ]
    cpu = jax.devices("cpu")[0]
    fallbacks0 = dict(_fallbacks(ops))
    done = []
    with phase("kernels", clock) as info:
        for name, kern, oracle in cases:
            got = jax.tree.map(np.asarray, jax.block_until_ready(kern()))
            with jax.default_device(cpu):
                want = jax.tree.map(np.asarray, oracle())
            for g, o in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                check(g.shape == o.shape, f"{name}: shape {g.shape} vs {o.shape}")
                scale = max(float(np.max(np.abs(o))), 1e-30)
                err = float(np.max(np.abs(g.astype(np.float64) - o)))
                check(err <= KERNEL_RTOL * scale,
                      f"{name}: max |kernel - oracle| = {err:.3g} > "
                      f"{KERNEL_RTOL} x {scale:.3g}")
            done.append(name)
        check(dict(_fallbacks(ops)) == fallbacks0,
              f"kernel fallbacks taken: {_fallbacks(ops)}")
        info["check"] = (f"{len(done)} kernels at n={n} p={p} f32 match "
                         f"their oracles (rtol {KERNEL_RTOL}), no fallback; "
                         f"interpret={ops._interpret()}")


def _loss_pair(pair):
    r, rows = pair
    return np.sum(np.asarray(rows)), np.asarray(r)[:, 0]


def _rep_loss_pair(pair):
    r, rows = pair
    return np.sum(np.asarray(rows), axis=1), np.asarray(r)[..., 0]


def _fallbacks(ops):
    """``{op: count}`` of the kernel wrappers' counted fallbacks."""
    return ops.COMPACT_METRICS.label_values("fallbacks", "op")


# -- driver -------------------------------------------------------------------

# The direct fits run the whole 100-point grid; the cv, served and x64
# phases its top L_cv, L_served and L_x64 points, so that the smoke stays
# well within the chip check's 1 200 s.  On one v5e the whole-grid 10-fold
# CV took 475 s (78 of 100 steps per fold in the masked O(n·p) fallback);
# each of the 8 served requests' direct references runs the 8-slot
# (256, 16 384 or 32 768) program alone, and one such fit over the top 3
# points took 6 s to run; f64 is emulated.  The tiny sizes keep paths that
# cross a chunk boundary of the async service.
FULL = dict(n=200, p=20000, k=20, L=100, L_cv=20, L_served=3, L_x64=4,
            requests=8, n_lo=150, n_hi=256, p_lo=12000, p_hi=20000, kn=256,
            kp=20480)
TINY = dict(n=40, p=400, k=5, L=20, L_cv=20, L_served=12, L_x64=12,
            requests=4, n_lo=20, n_hi=40, p_lo=100, p_hi=400, kn=64,
            kp=1024)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at tiny sizes (interpret-mode kernels)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "smoke"))
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        print(f"no TPU: JAX's first device is {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import use_checkout_cache

    sizes = TINY if args.tiny else FULL
    clock = CompileClock()
    with phase("device", clock) as info:
        info["check"] = (f"platform={dev.platform} kind={dev.device_kind} "
                         f"count={len(jax.devices())} jax={jax.__version__} "
                         f"compile_cache={use_checkout_cache(ROOT)}")
    store_dir = os.path.join(args.out, "store")
    shutil.rmtree(store_dir, ignore_errors=True)

    run_direct(sizes, args.seed, np.float32, clock)
    run_cv(sizes, args.seed, clock)
    try:
        reqs, first = run_served(sizes, args.seed, store_dir, clock)
        run_restart(reqs, first, store_dir,
                    top_of_path(sizes, sizes["L_served"]), clock)
    finally:
        # its 8-slot executables run to tens of MiB: keep the output small
        shutil.rmtree(store_dir, ignore_errors=True)
    run_kernels(sizes, args.seed, clock)
    jax.config.update("jax_enable_x64", True)
    try:
        run_direct_f64(sizes, args.seed, clock)
    finally:
        jax.config.update("jax_enable_x64", False)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


def run_direct_f64(sizes, seed, clock):
    """Phase 2's OLS fit in f64 over the top of its path, x64 on (f64 is
    emulated on v5e)."""
    from repro.api import Problem, SolverPolicy, slope_path
    from repro.core import ols
    from repro.data import make_regression

    n, p, k = sizes["n"], sizes["p"], sizes["k"]
    X, y, _ = make_regression(n, p, k, rho=0.5, seed=seed, design="equi")
    with phase("x64/ols", clock) as info:
        res = slope_path(Problem(X, y, ols),
                         top_of_path(sizes, sizes["L_x64"]), SolverPolicy())
        check(res.betas.dtype == np.float64, f"betas {res.betas.dtype}")
        lv = kkt_levels(X, y, res.betas, res.sigmas, res.lam, "ols")
        info["check"] = (f"n={n} p={p} steps={len(res.steps)} "
                         + kkt_gate(lv, "x64/ols"))


if __name__ == "__main__":
    sys.exit(main())
