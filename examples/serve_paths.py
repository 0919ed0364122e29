"""PathService: serving a mixed-shape stream of SLOPE path requests.

    PYTHONPATH=src python examples/serve_paths.py

A request stream where nearly every problem has its own (n, p) is the worst
case for one-request-at-a-time fitting on an XLA backend: each new shape
compiles its own program (seconds) to run a solve (milliseconds).  The
service pads requests into power-of-two buckets, micro-batches same-bucket
requests into one compiled program, and caches compiled executables — so a
whole stream funnels through a handful of compilations.

Requests are the same declarative ``(Problem, PathSpec, SolverPolicy)``
triples the direct ``repro.api.slope_path`` front door takes, so served
results are bit-identical to direct ``pad="bucket"`` execution of the same
specs, and ``svc.stats()["plans"]`` shows which execution plans actually
ran.

The service is built with ``tracing=True``, so every response carries a
gap-free admit→deliver span timeline (``resp.trace``), and the unified
metrics registry behind ``svc.stats()`` is dumped in Prometheus text
format at the end.
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
from repro.obs import prometheus_text
from repro.serve import PathService


def make_stream(R, rng):
    reqs = []
    for i in range(R):
        n = int(rng.integers(33, 64))
        p = int(rng.integers(40, 120))
        X, y, _ = make_regression(n, p, k=5, rho=0.2, seed=i)
        reqs.append(Problem(X, y))
    return reqs


def main():
    rng = np.random.default_rng(0)
    R = 12
    reqs = make_stream(R, rng)
    shapes = sorted({pb.X.shape for pb in reqs})
    print(f"{R} requests over {len(shapes)} distinct shapes: {shapes}\n")
    # early_stop=False: served responses always carry the full σ grid, so
    # the one-at-a-time arm and the bitwise comparison run the same grid
    spec = PathSpec(lam=LambdaSpec("bh", q=0.1), path_length=40,
                    sigma_ratio=0.1, early_stop=False)
    policy = SolverPolicy(solver_tol=1e-8, max_iter=20000)
    # baseline arm: the device engine one request at a time, native shapes —
    # a fresh XLA compilation per distinct (n, p)
    unbatched = SolverPolicy(backend="masked", solver_tol=1e-8,
                             max_iter=20000)
    padded = SolverPolicy(backend="masked", pad="bucket", solver_tol=1e-8,
                          max_iter=20000)

    # -- one-request-at-a-time baseline: a compile per distinct shape -------
    t0 = time.perf_counter()
    base = [slope_path(pb, spec, unbatched) for pb in reqs]
    t_base = time.perf_counter() - t0
    print(f"one-at-a-time: {t_base:.1f}s  ({R / t_base:.2f} req/s)  "
          f"[{base[0].plan.summary()}]")

    # -- served: bucketed, micro-batched, compiled-program cache ------------
    svc = PathService(max_batch=8, max_delay=0.05, tracing=True)
    t0 = time.perf_counter()
    rids = [svc.submit(problem=pb, path=spec, policy=policy) for pb in reqs]
    svc.flush()
    resps = [svc.poll(r) for r in rids]
    t_serve = time.perf_counter() - t0
    st = svc.stats()
    print(f"served:        {t_serve:.1f}s  ({R / t_serve:.2f} req/s, "
          f"{t_base / t_serve:.1f}x) — {st['cache']['size']} compiled "
          f"programs, occupancy {st['occupancy_mean']:.2f}, "
          f"p50 {st['latency_ms_p50']:.0f}ms / p95 {st['latency_ms_p95']:.0f}ms")
    print(f"executed plans: {st['plans']}")

    # served == direct padded call of the SAME spec triple, bit for bit
    direct = slope_path(reqs[0], spec, padded)
    assert np.array_equal(resps[0].betas, direct.betas)
    diff = float(np.abs(resps[0].betas - base[0].betas).max())
    print(f"\nserved betas == direct pad='bucket' betas (bitwise); "
          f"vs native shape max|Δ| = {diff:.1e} (solver tolerance)")

    # steady state: the cache is warm, requests just batch and run
    t0 = time.perf_counter()
    rids = [svc.submit(problem=pb, path=spec, policy=policy) for pb in reqs]
    svc.flush()
    assert all(svc.poll(r) is not None for r in rids)
    t_steady = time.perf_counter() - t0
    print(f"steady state:  {t_steady:.1f}s  ({R / t_steady:.2f} req/s, "
          f"{t_base / t_steady:.1f}x)")

    # -- a CV request rides the same queues as plain fits -------------------
    X, y, _ = make_regression(60, 50, k=4, rho=0.0, seed=99, noise=0.3)
    rid = svc.submit(
        problem=Problem(X, y),
        path=PathSpec(lam=LambdaSpec("bh", q=0.1), path_length=25,
                      cv_folds=4, selection="1se"),
        policy=SolverPolicy(solver_tol=1e-9, max_iter=5000))
    cv = svc.poll(rid, flush=True)
    print(f"\n4-fold CV via the service: best σ (1-SE rule) = "
          f"{cv.best_sigma:.4f} at index {cv.best_index} "
          f"(min rule: index {cv.best_index_min}); "
          f"fold occupancy {cv.fold_responses[0].batch_occupancy:.2f}")

    # -- observability: one request's span timeline + the registry dump -----
    # tracing=True stamps every response with a gap-free admit→deliver
    # timeline; where a request's wall time went (queueing? compile?
    # execute?) is readable straight off the response
    tr = resps[0].trace
    print(f"\nrequest {tr.rid} timeline ({tr.total_s * 1e3:.0f} ms total):")
    print(tr.render())
    # every counter/gauge/histogram behind svc.stats() lives in one
    # registry; the Prometheus text dump is scrape-ready
    dump = prometheus_text(svc.metrics)
    print(f"\nmetrics registry ({len(dump.splitlines())} lines, head):")
    print("\n".join(dump.splitlines()[:18]))


if __name__ == "__main__":
    main()
