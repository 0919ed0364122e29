"""Restart recovery against a durable program store (ISSUE 10).

    PYTHONPATH=src python examples/crash_restart.py [store_dir]

Simulates the crash-restart lifecycle the CI chaos job exercises:

1. **Boot A** with ``store=DurableProgramStore(dir)``, serve live traffic —
   every compiled program is serialized into the store and the warmup
   manifest records which specs traffic actually used.
2. **Checkpoint** boot A mid-flight (some requests still queued or
   mid-chunk) and abandon the process — the "kill".
3. **Boot B** against the same store: manifest replay deserializes every
   program (ZERO XLA compiles), the checkpoint is restored, and every
   interrupted request completes **bit-identical** (maxdiff == 0) to an
   uninterrupted reference run.

Exits non-zero if boot B compiled anything, lost a request, or produced a
single differing bit.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import use_checkout_cache

use_checkout_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import sys
import tempfile
import time
from concurrent.futures import FIRST_COMPLETED, wait

import numpy as np

from repro.serve import AsyncPathService, DurableProgramStore
from repro.data import make_regression

L = 12
KW = dict(path_length=L, solver_tol=1e-10, max_iter=20000)
SVC_KW = dict(max_batch=4, max_delay=0.005, step_chunk=3)


def _requests(count=6):
    # one (64, 64) bucket: the whole stream shares a single (init, chunk)
    # program pair, so boot A's manifest covers everything boot B serves
    reqs = []
    for i in range(count):
        X, y, _ = make_regression(33 + 2 * i, 40 + i, k=4, rho=0.2,
                                  seed=900 + i, noise=0.3)
        reqs.append((X, y))
    return reqs


def main():
    store_dir = sys.argv[1] if len(sys.argv) > 1 else tempfile.mkdtemp(
        prefix="repro-crash-restart-")
    reqs = _requests()

    # -- reference: one uninterrupted run (no store, fresh compiles) --------
    ref_svc = AsyncPathService(**SVC_KW)
    reference = [f.result(timeout=600) for f in
                 [ref_svc.submit(X, y, **KW) for X, y in reqs]]
    ref_svc.close()

    # -- boot A: populate the store, checkpoint mid-flight, "crash" ---------
    t0 = time.perf_counter()
    svc_a = AsyncPathService(store=DurableProgramStore(store_dir), **SVC_KW)
    futs_a = [svc_a.submit(X, y, **KW) for X, y in reqs]
    # "crash" mid-stream, not before serving started: wait for the first
    # delivery so the store provably holds what the stream compiles
    wait(futs_a, timeout=600, return_when=FIRST_COMPLETED)
    ckpt = svc_a.checkpoint(timeout=600)
    t_a = time.perf_counter() - t0
    stats_a = svc_a.stats()["cache"]
    done_a = {i: f.result() for i, f in enumerate(futs_a) if f.done()}
    rid_to_index = {f.rid: i for i, f in enumerate(futs_a)}
    print(f"boot A: {t_a:.2f}s  builds={stats_a['builds']}  "
          f"delivered={len(done_a)}/{len(reqs)}  "
          f"checkpointed={len(ckpt)} "
          f"(queued={len(ckpt.queued)} inflight={len(ckpt.inflight)})")
    # abandoned: no close-flush — the un-served futures die with the process

    # -- boot B: same store, fresh everything; replay + restore -------------
    t0 = time.perf_counter()
    svc_b = AsyncPathService(store=DurableProgramStore(store_dir), **SVC_KW)
    boot_b = svc_b.stats()["cache"]
    restored = svc_b.restore(ckpt)
    results = dict(done_a)
    for old_rid, fut in restored.items():
        results[rid_to_index[old_rid]] = fut.result(timeout=600)
    t_b = time.perf_counter() - t0
    stats_b = svc_b.stats()["cache"]
    svc_b.close()
    print(f"boot B: {t_b:.2f}s  boot_builds={boot_b['builds']}  "
          f"loaded={stats_b['store']['loaded']}  "
          f"restored={len(restored)}  served_builds={stats_b['builds']}")

    # -- acceptance ---------------------------------------------------------
    failures = []
    if stats_b["builds"] != 0:
        failures.append(
            f"boot B compiled {stats_b['builds']} programs (want 0)")
    if len(results) != len(reqs):
        failures.append(f"lost requests: {len(results)}/{len(reqs)}")
    maxdiff = 0.0
    for i, want in enumerate(reference):
        got = results[i]
        if got.betas.shape != want.betas.shape:
            failures.append(f"request {i}: shape {got.betas.shape} "
                            f"!= {want.betas.shape}")
            continue
        maxdiff = max(maxdiff,
                      float(np.max(np.abs(got.betas - want.betas))),
                      float(np.max(np.abs(got.deviance - want.deviance))))
    print(f"availability={len(results)}/{len(reqs)}  "
          f"restart_maxdiff={maxdiff:.1f}  "
          f"speedup_vs_bootA={t_a / t_b:.2f}x")
    if maxdiff != 0.0:
        failures.append(f"restored results differ: maxdiff={maxdiff}")
    if failures:
        print("FAIL: " + "; ".join(failures))
        return 1
    print("OK: zero rebuilds, full availability, bit-identical restore")
    return 0


if __name__ == "__main__":
    sys.exit(main())
