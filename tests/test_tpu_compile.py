"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler ships with the installed ``libtpu`` and compiles for a
chip that is described rather than attached.  That catches what the
interpreter-mode kernel tests cannot: Mosaic's tiling rules, its
unsupported primitives, the on-core memory limits, and any f64 op hiding
in an f32 engine program.  Nothing runs, so these tests say nothing about
results or speed.

The topology is described inside a fixture — never while a module is
imported — so every test worker collects the same tests and only the one
that runs this file loads the TPU library.
"""

import functools
import importlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ols
from repro.core.engine import chunk_path_engine

gemv = importlib.import_module("repro.kernels.slope_gemv")
screen = importlib.import_module("repro.kernels.screen_scan")
prox = importlib.import_module("repro.kernels.prox_sorted_l1")

# real widths: the paper's p = 20 000 rounded up to whole 512-wide column
# blocks, n = 256 rows (the serving bucket of n = 200), 128 padded lanes
N, P, M = 256, 20480, 128
B_REP = 4      # replicate members
N_LIVE = 8     # live column blocks of a block-compacted call
# the served phase's larger bucket: 8 slots of (256, 32768), 8-step chunks
SLOTS, N_BUCKET, P_BUCKET, CHUNK = 8, 256, 32768, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _kernel_cases():
    ols_kw = dict(family="ols", m_actual=1, interpret=False)
    x, r, b, y, mask = (N, P), (N, M), (P, M), (N, M), (1, P)
    live = ((N_LIVE,), jnp.int32)
    rep_r, rep_b, rep_w = (B_REP, N, M), (B_REP, P, M), (B_REP, N, 1)
    return {
        "xt_matmul": (functools.partial(gemv.xt_matmul, interpret=False),
                      [x, r]),
        "xt_matmul_masked": (
            functools.partial(gemv.xt_matmul_masked, interpret=False),
            [x, r, mask]),
        "xt_matmul_compact": (
            functools.partial(gemv.xt_matmul_compact, interpret=False),
            [x, r, mask, live]),
        "xb_residual": (functools.partial(gemv.xb_residual, **ols_kw),
                        [x, b, y]),
        "xb_residual_masked": (
            functools.partial(gemv.xb_residual_masked, **ols_kw),
            [x, b, y, mask]),
        "xb_residual_compact": (
            functools.partial(gemv.xb_residual_compact, **ols_kw),
            [x, b, y, mask, live]),
        "xb_loss_residual": (
            functools.partial(gemv.xb_loss_residual, family="logistic",
                              m_actual=1, interpret=False),
            [x, b, y]),
        "xb_loss_residual_compact": (
            functools.partial(gemv.xb_loss_residual_compact, **ols_kw),
            [x, b, y, mask, live]),
        "xt_matmul_replicate": (
            functools.partial(gemv.xt_matmul_replicate, interpret=False),
            [x, rep_r, rep_w]),
        "xb_residual_replicate": (
            functools.partial(gemv.xb_residual_replicate, **ols_kw),
            [x, rep_b, rep_r, rep_w]),
        "xb_loss_residual_replicate": (
            functools.partial(gemv.xb_loss_residual_replicate, **ols_kw),
            [x, rep_b, rep_r, rep_w]),
        "screen_scan": (
            functools.partial(screen.screen_scan_kernel_call,
                              block=screen.DEFAULT_BLOCK, interpret=False),
            [(P,), (P,)]),
        "prox_pool": (
            functools.partial(prox.prox_pool_kernel_call, interpret=False),
            [(P,)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(*(s if isinstance(s[0], tuple)
                                   else (s, jnp.float32)),
                                 sharding=one_chip) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_f32_chunk_program_compiles_without_f64(one_chip, monkeypatch):
    """The served chunk program in f32 holds no f64 op, even with x64 on,
    and pools its prox in the Pallas kernel, as it does on a TPU."""
    # the engines pick their prox from the backend while they trace; here
    # the backend is the CPU, so steer them to the TPU's choice
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    f32, i32 = np.float32, np.int32
    B, Nb, Pb, C = SLOTS, N_BUCKET, P_BUCKET, CHUNK

    def sds(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = chunk_path_engine.lower(
        sds((B, Nb, Pb)), sds((B, Nb)), sds((B, Pb)),       # Xs, ys, λ
        sds((B, C)), sds((B, C)), sds((B, C), bool),         # σ pairs, live
        sds((B, Pb, 1)), sds((B, Pb, 1)), sds((B, Pb), bool),  # β, ∇, active
        sds((B,)), sds((B,), i32),                           # L, health
        ols, sds((B,), i32), screening="strong", max_iter=5000, tol=1e-8,
        kkt_tol=1e-4, max_refits=32)
    text = lowered.compile().as_text()
    assert "f64[" not in text
    assert "tpu_custom_call" in text
