"""What every Pallas launcher here shares: tracing with x64 off.

The TPU kernel compiler (Mosaic) takes no 64-bit types, and under
``jax_enable_x64`` the Python ints of index maps, loop bounds and scalar
carries would trace as i64 and be refused.  The kernels compute in f32
(bf16 inputs allowed), so tracing them in 32-bit mode changes nothing else.
Interpret-mode calls (any backend but a TPU) trace as they are: there the
kernels also take f64 operands.
"""

from __future__ import annotations

import functools

import jax

__all__ = ["x32"]


def x32(launcher):
    """Run a compiled (``interpret=False``) kernel launcher with
    ``jax_enable_x64`` off while it traces."""

    @functools.wraps(launcher)
    def wrapped(*args, interpret: bool = False, **kwargs):
        if interpret:
            return launcher(*args, interpret=True, **kwargs)
        with jax.enable_x64(False):
            return launcher(*args, interpret=False, **kwargs)

    return wrapped
