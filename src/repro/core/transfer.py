"""Host↔device transfers of the host path driver, counted into a span.

The host path driver (``core/path.py``) and the helpers it calls
(:func:`~repro.core.kkt.kkt_violations`,
:func:`~repro.core.engine.null_gradient`,
:func:`~repro.core.engine.null_sigma_grid`) move data only through these
two functions.  Given the attribute dict of the open
:meth:`~repro.obs.trace.Trace.phase` span, they add to its
``h2d_bytes``, ``fetches`` and ``d2h_bytes``; given ``None`` they only
convert.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["put", "get"]


def put(a, span: dict | None = None) -> jax.Array:
    """``jnp.asarray(a)``.  Host data adds the bytes it lands as on the
    device to ``span["h2d_bytes"]``; an array already there adds 0."""
    out = jnp.asarray(a)
    if span is not None and not isinstance(a, jax.Array):
        span["h2d_bytes"] += out.nbytes
    return out


def get(a, span: dict | None = None) -> np.ndarray:
    """``np.asarray(a)`` of a device value: one blocking device→host read,
    counted in ``span["fetches"]`` with its bytes in ``span["d2h_bytes"]``."""
    out = np.asarray(a)
    if span is not None:
        span["fetches"] += 1
        span["d2h_bytes"] += out.nbytes
    return out
