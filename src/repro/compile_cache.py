"""Where JAX keeps its persistent compilation cache — decided in one place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
:func:`use_checkout_cache` once, before their first compile.  Library code
never places a cache.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here
  touches the setting.
* Unset: the cache goes to ``.jax_cache/`` at the root of the checkout
  (listed in ``.gitignore``).  A fixed path matters because the path is
  part of what a cache hit depends on.
* Unset on the CPU backend: no cache.  XLA:CPU cannot re-serialize an
  executable it took from that cache — the durable program store would
  save an entry that fails when it runs — and CPU compiles are what the
  tests pay anyway.
"""

from __future__ import annotations

import os

__all__ = ["use_checkout_cache"]


def use_checkout_cache(checkout_root: str | os.PathLike) -> str | None:
    """Point JAX's persistent compilation cache at ``<root>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` already names one (or the backend
    is the CPU); returns the directory in use, None for none."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    path = os.path.join(os.path.abspath(checkout_root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
