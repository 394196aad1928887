"""Where JAX's persistent compilation cache lives.

Entry points (the ``python -m repro`` CLI, ``chip_smoke.py`` and the
``benchmarks`` scripts) call :func:`enable` once at start-up; importing
this module, or the library, changes nothing.

  - If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing here overrides it.
  - Otherwise the cache goes to ``<checkout>/.jax_cache``: one fixed
    path (listed in ``.gitignore``), so a later run from the same
    checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib
from typing import Mapping, Optional

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout this package was loaded from (``src/repro/..``)
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def default_dir(environ: Optional[Mapping[str, str]] = None) -> Optional[str]:
    """The directory :func:`enable` would set, or None when ``environ``
    (default ``os.environ``) already names one for JAX."""
    environ = os.environ if environ is None else environ
    if environ.get(ENV):
        return None
    return str(CHECKOUT / ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = default_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return jax.config.jax_compilation_cache_dir
