"""One rule for where JAX's persistent compilation cache lives.

``chip_smoke.py``, ``benchmarks/chip/run.py`` and ``tests/conftest.py``
all call :func:`enable`; nothing else in the repo names a cache directory.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, so nothing is
  touched and no directory is set in code (a code-side directory would
  override the one the caller chose).
* unset: ``<checkout>/.jax_cache`` (git-ignored). The path is part of the
  cache key, so it is fixed: never a temp name, a pid or a time.
"""

from __future__ import annotations

import os
from typing import Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable() -> Optional[str]:
    """Apply the rule. Returns the directory set in code, or None when
    the environment already chose one."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
