"""The program's one persistent compilation cache setting.

Entry points that compile at full size (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable` once, after importing JAX and
before the first compile.  Nothing else in the repository sets a cache
directory.
"""

from __future__ import annotations

import os
import pathlib

#: fixed in-checkout cache path, used when JAX_COMPILATION_CACHE_DIR is
#: unset (the path is part of what makes an entry findable again, so it
#: is never derived from a temporary directory, a pid or the time)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads
    the variable itself and this sets nothing.  Otherwise the cache
    lives at :data:`DEFAULT_DIR` inside the checkout."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
