"""Where JAX's persistent compilation cache lives.

Every process entry point (``cli.main``, ``bench.py``, ``benchmarks/*.py``,
``chip_smoke.py``) calls ``place_compile_cache()`` before it first imports
JAX. A machine with a chip starts cold on every call and a 1B-parameter
step takes minutes to compile, so processes that share a compilation must
share one cache — and the directory is part of JAX's cache key, so it must
never move: no ``tempfile``, process id or clock in the path.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# Gitignored; one fixed path so a second process (or a second run in the
# same chip-tool command) hits what the first one compiled.
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def place_compile_cache() -> Optional[str]:
    """Default ``JAX_COMPILATION_CACHE_DIR`` to the in-checkout directory.

    Where the variable is already set this does nothing: JAX reads it, and
    no other directory is set in code. Where it is not, the environment
    default is set (so child processes inherit the same path) — which only
    takes effect before JAX is imported, so a process that already imported
    JAX (the test suite, which runs without a cache) is left alone. Returns
    the directory this call set, or None when it set nothing."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or "jax" in sys.modules:
        return None
    os.environ["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_DIR
    return DEFAULT_DIR
