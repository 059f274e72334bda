"""Where compiled programs are kept between processes.

A full-width round takes minutes to compile for a TPU.  JAX's persistent
compilation cache keeps the result on disk, so a second process running
the same program loads it instead.  One rule, applied by every entry point
(``launch/train.py``, ``chip_smoke.py``) before it compiles anything:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing here
    changes it.
  * otherwise, on an accelerator: ``<checkout>/.jax_cache``.  The path is
    fixed (never a temp name, a pid or a time), so the next process in the
    same checkout finds what this one wrote.
  * otherwise, on the CPU: no cache.  XLA:CPU compiles take seconds, and
    the test suite's parallel workers would all write one directory.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Apply the rule above; returns the cache directory in use, or None."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
