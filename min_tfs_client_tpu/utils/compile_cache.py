"""Where XLA's persistent compilation cache lives.

Every process that compiles for the device calls `configure()` before its
first jit: the server CLI, the `tpu://` boot, `chip_smoke.py`'s
children and the `tests/tpu` driver. A cache that moves
between runs never hits, so the directory is decided in one place:

 * `JAX_COMPILATION_CACHE_DIR` set — JAX reads the variable itself; code
   sets no directory, so whoever runs the program decides where the cache
   lives (a machine that keeps one directory between runs points it there);
 * unset — one fixed path under the checkout, never a tempdir, a pid or a
   timestamp.
"""

from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """The directory in use, by the rule above (no jax needed to ask)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)


def configure() -> str:
    """Point JAX at the persistent cache; returns the directory in use."""
    import jax  # function scope: utils/ stays importable without jax

    # JAX skips programs that compiled in under a second by default; a
    # program near that line would be written by whichever boot happened
    # to compile it slowly. Cache everything so a second boot is all hits.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return cache_dir()
