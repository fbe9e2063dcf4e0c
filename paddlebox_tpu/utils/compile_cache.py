"""XLA persistent compilation cache placement.

Reference analogue: the CUDA path compiles nothing at runtime — kernels
ship precompiled in the binary, so a cold worker's first pass boundary
costs milliseconds. Under XLA every program compiles at first trace,
paid by every cold process and every elastic replacement rank. jax's
on-disk compilation cache serializes each compile once per machine;
later processes deserialize instead.

Two sources for the directory, no third:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this
  module sets no directory.
- unset: one fixed directory inside the checkout (``<repo>/.jax_cache``,
  git-ignored). The path is part of the cache key, so it never moves
  with cwd, pid or time. On a CPU backend the in-checkout cache stays
  OFF: XLA:CPU executables are specific to the host's machine features
  (``cpu_aot_loader`` rejects foreign ones), and a CPU test run must not
  fill the tree that is copied to the chip machine.

``enable_compilation_cache()`` is the one switch, called from the one
point every entry shares (trainers, ServingModel, benchmarks/run.py,
chip_smoke.py all build a table before their first compile):
``ps/table.init_table_state``.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

from paddlebox_tpu.utils.logging import get_logger

log = get_logger(__name__)

#: the fixed in-checkout cache directory (listed in .gitignore)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

@functools.cache
def enable_compilation_cache() -> Optional[str]:
    """Turn the persistent compilation cache on for this process (once;
    later calls return the first answer). Returns the directory in use,
    or None when the cache stays off (CPU backend with no
    ``JAX_COMPILATION_CACHE_DIR``)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if jax.devices()[0].platform == "cpu":
            log.info("persistent XLA compilation cache off (cpu backend)")
            return None
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only the >= 1 s ones: a trainer's set-up
    # runs dozens of small eager programs, and a process that finds them
    # all cached starts in seconds
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log.info("persistent XLA compilation cache at %s", path)
    return path
