"""Where XLA's compiled programs persist between processes.

Every tile kernel, every (class x bucket) stacked program and every
fused stage is an XLA compile; on a chip a cold process pays for all of
them again.  JAX keeps a persistent cache when it is told a directory.
The directory is part of a deployment, so it is placeable from outside
through JAX's own variable, and fixed otherwise (a temp name, a pid or
a timestamp in the path would make every run a cold one):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing here
  names a directory.
- unset: ``<checkout>/.jax_cache`` (git-ignored), the same from any cwd.

On the chip the size and compile-time floors are dropped as well:
most tile kernels compile in well under JAX's default 1 s persistence
threshold and would never be written.

A process held to the host (``JAX_PLATFORMS=cpu``: the tests, a
rehearsal) is left with JAX's own defaults.  Nobody waits on XLA:CPU
compiles of test-sized tiles, and this jaxlib's CPU loader logs a
spurious "machine type doesn't match" error for every entry it reads
back on the very machine that wrote it.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the one in-checkout location (``parsec_tpu/utils/`` is two levels
#: below the checkout root)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> None:
    """Point JAX's persistent compilation cache at the deployment's
    directory before the first compile.  Called at package import
    (``ptg.wave`` / ``ptg.capture`` compile without ever building a
    Context); ``jax.config.jax_compilation_cache_dir`` says what is in
    force."""
    import jax
    if jax.config.jax_platforms != "cpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        if not os.environ.get(ENV_VAR):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
