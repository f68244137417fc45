"""Where JAX's persistent compilation cache lives.

A chip run starts on a fresh machine and the flagship step takes tens of
seconds to compile, so compiled programs are kept on disk. The directory is
chosen from outside: ``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself,
wins and this module then sets no directory. Otherwise the cache goes to one
fixed path inside the checkout — the path is part of the cache key, so a
directory made from a temporary name, a process id or the time never hits.
"""

import os
from typing import Mapping, Optional

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir(environ: Mapping[str, str], checkout_root: str) -> Optional[str]:
    """The directory this program has to set, or None where it sets none
    because ``JAX_COMPILATION_CACHE_DIR`` already placed the cache."""
    # graftlint: disable=GL007(JAX's own variable, not an AUTODIST flag; read from the mapping the caller passes so the choice stays a pure function)
    if environ.get(CACHE_DIR_ENV):
        return None
    return os.path.join(checkout_root, ".jax_cache")


def configure() -> Optional[str]:
    """Turn the persistent cache on for accelerator backends; returns the
    directory in use. Does nothing on the CPU backend (returns None): the
    test suite neither writes a cache into the checkout nor changes its
    timing. Idempotent; call before the first compile that should be kept
    (it initializes the backend, so after ``jax.distributed`` where that is
    used)."""
    import jax
    if jax.default_backend() == "cpu":
        return None
    path = cache_dir(os.environ, CHECKOUT_ROOT)
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    # Default 1 s would drop the kernels' one-to-few-second compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
