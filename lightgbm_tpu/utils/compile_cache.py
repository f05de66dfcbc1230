"""Where JAX's persistent compile cache lives — decided from outside.

``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself, and nothing in
this repo names another directory.  Unset: ``<checkout>/.jax_cache`` —
fixed and derived from this file (git-ignored).  The directory is part
of how a cached program is found again, so no temp name, pid or
timestamp may enter it.  Stdlib-only at import: ``utils/cpu_mesh.py``
runs this file by path before the package (and jax) may be imported.
"""
import os

_ENV = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> str:
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.environ.get(_ENV) or os.path.join(checkout, ".jax_cache")


def enable_compile_cache() -> None:
    """Call before the first jit of an entry point."""
    if os.environ.get(_ENV):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir())
