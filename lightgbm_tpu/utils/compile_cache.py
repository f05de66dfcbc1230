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
    """Call before the first jit of an entry point.

    An instruction's ``metadata`` is part of how a cached program is
    found: JAX leaves it out of the key by default, and a program that
    differs from a cached one only in its ``lgbm.<phase>`` scopes
    would come back with the old ones, which is what a traced run's
    ``Program::ops`` table is read from (``obs/tracer.py``; PR 38: the
    gradient programs came back from the parent's entries with no
    phase at all)."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if os.environ.get(_ENV):
        return
    jax.config.update("jax_compilation_cache_dir", cache_dir())
