"""Virtual n-device CPU mesh provisioning (shared by tests + dryrun).

Mirrors the reference's distributed-test strategy
(tests/distributed/_test_distributed.py:54-100 — N self-provisioned localhost
ranks on one machine): ``--xla_force_host_platform_device_count=N`` gives N
XLA CPU devices so shard_map learners exercise real collectives without TPUs.
"""
from __future__ import annotations

import os


def _compile_cache_dir() -> str:
    # compile_cache.py is stdlib-only and run by path: this module is
    # itself loaded by path (tests/conftest.py) before the package, and
    # with it jax, may be imported
    import runpy
    return runpy.run_path(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "compile_cache.py"))["cache_dir"]()


def cpu_mesh_env(n_devices: int, env: dict | None = None) -> dict:
    """Return an environment dict forcing an ``n_devices`` CPU mesh."""
    env = dict(os.environ if env is None else env)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    # persistent compilation cache: the jitted grow loop costs ~25s to
    # compile per (num_leaves, bins, rows) shape on CPU
    env.setdefault("JAX_COMPILATION_CACHE_DIR", _compile_cache_dir())
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")
    return env


def force_cpu_devices(n_devices: int) -> None:
    """Force THIS interpreter onto an ``n_devices`` CPU mesh.

    Must run before the first jax backend query (jax.devices()/jit); an
    earlier plain ``import jax`` is tolerated — the live config is
    updated as well as the environment.
    """
    os.environ.update(cpu_mesh_env(n_devices))
    import jax
    jax.config.update("jax_platforms", "cpu")
