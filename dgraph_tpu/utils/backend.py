"""JAX start-up: which platform a process runs on, and where its
persistent compile cache lives.

One rule for every entry point (cli.main, the root scripts, the
tests): the platform is whatever JAX_PLATFORMS says, else the
accelerator JAX finds, and finding none is an error — never a quiet
CPU run. A CPU run (tests, the CI gates) is asked for with
JAX_PLATFORMS=cpu in the environment before the interpreter starts.

A chip belongs to one process at a time, and jax.devices() is what
takes it: host-only processes (bulk, zero, backup, a bench parent that
spawns servers) import this module freely but never call
require_devices().
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


class NoAcceleratorError(RuntimeError):
    """JAX came up CPU-only although nobody asked for the CPU."""


def configure_compile_cache() -> str:
    """Place jax's persistent compile cache; returns the directory.

    JAX_COMPILATION_CACHE_DIR wins: jax reads it by itself and nothing
    is set here. Otherwise the cache is `<checkout>/.jax_cache` — a
    FIXED path (the path is part of the cache key's surroundings: a
    directory derived from a temp name, pid or time never hits). A
    served stage compiles in well under jax's default 1 s floor, so
    the floors are dropped or a restarted server would recompile
    every one of them."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return env_dir or DEFAULT_CACHE_DIR


def cpu_requested() -> bool:
    """Whether this process was explicitly pinned to the CPU backend
    (JAX_PLATFORMS=cpu, which jax mirrors into its config)."""
    import jax

    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def require_devices() -> list:
    """Initialize the backend (this TAKES the chip) and return its
    devices. Backend errors propagate; a CPU-only backend that was not
    asked for raises NoAcceleratorError."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" and not cpu_requested():
        raise NoAcceleratorError(
            "jax found no accelerator and fell back to the CPU "
            "backend; set JAX_PLATFORMS=cpu to run on the CPU on "
            "purpose")
    return devs


def device_report(devs=None) -> dict:
    """{"platform", "kind", "count"} as jax reports the devices this
    process runs on — the stamp every server log, /health reply and
    benchmark line carries."""
    devs = require_devices() if devs is None else devs
    return {"platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs)}
