"""Process-level JAX set-up shared by the entry points (manager,
runner, __graft_entry__): where the persistent compile
cache lives, and one line saying what the process runs on.

Platform selection is JAX's own: `JAX_PLATFORMS` is honoured by JAX
itself, so nothing here (or anywhere else in the tree) forces one.
"""

from __future__ import annotations

import os
from typing import Optional

from .native import native_status

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: Where compiled programs persist when JAX_COMPILATION_CACHE_DIR is
#: unset. Fixed and inside the checkout: a tempfile/pid/time path
#: would never be found again by the next process.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def compile_cache_dir() -> str:
    """The persistent compile cache's directory: wherever the operator
    placed it (JAX_COMPILATION_CACHE_DIR), else the fixed in-checkout
    default — the same answer in every process of a deployment."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
            or DEFAULT_COMPILE_CACHE_DIR)


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache; call once, before
    the first compilation. Returns the directory, or None where the
    cache stays off.

    With JAX_COMPILATION_CACHE_DIR set, JAX reads the variable itself
    and no directory is set in code. Otherwise the cache goes to
    `DEFAULT_COMPILE_CACHE_DIR` on accelerator backends and stays off
    on the CPU backend (tests, references): XLA:CPU's loader logs two
    multi-kilobyte error lines per executable it reloads, which would
    bury every log tail, to save compiles that take milliseconds.
    The minimum-compile-time floor is dropped to zero: the served path
    compiles many bucketed steps that each take well under JAX's
    default 1 s floor, and those are exactly the ones every manager
    and runner would otherwise recompile from cold."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not placed:
        if jax.default_backend() == "cpu":
            return None
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return compile_cache_dir()


def runtime_banner() -> str:
    """`platform=… device_kind=… devices=N native=… compile_cache=…`
    — printed once at start by the manager and the runner so a log
    alone shows what a process ran on. Initializes the JAX backend: a
    process told to use a platform it cannot get fails here, at
    start, not at its first request."""
    import jax

    devs = jax.devices()
    cache = jax.config.jax_compilation_cache_dir or "off"
    return (f"platform={devs[0].platform} "
            f"device_kind={devs[0].device_kind!r} "
            f"devices={len(devs)} native={native_status()} "
            f"compile_cache={cache}")
