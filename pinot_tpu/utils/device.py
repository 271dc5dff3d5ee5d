"""What this process runs on, and where its compiled kernels persist.

Two facts every chip-owning process must be able to state without a
reader guessing: which backend JAX selected (a server that silently
came up on the CPU serves correct answers at the wrong speed), and
which persistent compilation cache it reads (a cache whose path moves
between runs never hits — the directory is part of the cache key's
lookup).
"""
from __future__ import annotations

import os

#: the in-checkout default; fixed so every process of every run of this
#: checkout shares one cache (listed in .gitignore)
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_location() -> str:
    """Where this checkout's processes keep their compiled kernels: the
    deployment's ``JAX_COMPILATION_CACHE_DIR`` if set, else the fixed
    in-checkout path — never a temporary name, a pid or a time. Touches
    nothing (a parent that must stay off JAX may call it)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_CACHE_DIR


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache at
    `compile_cache_location()`; returns the directory. Call before the
    first kernel is built. With the variable set, JAX reads it itself
    and no directory is set in code."""
    where = compile_cache_location()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        if jax.config.jax_compilation_cache_dir != where:
            jax.config.update("jax_compilation_cache_dir", where)
    return where


def device_report() -> dict:
    """Platform, kind and count as JAX reports them, the x64 mode, the
    compile cache in use, and the backend's own bytes-in-use and their
    peak since the process started where it keeps those statistics
    (None where it does not: the CPU backend). Initialises the
    backend — only a process that owns the device may call this."""
    import jax
    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    return {
        "platform": devices[0].platform,
        "deviceKind": devices[0].device_kind,
        "count": len(devices),
        "x64": bool(jax.config.jax_enable_x64),
        "bytesInUse": stats.get("bytes_in_use"),
        "peakBytesInUse": stats.get("peak_bytes_in_use"),
        "compileCacheDir": jax.config.jax_compilation_cache_dir,
    }
