"""Where the persistent XLA/Mosaic compilation cache lives, and what
compiling cost this process.

One policy for every entry point that compiles device code (the
coordinator runner, ``tools/trace_overhead.py``): the cache directory is
placed from OUTSIDE. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and this module leaves the directory alone; otherwise the cache goes to
``<checkout>/.jax_cache`` (git-ignored) — a fixed path, so every process
started from a checkout finds what an earlier one built. The fold kernels
compile in seconds to tens of seconds and the in-graph ChaCha derive in
minutes, so a restarted coordinator must find them.
"""

from __future__ import annotations

import os
import threading

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_stats = {"seconds": 0.0, "compiles": 0, "cache_hits": 0, "cache_writes": 0}
_entries_at_start: int | None = None


def cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV_DIR) or os.path.join(_CHECKOUT, ".jax_cache")


def _count_entries(path: str) -> int:
    try:
        return sum(1 for name in os.listdir(path) if not name.startswith("."))
    except FileNotFoundError:
        return 0


def _on_duration(event: str, duration: float, **_kwargs) -> None:
    if event == _BACKEND_COMPILE:
        with _lock:
            _stats["seconds"] += duration
            _stats["compiles"] += 1


def _on_event(event: str, **_kwargs) -> None:
    if event == _CACHE_HIT or event == _CACHE_WRITE:
        with _lock:
            _stats["cache_hits" if event == _CACHE_HIT else "cache_writes"] += 1


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on at :func:`cache_dir` and
    start counting compiles. Call once, right after importing jax and
    before the first compile. Returns the directory.

    Every executable is cached, however quick its compile: with JAX's
    default 1 s floor a compile that takes 0.9 s in one run and 1.1 s in
    the next adds an entry on the WARM run, and "a second run adds no
    entries" stops being checkable.
    """
    global _entries_at_start
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_DIR):
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with _lock:
        first = _entries_at_start is None
        if first:
            _entries_at_start = _count_entries(path)
    if first:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
    return path


def compile_report() -> dict:
    """Set-up cost so far: backend compile seconds (cache retrieval
    included, so a warm start reads lower), compile and cache-hit counts,
    and the cache directory with its entry count at start and now."""
    path = cache_dir()
    with _lock:
        report = dict(_stats)
        start = _entries_at_start
    report["seconds"] = round(report["seconds"], 3)
    report["cache_dir"] = path
    report["cache_entries_start"] = start
    report["cache_entries_now"] = _count_entries(path)
    return report
