"""Retrace/recompile telemetry + the persistent executable cache.

The streaming tier's latency tail is almost entirely trace + compile time:
a micro-batch whose delta shape has not been seen yet re-traces the whole
refresh path and waits on XLA.  This module makes that visible and
survivable:

  * **Trace counters** — every jitted kernel on the refresh path calls
    :func:`count_trace` at the top of its Python body.  A jit body only
    executes when JAX is *tracing* (a jit-cache miss), so the counter is
    an exact retrace count with zero steady-state overhead, read per
    kernel by :func:`trace_counts`.  The monotonically increasing
    :func:`generation` lets a caller bracket a region ("did this refresh
    trace anything?") — the stream scheduler uses it to exclude
    compile-polluted cost observations.
  * **Compile counters** — a ``jax.monitoring`` listener counts actual
    XLA backend compiles and their seconds (a persistent-cache hit traces
    but does not compile, so the two counters differ exactly by the
    cache's hits); :func:`snapshot` reads the totals of both counters,
    :func:`compile_seconds_total` the compile seconds.
  * **Persistent compilation cache** — :func:`enable_persistent_cache`
    points JAX's disk cache at ``JAX_COMPILATION_CACHE_DIR`` when that is
    set, else at the caller's directory (``RunConfig(
    compilation_cache_dir=...)``), with the entry-size/compile-time
    floors dropped so the many small refresh executables qualify.
    Executables then survive process restarts: a restarted serving node
    re-traces (milliseconds) but does not re-compile (hundreds of
    milliseconds per shape bucket).
"""
from __future__ import annotations

import collections
import os
import threading
from typing import Dict, Optional

import jax
from jax.experimental.compilation_cache import compilation_cache

_lock = threading.Lock()
_traces: collections.Counter = collections.Counter()
_generation = 0
_compiles = 0
_compile_seconds = 0.0
_listener_installed = False
_cache_dir: Optional[str] = None

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def count_trace(name: str) -> None:
    """Record one retrace.  Call from *inside* a jitted function body —
    the body only runs on a jit-cache miss, i.e. exactly once per trace."""
    global _generation
    with _lock:
        _traces[name] += 1
        _generation += 1


def generation() -> int:
    """Monotonic counter bumped on every trace (bracket refreshes with it)."""
    return _generation


def trace_counts() -> Dict[str, int]:
    """Per-kernel retrace counts since process start."""
    with _lock:
        return dict(_traces)


def compile_seconds_total() -> float:
    return _compile_seconds


def snapshot() -> Dict[str, float]:
    """One consistent view of all counters (for benchmarks/metrics)."""
    with _lock:
        return {"traces": sum(_traces.values()),
                "compiles": _compiles,
                "compile_seconds": _compile_seconds}


def _on_event_duration(event: str, duration: float, **_kw) -> None:
    global _compiles, _compile_seconds
    if event == _COMPILE_EVENT:
        with _lock:
            _compiles += 1
            _compile_seconds += duration


def install_compile_listener() -> None:
    """Idempotently subscribe the compile counter to jax.monitoring."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True
    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)


CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_persistent_cache(path=None) -> Optional[str]:
    """Turn on JAX's persistent compilation cache (idempotent).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there
    and that directory wins: ``path`` is ignored and no other directory is
    set.  Otherwise the cache goes to ``path`` (nothing happens when it is
    None).  Either way the default entry-size and compile-time floors are
    dropped, so that the refresh path's many small executables are cached
    too, and the underlying XLA caches are enabled.  Returns the directory
    in use.
    """
    global _cache_dir
    env = os.environ.get(CACHE_ENV)
    target = env or (None if path is None else str(path))
    if target is None or _cache_dir == target:
        return _cache_dir
    if not env:
        jax.config.update("jax_compilation_cache_dir", target)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    # JAX latches the cache-enabled decision at the first compile; if
    # anything compiled before this call (module import commonly does),
    # the latch must be cleared for the new settings to take effect
    compilation_cache.reset_cache()
    _cache_dir = target
    return _cache_dir


def persistent_cache_dir() -> Optional[str]:
    return _cache_dir


install_compile_listener()
