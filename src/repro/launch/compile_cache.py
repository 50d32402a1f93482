"""JAX's persistent compilation cache, placed one way for every entry point.

:func:`enable_compile_cache` is called by each entry point (the partition
and serve CLIs, the serve bench, ``chip_smoke.py``) before its first
compile.  The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, if it
is set, and otherwise at ``<checkout>/.jax-compile-cache``: a fixed path,
because the path is part of the cache key and a moving directory never
hits.  Thresholds are zeroed so every executable persists — the
partitioner's per-rung programs are small but numerous, exactly the
population the default min-compile-time filter would skip.

:class:`CompileCacheStats` counts JAX's monitoring events: backend
compiles (persistent-cache hits included) with their seconds, tracing
and lowering seconds, and the cache's hits and misses, for the process
and for each thread.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax-compile-cache"

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
# JAX's duration events, summed under these keys
_DURATION_KEYS = {
    _TRACE_EVENT: "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}


class CompileCacheStats:
    """Counter sink for JAX's compile and compilation-cache events.

    ``compiles`` counts every backend compile request and ``compile_s``
    sums their seconds; a persistent-cache hit is one of them, served from
    disk instead of XLA.  ``trace_s`` sums the seconds spent tracing
    functions to jaxprs and ``lower_s`` those lowering jaxprs to MLIR, the
    two steps before the backend that the persistent cache cannot skip.
    An inner ``jit`` traced while an outer one is being traced reports a
    trace of its own inside the outer one; only the outermost counts, so
    ``trace_s`` is wall time (JAX marks each trace's start with a scalar
    event, :meth:`on_scalar`).  ``cache_hits`` / ``cache_misses`` are emitted
    only while the persistent cache is enabled.  JAX reports each event
    in the thread that compiled, so every thread also keeps its own
    tally (:meth:`snapshot` with ``this_thread=True``).
    """

    def __init__(self):
        self.counts: dict[str, float] = {}
        self._per_thread: dict[int, dict[str, float]] = {}
        self._trace_depth: dict[int, int] = {}
        self._lock = threading.Lock()

    def _add(self, key: str, value: float) -> None:
        with self._lock:
            mine = self._per_thread.setdefault(threading.get_ident(), {})
            for counts in (self.counts, mine):
                counts[key] = counts.get(key, 0) + value

    def __call__(self, name: str, **kw) -> None:
        if name.startswith("/jax/compilation_cache/"):
            self._add(name.rsplit("/", 1)[-1], 1)

    def on_scalar(self, name: str, value: float, **kw) -> None:
        if name == _TRACE_EVENT:  # a trace starts
            me = threading.get_ident()
            self._trace_depth[me] = self._trace_depth.get(me, 0) + 1

    def on_duration(self, name: str, secs: float, **kw) -> None:
        key = _DURATION_KEYS.get(name)
        if key is None:
            return
        if name == _TRACE_EVENT:
            me = threading.get_ident()
            depth = max(self._trace_depth.get(me, 0) - 1, 0)
            self._trace_depth[me] = depth
            if depth:  # nested in a trace that is still running
                return
        if key == "compile_s":
            self._add("compiles", 1)
        self._add(key, secs)

    def snapshot(self, this_thread: bool = False) -> dict[str, float]:
        with self._lock:
            if this_thread:
                return dict(self._per_thread.get(threading.get_ident(), {}))
            return dict(self.counts)

    @staticmethod
    def delta(before: dict, after: dict) -> dict[str, float]:
        return {k: after.get(k, 0) - before.get(k, 0)
                for k in set(before) | set(after)}


_CACHE_STATS: CompileCacheStats | None = None


def cache_stats() -> CompileCacheStats:
    """The process-wide event listener (registered once, lazily)."""
    global _CACHE_STATS
    if _CACHE_STATS is None:
        _CACHE_STATS = CompileCacheStats()
        jax.monitoring.register_event_listener(_CACHE_STATS)
        jax.monitoring.register_event_duration_secs_listener(
            _CACHE_STATS.on_duration)
        jax.monitoring.register_scalar_listener(_CACHE_STATS.on_scalar)
    return _CACHE_STATS


def enable_compile_cache() -> CompileCacheStats:
    """Turn on the persistent cache at its one place; returns the counter.

    Call before the first compile, so the counter sees every event.
    """
    from jax.experimental.compilation_cache import compilation_cache as cc

    stats = cache_stats()
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # any jit that ran before this call (repro modules compile helpers at
    # import) memoizes the cache object as "disabled"; reset so the
    # directory takes effect
    cc.reset_cache()
    return stats
