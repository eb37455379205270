"""Process-wide count of the XLA programs this process compiled.

JAX reports each stage of building an executable through
``jax.monitoring``: tracing a function to a jaxpr, lowering it to an MLIR
module, and handing the module to XLA (the backend-compile event, whose
duration also covers a load from the persistent compilation cache).  A
load from that cache additionally records a cache-hit event.  The counter
listens to both, once per process, so it counts what the process really
compiled, unlike ``PlanCache`` misses, which count new launch shapes.

The counts are process-wide: a cluster reports them once per process
(``ClusterService.stats`` for its own process, each shard server for
its), never once per shard of a process.
"""
from __future__ import annotations

import threading

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
# tracing, lowering, and compiling or loading from the persistent cache:
# the cache retrieval time is inside the backend-compile event's duration
COMPILE_STAGES = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    BACKEND_COMPILE,
})


class CompileCounter:
    """Counts backend compiles and compile seconds from JAX's events."""

    def __init__(self):
        self._lock = threading.Lock()
        self._installed = False
        self._backend = 0  # executables built or loaded from the cache
        self._cache_hits = 0
        self._seconds = 0.0

    def install(self) -> None:
        """Register the listeners with ``jax.monitoring`` (once)."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        from jax import monitoring  # lazy: repro.obs itself needs no JAX

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event in COMPILE_STAGES:
            with self._lock:
                self._seconds += secs
                self._backend += event == BACKEND_COMPILE

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            with self._lock:
                self._cache_hits += 1

    def snapshot(self) -> dict:
        """``compiles``: executables XLA compiled (loads from the persistent
        cache left out); ``compile_s``: seconds spent tracing, lowering,
        compiling and loading from the cache."""
        with self._lock:
            return {
                "compiles": self._backend - self._cache_hits,
                "compile_s": self._seconds,
            }


#: the process's counter; ``PlanCache`` installs it
COMPILES = CompileCounter()
