"""Compile accounting and the persistent compile cache."""
from __future__ import annotations

import os
from pathlib import Path


class CompileLog:
    """Counts traces, XLA compiles, persistent-cache hits and compile
    seconds through jax.monitoring (every jit, the kernels' included)
    while the ``with`` block runs."""

    _STAGES = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self, monitoring):
        self._monitoring = monitoring
        self.traces = 0
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0

    def __enter__(self) -> "CompileLog":
        self._monitoring.register_event_duration_secs_listener(
            self._duration)
        self._monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        self._monitoring.unregister_event_duration_listener(self._duration)
        self._monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in self._STAGES:
            self.seconds += secs
        if event == self._STAGES[0]:
            self.traces += 1
        if event == self._STAGES[-1]:
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"traces": self.traces, "compiles": self.compiles,
                "cache_hits": self.cache_hits, "compile_s": self.seconds}


def configure_compile_cache(jax, default_dir: Path) -> str:
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; without it the cache
    sits at a fixed path in the checkout (the path is part of the key, so
    a moving directory would never hit).  The kernels compile in about a
    second, under JAX's default threshold for caching, hence 0."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(default_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
