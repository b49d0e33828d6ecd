"""Host seconds JAX spends tracing, lowering, compiling and fetching
programs from its persistent cache, from its monitoring events. Copied
from the bring-up check (``chip_smoke.py``, ``CompileClock``), with a count
of lowered programs and of persistent-cache hits and misses added: a
program that the cache serves is still traced and lowered to MLIR in each
process, so the lowering count, and not the backend count, says how many
jitted programs were first met (trace events count inner jaxprs too,
Pallas kernels among them)."""
from __future__ import annotations

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


class CompileClock:
    def __init__(self):
        self.seconds = 0.0
        self.backend_seconds = 0.0
        self.lowerings = 0
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def __call__(self, event: str, duration: float, **_):
        if event in (TRACE, LOWER, BACKEND, FETCH):
            self.seconds += duration
        if event == LOWER:
            self.lowerings += 1
        if event == BACKEND:
            self.backend_seconds += duration
            self.backend_compiles += 1

    def count(self, event: str, **_):
        self.cache_hits += event == HIT
        self.cache_misses += event == MISS

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        jax.monitoring.register_event_listener(self.count)
        return self
