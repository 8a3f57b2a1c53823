"""Cross-cutting utilities: the persistent compile cache and its counters."""

from photon_tpu.utils.compile_cache import (
    cache_stats,
    compile_event_count,
    enable_compilation_cache,
)

__all__ = [
    "cache_stats",
    "compile_event_count",
    "enable_compilation_cache",
]
