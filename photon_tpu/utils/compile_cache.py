"""Persistent XLA compilation cache wiring + hit/miss instrumentation.

The reference pays no compilation cost (Spark ships interpreted closures);
the TPU build's analog of that "instant start" is XLA's persistent
compilation cache: compiled executables keyed by HLO hash land in a local
directory, so repeated runs of the same shapes (the CLI on a daily cadence,
the bench, tuner re-entries in fresh processes) skip the compile entirely.

``cache_stats()`` exposes what the cache actually did this process —
hit/miss counts from JAX's monitoring events plus the on-disk entry
count/bytes — so ``bench.py`` can report the hit-rate next to
``warm_cache_e2e_seconds`` (the BENCH_r05 anomaly where the warm rerun was
SLOWER than cold is unexplainable without knowing whether the cache ever
hit).
"""

from __future__ import annotations

import os
import threading

from photon_tpu import CHECKOUT_ROOT

# The cache path is part of JAX's cache key, so the default must be the
# same string on every run from one checkout.
_DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")

# Host-concurrency contract (audited by `python -m photon_tpu.analysis
# --concurrency`). The counters here are written from whatever thread
# happens to compile: `_on_event` fires from JAX's monitoring hooks
# during any compile (including the ingest pipeline's background
# AOT-compile thread), and `aot_compile` itself runs ON that thread —
# concurrent with the training thread's jit fallbacks. Before this
# contract the dict updates were bare `+=` on a module global (torn
# read-modify-write under free threading, lost updates under the GIL's
# ~5ms switch interval); every write now takes the module lock. The
# XLA compile in `aot_compile` runs OUTSIDE the lock (minutes-long on
# real programs — the `blocking-under-lock` rule's worst case).
CONCURRENCY_AUDIT = dict(
    name="compile-cache",
    locks={
        "_lock": ("_stats", "_listener_installed", "_dir_in_effect"),
    },
    thread_entries=(
        "_on_event", "_on_trace_start", "_on_duration", "aot_compile"),
    jax_dispatch_ok={
        "aot_compile": "the whole point of the entry: XLA compiles in "
        "C++ with the GIL released on the pipeline's dedicated compile "
        "thread; the Lowered it compiles is thread-private and the "
        "persistent-cache singleton is thread-safe in JAX",
    },
)

_lock = threading.Lock()

# Monitoring event -> counter key. Misses are recorded by
# jax/_src/compilation_cache.py on a failed lookup; hits by
# jax/_src/compiler.py when a compiled executable is served from disk.
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "persistent_hits",
    "/jax/compilation_cache/cache_misses": "persistent_misses",
}

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"

# Monitoring duration event -> (counter key, record name). JAX publishes
# them through `record_event_duration_secs` only when something traces,
# lowers or compiles: a warm call fires none. `backend_compile_duration`
# wraps the cache lookup, so a cache load is inside it.
_DURATIONS = {
    _TRACE_EVENT: ("trace_seconds", "compile.trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lower_seconds", "compile.lower"),
    "/jax/core/compile/backend_compile_duration":
        ("backend_compile_seconds", "compile.backend"),
    "/jax/compilation_cache/cache_retrieval_time_sec":
        ("cache_load_seconds", "compile.cache_load"),
}

_stats = {
    "persistent_hits": 0,
    "persistent_misses": 0,
    "trace_seconds": 0.0,
    "lower_seconds": 0.0,
    "backend_compile_seconds": 0.0,
    "cache_load_seconds": 0.0,
    # Ingest pipeline's overlapped warm compiles (data/pipeline.py): how
    # many AOT compiles ran in the background and their total seconds —
    # compile work that e2e wall-clock should NOT see when the overlap
    # holds.
    "aot_compiles": 0,
    "aot_compile_seconds": 0.0,
}
_listener_installed = False
_dir_in_effect: str | None = None


def aot_compile(lowered, *, ledger_key: str | None = None):
    """Compile a ``jax.stages.Lowered`` for the warm-compile stage.

    The compile runs through the SAME persistent-cache wiring as any jit
    compile (the cache singleton keys on HLO hash), so even when the
    resulting executable goes unused — a stale shape prediction — the
    fallback jit path's compile becomes a cache hit instead of a second
    full compile. Counted in ``cache_stats()``.

    A RETRIED site (resilience layer): a transient compile failure —
    an UNAVAILABLE from the runtime, the injected ``compile.aot`` fault
    — re-runs ``lowered.compile()`` with backoff; deterministic compile
    errors (a Mosaic refusal, out of HBM) propagate on the first
    attempt.

    ``ledger_key`` names this compile in the cost ledger's compile-time
    account (obs/ledger.py) — callers pass their cache key (the serve
    ladder's rung, the fused generation's AOT label); None books under
    ``aot`` when the ledger is armed.
    """
    import time

    from photon_tpu.resilience import retry

    t0 = time.perf_counter()
    compiled = retry.retrying_check(
        "compile.aot", lowered.compile, site="compile_cache.aot_compile"
    )
    seconds = time.perf_counter() - t0
    with _lock:
        _stats["aot_compiles"] += 1
        _stats["aot_compile_seconds"] += seconds
    try:
        from photon_tpu.obs import ledger

        ledger.record_compile(ledger_key or "aot", seconds)
    except Exception:  # pragma: no cover — telemetry must never abort
        pass
    return compiled


def _on_event(event: str, **kwargs) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        with _lock:
            _stats[key] += 1
        # Side-feed the unified telemetry registry (photon_tpu.obs) so
        # cache behavior shows up in the same snapshot/JSONL stream as
        # spans and pipeline stages (outside the module lock — the
        # registry takes its own). Guarded: monitoring events can fire
        # from compile paths during interpreter teardown.
        try:
            from photon_tpu import obs

            if obs.enabled():
                obs.REGISTRY.counter(
                    "compile_cache_events_total",
                    event=key.removeprefix("persistent_"),
                ).inc()
        except Exception:  # pragma: no cover — telemetry must never abort
            pass


# Open traces of the calling thread: JAX traces a jit met inside another
# jit's trace within the outer one's duration (hundreds in one fused
# program), so only the outermost is counted and recorded.
_tracing = threading.local()


def _on_trace_start(event: str, value, **kwargs) -> None:
    # JAX announces the start of a timed section as a scalar (the start
    # time) under the section's own event name.
    if event == _TRACE_EVENT:
        _tracing.depth = getattr(_tracing, "depth", 0) + 1


def _on_duration(event: str, duration: float, **kwargs) -> None:
    known = _DURATIONS.get(event)
    if known is None:
        return
    if event == _TRACE_EVENT:
        _tracing.depth = depth = max(getattr(_tracing, "depth", 1) - 1, 0)
        if depth:
            return
    key, name = known
    with _lock:
        _stats[key] += duration
    # A finished record in the tracer's ring, ending now, under the
    # calling thread's name (the background AOT thread's compiles are
    # told from the training thread's), JAX's own labels (`fun_name`) as
    # its attrs. Always recorded, as a stage is.
    try:
        from photon_tpu import obs

        obs.TRACER.record(name, duration, **kwargs)
    except Exception:  # pragma: no cover — telemetry must never abort
        pass


def _install_listener() -> None:
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        import jax.monitoring

        # Listeners are append-only in jax (no unregister API); one
        # process-lifetime counter hook is the intended use. Latched
        # under the lock so two racing enable calls cannot register
        # the listener (and double-count every event) twice.
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_scalar_listener(_on_trace_start)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listener_installed = True


def enable_compilation_cache(cache_dir: str | None = None) -> str | None:
    """Switch JAX's persistent compilation cache on.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    into its own config: this sets NO directory in code and reports the
    one JAX holds. Otherwise the cache lives at ``<checkout>/.jax_cache``.
    The explicit ``cache_dir`` argument (tests) overrides both; the
    value ``off`` disables wiring. Safe to call multiple times; returns
    the directory in effect (or None when disabled).
    """
    import jax

    global _dir_in_effect

    if cache_dir is not None and cache_dir.lower() in ("", "off"):
        # Genuinely disable: a process that enabled the cache earlier
        # must stop persisting/hitting it, or cache_stats() would report
        # dir=None while the counters keep climbing.
        jax.config.update("jax_compilation_cache_dir", None)
        _reset_cache_singleton()
        with _lock:
            _dir_in_effect = None
        return None
    if cache_dir is None and os.environ.get("JAX_COMPILATION_CACHE_DIR"):  # photon: ignore[spmd-host-divergence] -- cache dir is host-local config; changes where artifacts persist, never what is traced
        # JAX read the variable into its config at import; report what
        # it holds and leave it alone.
        cache_dir = jax.config.jax_compilation_cache_dir
    else:
        cache_dir = cache_dir or _DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Cache everything that took meaningful compile time; the default
    # threshold (1s) would skip many of the small eager-op programs
    # that a cold start compiles by the dozen.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    # JAX initializes the cache singleton AT MOST ONCE, on the first
    # compile: if anything jitted before this call (an import-time eager
    # op is enough), the singleton latched "no directory" and every
    # later compile skips the cache silently. Reset so the directory
    # configured above actually takes effect.
    _reset_cache_singleton()
    _install_listener()
    with _lock:
        _dir_in_effect = cache_dir
    return cache_dir


def _reset_cache_singleton() -> None:
    # Private API: if it moves, fail here rather than run with the cache
    # silently off (chip_smoke.py proves the cache took: entries > 0).
    from jax._src import compilation_cache as _cc

    _cc.reset_cache()


def _dir_stats(cache_dir: str) -> tuple[int, int]:
    entries = 0
    total = 0
    try:
        for de in os.scandir(cache_dir):
            if de.is_file():
                entries += 1
                total += de.stat().st_size
    except OSError:
        pass
    return entries, total


def compile_event_count() -> int:
    """Total persistent-cache requests seen so far (hits + misses).

    A DELTA of this across a window is the runtime zero-recompile
    check the serving path uses: any compile attempted in the window —
    whether the disk cache served it or not — moves the count, so a
    steady-state loop that "adds zero programs" must leave it flat
    (bench.py ``serving_compile_events``, cli/serve.py
    ``compile_events_during_serving``). Only meaningful while the
    persistent cache is enabled (the monitoring listener is installed
    by ``enable_compilation_cache``).
    """
    with _lock:
        return _stats["persistent_hits"] + _stats["persistent_misses"]


def cache_stats() -> dict:
    """Hit/miss counters + on-disk footprint of the persistent cache.

    ``persistent_hits``/``persistent_misses`` count this process's
    compile requests served from / missed in the directory cache (a miss
    is a real compile). ``hit_rate`` is None before any request. The
    ``entries``/``bytes`` pair is the directory scan at call time — a
    cross-process view of what the next cold start will find.
    """
    with _lock:
        snap = dict(_stats)
        cache_dir = _dir_in_effect
    hits = snap["persistent_hits"]
    misses = snap["persistent_misses"]
    total = hits + misses
    # The directory scan stays outside the lock: it is filesystem I/O
    # and must not stall a compile thread's counter update.
    entries, size = _dir_stats(cache_dir) if cache_dir else (0, 0)
    return {
        "dir": cache_dir,
        "persistent_hits": hits,
        "persistent_misses": misses,
        "hit_rate": (hits / total) if total else None,
        "entries": entries,
        "bytes": size,
        "aot_compiles": snap["aot_compiles"],
        "aot_compile_seconds": round(snap["aot_compile_seconds"], 4),
        # Summed over every thread, from JAX's duration events; a cache
        # load is inside backend_compile_seconds as well.
        "trace_seconds": snap["trace_seconds"],
        "lower_seconds": snap["lower_seconds"],
        "backend_compile_seconds": snap["backend_compile_seconds"],
        "cache_load_seconds": snap["cache_load_seconds"],
    }
