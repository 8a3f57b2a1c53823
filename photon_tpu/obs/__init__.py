"""photon_tpu.obs — unified runtime telemetry.

One coherent layer over what used to be four unconnected surfaces
(ad-hoc section logs, ``data/pipeline.py::PIPELINE_STATS``,
``utils/compile_cache.cache_stats()``, and the ``events.py`` listener
bus): hierarchical **spans** with a host/device split measured only at
span roots (``obs/spans.py``), a labeled **metrics registry**
(``obs/metrics.py``), **async device-side convergence traces** computed
inside the already-traced fit programs (``obs/convergence.py``), and
**exporters** — ``snapshot()`` for bench/driver JSON, a documented JSONL
stream, and an end-of-run text table (``obs/export.py``; schema in
OBSERVABILITY.md).

Telemetry is OFF by default and enabling it is a host-side decision
only: the device programs are identical either way. That is not a
promise but an audited contract — see PROGRAM_AUDIT below. The one part
that is always on is ``obs.stage``: the few dozen coarse sections of a
job (prepare, plan, fit, save, ...) land in the span ring and, as
``photon.<path>`` annotations, in any profiler session, enabled or not.

Usage::

    from photon_tpu import obs

    obs.enable()
    with obs.span("prepare"):
        datasets, _ = est.prepare(data)
    ...
    print(obs.summary_table())
    obs.write_jsonl("run-telemetry.jsonl")
"""

from __future__ import annotations

import contextlib
import logging
import time

from photon_tpu.obs import convergence
from photon_tpu.obs import fleet
from photon_tpu.obs import flight
from photon_tpu.obs import health
from photon_tpu.obs import ledger
from photon_tpu.obs import trace


def __getattr__(name: str):
    # Lazy submodule (PEP 562): `photon_tpu.obs` is imported by every
    # training/serving path, and eagerly pulling obs.monitor would tax
    # each of them with the http.server import chain for a surface
    # only `--monitor-port` users touch. `from photon_tpu.obs import
    # monitor` still works — the from-import falls back to this hook.
    if name == "monitor":
        import importlib

        return importlib.import_module("photon_tpu.obs.monitor")
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}"
    )
from photon_tpu.obs.export import (
    snapshot,
    summary_table,
    validate_jsonl,
    write_jsonl,
)
from photon_tpu.obs.metrics import (
    REGISTRY,
    MetricsRegistry,
    metrics_listener,
)
from photon_tpu.obs.spans import Span, SpanTracer
from photon_tpu.obs.trace import profile_session, write_chrome_trace

TRACER = SpanTracer()
span = TRACER.span
# The always-recorded class (obs/spans.py): coarse sections of a job,
# also TraceAnnotations, never a sync. `stage_sum` is the same for a
# section that runs in interleaved pieces.
stage = TRACER.stage
stage_sum = TRACER.stage_sum

# Program contracts (audited by `python -m photon_tpu.analysis
# --semantic`; machinery in analysis/program.py build_telemetry /
# build_trace):
#
# - `telemetry`: the instrumented public entry points — the fused
#   materialize + whole-fit programs, the ones every obs span and
#   convergence trace hangs off — must trace to BYTE-IDENTICAL jaxprs
#   with telemetry enabled vs disabled. Zero new dispatches (census
#   bound is the fused generation's own 2 programs), zero host
#   callbacks (hot_loop), identical recompile keys
#   (stable_under=telemetry_toggle). Convergence metrics achieve this
#   by being UNCONDITIONAL outputs of the fit program: the enable flag
#   only controls host-side recording, never the trace.
# - `trace`: the SAME bar for the timeline layer (obs/trace.py +
#   obs/flight.py): with telemetry enabled, a flight recorder
#   installed, and instants/counters/request records being emitted, the
#   traced programs stay byte-identical to the all-off base
#   (stable_under=trace_toggle) — events and dumps are host-ring
#   bookkeeping, never a traced operand or callback.
PROGRAM_AUDIT = [
    dict(
        name="telemetry",
        entry="obs instrumentation over algorithm.fused_fit "
        "(materialize + whole-fit programs, telemetry on vs off)",
        builder="build_telemetry",
        max_programs=2,
        stable_under=("telemetry_toggle",),
        hot_loop=True,
    ),
    dict(
        name="trace",
        entry="obs.trace event ring + obs.flight recorder over "
        "algorithm.fused_fit (tracing fully armed vs off)",
        builder="build_trace",
        max_programs=2,
        stable_under=("trace_toggle",),
        hot_loop=True,
    ),
    # `monitor`: the live-monitoring layer (obs/monitor.py). The
    # serving score program is traced with the layer fully ARMED — the
    # HTTP exporter up and being scraped, the window ring / hotness
    # sketch / SLO tracker receiving observations from another thread —
    # and must stay byte-identical to the all-off base with ZERO added
    # programs: a scrape is host bookkeeping + socket I/O, never a
    # traced operand, a callback, or a recompile.
    dict(
        name="monitor",
        entry="obs.monitor exporter + window rings + SLO/hotness "
        "surfaces over serve.ScorePrograms (scrape under load vs "
        "all-off)",
        builder="build_monitor",
        max_programs=1,
        stable_under=("monitor_scrape",),
        hot_loop=True,
    ),
    # `ledger`: the cost-attribution layer (obs/ledger.py). The fused
    # materialize + whole-fit programs are traced with the ledger
    # fully ARMED — enabled, a program registered in the census,
    # dispatch/compile/resident records landing from the recording
    # helpers — and must stay byte-identical to the all-off base with
    # ZERO added programs: rows are host dicts under a host lock,
    # static cost is priced at report time from a lazy thunk, never
    # inside (or as) a traced program.
    dict(
        name="ledger",
        entry="obs.ledger cost-attribution census + dispatch rows "
        "over algorithm.fused_fit (ledger armed vs off)",
        builder="build_ledger",
        max_programs=2,
        stable_under=("ledger_toggle",),
        hot_loop=True,
    ),
    # `health`: the model/data-health layer (obs/health.py). The fused
    # materialize + whole-fit programs are traced with health fully
    # ARMED — enabled, a train sketch registered, the serve tap fed,
    # numerics sentinels parked — and must stay byte-identical to the
    # all-off base with ZERO added programs: sketches are host numpy,
    # the sentinel parks a reference to an array the fit ALREADY
    # outputs (the convergence block), and every scan/compare happens
    # at report time, never inside (or as) a traced program.
    dict(
        name="health",
        entry="obs.health sketches + serve tap + numerics sentinels "
        "over algorithm.fused_fit (health armed vs off)",
        builder="build_health",
        max_programs=2,
        stable_under=("health_toggle",),
        hot_loop=True,
    ),
    # `fleet-obs`: the distributed-observability layer (obs/fleet.py).
    # The fused materialize + whole-fit programs are traced with fleet
    # shipping fully ARMED — identity stamped, the clock handshake
    # marked, a bundle committed to disk between traces — and must stay
    # byte-identical to the all-off base with ZERO added programs, zero
    # added collectives, and zero host callbacks in the hot loop:
    # identity is a cached host dict, clock samples are two time() reads,
    # and a bundle ship is ring snapshots + atomic file writes — never a
    # traced operand, a callback, or a cross-host exchange inside a
    # program.
    dict(
        name="fleet-obs",
        entry="obs.fleet identity/clock/bundle shipping over "
        "algorithm.fused_fit (fleet armed + bundle shipped vs off)",
        builder="build_fleet",
        max_programs=2,
        stable_under=("fleet_toggle",),
        hot_loop=True,
    ),
]


@contextlib.contextmanager
def logged_span(msg: str, log: logging.Logger | None = None):
    """A span that also keeps the reference's ``Timed`` logging contract
    ("<msg>: begin execution" / "<msg>: executed in <t> s",
    util/Timed.scala:53-80) — THE one logged-section helper; the CLI
    drivers route here so the log contract and the span naming live in
    a single place."""
    log = log or logging.getLogger("photon_tpu.timed")
    log.info("%s: begin execution", msg)
    t0 = time.perf_counter()
    try:
        with span(msg):
            yield
    finally:
        log.info(
            "%s: executed in %.3f s", msg, time.perf_counter() - t0
        )


def enable() -> None:
    """Turn telemetry on: spans record, fit-level roots sync for the
    host/device split, convergence traces are parked for async fetch."""
    TRACER.enabled = True


def disable() -> None:
    TRACER.enabled = False


def enabled() -> bool:
    return TRACER.enabled


def reset() -> None:
    """Drop all recorded telemetry (spans, metrics, convergence traces,
    trace events, ledger accumulators, health sketches/sentinels).
    Does not touch the enabled flags."""
    TRACER.reset()
    REGISTRY.reset()
    convergence.reset()
    trace.reset()
    ledger.reset()
    health.reset()
    fleet.reset()


def set_span_retention(max_spans: int) -> None:
    """Rebind the completed-span ring's bound (default 4096; newest
    spans kept). The trace-event ring has ``obs.trace.set_retention``;
    drops feed the ``spans_dropped_total`` / ``trace_events_dropped_total``
    registry counters as well as the snapshot/JSONL headers."""
    TRACER.set_retention(max_spans)


__all__ = [
    "PROGRAM_AUDIT",
    "REGISTRY",
    "MetricsRegistry",
    "Span",
    "SpanTracer",
    "TRACER",
    "convergence",
    "disable",
    "enable",
    "enabled",
    "fleet",
    "flight",
    "health",
    "ledger",
    "logged_span",
    "metrics_listener",
    "monitor",
    "profile_session",
    "reset",
    "set_span_retention",
    "snapshot",
    "span",
    "stage",
    "stage_sum",
    "summary_table",
    "trace",
    "validate_jsonl",
    "write_chrome_trace",
    "write_jsonl",
]
