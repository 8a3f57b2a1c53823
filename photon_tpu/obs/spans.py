"""Hierarchical span tracer: one tree answering "where did the time go".

The reference scatters runtime visibility across the Spark UI plus ad-hoc
``Timed{}`` wall-clock logging (util/Timed.scala:33); our rebuild had
grown the same scatter — ``Timed`` shims, ``PIPELINE_STATS.stage``,
per-update ``time.perf_counter()`` in the descent loops. This module is
the one surface they all feed: thread-safe, hierarchical spans recording
wall seconds and — at span ROOTS only — the host-vs-device split.

Design constraints (the audited zero-overhead contract,
``photon_tpu/obs/__init__.py`` PROGRAM_AUDIT):

- **Nothing device-side.** Spans are pure host bookkeeping around
  dispatch; no span ever appears inside a jitted program, so the traced
  jaxprs are byte-identical with telemetry on or off.
- **Device time only at roots.** A span constructed with ``sync=...`` (or
  given ``span.sync = outputs`` before exit) calls
  ``jax.block_until_ready`` ON EXIT and records the blocked wait as
  ``device_wait_seconds``. Only coarse fit-level spans pass ``sync`` —
  never per-iteration code — so telemetry adds at most one host sync per
  fit, at a point the caller's first blocking read would have paid
  anyway.
- **Disabled == free.** With the tracer disabled, ``span()`` is a single
  flag check yielding ``None``; no allocation, no lock, no sync.
- **Stages are always recorded.** ``stage()`` is the one class of span
  that records whether or not telemetry is enabled: the coarse sections
  of a job (prepare, plan, fit, save, ...), a few dozen a job. It costs
  two clock reads, one ring append and one ``TraceAnnotation``; it never
  syncs. Anything per solver iteration, per coordinate update or per
  serving batch stays a gated ``span()``.
- **On the profiler's clock.** Every stage (and every enabled span) holds
  a ``jax.profiler.TraceAnnotation("photon." + path)`` for its length, so
  a profiler session shows the program's sections in the xplane's host
  plane beside the device's operations.

Hierarchy is per thread: each thread keeps its own span stack, and a
span's ``path`` is its ancestors' names joined with ``/`` (worker-pool
spans — the ingest planners, the background AOT compile — root their own
subtrees, labeled by thread). Aggregation by path happens at export time
(``obs/export.py``), so recording stays O(1) per span.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque

# Default retention bound on completed spans — the same concern that
# caps convergence traces: a long telemetry-on production run (or the
# bench's steady-state loop) must not grow host memory linearly. Oldest
# spans drop first; the tracer counts drops (and feeds the
# `spans_dropped_total` registry counter) so exporters can say so
# instead of silently under-reporting. Configurable per tracer via
# ``SpanTracer.set_retention`` / ``obs.set_span_retention``.
_MAX_SPANS = 4096

# Host-concurrency contract (audited by `python -m photon_tpu.analysis
# --concurrency`). Worker threads (ingest planners, the AOT compile
# thread) record spans concurrently with the training thread, so the
# completed-span deque and the drop counter live under one lock; the
# per-thread span STACKS are `threading.local` and need none. The
# `enabled` flag is deliberately unguarded: it is a benign latch read
# once per span entry, and a racing enable/disable can only gain or
# lose one span at the boundary, never corrupt the record.
CONCURRENCY_AUDIT = dict(
    name="obs-spans",
    locks={
        "SpanTracer._lock": (
            "SpanTracer._spans",
            "SpanTracer.dropped",
        ),
    },
    thread_entries=(),
    jax_dispatch_ok={},
)


_annotation_cls = None


def _annotation(path: str):
    """``TraceAnnotation("photon.<path>")``: free while no profiler
    session runs (jax resolved on first use; obs imports stay jax-free)."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    return _annotation_cls("photon." + path)


class Span:
    """One completed (or in-flight) timed section.

    ``kind`` tells the three writers of the ring apart: ``span`` (gated
    by ``obs.enable()``), ``stage`` (always recorded) and ``event`` (a
    finished record handed over with its duration: compile durations,
    sums over the blocks of one file). An event's ``seconds`` may be a
    SUM of intervals inside its [t0, t1] envelope."""

    __slots__ = (
        "name",
        "path",
        "thread",
        "kind",
        "t0",
        "t1",
        "seconds",
        "device_wait_seconds",
        "sync",
        "attrs",
    )

    def __init__(self, name: str, path: str, thread: str,
                 kind: str = "span"):
        self.name = name
        self.path = path
        self.thread = thread
        self.kind = kind
        self.t0 = 0.0
        self.t1 = 0.0
        self.seconds = 0.0
        # Time spent blocked in jax.block_until_ready at span exit — the
        # device-work tail the host had to wait out. None when the span
        # carried no sync (host-only span).
        self.device_wait_seconds: float | None = None
        # Arrays (any pytree) to block on at exit; set via the ``sync=``
        # kwarg or assigned inside the ``with`` body once outputs exist.
        self.sync = None
        self.attrs: dict | None = None

    def to_json(self) -> dict:
        return {
            "type": "span",
            "kind": self.kind,
            "path": self.path,
            "name": self.name,
            "thread": self.thread,
            "seconds": round(self.seconds, 6),
            "device_wait_seconds": (
                None
                if self.device_wait_seconds is None
                else round(self.device_wait_seconds, 6)
            ),
            "attrs": self.attrs or {},
        }


class StageSum:
    """A section that runs in pieces interleaved with another (encode and
    write, block by block, in one file): each ``with`` adds one interval,
    held under the profiler annotation like a stage's; ``close()`` leaves
    ONE record whose ``seconds`` is the sum and whose [t0, t1] is the
    envelope. Recorded always, as a stage is. One thread."""

    __slots__ = ("_tracer", "_annotation", "_start", "name", "path",
                 "attrs", "seconds", "intervals", "t0", "t1")

    def __init__(self, tracer: "SpanTracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.path = tracer._path(name)
        self.attrs = attrs
        self.seconds = 0.0
        self.intervals = 0
        self.t0 = self.t1 = None

    def __enter__(self):
        self._annotation = _annotation(self.path)
        self._annotation.__enter__()
        self._start = time.perf_counter()
        if self.t0 is None:
            self.t0 = self._start
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self.seconds += self.t1 - self._start
        self.intervals += 1
        self._annotation.__exit__(*exc)

    def close(self) -> None:
        if self.intervals:
            self._tracer.record(
                self.name, self.seconds, t0=self.t0, t1=self.t1,
                intervals=self.intervals, **self.attrs)
            self.intervals = 0


class SpanTracer:
    """Thread-safe span recorder with per-thread hierarchy.

    One process-global instance lives at ``photon_tpu.obs.TRACER``;
    ``obs.enable()/disable()`` flip recording for the whole telemetry
    layer (spans, convergence capture, metric side-feeds).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._spans: deque[Span] = deque(maxlen=_MAX_SPANS)
        self.dropped = 0
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def set_retention(self, max_spans: int) -> None:
        """Rebind the completed-span ring to a new bound (the newest
        spans are kept). Spans a shrinking bound evicts count as drops —
        the same accounting as ring overflow. The trace-event ring has
        the analogous ``obs.trace.set_retention``."""
        if max_spans < 1:
            raise ValueError(
                f"span retention must be >= 1, got {max_spans}"
            )
        with self._lock:
            evicted = max(0, len(self._spans) - int(max_spans))
            self._spans = deque(self._spans, maxlen=int(max_spans))
            self.dropped += evicted
        if evicted:
            from photon_tpu.obs.metrics import REGISTRY

            REGISTRY.counter("spans_dropped_total").inc(evicted)

    def completed(self) -> list[Span]:
        """Snapshot of the completed spans (record order; bounded to the
        most recent _MAX_SPANS — ``dropped`` counts the evicted)."""
        with self._lock:
            return list(self._spans)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _path(self, name: str) -> str:
        stack = self._stack()
        return f"{stack[-1].path}/{name}" if stack else name

    def _open(self, name: str, kind: str, attrs: dict | None) -> Span:
        sp = Span(
            name, self._path(name), threading.current_thread().name, kind)
        if attrs:
            sp.attrs = dict(attrs)
        self._stack().append(sp)
        return sp

    def _append(self, sp: Span) -> None:
        evicted = False
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
                evicted = True
            self._spans.append(sp)
        if evicted:
            # Outside the tracer lock (never nest it with the
            # registry's): retention pressure is a REAL metric — the
            # snapshot header's spans_dropped only says what was lost,
            # the counter makes it alertable.
            from photon_tpu.obs.metrics import REGISTRY

            REGISTRY.counter("spans_dropped_total").inc()

    @contextlib.contextmanager
    def stage(self, name: str, **attrs):
        """Record a coarse section of a job ALWAYS, telemetry enabled or
        not; yields the live Span. No sync: a stage that dispatches
        device work ends when the dispatch returns."""
        sp = self._open(name, "stage", attrs)
        try:
            with _annotation(sp.path):
                sp.t0 = time.perf_counter()
                try:
                    yield sp
                finally:
                    sp.t1 = time.perf_counter()
                    sp.seconds = sp.t1 - sp.t0
                    self._append(sp)
        finally:
            # Also when the annotation itself could not be built (a jax
            # that fails to import): the thread's stack must not keep a
            # dead stage.
            self._stack().pop()

    def stage_sum(self, name: str, **attrs) -> StageSum:
        """A stage made of several intervals (see ``StageSum``)."""
        return StageSum(self, name, attrs)

    def record(self, name: str, seconds: float, *, t0: float | None = None,
               t1: float | None = None, **attrs) -> Span:
        """Append a FINISHED record (kind ``event``) under the calling
        thread's open section: ``seconds`` long, ending at ``t1`` (now
        when not given) and starting at ``t0`` (``t1 - seconds`` when not
        given; an earlier ``t0`` makes [t0, t1] the envelope of a sum)."""
        sp = Span(
            name, self._path(name), threading.current_thread().name,
            "event")
        sp.t1 = time.perf_counter() if t1 is None else t1
        sp.t0 = sp.t1 - seconds if t0 is None else t0
        sp.seconds = seconds
        if attrs:
            sp.attrs = attrs
        self._append(sp)
        return sp

    @contextlib.contextmanager
    def span(self, name: str, *, sync=None, attrs: dict | None = None):
        """Record a named section; yields the live Span (or None when
        telemetry is disabled — callers must tolerate both).

        ``sync``: pytree of jax arrays to ``block_until_ready`` at exit
        (roots-only policy: pass it on fit-level spans, never inside
        loops). The blocked time lands in ``device_wait_seconds``.
        """
        if not self.enabled:
            yield None
            return
        sp = self._open(name, "span", attrs)
        sp.sync = sync
        try:
            with _annotation(sp.path):
                sp.t0 = time.perf_counter()
                try:
                    yield sp
                finally:
                    t1 = time.perf_counter()
                    try:
                        if sp.sync is not None:
                            import jax

                            # Clear before blocking: don't pin device
                            # arrays in the record, and a raising sync
                            # (async device failure surfacing here) must
                            # not leave them held.
                            sync, sp.sync = sp.sync, None
                            jax.block_until_ready(sync)
                            t_done = time.perf_counter()
                            sp.device_wait_seconds = t_done - t1
                            t1 = t_done
                    finally:
                        # Record UNCONDITIONALLY: if block_until_ready
                        # raised, the exception propagates with the span
                        # recorded.
                        sp.t1 = t1
                        sp.seconds = t1 - sp.t0
                        self._append(sp)
        finally:
            # Pop UNCONDITIONALLY (a raising sync, an annotation that
            # could not be built): the thread's span stack must not keep
            # the dead span (every later span on this thread would
            # inherit its path prefix).
            self._stack().pop()


def aggregate(spans: list[Span]) -> dict[str, dict]:
    """Path -> {count, seconds, device_wait_seconds} over completed spans.

    The rendered "span tree": paths sort hierarchically, seconds are the
    SUM over occurrences (a path entered from several threads or fits
    accumulates), and ``device_wait_seconds`` sums only over occurrences
    that carried a sync (None when none did).
    """
    out: dict[str, dict] = {}
    for sp in spans:
        agg = out.setdefault(
            sp.path,
            {"count": 0, "seconds": 0.0, "device_wait_seconds": None},
        )
        agg["count"] += 1
        agg["seconds"] += sp.seconds
        if sp.device_wait_seconds is not None:
            agg["device_wait_seconds"] = (
                agg["device_wait_seconds"] or 0.0
            ) + sp.device_wait_seconds
    for agg in out.values():
        agg["seconds"] = round(agg["seconds"], 6)
        if agg["device_wait_seconds"] is not None:
            agg["device_wait_seconds"] = round(
                agg["device_wait_seconds"], 6
            )
    return dict(sorted(out.items()))
