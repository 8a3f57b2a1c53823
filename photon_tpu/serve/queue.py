"""The serving micro-batch queue: bounded, lingering, draining, degrading.

One worker thread owns all device dispatch; producers (request handler
threads, the synchronous driver) hand ``(features, entity_ids)`` pairs
to ``submit`` and get a ``Future`` back. The flush policy is the usual
latency/throughput dial: a batch dispatches when it reaches
``max_batch`` requests (clamped to the score ladder's top rung) OR when
the OLDEST queued request has lingered ``max_linger_s`` — small linger
= low p99, large linger = fuller batches = higher QPS. The queue is
bounded (``max_queue``): producers block for space, so an overloaded
server applies backpressure instead of growing an unbounded heap.

Degraded mode (the resilience layer). Deadlines, shedding, and the
circuit breaker default OFF, so those stay off the clean path entirely;
dispatch retry is the one knob that defaults ON
(``dispatch_retry=_DISPATCH_RETRY``: 3 attempts, 5 ms base backoff) —
a transient device fault is retried in place before any error fans
out, and a retry's backoff does stack onto that batch's latency. Pass
``dispatch_retry=None`` for the old fail-on-first-attempt semantics.

- **Deadlines**: a request submitted with ``deadline_s`` (or a queue
  ``default_deadline_s``) that is still queued when it expires FAILS
  FAST with ``DeadlineExceededError`` — before any padding or device
  work is spent on it. A late response is worth nothing; the capacity
  goes to requests that can still make their deadline. Deadlines also
  CUT THE LINGER SHORT: a batch whose earliest deadline would lapse
  mid-linger flushes early enough to dispatch in time, so a deadline
  tighter than ``max_linger_s`` is served, not expired on an idle
  device.
- **Shedding**: with ``shed_watermark`` set, a submit finding that many
  requests already queued is rejected immediately with
  ``OverloadedError`` (typed, countable) instead of blocking — the
  overloaded server stays responsive about being overloaded.
- **Circuit breaker**: ``breaker_threshold`` consecutive dispatch
  failures open the breaker — the pending queue drains with
  ``CircuitOpenError``, new submits fail fast, and ``reset_breaker()``
  re-arms after the operator (or a supervisor) intervenes. A wedged
  model never spins the worker through an unbounded failure loop.
- **Dispatch retry**: transient dispatch failures (``TransientError``,
  e.g. the injected ``serve.dispatch`` fault) are retried with backoff
  before any error fans out; deterministic failures (``PoisonError``, a
  malformed request) fan out to exactly their batch on the first
  attempt.
- **health()**: one locked snapshot — queue depth, shed / deadline /
  error / retry / breaker counters, coefficient-table generation — the
  CLI and bench surface it.
- **reload_model() / quiesce()**: hot model swap on the LIVE queue — a
  values-only refresh flips table references with dispatch running; a
  structure change compiles the new generation's ladder off-path, then
  swaps tables and the queue's program binding inside one ``quiesce``
  window (the worker parks before popping; producers keep queueing, no
  request is dropped). The pilot's promotion path and ``cli.serve
  --reload-model`` both ride this.

Request-scoped tracing (``photon_tpu.obs.trace``): with telemetry
enabled, every ``submit`` mints a process-unique request id and every
request resolves to exactly one trace record — outcome ``served``,
``expired``, ``shed``, ``breaker``, ``closed``, ``error``, or
``shutdown`` — with served requests carrying the
queue-wait → batch-fill → dispatch → scatter segment timestamps that
render as per-request async span trees in the exported ``trace.json``
(OBSERVABILITY.md). Telemetry off, each boundary is one flag check and
nothing is recorded.

Shutdown drains: ``close()`` wakes the worker, which keeps flushing
until the queue is empty, then exits; every in-flight future resolves.
``close(timeout=...)`` bounds the drain: if the worker is wedged in a
dispatch past the timeout, every still-queued future fails with
``ShutdownError`` and close returns False (the worker thread is a
daemon, so a wedged executable cannot hang process exit). A submit
after close fails fast. Exceptions from a batch dispatch fan out to
THAT batch's futures (each waiter sees the error; the worker keeps
serving subsequent batches).
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import logging
import threading
import time

import numpy as np

from photon_tpu.resilience import retry as _retry
from photon_tpu.resilience.errors import (
    CircuitOpenError,
    DeadlineExceededError,
    OverloadedError,
    ShutdownError,
)

logger = logging.getLogger(__name__)

# Host-concurrency contract (audited by `python -m photon_tpu.analysis
# --concurrency`). The threading model is single-consumer: ONE worker
# thread pops, pads, dispatches, and scatters; any number of producer
# threads push. `_cond` (a Condition, which is also the mutex) guards
# the pending deque, the closed flag, the stats dict, the degraded-
# mode state (breaker open/failure-streak, the deadline-scan latch),
# and the double-buffer staging slot: `_staged` holds the NEXT batch,
# popped and host-packed by `_stage_next` while the previous batch's
# dispatch is in flight on the device (the PR 18 pipelined worker).
# The slot is only ever filled and emptied on the one worker thread —
# the lock covers its visibility to the breaker drain and to quiesce —
# so the single-consumer invariant is unchanged. The worker snapshots
# a batch UNDER the lock and dispatches OUTSIDE it, so producers never
# queue behind an XLA execution — and every future resolution
# (results, errors, deadline expiry, breaker drain, shutdown strand)
# also runs OUTSIDE the lock, because resolution runs user callbacks.
# Futures are created here (not executor-submitted) and every one is
# resolved — by the batch's results, by the batch's exception, by
# deadline expiry, by the breaker drain (which drains the staged batch
# alongside the pending deque), or by close()'s drain/timeout — so no
# waiter can hang on a dropped future.
CONCURRENCY_AUDIT = dict(
    name="serve-queue",
    locks={
        "MicroBatchQueue._cond": (
            "MicroBatchQueue._pending",
            "MicroBatchQueue._closed",
            "MicroBatchQueue._stats",
            "MicroBatchQueue._coord_stats",
            "MicroBatchQueue._breaker_open",
            "MicroBatchQueue._consecutive_failures",
            "MicroBatchQueue._has_deadlines",
            "MicroBatchQueue._close_stranded",
            "MicroBatchQueue._paused",
            "MicroBatchQueue._dispatching",
            "MicroBatchQueue._staged",
            "MicroBatchQueue.programs",
            "MicroBatchQueue._re_types",
            "MicroBatchQueue.hotness",
        ),
        "_Future._lock": (
            "_Future._callbacks",
            "_Future._value",
            "_Future._exc",
            "_Future._resolved",
        ),
    },
    thread_entries=(
        "MicroBatchQueue._worker",
        "MicroBatchQueue._dispatch",
        "MicroBatchQueue._stage_next",
        "MicroBatchQueue._pop_staged",
    ),
    jax_dispatch_ok={
        "_worker": "the worker loop itself only pops/waits/expires; "
        "all device work is in _dispatch (declared below)",
        "_dispatch": "dispatches PRE-COMPILED AOT executables only "
        "(ScorePrograms.dispatch_padded / score_padded) — no tracing, "
        "no compilation can occur on this thread (the ladder is "
        "compiled at construction on the caller's thread and the "
        "dispatch raises on an un-compiled rung); the single worker "
        "thread serializes every dispatch (the transient-retry loop "
        "re-enters the same executables with the same operands), and "
        "the fetch_padded/np.asarray fetch is the request path's one "
        "intended host sync",
        "_stage_next": "host work only: pops the next batch under "
        "_cond and packs it with ScorePrograms.pack_requests (pure "
        "numpy pad/stack/vocab lookup — no jax entry point); the "
        "device work it overlaps is the PREVIOUS batch's "
        "already-dispatched executable",
        "_pop_staged": "pops/waits under _cond only; the staged "
        "batch's device work happens in _dispatch",
    },
)


class QueueClosed(RuntimeError):
    """submit() after close()."""


# Request ids are minted at submit (every submit, including rejected
# ones) so EVERY request — served, expired, shed, breaker-failed —
# yields exactly one trace record under a process-unique id
# (obs/trace.py request-span taxonomy, OBSERVABILITY.md).
_REQUEST_IDS = itertools.count(1)


class _Request:
    __slots__ = (
        "features", "entity_ids", "future", "enqueued_at", "deadline",
        "rid", "take_ts",
    )

    def __init__(self, features: dict, entity_ids: dict,
                 deadline_s: float | None = None):
        self.features = features
        self.entity_ids = entity_ids
        self.future = _Future()
        self.rid = next(_REQUEST_IDS)
        self.enqueued_at = time.perf_counter()
        # Stamped (telemetry on only) when the worker pops the request
        # into a batch: submit→take is the queue_wait trace segment.
        self.take_ts: float | None = None
        self.deadline = (
            None if deadline_s is None
            else self.enqueued_at + float(deadline_s)
        )


def _record_request(req: _Request, outcome: str, **extra) -> None:
    """Emit one request-scoped trace record (no-op when telemetry is
    disabled). ``extra`` carries the served path's segment timestamps
    (``dispatch_ts``/``scatter_ts``/``batch``/``batch_size``) or the
    failure path's ``error``."""
    from photon_tpu import obs

    if not obs.enabled():
        return
    rec = {
        "id": req.rid,
        "outcome": outcome,
        "submit_ts": req.enqueued_at,
        "done_ts": time.perf_counter(),
    }
    if req.take_ts is not None:
        rec["take_ts"] = req.take_ts
    rec.update(extra)
    obs.trace.request(rec)


class _Future:
    """Minimal single-shot future (no executor): set exactly once by
    the worker, waited on by the producer. Done callbacks run on the
    worker thread at resolution — the driver uses them to timestamp
    completion without a per-request host thread. ``_lock`` closes the
    register-vs-resolve race: without it a callback added while the
    worker resolves could be dropped silently."""

    __slots__ = (
        "_lock", "_event", "_value", "_exc", "_callbacks", "_resolved"
    )

    def __init__(self):
        self._lock = threading.Lock()
        self._event = threading.Event()
        self._value = None
        self._exc: BaseException | None = None
        self._callbacks: list = []
        self._resolved = False

    def _resolve(self, value, exc: BaseException | None) -> None:
        with self._lock:
            if self._resolved:
                raise RuntimeError("future resolved twice")
            self._resolved = True
            self._value = value
            self._exc = exc
            cbs, self._callbacks = self._callbacks, []
        for cb in cbs:  # outside the lock: callbacks are user code
            try:
                cb(self)
            except Exception:  # noqa: BLE001 — a raising callback must
                # not kill the worker thread (stranding every queued
                # future); same logged-and-continue contract as
                # concurrent.futures.
                logger.exception("serve future done-callback raised")
        # The event flips only AFTER the registered callbacks ran, so a
        # waiter that observes done() may rely on its callback's side
        # effects (the driver's latency append). Callbacks therefore
        # must never wait on this future themselves.
        self._event.set()

    def set_result(self, value) -> None:
        self._resolve(value, None)

    def set_exception(self, exc: BaseException) -> None:
        self._resolve(None, exc)

    def add_done_callback(self, cb) -> None:
        with self._lock:
            if not self._resolved:
                self._callbacks.append(cb)
                return
        cb(self)

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None):
        if not self._event.wait(timeout):
            raise TimeoutError("score request still queued")
        if self._exc is not None:
            raise self._exc
        return self._value

    def exception(self, timeout: float | None = None):
        if not self._event.wait(timeout):
            raise TimeoutError("score request still queued")
        return self._exc


class _Staged:
    """One double-buffered batch: popped from the pending deque and
    host-packed (pad/stack/code resolution) by ``_stage_next`` while
    the PREVIOUS batch's dispatch is still in flight on the device.
    ``programs`` pins the generation the operands were packed against:
    a structure reload that adopts new programs between stage and
    dispatch invalidates ``packed`` (codes resolve against the OLD
    vocabulary), so ``_dispatch`` re-packs from ``requests`` whenever
    the identity check fails. A values-only reload keeps the programs
    object (tables swap in place) and the packed operands stay valid."""

    __slots__ = ("requests", "packed", "programs")

    def __init__(self, requests, packed, programs):
        self.requests = requests
        self.packed = packed
        self.programs = programs


# Dispatch retry default: two quick re-attempts. A transient dispatch
# failure clears in milliseconds or not at all; long backoff would just
# stack linger on every queued request behind the batch.
_DISPATCH_RETRY = _retry.RetryPolicy(
    max_attempts=3, base_delay_s=0.005, max_delay_s=0.1
)

# How far BEFORE the earliest pending deadline the linger wait flushes:
# waking exactly at the deadline would expire the request in the same
# scan that was meant to save it, and Condition.wait oversleeps by
# scheduler jitter (tens of ms observed on the loaded 2-core CI box).
# Erring early is safe — the batch just dispatches a little less full —
# erring late expires a servable request, so the slack is generous. A
# request with less budget left than this was unservable anyway.
_DEADLINE_FLUSH_SLACK_S = 25e-3


class MicroBatchQueue:
    """Bounded micro-batching front of a ``ScorePrograms`` ladder."""

    def __init__(
        self,
        programs,
        *,
        max_batch: int | None = None,
        max_linger_s: float = 0.002,
        max_queue: int = 4096,
        default_deadline_s: float | None = None,
        shed_watermark: int | None = None,
        breaker_threshold: int | None = None,
        dispatch_retry: "_retry.RetryPolicy | None" = _DISPATCH_RETRY,
        pipeline_staging: bool = True,
        close_timeout_s: float | None = None,
        slo=None,
        latency_window_s: float = 10.0,
        latency_windows: int = 6,
        hotness_k: int = 64,
    ):
        self.programs = programs
        top = programs.ladder.max_batch
        self.max_batch = min(
            top if max_batch is None else int(max_batch), top
        )
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.max_linger_s = float(max_linger_s)
        self.max_queue = max(int(max_queue), self.max_batch)
        self.default_deadline_s = default_deadline_s
        self.shed_watermark = (
            None if shed_watermark is None
            else max(int(shed_watermark), 1)
        )
        self.breaker_threshold = (
            None if breaker_threshold is None
            else max(int(breaker_threshold), 1)
        )
        self.dispatch_retry = dispatch_retry
        # Double-buffered staging (PR 18): while batch k's dispatch is
        # in flight, the worker pops + host-packs batch k+1 into
        # `_staged` so pad/stack/code-resolution time overlaps the
        # device round trip instead of serializing with it. False
        # restores the strictly serial worker (the byte-identical
        # parity reference for the pipelined path).
        self.pipeline_staging = bool(pipeline_staging)
        # Bounds the context-manager exit (``with`` blocks call close()
        # with no argument, which would otherwise join a wedged
        # dispatch forever).
        self.close_timeout_s = close_timeout_s
        self._cond = threading.Condition()
        self._pending: collections.deque[_Request] = collections.deque()
        self._closed = False
        self._close_stranded = False
        self._breaker_open = False
        self._consecutive_failures = 0
        # Quiesce state (``quiesce()`` / ``reload_model``): while
        # ``_paused`` the worker parks BEFORE popping a batch;
        # ``_dispatching`` is True from batch pop to dispatch return so
        # the quiescer can wait out an in-flight batch.
        self._paused = False
        self._dispatching = False
        # Staging hand-off slot: filled by _stage_next (worker thread,
        # while the previous dispatch is in flight), emptied by
        # _pop_staged / the breaker drain. Guarded by _cond.
        self._staged: _Staged | None = None
        # Latched on the first deadline-bearing submit so the worker's
        # expiry scan stays off the clean path entirely.
        self._has_deadlines = default_deadline_s is not None
        self._stats = {
            "requests": 0,
            "batches": 0,
            "batched_requests": 0,
            "cold_lookups": 0,
            "entity_lookups": 0,
            "rejected": 0,
            "dispatch_errors": 0,
            "dispatch_retries": 0,
            "deadline_expired": 0,
            "shed": 0,
            "breaker_trips": 0,
            "breaker_rejected": 0,
            "shutdown_stranded": 0,
            # Staging pipeline accounting: staged_batches counts
            # batches popped + packed AHEAD of their dispatch;
            # staging_seconds is ALL host pack time (staged or not),
            # staging_overlapped_seconds the part hidden behind an
            # in-flight device dispatch. overlap/total is the
            # `staging_overlap_fraction` surfaced on /metrics.
            "staged_batches": 0,
            "staging_seconds": 0.0,
            "staging_overlapped_seconds": 0.0,
        }
        # Live-monitoring surfaces (photon_tpu.obs.monitor; PR 9).
        # Per-COORDINATE cold/lookups counters (the global
        # cold_entity_rate hides a cold coordinate when two coordinates
        # share a re_type with different vocab coverage) ride the one
        # queue lock next to _stats; the latency window ring, the SLO
        # burn tracker, and the per-coordinate hotness sketches each
        # keep their OWN lock (obs-monitor CONCURRENCY_AUDIT) so a
        # /metrics scrape never queues behind the dispatch worker.
        from photon_tpu.obs.monitor import (
            RollingHistogram,
            SloTracker,
            SpaceSavingSketch,
        )

        random_tables = getattr(
            getattr(programs, "tables", None), "random", None
        ) or {}
        self._coord_stats = {
            name: {"entity_lookups": 0, "cold_lookups": 0}
            for name in random_tables
        }
        self._re_types = {
            name: t.random_effect_type
            for name, t in random_tables.items()
        }
        self.latency = RollingHistogram(
            window_s=latency_window_s, num_windows=latency_windows
        )
        self.slo_tracker = None if slo is None else SloTracker(slo)
        self._hotness_k = int(hotness_k)
        self.hotness = {
            name: SpaceSavingSketch(hotness_k)
            for name in random_tables
        }
        self._thread = threading.Thread(
            target=self._worker, name="photon-serve-worker",
            # Daemon: a dispatch wedged in native code past a
            # close(timeout=...) must not be able to hang process exit.
            daemon=True,
        )
        self._thread.start()

    # -- producer side ----------------------------------------------------

    def submit(self, features: dict, entity_ids: dict | None = None,
               *, deadline_s: float | None = None):
        """Queue one request; returns its Future.

        ``features`` maps feature shard id -> the spec's request leaf
        (dense: [d] vector; sparse: ([k] indices, [k] values));
        ``entity_ids`` maps random-effect type -> entity key;
        ``deadline_s`` (or the queue's ``default_deadline_s``) bounds
        how long the request may wait before it fails fast. Blocks
        while the queue is at ``max_queue`` (backpressure) unless a
        ``shed_watermark`` rejects first; raises typed errors instead
        of queueing when the queue is closed, shedding, or the
        dispatch circuit breaker is open.
        """
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        req = _Request(features, dict(entity_ids or {}), deadline_s)
        rejection = None  # (outcome, exc), resolved OUTSIDE the lock
        with self._cond:
            while True:
                if self._closed:
                    self._stats["rejected"] += 1
                    rejection = (
                        "closed", QueueClosed("serve queue is closed"))
                    break
                if self._breaker_open:
                    self._stats["breaker_rejected"] += 1
                    rejection = ("breaker", CircuitOpenError(
                        "serve dispatch circuit breaker is open "
                        f"(tripped after {self.breaker_threshold} "
                        "consecutive batch failures); reset_breaker() "
                        "to resume"))
                    break
                if (
                    self.shed_watermark is not None
                    and len(self._pending) >= self.shed_watermark
                ):
                    self._stats["shed"] += 1
                    rejection = ("shed", OverloadedError(
                        f"serve queue depth {len(self._pending)} is at "
                        f"the shed watermark {self.shed_watermark}; "
                        "request rejected instead of queued"))
                    break
                if len(self._pending) < self.max_queue:
                    break
                self._cond.wait()
            if rejection is None:
                if req.deadline is not None:
                    self._has_deadlines = True
                self._pending.append(req)
                self._stats["requests"] += 1
                self._cond.notify_all()
        if rejection is not None:
            # Trace emission (ring lock, registry lock on eviction)
            # stays off the queue lock — overload, the exact state that
            # takes these paths, is when the cond is hottest.
            outcome, exc = rejection
            _record_request(req, outcome)
            if self.slo_tracker is not None:
                self.slo_tracker.observe_errors(1)
            raise exc
        return req.future

    def close(self, timeout: float | None = None) -> bool:
        """Stop accepting requests, drain everything queued, join the
        worker. Idempotent.

        ``timeout`` bounds the drain-and-join: a dispatch wedged in
        native code can otherwise hang shutdown forever. On timeout,
        every request still QUEUED (never handed to the worker) fails
        with ``ShutdownError`` and close returns False — the in-flight
        batch's futures stay owned by the (daemon) worker, which will
        resolve them if the dispatch ever returns. Returns True when
        the drain completed. Once a bounded close has stranded the
        queue, a later ``close()`` with no timeout polls the wedged
        worker instead of joining it forever (the caller already opted
        into bounded shutdown).
        """
        with self._cond:
            self._closed = True
            already_stranded = self._close_stranded
            self._cond.notify_all()
        if already_stranded and timeout is None:
            # A prior bounded close already timed out and failed every
            # queued request; an unbounded join now (e.g. the ``with``
            # block exiting after a failed close(timeout=...)) would
            # reintroduce exactly the hang that close was bounded to
            # avoid. Poll the wedged worker instead of waiting on it.
            timeout = 0.0
        self._thread.join(timeout)
        if not self._thread.is_alive():
            return True
        if already_stranded:
            return False
        with self._cond:
            self._close_stranded = True
            stranded = list(self._pending)
            self._pending.clear()
            self._stats["shutdown_stranded"] += len(stranded)
            self._cond.notify_all()
        logger.error(
            "serve queue close(): drain did not finish in %.3fs; "
            "failing %d still-queued request(s) with ShutdownError",
            timeout, len(stranded))
        exc = ShutdownError(
            f"serve queue drain exceeded its {timeout}s close timeout; "
            "request abandoned before dispatch")
        for r in stranded:
            r.future.set_exception(exc)
            _record_request(r, "shutdown")
        if self.slo_tracker is not None:
            self.slo_tracker.observe_errors(len(stranded))
        return False

    def reset_breaker(self) -> None:
        """Re-arm a tripped dispatch circuit breaker (operator action
        after the underlying failure — bad model reload, device loss —
        is addressed)."""
        with self._cond:
            self._breaker_open = False
            self._consecutive_failures = 0
            self._cond.notify_all()

    @contextlib.contextmanager
    def quiesce(self):
        """Pause dispatch for the duration of the block — the swap
        window ``CoefficientTables.rebuild_from`` needs.

        Entering waits out any in-flight batch; while held, the worker
        parks BEFORE popping (no request is dispatched, none is
        dropped — producers keep queueing against the normal
        backpressure bound). Exiting resumes dispatch. Not reentrant;
        ``close()`` overrides a held pause so shutdown still drains."""
        with self._cond:
            self._paused = True
            while self._dispatching:
                self._cond.wait()
        try:
            yield self
        finally:
            with self._cond:
                self._paused = False
                self._cond.notify_all()

    def _adopt_programs_locked(self, programs) -> None:
        """Rebind the queue to a new generation's ``ScorePrograms``
        (caller holds ``_cond`` AND the quiesce pause — the worker is
        parked, so no dispatch can straddle generations). Per-coordinate
        counters and hotness sketches carry over where the coordinate
        survives the structure change and start fresh where it doesn't."""
        from photon_tpu.obs.monitor import SpaceSavingSketch

        self.programs = programs  # photon: ignore[unlocked-shared-write] -- reload_model's adopt callback holds _cond (the _locked suffix is the calling convention)
        self.max_batch = min(self.max_batch, programs.ladder.max_batch)
        random_tables = getattr(
            getattr(programs, "tables", None), "random", None
        ) or {}
        self._coord_stats = {  # photon: ignore[unlocked-shared-write] -- reload_model's adopt callback holds _cond (see docstring)
            name: self._coord_stats.get(
                name, {"entity_lookups": 0, "cold_lookups": 0}
            )
            for name in random_tables
        }
        self._re_types = {  # photon: ignore[unlocked-shared-write] -- same: caller holds _cond
            name: t.random_effect_type
            for name, t in random_tables.items()
        }
        self.hotness = {  # photon: ignore[unlocked-shared-write] -- same: caller holds _cond
            name: self.hotness.get(name)
            or SpaceSavingSketch(self._hotness_k)
            for name in random_tables
        }

    def reload_model(self, model) -> dict:
        """Hot-swap a refreshed ``GameModel`` into the LIVE queue.

        Values-only delta (the daily-retrain case): the tables' in-place
        reference swap — safe against live dispatch, zero recompiles,
        no pause. Structure change: the full ``rebuild_from`` dance —
        new tables + AOT ladder compiled off-path while the old
        generation keeps serving, then tables AND the queue's program
        binding swap inside one ``quiesce`` window. Either way no
        queued request is dropped. Returns
        ``{"values_only", "generation", "programs_compiled"}``."""
        from photon_tpu.serve.tables import CoefficientTables

        tables = self.programs.tables
        # Build the candidate at the LIVE precision: a bf16-serving
        # queue reloading an f32-trained model must stay values-only.
        new = CoefficientTables.from_game_model(model, tables.precision)
        if tables._values_only_delta(new):
            tables._reload_built(new)
            return {
                "values_only": True,
                "generation": tables.generation,
                "programs_compiled": 0,
            }

        def adopt(new_programs):
            with self._cond:
                self._adopt_programs_locked(new_programs)

        new_programs = tables.rebuild_from(
            model,
            programs=self.programs,
            quiesce=self.quiesce,
            adopt=adopt,
            prebuilt=new,
        )
        return {
            "values_only": False,
            "generation": tables.generation,
            "programs_compiled": new_programs.stats["programs_compiled"],
        }

    def __enter__(self) -> "MicroBatchQueue":
        return self

    def __exit__(self, *exc) -> None:
        self.close(self.close_timeout_s)

    def stats(self) -> dict:
        """Snapshot of the queue counters (+ derived fill/cold rates,
        per-coordinate cold counters)."""
        with self._cond:
            snap = dict(self._stats)
            snap["queued_now"] = len(self._pending)
            per_coord = {
                nm: dict(cs) for nm, cs in self._coord_stats.items()
            }
        for nm, cs in per_coord.items():
            cs["cold_entity_rate"] = (
                round(cs["cold_lookups"] / cs["entity_lookups"], 4)
                if cs["entity_lookups"]
                else None
            )
        snap["per_coordinate"] = per_coord
        if snap["batches"]:
            snap["batch_fill_fraction"] = round(
                snap["batched_requests"]
                / (snap["batches"] * self.max_batch),
                4,
            )
            snap["mean_batch_size"] = round(
                snap["batched_requests"] / snap["batches"], 2
            )
        else:
            snap["batch_fill_fraction"] = None
            snap["mean_batch_size"] = None
        snap["cold_entity_rate"] = (
            round(snap["cold_lookups"] / snap["entity_lookups"], 4)
            if snap["entity_lookups"]
            else None
        )
        # Fraction of host pack (pad/stack/code-resolution) time that
        # the pipelined worker hid behind an in-flight device dispatch.
        # 0 on the serial worker; None before any batch packed.
        snap["staging_overlap_fraction"] = (
            round(
                snap["staging_overlapped_seconds"]
                / snap["staging_seconds"],
                4,
            )
            if snap["staging_seconds"] > 0
            else None
        )
        return snap

    def health(self) -> dict:
        """One consistent degraded-mode snapshot: queue depth, breaker
        state, shed/deadline/error/retry counters, and the coefficient
        tables' reload generation — what a load balancer's health probe
        (and ``cli.serve``) reads."""
        with self._cond:
            per_coord = {
                nm: dict(cs) for nm, cs in self._coord_stats.items()
            }
            snap = {
                "queue_depth": len(self._pending),
                "closed": self._closed,
                "breaker_open": self._breaker_open,
                "consecutive_failures": self._consecutive_failures,
                "requests": self._stats["requests"],
                "shed": self._stats["shed"],
                "deadline_expired": self._stats["deadline_expired"],
                "dispatch_errors": self._stats["dispatch_errors"],
                "dispatch_retries": self._stats["dispatch_retries"],
                "breaker_trips": self._stats["breaker_trips"],
                "breaker_rejected": self._stats["breaker_rejected"],
                "shutdown_stranded": self._stats["shutdown_stranded"],
                "staged_batches": self._stats["staged_batches"],
                "staging_overlap_fraction": (
                    round(
                        self._stats["staging_overlapped_seconds"]
                        / self._stats["staging_seconds"],
                        4,
                    )
                    if self._stats["staging_seconds"] > 0
                    else None
                ),
            }
        snap["pipeline_staging"] = self.pipeline_staging
        snap["max_queue"] = self.max_queue
        snap["shed_watermark"] = self.shed_watermark
        snap["breaker_threshold"] = self.breaker_threshold
        snap["default_deadline_s"] = self.default_deadline_s
        snap["table_generation"] = getattr(
            self.programs.tables, "generation", 0
        )
        # Live-monitoring block (obs/monitor.py): sliding-window
        # latency quantiles — the LAST N seconds, not the whole run —
        # per-coordinate cold rates (copied under the same _cond hold
        # as the rest of the snapshot), and the declared-SLO burn
        # report. The ring and the tracker snapshot under their own
        # locks, outside _cond.
        window = self.latency.quantiles_ms()
        window["window_seconds"] = (
            self.latency.window_s * self.latency.num_windows
        )
        snap["window_latency"] = window
        snap["cold_entity_rate_by_coordinate"] = {
            nm: (
                round(cs["cold_lookups"] / cs["entity_lookups"], 4)
                if cs["entity_lookups"] else None
            )
            for nm, cs in per_coord.items()
        }
        if self.slo_tracker is not None:
            snap["slo"] = self.slo_tracker.report()
        return snap

    def hotness_top(self, n: int = 10) -> dict:
        """Per-coordinate top-``n`` hottest entities (space-saving
        sketch: counts overestimate by at most their recorded error) —
        the shard/cache-planning signal of ROADMAP items 1 and 4."""
        return {
            nm: sketch.top(n) for nm, sketch in self.hotness.items()
        }

    def metrics_families(self) -> list[dict]:
        """The queue's ``/metrics`` collector (register with
        ``MonitorServer(collectors=[queue.metrics_families])``): live
        depth/breaker gauges, per-coordinate cold counters, the
        windowed-latency histogram + quantile gauges, hotness top-K,
        and the SLO burn gauges. Every number is copied under its own
        surface's lock and rendered lockless."""
        from photon_tpu.obs import monitor

        with self._cond:
            depth = len(self._pending)
            breaker = self._breaker_open
            closed = self._closed
            stats = dict(self._stats)
            per_coord = {
                nm: dict(cs) for nm, cs in self._coord_stats.items()
            }
        fams = [
            monitor.family(
                "serve_queue_depth_live", "gauge",
                "requests queued at scrape time", [("", {}, depth)],
            ),
            monitor.family(
                "serve_breaker_open_live", "gauge",
                "1 when the dispatch circuit breaker is open",
                [("", {}, float(breaker))],
            ),
            monitor.family(
                "serve_queue_closed", "gauge",
                "1 once close() was called", [("", {}, float(closed))],
            ),
            monitor.family(
                "serve_queue_requests_total", "counter",
                "requests accepted by the queue",
                [("", {}, float(stats["requests"]))],
            ),
            monitor.family(
                "serve_staging_overlap_fraction", "gauge",
                "fraction of host pad/stack time overlapped with "
                "in-flight device dispatch by the pipelined worker",
                [(
                    "", {},
                    (
                        stats["staging_overlapped_seconds"]
                        / stats["staging_seconds"]
                    )
                    if stats["staging_seconds"] > 0
                    else 0.0,
                )],
            ),
            monitor.family(
                "serve_staged_batches_total", "counter",
                "batches popped and host-packed ahead of dispatch",
                [("", {}, float(stats["staged_batches"]))],
            ),
            monitor.family(
                "serve_queue_events_total", "counter",
                "degraded-mode queue events by kind",
                [
                    ("", {"kind": k}, float(stats[k]))
                    for k in (
                        "shed", "deadline_expired", "dispatch_errors",
                        "dispatch_retries", "breaker_trips",
                        "breaker_rejected", "shutdown_stranded",
                    )
                ],
            ),
            monitor.family(
                "serve_entity_lookups_total", "counter",
                "entity lookups per random-effect coordinate",
                [
                    ("", {"coordinate": nm}, float(cs["entity_lookups"]))
                    for nm, cs in sorted(per_coord.items())
                ],
            ),
            monitor.family(
                "serve_cold_entity_lookups_total", "counter",
                "cold (out-of-vocabulary) lookups per coordinate",
                [
                    ("", {"coordinate": nm}, float(cs["cold_lookups"]))
                    for nm, cs in sorted(per_coord.items())
                ],
            ),
            self.latency.prometheus_family(
                "serve_request_latency_window_seconds",
                "submit-to-scatter latency over the sliding window "
                f"(last {self.latency.window_s * self.latency.num_windows:g}s)",
            ),
        ]
        quantiles = self.latency.quantiles_ms()
        fams.append(
            monitor.family(
                "serve_request_latency_window_ms", "gauge",
                "sliding-window latency quantiles, milliseconds",
                [
                    ("", {"quantile": str(int(q[1:q.index('_')]) / 100)}, v)
                    for q, v in quantiles.items()
                    if q.startswith("p") and v is not None
                ],
            )
        )
        hot_samples = [
            ("", {"coordinate": nm, "entity": item["key"]},
             float(item["count"]))
            for nm, items in sorted(self.hotness_top(10).items())
            for item in items
        ]
        if hot_samples:
            fams.append(
                monitor.family(
                    "serve_hot_entity_requests", "gauge",
                    "space-saving sketch count per hot entity "
                    "(overestimates by at most the sketch error)",
                    hot_samples,
                )
            )
        if self.slo_tracker is not None:
            fams.extend(self.slo_tracker.prometheus_families())
        return fams

    # -- worker side ------------------------------------------------------

    def _expire_locked(self) -> list[_Request]:
        """Pull every pending request whose deadline has passed (caller
        holds ``_cond``; the returned requests are resolved OUTSIDE the
        lock). Skipped entirely until a deadline-bearing request has
        ever been submitted."""
        if not self._has_deadlines or not self._pending:
            return []
        now = time.perf_counter()
        expired = [
            r for r in self._pending
            if r.deadline is not None and now >= r.deadline
        ]
        if expired:
            self._pending = collections.deque(  # photon: ignore[unlocked-shared-write] -- _expire_locked is called only from _take_batch's `with self._cond` scope (the _locked suffix is the calling convention)
                r for r in self._pending
                if r.deadline is None or now < r.deadline
            )
            self._stats["deadline_expired"] += len(expired)  # photon: ignore[unlocked-shared-write] -- same: caller holds _cond (see _expire_locked docstring)
            self._cond.notify_all()  # space freed: wake producers
        return expired

    def _take_batch(self):
        """Block for the next batch per the flush policy.

        Runs on the worker thread. Returns
        ``(batch, expired, depth, breaker_open)``: ``batch`` is None
        when the queue closed AND drained (exit), possibly-empty when
        only expirations happened this round; ``expired`` requests
        failed their deadline while queued and must be resolved by the
        caller (outside the lock), BEFORE any device work is spent on
        the batch; ``depth``/``breaker_open`` are sampled under the
        same lock hold so the worker's wakeup gauges cost no extra
        acquisition.
        """
        with self._cond:
            while True:
                # Quiesced: park WITHOUT popping — requests keep
                # queueing (backpressure holds) while reload_model
                # swaps the program generation. close() overrides the
                # pause so a quiesced queue still drains on shutdown.
                while self._paused and not self._closed:
                    self._cond.wait()
                expired = self._expire_locked()
                if self._pending:
                    linger_end = (
                        self._pending[0].enqueued_at + self.max_linger_s
                    )
                    while (
                        len(self._pending) < self.max_batch
                        and not self._closed
                        and not self._paused
                    ):
                        # The linger is cut short by request deadlines:
                        # a deadline that would lapse mid-linger flushes
                        # the batch _DEADLINE_FLUSH_SLACK_S early so the
                        # request DISPATCHES in time instead of expiring
                        # on an idle device (linger 200ms + deadline
                        # 100ms must serve, not fail 100%).
                        flush_at = linger_end
                        if self._has_deadlines:
                            earliest = min(
                                (r.deadline for r in self._pending
                                 if r.deadline is not None),
                                default=None,
                            )
                            if earliest is not None:
                                flush_at = min(
                                    flush_at,
                                    earliest - _DEADLINE_FLUSH_SLACK_S,
                                )
                        remaining = flush_at - time.perf_counter()
                        if remaining <= 0:
                            break
                        self._cond.wait(timeout=remaining)
                    # A quiesce can begin WHILE the worker lingers (the
                    # pause check at the loop top is behind us): popping
                    # now would dispatch the old ladder against a
                    # mid-swap table generation. Re-park before taking
                    # anything — the pop below must only ever run with
                    # the pause flag observed clear under this lock.
                    # Already-pulled expirations are handed back first
                    # (their futures must resolve, pause or not).
                    if self._paused and not self._closed:
                        if expired:
                            return (
                                [], expired,
                                len(self._pending), self._breaker_open,
                            )
                        continue
                    # Deadlines may have lapsed during the linger wait;
                    # a request must never reach dispatch already dead.
                    expired.extend(self._expire_locked())
                    batch = [
                        self._pending.popleft()
                        for _ in range(
                            min(len(self._pending), self.max_batch)
                        )
                    ]
                    if batch:
                        self._stats["batches"] += 1
                        self._stats["batched_requests"] += len(batch)
                        # Pinned under the SAME lock hold that popped
                        # the batch: a quiescer entering now waits for
                        # this dispatch to finish — there is no window
                        # where a popped batch is invisible to quiesce.
                        self._dispatching = True
                        from photon_tpu import obs

                        if obs.enabled():
                            # submit→take is the queue_wait segment of
                            # every batched request's span tree.
                            now = time.perf_counter()
                            for r in batch:
                                r.take_ts = now
                    self._cond.notify_all()  # space freed: wake producers
                    return (
                        batch, expired,
                        len(self._pending), self._breaker_open,
                    )
                if self._closed or expired:
                    return (
                        (None if self._closed else []), expired,
                        len(self._pending), self._breaker_open,
                    )
                self._cond.wait()

    def _resolve_expired(self, expired: list[_Request]) -> None:
        """Fail a round's deadline-expired requests (worker thread,
        OUTSIDE the lock — resolution runs user callbacks). Shared by
        the serial take path and the staging pre-pop."""
        from photon_tpu import obs

        if not expired:
            return
        exc = DeadlineExceededError(
            "request deadline expired while queued; failed "
            "fast before dispatch")
        for r in expired:
            r.future.set_exception(exc)
            _record_request(r, "expired")
        if self.slo_tracker is not None:
            self.slo_tracker.observe_errors(len(expired))
        if obs.enabled():
            obs.REGISTRY.counter(
                "serve_deadline_expired_total"
            ).inc(len(expired))

    def _pop_staged(self) -> "_Staged | None":
        """Claim the staged batch, if any (worker thread). Parks while
        quiesced — same gate as ``_take_batch`` — so a staged batch can
        never dispatch inside a reload's swap window; ``_dispatching``
        flips True under the SAME lock hold that claims the batch, so
        quiesce waits out a claimed-but-not-yet-dispatched batch
        exactly as it waits out an in-flight one."""
        with self._cond:
            while self._paused and not self._closed:
                self._cond.wait()
            staged = self._staged
            if staged is None:
                return None
            self._staged = None
            self._dispatching = True
            self._cond.notify_all()
            return staged

    def _stage_next(self) -> float:
        """Pop + host-pack the NEXT batch while the current batch's
        dispatch is in flight (called from ``_dispatch`` on the worker
        thread, after ``dispatch_padded`` and before the fetch).
        Returns the seconds of pack work overlapped with the device —
        ``fetch_padded`` subtracts them from its ledger window so the
        overlap cannot inflate the serve rows' vs_roofline. No-ops
        (returns 0.0) when a staged batch already exists (a dispatch
        retry re-entered), when quiesced (the swap window must not see
        popped-but-undispatched requests pile up), or when nothing is
        pending. Pops with the same bookkeeping as ``_take_batch`` —
        expiry scan first, batches/batched_requests counters, take_ts
        stamps — but never lingers: the staging pop only fires when the
        device is already busy, so waiting for a fuller batch would
        waste exactly the overlap window this path exists to use."""
        from photon_tpu import obs

        with self._cond:
            if self._staged is not None or self._paused:
                return 0.0
            expired = self._expire_locked()
            # Pop only what the flush policy would already release — a
            # full batch, a head request whose linger lapsed (it has
            # been waiting at least as long as _take_batch would have
            # let it), or a closing queue's drain. Anything younger
            # keeps accumulating toward a fuller batch; the worker
            # falls back to the lingering _take_batch after the fetch,
            # so no request waits longer than the serial policy allows.
            flush = bool(self._pending) and (
                len(self._pending) >= self.max_batch
                or self._closed
                or (
                    self._pending[0].enqueued_at + self.max_linger_s
                    <= time.perf_counter()
                )
            )
            reqs = (
                [
                    self._pending.popleft()
                    for _ in range(
                        min(len(self._pending), self.max_batch)
                    )
                ]
                if flush
                else []
            )
            if reqs:
                self._stats["batches"] += 1
                self._stats["batched_requests"] += len(reqs)
                self._stats["staged_batches"] += 1
                if obs.enabled():
                    now = time.perf_counter()
                    for r in reqs:
                        r.take_ts = now
                self._cond.notify_all()  # space freed: wake producers
        self._resolve_expired(expired)
        if not reqs:
            return 0.0
        t0 = time.perf_counter()
        try:
            packed = self.programs.pack_requests(
                [(r.features, r.entity_ids) for r in reqs]
            )
        except Exception:  # noqa: BLE001 — staging is an optimization:
            # a pack failure here (malformed request) must surface on
            # the DISPATCH path where the retry/breaker machinery and
            # the batch's futures handle it, not kill the in-flight
            # batch's fetch. _dispatch re-packs when packed is None.
            packed = None
        dt = time.perf_counter() - t0
        with self._cond:
            self._staged = _Staged(reqs, packed, self.programs)
            self._stats["staging_seconds"] += dt
            self._stats["staging_overlapped_seconds"] += dt
            self._cond.notify_all()
        return dt

    def _worker(self) -> None:
        from photon_tpu import obs

        while True:
            # A staged batch (popped + packed while the previous
            # dispatch was in flight) goes first: its requests are
            # already off the pending deque, so _take_batch cannot see
            # them — and close() must drain them before the None exit.
            staged = self._pop_staged()
            if staged is not None:
                try:
                    self._dispatch(staged.requests, staged=staged)
                finally:
                    with self._cond:
                        self._dispatching = False
                        self._cond.notify_all()
                continue
            # depth/breaker ride out of the lock hold _take_batch
            # already has — no second _cond acquisition per wakeup.
            batch, expired, depth, breaker = self._take_batch()
            if obs.enabled():
                # Queue-pressure sampling on EVERY worker wakeup: the
                # depth gauge and breaker state land in the metrics
                # registry (where /metrics reads them) — not just in
                # the end-of-run health() snapshot. The trace counter
                # TRACK is fed from _dispatch (one sample per batch).
                obs.REGISTRY.gauge("serve_queue_depth").set(depth)
                obs.REGISTRY.gauge("serve_breaker_open").set(
                    float(breaker)
                )
            self._resolve_expired(expired)
            if batch is None:
                return
            if batch:
                try:
                    self._dispatch(batch)
                finally:
                    with self._cond:
                        self._dispatching = False
                        self._cond.notify_all()

    def _dispatch(self, batch: list[_Request],
                  staged: "_Staged | None" = None) -> None:
        """Pad, score, scatter — outside the lock (producers keep
        queuing while XLA runs). Runs on the worker thread only.
        ``staged`` carries a batch ``_stage_next`` already host-packed
        during the previous dispatch; its operands are reused when the
        program generation still matches, re-packed otherwise (a
        structure reload swapped the vocabulary out from under them).
        On the pipelined path the dispatch is split — enqueue the
        device work (``dispatch_padded``), host-pack the NEXT batch
        while it runs, then fetch — with the overlapped pack seconds
        excluded from the ledger's device window. Transient failures
        retry with backoff (``dispatch_retry``); anything else fans out
        to THIS batch's futures and feeds the circuit breaker's
        consecutive-failure count."""
        from photon_tpu import obs

        t0 = time.perf_counter()
        # Segment stamps for the request span trees (take→dispatch is
        # batch_fill, dispatch→scatter is the device round trip). A
        # retried dispatch keeps the LAST attempt's stamps — the one
        # that produced the scores the requests were served from.
        dispatch_ts = scatter_ts = None

        def attempt():
            nonlocal dispatch_ts, scatter_ts
            if (
                staged is not None
                and staged.packed is not None
                and staged.programs is self.programs
            ):
                # Packed while the previous batch was in flight — the
                # whole point of the staging pipeline. Valid because a
                # values-only reload keeps the programs object and a
                # structure reload fails the identity check above.
                feats, codes, _rung = staged.packed
            else:
                pack_t0 = time.perf_counter()
                feats, codes, _rung = self.programs.pack_requests(
                    [(r.features, r.entity_ids) for r in batch]
                )
                # Un-overlapped pack time (first batch of a burst, a
                # re-pack after a structure reload, or the serial
                # worker): counted in staging_seconds so the overlap
                # fraction's denominator is ALL pack work, not just
                # the part the pipeline managed to hide.
                pack_dt = time.perf_counter() - pack_t0
                with self._cond:
                    self._stats["staging_seconds"] += pack_dt
            # Cold lookups PER COORDINATE (codes are keyed by
            # coordinate, each resolved against its own vocabulary):
            # the aggregate hides a cold coordinate when two
            # coordinates share a re_type with different coverage.
            cold_by_coord = {
                nm: int(np.sum(vec[: len(batch)] < 0))
                for nm, vec in codes.items()
            }
            dispatch_ts = time.perf_counter()
            dp = getattr(self.programs, "dispatch_padded", None)
            with obs.span("serve/batch"):
                if self.pipeline_staging and dp is not None:
                    handle = dp(feats, codes, len(batch))
                    # Device is busy: pop + pack batch k+1 NOW. The
                    # returned pack seconds are excluded from the
                    # fetch's ledger window (satellite: overlap must
                    # not inflate vs_roofline on serve rows).
                    overlap = self._stage_next()
                    scores = self.programs.fetch_padded(
                        handle, exclude_seconds=overlap
                    )
                else:
                    # Serial fallback: pipelining off, or a programs
                    # object without the split dispatch/fetch API.
                    scores = self.programs.score_padded(
                        feats, codes, len(batch)
                    )
            scatter_ts = time.perf_counter()
            return cold_by_coord, len(codes) * len(batch), scores

        def on_retry(attempt_no, exc):
            with self._cond:
                self._stats["dispatch_retries"] += 1
            if obs.enabled():
                obs.REGISTRY.counter("serve_dispatch_retries_total").inc()

        try:
            if self.dispatch_retry is not None:
                cold_by_coord, lookups, scores = _retry.retrying_check(
                    "serve.dispatch", attempt,
                    site="serve.dispatch",
                    policy=self.dispatch_retry,
                    on_retry=on_retry,
                )
            else:
                from photon_tpu.resilience import faults

                faults.check("serve.dispatch")
                cold_by_coord, lookups, scores = attempt()
        except Exception as exc:  # noqa: BLE001 — fan out to the waiters
            drained: list[_Request] = []
            with self._cond:
                self._stats["dispatch_errors"] += 1
                self._consecutive_failures += 1
                tripped = (
                    self.breaker_threshold is not None
                    and not self._breaker_open
                    and self._consecutive_failures
                    >= self.breaker_threshold
                )
                if tripped:
                    self._breaker_open = True
                    self._stats["breaker_trips"] += 1
                    drained = list(self._pending)
                    self._pending.clear()
                    # The staged batch is popped off the deque but not
                    # yet dispatched — its futures would strand if only
                    # the deque drained.
                    if self._staged is not None:
                        drained.extend(self._staged.requests)
                        self._staged = None
                    self._cond.notify_all()
            for r in batch:
                r.future.set_exception(exc)
                _record_request(
                    r, "error", error=type(exc).__name__,
                    batch_size=len(batch),
                )
            if tripped:
                logger.error(
                    "serve dispatch circuit breaker OPEN after %d "
                    "consecutive batch failure(s) (last: %r); drained "
                    "%d queued request(s)",
                    self._consecutive_failures, exc, len(drained))
                drain_exc = CircuitOpenError(
                    "serve dispatch circuit breaker opened while this "
                    f"request was queued (last failure: {exc!r})")
                for r in drained:
                    r.future.set_exception(drain_exc)
                    _record_request(r, "breaker")
                if obs.enabled():
                    obs.REGISTRY.counter("serve_breaker_trips_total").inc()
                    obs.trace.instant(
                        "serve.breaker_open", cat="serve",
                        consecutive_failures=self._consecutive_failures,
                        drained=len(drained),
                    )
            if self.slo_tracker is not None:
                self.slo_tracker.observe_errors(len(batch) + len(drained))
            return
        # Model/data-health tap (obs/health.py; off by default): fold a
        # bounded sample of batches — raw request features + served
        # scores — into the serve-side sketch, the train/serve-skew and
        # score-distribution evidence the pilot's health gate compares
        # against the ingest sketch. Outside the queue lock (the tap
        # has its own leaf lock; obs-health CONCURRENCY_AUDIT), host
        # numpy only — the audited `health` contract pins zero impact
        # on the traced score programs.
        from photon_tpu.obs import health as _health

        if _health.enabled():
            try:
                _health.observe_serve_batch(
                    [r.features for r in batch], np.asarray(scores),
                    # Spec widths size the sparse per-feature moments
                    # to the SERVING feature space (vocabulary width),
                    # so the serve-side sketch aligns with the training
                    # sketch's moments instead of being pinned by the
                    # first sampled batch's max index.
                    widths={
                        s: self.programs.specs[s].d
                        for s in self.programs.shard_order
                    },
                )
            except Exception:  # noqa: BLE001 — telemetry must never
                # alter serving semantics: this runs on the ONE worker
                # thread with the batch already scored but its futures
                # not yet resolved; a raising tap (one malformed
                # request's feature dict) would strand the waiters AND
                # kill the worker. Same policy as validators'
                # _record_failure and the pilot's gauge export.
                logger.exception("serve health tap failed; continuing")
        cold = sum(cold_by_coord.values())
        with self._cond:
            self._consecutive_failures = 0
            self._stats["cold_lookups"] += cold
            self._stats["entity_lookups"] += lookups
            for nm, c in cold_by_coord.items():
                cs = self._coord_stats[nm]
                cs["entity_lookups"] += len(batch)
                cs["cold_lookups"] += c
            batch_no = self._stats["batches"]
            depth = len(self._pending)
        # Hotness sketches + SLO lookup budget: outside the queue lock
        # (each surface has its own lock; obs-monitor CONCURRENCY_AUDIT).
        for nm in cold_by_coord:
            sketch = self.hotness[nm]
            rt = self._re_types[nm]
            for r in batch:
                key = r.entity_ids.get(rt)
                if key is not None:
                    sketch.observe(key)
        if self.slo_tracker is not None:
            self.slo_tracker.observe_lookups(lookups, cold)
        if obs.enabled():
            obs.REGISTRY.counter("serve_requests_total").inc(len(batch))
            obs.REGISTRY.counter("serve_batches_total").inc()
            if lookups:
                obs.REGISTRY.counter("serve_cold_lookups_total").inc(cold)
            obs.REGISTRY.histogram("serve_batch_fill").observe(
                len(batch) / self.max_batch
            )
            obs.REGISTRY.histogram("serve_batch_seconds").observe(
                time.perf_counter() - t0
            )
            # Queue depth after each batch: a counter track on the
            # exported timeline (how the backlog breathes under load).
            obs.trace.counter("serve_queue_depth", depth)
        for r, s in zip(batch, scores):
            # Submit→scatter is the request's SERVICE latency — the
            # number the rolling window ring and the latency SLO judge.
            # Measured BEFORE resolution so a slow driver done-callback
            # can never inflate the served tail.
            latency = scatter_ts - r.enqueued_at
            self.latency.observe(latency)
            if self.slo_tracker is not None:
                self.slo_tracker.observe_request(latency)
            r.future.set_result(float(s))
            # done_ts lands AFTER resolution: scatter→done covers the
            # result fan-out including the driver's done-callbacks.
            _record_request(
                r, "served",
                dispatch_ts=dispatch_ts, scatter_ts=scatter_ts,
                batch=batch_no, batch_size=len(batch),
            )
