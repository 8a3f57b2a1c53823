"""Pipelined ingest: executors, stage accounting, and chunked transfer.

The reference's ingest is a cluster-wide shuffle pipeline
(RandomEffectDataset.scala's groupBy/foldByKey); ours is a host-side numpy
planning pass feeding one packed device transfer and one AOT compile. Run
serially those three phases ADD (bench round 5: ``e2e_seconds =
ingest_seconds + compile_seconds``, and the planner fell below the 1M
rows/s ingest floor). This module owns the machinery that overlaps them:

- **Planning executors** (``plan_executor`` / ``chunk_executor``): the
  per-coordinate planning passes run concurrently (the hot numpy ops —
  radix argsort, bincount, fancy gathers — release the GIL), and
  within-coordinate elementwise passes chunk over rows
  (``map_chunked`` / ``bincount_chunked`` — exact, order-preserving, so
  results are BIT-IDENTICAL to the serial path; the deterministic
  reservoir hash order is the contract). ``group_rows`` groups the rows
  by entity by counting, the stable permutation a sort would give. Two
  separate pools: coordinate tasks block on their own chunk tasks, so
  running both levels on one bounded pool could deadlock (all workers
  waiting on queued chunks).
- **Chunked double-buffered transfer** (``packed_device_put``): the single
  packed plan buffer is pushed as granule-aligned chunks with each
  ``jax.device_put`` enqueued ASYNCHRONOUSLY while the host fills the
  next chunk's staging buffer, then fused into the one contiguous buffer
  by an in-trace concatenate (peak device memory is ~2x the buffer
  until the chunks are dropped: they cannot be donated into an output
  larger than each of them). Small builds (below one chunk) take
  the legacy single-shot path — byte-identical layout either way.
- **PIPELINE_STATS**: per-stage seconds (plan / pack / transfer /
  compile / compile_wait) + the measured compile-overlap fraction, reset
  per prepare; the benchmark's ``ingest.*`` metrics read its report.

``PHOTON_TPU_SERIAL_INGEST=1`` forces everything back to the serial
in-line path (the determinism property tests diff the two);
``PHOTON_TPU_INGEST_THREADS`` bounds the chunk pool (CI uses 2).
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np

logger = logging.getLogger(__name__)

# Program contract (audited by `python -m photon_tpu.analysis --semantic`;
# machinery in analysis/program.py): the ingest pipeline's AOT warm-compile
# entry must trace EXACTLY the programs the production fused fit runs — the
# skeleton-predicted materialize/fit jaxprs match the real generation's
# signatures (dispatch census unchanged: warm compile adds ZERO programs),
# and the overlap window introduces no host callback into either jaxpr.
PROGRAM_AUDIT = dict(
    name="ingest-pipeline",
    entry="data.pipeline + estimators.game_estimator._warm_compile "
    "(AOT warm compile from predicted shapes)",
    builder="build_ingest_pipeline",
    max_programs=2,
    stable_under=("aot_warm_compile",),
    hot_loop=True,
)

# Host-concurrency contract (audited by `python -m photon_tpu.analysis
# --concurrency`; machinery in analysis/concurrency.py). The threading
# model: `_Pool._lock` guards lazy pool construction/teardown;
# `PipelineStats._stats_lock` guards every accounting map plus the
# generation counter (worker threads in all three pools write stages
# concurrently with the training thread's reset). The two locks carry
# DISTINCT terminal names on purpose: the auditor identifies locks by
# terminal name within a module (and flags ambiguity), which is what
# keeps its lock-order and lockset checks sound here. Chunk thunks
# (`map_chunked.run`) are pure numpy over disjoint row spans — no JAX
# dispatch off-thread here; the AOT compile thread's dispatch is
# declared (with its reason) in game_estimator's contract, next to
# `_warm_compile` itself. `_concat_cache` is deliberately NOT
# lock-guarded: it is written only from the single thread that runs
# `packed_device_put`, and the worst case of a future race is one
# duplicate jit wrapper, never corruption.
CONCURRENCY_AUDIT = dict(
    name="ingest-pipeline",
    locks={
        "_Pool._lock": ("_Pool._pool",),
        "PipelineStats._stats_lock": (
            "PipelineStats._generation",
            "PipelineStats._seconds",
            "PipelineStats._spans",
            "PipelineStats._counts",
        ),
    },
    thread_entries=("map_chunked.run", "group_rows.sort_part"),
    jax_dispatch_ok={},
)


def serial_ingest() -> bool:
    """True when the serial reference path is forced (env contract)."""
    return os.environ.get("PHOTON_TPU_SERIAL_INGEST", "") == "1"


def ingest_threads() -> int:
    raw = os.environ.get("PHOTON_TPU_INGEST_THREADS", "")
    if raw.isdigit() and int(raw) > 0:
        return int(raw)
    return min(8, os.cpu_count() or 1)


# Minimum rows before an elementwise pass is worth chunking across
# threads: below this the submit/join overhead exceeds the work.
_CHUNK_MIN_ROWS = 1 << 19
_TRANSFER_GRANULE_ELEMS = (4 << 20) // 4  # 4 MiB of int32 elements


def transfer_chunk_elems() -> int:
    """Transfer chunk size in int32 elements (PHOTON_TPU_TRANSFER_CHUNK_MB,
    default 64 MiB), rounded up to the packed buffer's 4 MiB granule so
    every chunk but the last has one recurring transfer shape."""
    raw = os.environ.get("PHOTON_TPU_TRANSFER_CHUNK_MB", "")
    mb = int(raw) if raw.isdigit() and int(raw) > 0 else 64
    elems = (mb << 20) // 4
    g = _TRANSFER_GRANULE_ELEMS
    return max(-(-elems // g) * g, g)


class _Immediate(Future):
    """Already-resolved future for the serial in-line path."""

    def __init__(self, result=None, exc=None):
        super().__init__()
        if exc is not None:
            self.set_exception(exc)
        else:
            self.set_result(result)


class _Pool:
    """Lazy thread pool that degrades to in-line execution when serial
    ingest is forced (or only one worker would exist)."""

    def __init__(self, name: str, workers):
        self._name = name
        self._workers = workers  # int or callable () -> int
        self._pool: ThreadPoolExecutor | None = None
        self._lock = threading.Lock()

    def _resolve_workers(self) -> int:
        w = self._workers
        return w() if callable(w) else w

    def submit(self, fn, *args, **kwargs) -> Future:
        if serial_ingest() or self._resolve_workers() <= 1:
            try:
                return _Immediate(fn(*args, **kwargs))
            except Exception as exc:  # noqa: BLE001 — parity with Future
                return _Immediate(exc=exc)
        with self._lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=self._resolve_workers(),
                    thread_name_prefix=self._name,
                )
            # Submit INSIDE the lock: shutdown() swaps the pool out
            # under this lock before shutting it down, so a submit that
            # escaped the critical section could land on an executor
            # already past shutdown ("cannot schedule new futures").
            # Executor.submit is a quick enqueue; the blocking
            # shutdown(wait=True) stays outside the lock.
            return self._pool.submit(fn, *args, **kwargs)

    def shutdown(self) -> None:
        with self._lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)


# Coordinate-level planning tasks (each may block on its own chunk tasks,
# hence the separate pool) and one background slot for the AOT warm
# compile (XLA compiles in C++ with the GIL released).
plan_executor = _Pool("photon-plan", 4)
chunk_executor = _Pool("photon-chunk", ingest_threads)
compile_executor = _Pool("photon-compile", 2)


def reset_executors() -> None:
    """Drop pools so the next use re-reads the env (tests).

    Nested try/finally: a shutdown that raises (an interpreter tearing
    down, a worker's late exception surfacing in join) must still shut
    the remaining pools down — leaking the chunk or compile pool after
    a failed plan-pool shutdown strands daemon-less workers."""
    try:
        plan_executor.shutdown()
    finally:
        try:
            chunk_executor.shutdown()
        finally:
            compile_executor.shutdown()


def consume_futures(futs) -> list:
    """``[f.result() for f in futs]`` that consumes EVERY future.

    The naive loop abandons the remaining futures on the first raising
    ``result()`` — their thunks keep running and any exception they
    raise is silently swallowed (the auditor's ``dropped-future`` class,
    in its dynamic form). Here every future is awaited; the FIRST
    exception propagates (matching the naive loop's contract) after the
    rest completed, and later exceptions are logged so no failure is
    invisible."""
    results: list = []
    first_exc: Exception | None = None
    for f in futs:
        try:
            results.append(f.result())
        # Exception, NOT BaseException: a main-thread KeyboardInterrupt
        # or SystemExit delivered while blocked in result() must abort
        # the wait immediately — deferring it until every remaining
        # thunk completes could hold the interrupt for minutes.
        except Exception as exc:  # noqa: BLE001 — re-raised below
            if first_exc is None:
                first_exc = exc
            else:
                logger.warning(
                    "additional worker-thunk failure (first is being "
                    "re-raised): %r", exc,
                )
    if first_exc is not None:
        raise first_exc
    return results


class PipelineStats:
    """Thread-safe per-stage wall-clock accounting for one ingest.

    Stage seconds ACCUMULATE (two coordinates planning concurrently both
    add their thread-local seconds — the report also keeps the wall span
    per stage, which is what overlap claims are judged on).
    """

    def __init__(self):
        self._stats_lock = threading.Lock()
        self._generation = 0
        self.reset()

    def reset(self, keep: tuple = ()) -> None:
        """Start a new accounting generation.

        Stages entered BEFORE the reset record nothing when they finish
        (the generation token they captured is stale) — an orphaned
        background compile from a previous dataset generation must not
        write its seconds into the new generation's report. ``keep``
        names stages whose accumulation survives the reset (the raw-data
        transfer recorded at ``make_game_dataset`` time, which happens
        before any estimator exists).
        """
        with self._stats_lock:
            kept_s = {
                k: v
                for k, v in getattr(self, "_seconds", {}).items()
                if k in keep
            }
            kept_sp = {
                k: v
                for k, v in getattr(self, "_spans", {}).items()
                if k in keep
            }
            kept_c = {
                k: v
                for k, v in getattr(self, "_counts", {}).items()
                if k in keep
            }
            self._generation += 1
            self._seconds: dict[str, float] = kept_s
            self._spans: dict[str, list[float]] = kept_sp
            self._counts: dict[str, int] = kept_c

    @contextlib.contextmanager
    def stage(self, name: str):
        # Every stage IS a record of the unified telemetry layer: an
        # always-recorded ``obs.stage`` of the same name (worker-thread
        # stages root their own subtree, labeled by thread), which also
        # puts it on the profiler's clock. The per-stage histogram is
        # fed only while telemetry is enabled; this accounting stays
        # authoritative either way.
        from photon_tpu import obs

        with self._stats_lock:
            gen = self._generation
        try:
            with obs.stage(name) as sp:
                yield sp
        finally:
            t0, t1 = sp.t0, sp.t1
            with self._stats_lock:
                # A stale generation token (reset() ran mid-stage, e.g.
                # an orphaned background compile) records nothing — it
                # must not pollute the new generation's report. The
                # telemetry histogram below follows the SAME rule so the
                # two absorbed views never diverge (the stage above still
                # records: the ring is a faithful trace of wall events,
                # not generation accounting).
                if gen == self._generation:
                    if obs.enabled():
                        obs.REGISTRY.histogram(
                            "pipeline_stage_seconds", stage=name
                        ).observe(t1 - t0)
                    self._seconds[name] = self._seconds.get(
                        name, 0.0
                    ) + (t1 - t0)
                    self._counts[name] = self._counts.get(name, 0) + 1
                    span = self._spans.get(name)
                    if span is None:
                        self._spans[name] = [t0, t1]
                    else:
                        span[0] = min(span[0], t0)
                        span[1] = max(span[1], t1)

    def add(self, name: str, seconds: float) -> None:
        with self._stats_lock:
            self._seconds[name] = self._seconds.get(name, 0.0) + seconds
            self._counts[name] = self._counts.get(name, 0) + 1

    def seconds(self, name: str) -> float:
        with self._stats_lock:
            return self._seconds.get(name, 0.0)

    def report(self) -> dict:
        """The JSON-ready stage breakdown (``obs.snapshot()["pipeline"]``;
        the benchmark's ``ingest.plan_s`` / ``ingest.transfer_s``).

        ``compile_overlap_fraction`` is measured, not inferred: the AOT
        warm compile's duration minus the time the first fit actually
        BLOCKED waiting for it, over the duration — 1.0 means the compile
        hid entirely under ingest + operand assembly, 0.0 means it was
        paid serially after all (and None means no warm compile ran)."""
        with self._stats_lock:
            seconds = dict(self._seconds)
            spans = {k: tuple(v) for k, v in self._spans.items()}
        compile_s = seconds.get("compile", 0.0)
        wait_s = seconds.get("compile_wait", 0.0)
        overlap = (
            max(0.0, min(1.0, 1.0 - wait_s / compile_s))
            if compile_s > 0.0
            else None
        )
        out = {
            "plan_seconds": round(seconds.get("plan", 0.0), 4),
            "pack_seconds": round(seconds.get("pack", 0.0), 4),
            "transfer_seconds": round(seconds.get("transfer", 0.0), 4),
            "compile_seconds": round(compile_s, 4),
            "compile_wait_seconds": round(wait_s, 4),
            "compile_overlap_fraction": (
                None if overlap is None else round(overlap, 4)
            ),
            "stages": {k: round(v, 4) for k, v in sorted(seconds.items())},
        }
        plan_span = spans.get("plan")
        if plan_span is not None:
            out["plan_wall_seconds"] = round(
                plan_span[1] - plan_span[0], 4
            )
        return out


PIPELINE_STATS = PipelineStats()


# --------------------------------------------------------------------------
# chunked host passes (bit-identical to the serial forms)
# --------------------------------------------------------------------------


def _chunk_bounds(n: int, workers: int) -> list[tuple[int, int]]:
    per = -(-n // workers)
    return [(lo, min(lo + per, n)) for lo in range(0, n, per)]


def map_chunked(fn, out: np.ndarray, *arrays: np.ndarray) -> np.ndarray:
    """``out[lo:hi] = fn(*[a[lo:hi] for a in arrays])`` over row chunks.

    For ELEMENTWISE ``fn`` only (each output row depends on the same row
    of the inputs): chunking is then exact, so the parallel result is
    byte-identical to ``out[:] = fn(*arrays)``. Serial mode (or small
    inputs) takes the one-shot path.
    """
    n = out.shape[0]
    workers = ingest_threads()
    if serial_ingest() or workers <= 1 or n < _CHUNK_MIN_ROWS:
        out[:] = fn(*arrays)
        return out

    def run(lo: int, hi: int) -> None:
        from photon_tpu.resilience import faults

        # Chaos boundary: a chunk worker dying mid-pass must surface
        # through consume_futures (first exception re-raised after all
        # complete), never silently zero a span of the output.
        faults.check("ingest.chunk")
        out[lo:hi] = fn(*[a[lo:hi] for a in arrays])

    consume_futures(
        [
            chunk_executor.submit(run, lo, hi)
            for lo, hi in _chunk_bounds(n, workers)
        ]
    )
    return out


def bincount_chunked(codes: np.ndarray, minlength: int) -> np.ndarray:
    """Exact parallel ``np.bincount`` (partial integer counts sum
    associatively, so the chunked result is identical)."""
    n = codes.shape[0]
    workers = ingest_threads()
    if serial_ingest() or workers <= 1 or n < _CHUNK_MIN_ROWS:
        return np.bincount(codes, minlength=minlength)
    parts = consume_futures(
        [
            chunk_executor.submit(
                np.bincount, codes[lo:hi], minlength=minlength
            )
            for lo, hi in _chunk_bounds(n, workers)
        ]
    )
    total = parts[0].astype(np.int64, copy=True)
    for p in parts[1:]:
        total += p
    return total


# A high digit's rows are found by one pass over all the codes for each of
# its values, so the split form stops at this many values (2**20 groups).
_SPLIT_MAX_PARTS = 16


def group_rows(codes: np.ndarray, num_groups: int) -> tuple[np.ndarray, str]:
    """``(perm, how)``: ``perm`` is ``np.argsort(codes, kind="stable")``
    for codes in ``[0, num_groups)``, found by counting: numpy's stable
    argsort is a radix sort for 8- and 16-bit keys ONLY (wider integers
    take a merge sort), so the codes are sorted a 16-bit digit at a time.
    ``how`` names the form: ``radix16`` (one digit), ``radix16x2`` (two:
    the rows of each high digit, ascending, sorted by the low one, the
    parts side by side on the chunk pool; past ``_SPLIT_MAX_PARTS`` high
    digits, low digit first and the high one over that order) or ``sort``
    (over 2**32 groups: the stable argsort itself)."""
    n = codes.shape[0]
    if num_groups <= 1 << 16:
        return np.argsort(codes.astype(np.uint16), kind="stable"), "radix16"
    if num_groups > 1 << 32:
        return np.argsort(codes, kind="stable"), "sort"
    low = codes.astype(np.uint16)
    high = codes >> 16
    parts = ((num_groups - 1) >> 16) + 1
    if parts > _SPLIT_MAX_PARTS:
        by_low = np.argsort(low, kind="stable")
        by_high = np.argsort(
            high.astype(np.uint16)[by_low], kind="stable")
        return by_low[by_high], "radix16x2"
    perm = np.empty(n, dtype=np.intp)
    ends = np.cumsum(bincount_chunked(high, parts))

    def sort_part(k: int) -> None:
        rows = np.flatnonzero(high == k)  # ascending: the gather streams
        perm[ends[k] - rows.size:ends[k]] = rows[
            np.argsort(low[rows], kind="stable")]

    consume_futures(
        [chunk_executor.submit(sort_part, k) for k in range(parts)])
    return perm, "radix16x2"


# --------------------------------------------------------------------------
# chunked double-buffered packed transfer
# --------------------------------------------------------------------------


def padded_len(n: int) -> int:
    """Packed-buffer length after granule padding — THE pad rule shared
    by the real transfer and the shape oracle's predicted layout."""
    g = _TRANSFER_GRANULE_ELEMS
    return max(-(-n // g) * g, g)


def _packed_len(arrays) -> tuple[int, int]:
    n = sum(int(np.prod(a.shape)) if a.shape else 1 for a in arrays)
    return n, padded_len(n)


def _fill_chunks(arrays, n_pad: int, chunk_elems: int):
    """Yield freshly allocated int32 staging buffers covering the packed
    layout [0, n_pad) in order. Fresh per chunk: ``jax.device_put`` may
    read the source asynchronously, so staging buffers are never reused
    while a transfer could still be draining (the double-buffering
    contract)."""
    remaining = n_pad
    chunk = np.zeros(min(chunk_elems, remaining), dtype=np.int32)
    filled = 0
    for a in arrays:
        flat = np.ascontiguousarray(a, dtype=np.int32).reshape(-1)
        o = 0
        while o < flat.size:
            take = min(flat.size - o, chunk.size - filled)
            chunk[filled:filled + take] = flat[o:o + take]
            filled += take
            o += take
            if filled == chunk.size:
                yield chunk
                remaining -= chunk.size
                chunk = np.zeros(
                    min(chunk_elems, remaining), dtype=np.int32
                )
                filled = 0
    while remaining > 0:  # zero padding tail (buffers start zeroed)
        yield chunk
        remaining -= chunk.size
        chunk = np.zeros(min(chunk_elems, remaining), dtype=np.int32)


_concat_cache: dict[int, object] = {}


def _concat_chunks(chunks: tuple):
    """In-trace concatenate: one program per chunk COUNT (chunk sizes
    recur — all equal but the last — so similarly sized ingests share
    the executable). The chunks are NOT donated: the output is larger
    than any one input, so XLA cannot alias it into a chunk — on the
    TPU v5e the donation only produced "Some donated buffers were not
    usable" on every call (PR 21 chip run); the chunk buffers are freed
    when the caller drops its references."""
    import jax

    fn = _concat_cache.get(len(chunks))
    if fn is None:
        import jax.numpy as jnp

        fn = jax.jit(lambda cs: jnp.concatenate(cs))
        _concat_cache[len(chunks)] = fn
    return fn(tuple(chunks))


def packed_device_put(arrays) -> tuple:
    """Place the packed int32 plan layout on device; returns (buf, shapes).

    Below one chunk this is the legacy single-shot path (one staging fill,
    one ``device_put``). Above it, granule-aligned chunks stream out with
    the host filling chunk i+1 while chunk i's transfer drains, and a
    concatenate restores the ONE contiguous buffer every packed
    consumer slices at static offsets (the layout contract is unchanged —
    byte-identical to the single-shot buffer).

    The transfer is a RETRIED site (resilience layer): a transient
    host->device failure — preemption blips, the injected
    ``transfer.packed`` fault — re-runs the whole put (it is pure: host
    arrays in, fresh device buffer out), with backoff; stage seconds
    accumulate across attempts because the time was really spent.
    """
    from photon_tpu.resilience import retry

    return retry.retrying_check(
        "transfer.packed",
        lambda: _packed_device_put_once(arrays),
        site="ingest.packed_transfer",
    )


def _packed_device_put_once(arrays) -> tuple:
    import jax

    shapes = tuple(a.shape for a in arrays)
    n, n_pad = _packed_len(arrays)
    chunk_elems = transfer_chunk_elems()
    if serial_ingest() or n_pad <= chunk_elems:
        with PIPELINE_STATS.stage("pack"):
            flat = np.empty(n_pad, dtype=np.int32)
            o = 0
            for a in arrays:
                flat[o:o + a.size] = np.ascontiguousarray(
                    a, dtype=np.int32
                ).reshape(-1)
                o += a.size
            flat[o:] = 0
        with PIPELINE_STATS.stage("transfer"):
            buf = jax.device_put(flat)
        return buf, shapes
    parts = []
    with PIPELINE_STATS.stage("transfer"):
        for chunk in _fill_chunks(arrays, n_pad, chunk_elems):
            parts.append(jax.device_put(chunk))
        buf = _concat_chunks(tuple(parts))
    return buf, shapes
