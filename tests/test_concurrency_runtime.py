"""Runtime counterparts of the tier-3 static concurrency contracts.

The auditor (analysis/concurrency.py) proves the lock/future discipline
from the AST; these tests prove the behaviors it cannot see: the
barrier-orchestrated overlap between the background AOT-compile thread
and ``FusedFit.run``'s consumption, pipeline executor shutdown racing
in-flight ingest work (no deadlock, no lost ``PIPELINE_STATS`` updates),
and the consume-every-future fix for swallowed worker exceptions
(``game_estimator.py`` priming pool / ``pipeline.map_chunked``).
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time

import numpy as np
import pytest

from photon_tpu.data import pipeline


@contextlib.contextmanager
def ingest_mode(*, serial: bool, threads: int = 2, chunk_min: int = 8):
    """Force the serial or parallel ingest path for one block (the
    test_ingest_pipeline helper, kept local so this module stands
    alone)."""
    saved = {
        k: os.environ.get(k)
        for k in ("PHOTON_TPU_SERIAL_INGEST", "PHOTON_TPU_INGEST_THREADS")
    }
    saved_chunk = pipeline._CHUNK_MIN_ROWS
    os.environ["PHOTON_TPU_SERIAL_INGEST"] = "1" if serial else ""
    os.environ["PHOTON_TPU_INGEST_THREADS"] = str(threads)
    pipeline._CHUNK_MIN_ROWS = chunk_min
    pipeline.reset_executors()
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        pipeline._CHUNK_MIN_ROWS = saved_chunk
        pipeline.reset_executors()


# ---------------------------------------------------------------------------
# consume_futures: every worker exception is observed
# ---------------------------------------------------------------------------


class _DoneFuture:
    def __init__(self, result=None, exc=None):
        self._result, self._exc = result, exc

    def result(self):
        if self._exc is not None:
            raise self._exc
        return self._result


def test_consume_futures_awaits_all_and_raises_first(caplog):
    first = RuntimeError("first")
    second = RuntimeError("second")
    futs = [
        _DoneFuture(result=1),
        _DoneFuture(exc=first),
        _DoneFuture(result=2),
        _DoneFuture(exc=second),
    ]
    with caplog.at_level(logging.WARNING, logger="photon_tpu.data.pipeline"):
        with pytest.raises(RuntimeError, match="first"):
            pipeline.consume_futures(futs)
    # The SECOND failure was consumed and logged, not dropped.
    assert any("second" in r.getMessage() for r in caplog.records)


def test_consume_futures_clean_returns_in_order():
    assert pipeline.consume_futures(
        [_DoneFuture(result=i) for i in range(5)]
    ) == [0, 1, 2, 3, 4]


def test_prime_compilations_consumes_every_thunk(caplog):
    """The game_estimator.py priming-pool satellite: a thunk that fails
    AFTER another already raised must still be awaited and its failure
    surfaced in the log — the pre-fix loop abandoned it silently."""
    from photon_tpu.estimators.game_estimator import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
    )
    from photon_tpu.types import TaskType

    est = GameEstimator(
        TaskType.LINEAR_REGRESSION,
        {"global": FixedEffectCoordinateConfiguration("s")},
        mesh="off",
    )
    ran: list[str] = []
    gate = threading.Barrier(3, timeout=30)

    class FakeCoord:
        def __init__(self, name: str, fail: bool):
            self.name, self.fail = name, fail

        def warmup_thunks(self):
            def thunk():
                # All three thunks rendezvous before any finishes, so
                # both failures are in flight together.
                gate.wait()
                ran.append(self.name)
                if self.fail:
                    raise RuntimeError(f"boom-{self.name}")

            return [thunk]

    coords = {
        "a": FakeCoord("a", True),
        "b": FakeCoord("b", True),
        "c": FakeCoord("c", False),
    }
    with caplog.at_level(logging.WARNING, logger="photon_tpu.data.pipeline"):
        with pytest.raises(RuntimeError, match="boom-"):
            est._prime_compilations(coords, datasets=object())
    assert sorted(ran) == ["a", "b", "c"]
    assert any(
        "additional worker-thunk failure" in r.getMessage()
        for r in caplog.records
    ), "the second thunk's exception was swallowed"


def test_map_chunked_consumes_every_chunk_failure(caplog):
    """The pipeline satellite twin: one chunk raising must not silence
    a sibling chunk's failure."""
    calls: list[int] = []

    def fn(a):
        calls.append(int(a[0]))
        if a[0] < 2:  # the first two chunks fail
            raise ValueError(f"chunk-{int(a[0])}")
        return a

    with ingest_mode(serial=False, threads=4, chunk_min=1):
        arr = np.repeat(np.arange(4), 2).astype(np.int64)
        out = np.empty_like(arr)
        with caplog.at_level(
            logging.WARNING, logger="photon_tpu.data.pipeline"
        ):
            with pytest.raises(ValueError, match="chunk-"):
                pipeline.map_chunked(fn, out, arr)
    assert len(calls) == 4, "not every chunk thunk was awaited"
    assert any(
        "additional worker-thunk failure" in r.getMessage()
        for r in caplog.records
    )


# ---------------------------------------------------------------------------
# barrier-orchestrated: AOT-compile thread vs FusedFit.run consumption
# ---------------------------------------------------------------------------


def test_aot_compile_thread_vs_fit_consumption(monkeypatch):
    """Deterministic overlap orchestration: the warm compile is gated
    until the fit actually enters its ``compile_wait`` stage, so the
    consumption path MUST block on the future — proving (a) the compile
    runs on a pool thread concurrent with prepare, (b) ``FusedFit.run``
    consumes the artifacts through the future, and (c) the blocked tail
    lands in ``compile_wait_seconds`` without deadlock or lost stats."""
    from photon_tpu.analysis.program import _tiny_glmix

    with ingest_mode(serial=False):
        est, data = _tiny_glmix()
        release = threading.Event()
        seen: dict[str, str] = {}
        real_warm = est._warm_compile

        def gated_warm(d):
            seen["thread"] = threading.current_thread().name
            # Wait until the training thread is provably blocked in
            # _consume_aot (the stage hook below); time out rather than
            # deadlock if the fit never consumes.
            release.wait(timeout=30)
            return real_warm(d)

        real_stage = pipeline.PIPELINE_STATS.stage

        @contextlib.contextmanager
        def stage_hook(name):
            if name == "compile_wait":
                release.set()
            with real_stage(name) as sp:
                yield sp

        monkeypatch.setattr(est, "_warm_compile", gated_warm)
        monkeypatch.setattr(pipeline.PIPELINE_STATS, "stage", stage_hook)
        results = est.fit(data)
        report = pipeline.PIPELINE_STATS.report()
        fused = next(reversed(est._fused_cache.values()))

    assert seen["thread"] != threading.current_thread().name
    assert fused._aot is not None, "fit did not consume the AOT artifacts"
    assert len(results) == 1
    assert report["compile_seconds"] > 0.0
    # The fit was forced to wait out the entire gated compile.
    assert report["compile_wait_seconds"] > 0.0
    assert report["compile_overlap_fraction"] is not None


# ---------------------------------------------------------------------------
# pipeline shutdown racing in-flight ingest
# ---------------------------------------------------------------------------


def test_pipeline_shutdown_during_ingest_hammer():
    """reset_executors() racing live map_chunked work from several
    threads: no deadlock, no submit-after-shutdown crash (the _Pool
    lock serializes swap-vs-submit), every chunk result exact, and no
    PIPELINE_STATS update lost across the races."""
    T, K = 4, 24
    with ingest_mode(serial=False, threads=2, chunk_min=4):
        pipeline.PIPELINE_STATS.reset()
        start = threading.Barrier(T + 1, timeout=30)
        errors: list[BaseException] = []

        def worker(tid: int):
            rng = np.random.default_rng(tid)
            try:
                start.wait()
                for _ in range(K):
                    arr = rng.integers(0, 100, size=64)
                    out = np.empty_like(arr)
                    with pipeline.PIPELINE_STATS.stage("hammer"):
                        pipeline.map_chunked(lambda a: a * 2 + 1, out, arr)
                    np.testing.assert_array_equal(out, arr * 2 + 1)
            except BaseException as exc:  # noqa: BLE001 — reported below
                errors.append(exc)

        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(T)
        ]
        for t in threads:
            t.start()
        start.wait()
        # Tear the pools down repeatedly while the workers hammer them;
        # each next submit lazily rebuilds.
        for _ in range(6):
            pipeline.reset_executors()
            time.sleep(0.01)
        for t in threads:
            t.join(timeout=60)
        alive = [t for t in threads if t.is_alive()]
        counts = dict(pipeline.PIPELINE_STATS._counts)
        seconds = pipeline.PIPELINE_STATS.seconds("hammer")
        pipeline.PIPELINE_STATS.reset()
    assert not alive, "deadlocked ingest threads after shutdown race"
    assert not errors, errors
    # Every stage entry survived the concurrent resets: lockset holds.
    assert counts.get("hammer") == T * K
    assert seconds > 0.0


def test_reset_executors_shuts_all_pools_despite_errors(monkeypatch):
    """The error-path satellite: a failing plan-pool shutdown must not
    leak the chunk/compile pools."""
    with ingest_mode(serial=False, threads=2, chunk_min=1):
        # Materialize all three pools.
        arr = np.arange(8)
        out = np.empty_like(arr)
        pipeline.map_chunked(lambda a: a, out, arr)
        pipeline.plan_executor.submit(lambda: None).result()
        pipeline.compile_executor.submit(lambda: None).result()
        assert pipeline.chunk_executor._pool is not None

        real = pipeline._Pool.shutdown

        def failing_shutdown(self):
            if self is pipeline.plan_executor:
                raise RuntimeError("teardown interrupted")
            return real(self)

        monkeypatch.setattr(pipeline._Pool, "shutdown", failing_shutdown)
        with pytest.raises(RuntimeError, match="teardown interrupted"):
            pipeline.reset_executors()
        monkeypatch.setattr(pipeline._Pool, "shutdown", real)
        assert pipeline.chunk_executor._pool is None
        assert pipeline.compile_executor._pool is None
