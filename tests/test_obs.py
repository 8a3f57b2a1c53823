"""photon_tpu.obs — unified runtime telemetry.

Covers the span tracer (hierarchy, disabled-is-free, root sync), the
metrics registry (labels + the thread-safety hammer the ingest pools
demand), async convergence traces from inside the fused fit program, the
exporters (JSONL schema + validator, summary table, snapshot), the fused
path's attributed per-record seconds, and the audited zero-overhead
contract (telemetry on vs off traces identical programs).
"""

from __future__ import annotations

import json
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from photon_tpu import obs


@pytest.fixture
def telemetry():
    """Enabled telemetry with clean state; restores the global flag."""
    was = obs.enabled()
    obs.reset()
    obs.enable()
    yield obs
    obs.TRACER.enabled = was
    obs.reset()


@pytest.fixture
def telemetry_off():
    was = obs.enabled()
    obs.reset()
    obs.disable()
    yield obs
    obs.TRACER.enabled = was
    obs.reset()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_nesting_builds_paths(telemetry):
    with obs.span("outer"):
        with obs.span("inner"):
            pass
        with obs.span("inner"):
            pass
    with obs.span("solo"):
        pass
    agg = obs.snapshot()["spans"]
    assert agg["outer"]["count"] == 1
    assert agg["outer/inner"]["count"] == 2
    assert agg["solo"]["count"] == 1
    assert agg["outer"]["seconds"] >= agg["outer/inner"]["seconds"]


def test_span_disabled_yields_none_and_records_nothing(telemetry_off):
    with obs.span("ghost") as sp:
        assert sp is None
    assert obs.TRACER.completed() == []
    assert obs.snapshot()["spans"] == {}


def test_span_threads_root_their_own_subtrees(telemetry):
    def work():
        with obs.span("worker"):
            pass

    t = threading.Thread(target=work, name="pool-thread")
    with obs.span("driver"):
        t.start()
        t.join()
    agg = obs.snapshot()["spans"]
    # The worker span is a root of its own thread, not a child of
    # "driver" (per-thread stacks; the thread label disambiguates).
    assert set(agg) == {"driver", "worker"}
    spans = {s.path: s for s in obs.TRACER.completed()}
    assert spans["worker"].thread == "pool-thread"


def test_span_sync_failure_does_not_corrupt_thread_stack(
    telemetry, monkeypatch
):
    """An async device failure surfacing at the root sync must still
    pop + record the span: a dead span left on the thread-local stack
    would prefix every later span on that thread."""
    import jax

    def boom(x):
        raise RuntimeError("device failure")

    monkeypatch.setattr(jax, "block_until_ready", boom)
    with pytest.raises(RuntimeError, match="device failure"):
        with obs.span("root") as sp:
            sp.sync = object()
    failed = obs.TRACER.completed()[-1]
    assert failed.path == "root"
    assert failed.device_wait_seconds is None  # sync never completed
    with obs.span("after"):
        pass
    assert obs.TRACER.completed()[-1].path == "after"  # no root/ prefix


def test_span_sync_measures_device_wait(telemetry):
    import jax.numpy as jnp

    with obs.span("root") as sp:
        assert sp is not None
        sp.sync = jnp.arange(128.0) * 2.0
    done = obs.TRACER.completed()[-1]
    assert done.device_wait_seconds is not None
    assert 0.0 <= done.device_wait_seconds <= done.seconds
    assert done.sync is None  # device arrays are not pinned by records


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram(telemetry):
    obs.REGISTRY.counter("c_total", kind="x").inc()
    obs.REGISTRY.counter("c_total", kind="x").inc(2.0)
    obs.REGISTRY.counter("c_total", kind="y").inc()
    obs.REGISTRY.gauge("g").set(7.5)
    for v in (1.0, 3.0, 2.0):
        obs.REGISTRY.histogram("h", stage="s").observe(v)
    snap = obs.REGISTRY.snapshot()
    assert snap["counters"]["c_total{kind=x}"] == 3.0
    assert snap["counters"]["c_total{kind=y}"] == 1.0
    assert snap["gauges"]["g"] == 7.5
    h = snap["histograms"]["h{stage=s}"]
    assert h == {"count": 3, "sum": 6.0, "min": 1.0, "max": 3.0}


def test_registry_thread_hammer_no_lost_updates(telemetry):
    """The no-torn-no-lost-updates contract the ingest pools rely on:
    16 threads x 500 increments + observations must all land."""
    threads, per = 16, 500

    def hammer(tid):
        for i in range(per):
            obs.REGISTRY.counter("hammer_total").inc()
            obs.REGISTRY.counter("hammer_total", thread=tid % 4).inc()
            obs.REGISTRY.histogram("hammer_seconds").observe(1.0)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        for f in [pool.submit(hammer, t) for t in range(threads)]:
            f.result()
    snap = obs.REGISTRY.snapshot()
    assert snap["counters"]["hammer_total"] == threads * per
    assert (
        sum(
            v for k, v in snap["counters"].items()
            if k.startswith("hammer_total{")
        )
        == threads * per
    )
    h = snap["histograms"]["hammer_seconds"]
    assert h["count"] == threads * per
    assert h["sum"] == pytest.approx(threads * per)


def test_pipeline_stats_thread_hammer_no_lost_updates(
    telemetry, monkeypatch
):
    """PIPELINE_STATS accounting under the executor pools (PR 3): stage
    seconds and counts accumulate exactly, from the real chunk pool AND
    a raw thread pool, with no lost or torn updates."""
    from photon_tpu.data.pipeline import PipelineStats, chunk_executor

    monkeypatch.delenv("PHOTON_TPU_SERIAL_INGEST", raising=False)
    stats = PipelineStats()
    threads, per = 8, 200

    def hammer():
        for _ in range(per):
            with stats.stage("hammer"):
                pass
            stats.add("fixed", 0.001)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        for f in [pool.submit(hammer) for _ in range(threads)]:
            f.result()
    # The ingest pipeline's own chunk pool path too (degrades to in-line
    # under forced-serial env; the accounting contract is identical).
    for f in [chunk_executor.submit(hammer) for _ in range(4)]:
        f.result()

    total = (threads + 4) * per
    assert stats._counts["hammer"] == total
    assert stats._counts["fixed"] == total
    assert stats.seconds("fixed") == pytest.approx(total * 0.001)
    assert stats.seconds("hammer") >= 0.0
    rep = stats.report()
    assert rep["stages"]["hammer"] == pytest.approx(
        stats.seconds("hammer"), abs=1e-3)


def test_metrics_listener_feeds_registry_from_event_bus(telemetry):
    from photon_tpu.algorithm.coordinate_descent import (
        CoordinateUpdateRecord,
    )
    from photon_tpu.events import (
        CoordinateUpdateEvent,
        EventEmitter,
        FitEndEvent,
    )

    emitter = EventEmitter([obs.metrics_listener])
    rec = CoordinateUpdateRecord(
        iteration=0, coordinate_id="global", seconds=0.25,
        diagnostics=None, evaluation=None,
    )
    emitter.send_event(CoordinateUpdateEvent(rec))
    emitter.send_event(FitEndEvent(config_index=0, result=None))
    snap = obs.REGISTRY.snapshot()
    assert (
        snap["counters"]["coordinate_updates_total{coordinate=global}"]
        == 1.0
    )
    assert snap["counters"]["fit_configs_total"] == 1.0
    h = snap["histograms"][
        "coordinate_update_dispatch_seconds{coordinate=global}"
    ]
    assert h["count"] == 1 and h["sum"] == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# convergence traces
# ---------------------------------------------------------------------------


def test_convergence_record_and_async_fetch(telemetry):
    arr = np.arange(2 * 1 * 5, dtype=np.float32).reshape(2, 1, 5)
    obs.convergence.record(("per-user",), arr)
    traces = obs.convergence.traces()
    assert len(traces) == 1
    series = traces[0]["per-user"]
    assert list(series) == list(obs.convergence.METRICS)
    assert series["loss"] == [0.0, 5.0]
    assert series["weight_norm_sq"] == [4.0, 9.0]
    snap = obs.convergence.snapshot()
    assert snap["fits_recorded"] == 1
    assert snap["last"]["per-user"]["grad_norm"] == [1.0, 6.0]


def test_convergence_traces_are_bounded(telemetry):
    from photon_tpu.obs.convergence import _MAX_TRACES

    arr = np.zeros((1, 1, 5), np.float32)
    for _ in range(_MAX_TRACES + 5):
        obs.convergence.record(("c",), arr)
    snap = obs.convergence.snapshot()
    assert snap["fits_recorded"] == _MAX_TRACES + 5
    assert len(obs.convergence.traces()) == _MAX_TRACES


# ---------------------------------------------------------------------------
# the fused fit integration: convergence series + attributed seconds
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_glmix_fit():
    """One telemetry-ENABLED fused fit on the canonical tiny workload
    (module-scoped: the fused compile is the expensive part)."""
    import jax

    from photon_tpu.analysis import program

    was = obs.enabled()
    obs.reset()
    obs.enable()
    try:
        with jax.enable_x64(False):
            est, data = program._tiny_glmix()
            est.prepare(data)
            result = est.fit(data)[0]
        snap = obs.snapshot()
        spans = obs.TRACER.completed()
    finally:
        obs.TRACER.enabled = was
    yield est, result, snap, spans
    obs.reset()


def test_fused_fit_records_convergence_series(tiny_glmix_fit):
    est, result, snap, _ = tiny_glmix_fit
    conv = snap["convergence"]
    assert conv["fits_recorded"] >= 1
    last = conv["last"]
    assert set(last) == {"global", "per-user"}
    for series in last.values():
        assert set(series) == set(obs.convergence.METRICS)
        for values in series.values():
            assert len(values) == est.num_iterations
            assert all(np.isfinite(v) for v in values)
    # The per-coordinate signals that must be real, not padding: the
    # fixed effect's solver loss is positive, and both coordinates moved
    # on the first sweep (cold start: residual delta = ||score||^2 > 0).
    assert all(v > 0 for v in last["global"]["loss"])
    assert last["global"]["residual_delta_sq"][0] > 0
    assert last["per-user"]["residual_delta_sq"][0] > 0
    # RE solvers report no objective: documented zero columns.
    assert last["per-user"]["loss"] == [0.0] * est.num_iterations


def test_fused_seconds_attributed_from_measured_wall(tiny_glmix_fit):
    est, result, snap, spans = tiny_glmix_fit
    history = result.descent.history
    assert len(history) == est.num_iterations * 2
    secs = [rec.seconds for rec in history]
    assert all(isinstance(s, float) and s >= 0.0 for s in secs)
    # Shares sum to the fit program's measured dispatch->completion
    # window (the span's fit_seconds attr) — attribution of ONE real
    # measurement, per the CoordinateUpdateRecord contract — and that
    # window excludes materialize/AOT-wait, so it is bounded by the
    # whole span.
    (fused,) = [s for s in spans if s.name == "fused_fit"]
    fit_seconds = fused.attrs["fit_seconds"]
    assert 0.0 < fit_seconds <= fused.seconds
    # The span attr is rounded to 1e-6 (Span export contract) while the
    # record shares carry full precision, so a sub-5ms fit window on a
    # slow box can exceed a rel-only bound by the rounding quantum —
    # allow that half-quantum absolutely.
    assert sum(secs) == pytest.approx(fit_seconds, rel=1e-4, abs=5.1e-7)
    assert fused.device_wait_seconds is not None


def test_fused_cold_jit_window_is_not_attributed(telemetry, monkeypatch):
    """With no AOT warm compile (serial ingest), the first fit's jit
    fallback traces/compiles inside the dispatch window: records keep
    seconds=None. The warm re-entry's window is pure and attributes."""
    import jax

    from photon_tpu.analysis import program

    monkeypatch.setenv("PHOTON_TPU_SERIAL_INGEST", "1")
    with jax.enable_x64(False):
        est, data = program._tiny_glmix()
        est.prepare(data)
        cold = est.fit(data)[0]
        warm = est.fit(data)[0]
    assert all(rec.seconds is None for rec in cold.descent.history)
    assert all(
        isinstance(rec.seconds, float) for rec in warm.descent.history
    )
    fused = [s for s in obs.TRACER.completed() if s.name == "fused_fit"]
    assert [s.attrs["fit_window_pure"] for s in fused] == [False, True]


def test_fused_retried_dispatch_window_is_not_attributed(
    telemetry, monkeypatch
):
    """A retried fit dispatch puts a failed attempt + the backoff sleep
    inside the timed window — even a warm re-entry must keep
    seconds=None (regression: attempt 2 re-derived fit_window_pure from
    _jit_seen, which attempt 1 had already populated, and attributed a
    window that contained the retry)."""
    import jax

    from photon_tpu.analysis import program
    from photon_tpu.resilience import (
        FaultPlan,
        faults,
        reset_retry_stats,
    )

    monkeypatch.setenv("PHOTON_TPU_SERIAL_INGEST", "1")
    try:
        with jax.enable_x64(False):
            est, data = program._tiny_glmix()
            est.prepare(data)
            est.fit(data)  # warm the jit path: statics enter _jit_seen
            plan = FaultPlan([dict(point="fit.dispatch", nth=1)])
            with faults.injected(plan):
                retried = est.fit(data)[0]
    finally:
        reset_retry_stats()
    assert all(rec.seconds is None for rec in retried.descent.history)
    fused = [s for s in obs.TRACER.completed() if s.name == "fused_fit"]
    assert fused[-1].attrs["fit_window_pure"] is False


def test_fused_fit_telemetry_off_keeps_seconds_none(telemetry_off):
    import jax

    from photon_tpu.analysis import program

    with jax.enable_x64(False):
        est, data = program._tiny_glmix()
        est.prepare(data)
        result = est.fit(data)[0]
    assert all(rec.seconds is None for rec in result.descent.history)
    assert obs.convergence.snapshot()["fits_recorded"] == 0
    # Disabled, the ring holds the job's stages (and what compiled) and
    # not one gated span: no "fused_fit", no "fit/config:0".
    done = obs.TRACER.completed()
    assert {s.kind for s in done} <= {"stage", "event"}
    names = {s.name for s in done}
    assert {"prepare", "plan", "fit", "fit.operands",
            "fit.dispatch"} <= names
    assert not {"fused_fit", "fit/config:0"} & names


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_snapshot_is_json_serializable(tiny_glmix_fit):
    _, _, snap, _ = tiny_glmix_fit
    text = json.dumps(snap)
    round_tripped = json.loads(text)
    assert round_tripped["enabled"] is True
    assert round_tripped["pipeline"] is not None
    assert round_tripped["compile_cache"] is not None


def test_jsonl_write_and_validate(telemetry, tmp_path):
    import jax.numpy as jnp

    with obs.span("root") as sp:
        sp.sync = jnp.ones(8)
    obs.REGISTRY.counter("c").inc()
    obs.REGISTRY.gauge("g").set(1.0)
    obs.REGISTRY.histogram("h").observe(2.0)
    obs.convergence.record(("cid",), np.zeros((1, 1, 5), np.float32))
    path = str(tmp_path / "t.jsonl")
    n = obs.write_jsonl(path)
    assert obs.validate_jsonl(path) == n
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["type"] == "telemetry"
    assert lines[0]["version"] == 1
    assert lines[0]["spans_dropped"] == 0
    types = {l["type"] for l in lines}
    assert {"span", "counter", "gauge", "histogram", "series",
            "report"} <= types
    series = [l for l in lines if l["type"] == "series"]
    assert {s["metric"] for s in series} == set(obs.convergence.METRICS)


def test_validate_jsonl_rejects_schema_violations(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"type": "telemetry", "version": 1}\n{"type": "span"}\n')
    with pytest.raises(ValueError, match="span record missing"):
        obs.validate_jsonl(str(bad))
    noheader = tmp_path / "nh.jsonl"
    noheader.write_text('{"type": "counter", "series": "c", "value": 1}\n')
    with pytest.raises(ValueError, match="header"):
        obs.validate_jsonl(str(noheader))
    # A blank first line must not smuggle a headerless stream through.
    blank = tmp_path / "blank.jsonl"
    blank.write_text('\n{"type": "counter", "series": "c", "value": 1}\n')
    with pytest.raises(ValueError, match="header"):
        obs.validate_jsonl(str(blank))
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    with pytest.raises(ValueError, match="empty"):
        obs.validate_jsonl(str(empty))


def test_summary_table_renders_all_sections(telemetry):
    with obs.span("a"):
        with obs.span("b"):
            pass
    obs.REGISTRY.counter("c_total").inc(3)
    obs.REGISTRY.histogram("h").observe(0.5)
    obs.convergence.record(("cid",), np.zeros((1, 1, 5), np.float32))
    table = obs.summary_table()
    assert "a/b" not in table  # tree renders leaf names, indented
    assert "c_total = 3" in table
    assert "convergence: 1 fit(s) recorded" in table
    assert "spans" in table and "histograms" in table


# ---------------------------------------------------------------------------
# the audited zero-overhead contract
# ---------------------------------------------------------------------------


def test_telemetry_contract_zero_overhead():
    """Telemetry on vs off: identical program signatures (zero added
    dispatches, identical recompile keys) and a callback-free hot-loop
    jaxpr — the tier-2 `telemetry` contract, run directly."""
    import jax

    from photon_tpu.analysis import program

    with jax.enable_x64(False):
        trace = program.build_telemetry()
    base = {name: p.signature for name, p in trace.programs.items()}
    assert set(base) == {"materialize", "fit"}
    (toggled,) = trace.variants["telemetry_toggle"]
    assert toggled == base, (
        "enabling telemetry changed a traced program — the zero-overhead "
        "guarantee is broken"
    )
    contracts = {c.name: c for c in program.collect_contracts()}
    findings = program.run_checks(contracts["telemetry"], trace)
    assert [f for f in findings if not f.suppressed] == []
