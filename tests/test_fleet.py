"""photon_tpu.obs.fleet — distributed observability.

Covers the host-identity provenance block (cached probe, run-id
plumbing, stamping into snapshot/JSONL/flight artifacts), the
clock-alignment handshake math, bundle shipping (artifact schema +
commit-point discipline), the fleet merge (synthetic two-host bundles
with a KNOWN injected clock offset landing monotonic on one timeline
within the reported skew bound), degradation (torn spans.jsonl, missing
rank — named gaps, never a crash), the straggler/collective rollup,
monitor-port arbitration (two in-process exporters coexisting) and the
MULTICHIP row artifact.
"""

from __future__ import annotations

import json
import os
import socket
import time
import urllib.request

import pytest

from photon_tpu import obs
from photon_tpu.obs import export, fleet, flight
from photon_tpu.obs import trace as obs_trace
from photon_tpu.obs.trace import validate_chrome_trace


@pytest.fixture
def telemetry():
    was = obs.enabled()
    obs.reset()
    obs.enable()
    yield obs
    obs.TRACER.enabled = was
    obs.reset()


@pytest.fixture(autouse=True)
def _clean_fleet():
    fleet.reset()
    yield
    fleet.reset()


# ---------------------------------------------------------------------------
# host identity
# ---------------------------------------------------------------------------


def test_host_identity_fields(monkeypatch):
    monkeypatch.delenv("PHOTON_RUN_ID", raising=False)
    ident = fleet.host_identity()
    for key in (
        "process_index", "process_count", "hostname", "pid",
        "device_kind", "local_device_count", "global_device_count",
        "jax_version", "run_id",
    ):
        assert key in ident
    assert ident["pid"] == os.getpid()
    assert ident["hostname"] == socket.gethostname()
    assert ident["process_index"] == 0
    assert ident["process_count"] >= 1
    assert ident["run_id"] is None


def test_host_identity_is_cached_until_refresh():
    a = fleet.host_identity()
    b = fleet.host_identity()
    assert a == b
    # refresh re-probes but the identity of THIS process is stable
    c = fleet.host_identity(refresh=True)
    assert c["pid"] == a["pid"]


def test_run_id_explicit_wins_over_env(monkeypatch):
    monkeypatch.setenv("PHOTON_RUN_ID", "from-env")
    assert fleet.host_identity()["run_id"] == "from-env"
    fleet.set_run_id("explicit")
    assert fleet.run_id() == "explicit"
    fleet.set_run_id(None)
    assert fleet.run_id() == "from-env"


def test_snapshot_and_jsonl_header_carry_host(telemetry, tmp_path):
    with obs.span("stamped"):
        pass
    snap = obs.snapshot()
    assert snap["host"]["pid"] == os.getpid()
    path = tmp_path / "telemetry.jsonl"
    export.write_jsonl(str(path))
    header = json.loads(path.read_text().splitlines()[0])
    assert header["type"] == "telemetry"
    assert header["host"]["hostname"] == socket.gethostname()
    export.validate_jsonl(str(path))


def test_chrome_trace_other_data_carries_host(telemetry):
    with obs.span("traced"):
        pass
    doc = obs_trace.chrome_trace()
    assert doc["otherData"]["host"]["pid"] == os.getpid()


def test_flight_dump_rank_suffixed_filename(telemetry, tmp_path, monkeypatch):
    forged = dict(
        fleet._probe_identity(), process_index=1, process_count=2,
        run_id=None,
    )
    monkeypatch.setattr(fleet, "host_identity", lambda **kw: forged)
    rec = flight.FlightRecorder(str(tmp_path))
    path = rec.dump("test")
    assert path is not None
    assert os.path.basename(path) == f"flight-{os.getpid()}-r1.json"
    payload = json.loads(open(path).read())
    assert payload["host"]["process_index"] == 1


def test_flight_dump_single_process_keeps_plain_name(telemetry, tmp_path):
    rec = flight.FlightRecorder(str(tmp_path))
    path = rec.dump("test")
    assert os.path.basename(path) == f"flight-{os.getpid()}.json"


# ---------------------------------------------------------------------------
# clock alignment
# ---------------------------------------------------------------------------


def test_clock_sample_offset_maps_perf_to_epoch():
    s = fleet.clock_sample()
    assert set(s) == {"offset", "spread", "epoch", "perf_counter"}
    # offset + perf ≈ epoch, and a fresh independent measurement agrees
    now = time.time() - time.perf_counter()
    assert abs(s["offset"] - now) < 1.0
    assert s["spread"] >= 0.0


def test_clock_alignment_handshake_bounds_drift():
    fleet.mark_init()
    align = fleet.clock_alignment()
    assert align["init"] is not None
    bound = align["skew_bound_seconds"]
    assert bound >= 0.0
    # the bound is delta(offsets) + both spreads, by construction
    expect = (
        abs(align["commit"]["offset"] - align["init"]["offset"])
        + align["commit"]["spread"] + align["init"]["spread"]
    )
    assert bound == pytest.approx(expect)
    # on one host the two samples are milliseconds apart
    assert bound < 1.0


def test_clock_alignment_without_init_stands_alone():
    align = fleet.clock_alignment()
    assert align["init"] == align["commit"]


# ---------------------------------------------------------------------------
# bundle shipping
# ---------------------------------------------------------------------------


def test_ship_bundle_artifacts(telemetry, tmp_path, monkeypatch):
    monkeypatch.setenv("PHOTON_RUN_ID", "test-run")
    fleet.mark_init()
    with obs.span("fit"):
        with obs.span("solve"):
            pass
    obs_trace.instant("promoted", cat="pilot")
    obs_trace.counter("queue_depth", 3)
    out_dir = fleet.ship_bundle(str(tmp_path))
    assert os.path.basename(out_dir) == "obs-host-0"

    # spans.jsonl is a valid telemetry stream whose records carry the
    # raw perf stamps the merge needs
    spans_path = os.path.join(out_dir, fleet.SPANS_FILE)
    export.validate_jsonl(spans_path)
    lines = [json.loads(x) for x in open(spans_path)]
    assert lines[0]["host"]["run_id"] == "test-run"
    spans = [x for x in lines if x.get("type") == "span"]
    assert {s["name"] for s in spans} == {"fit", "solve"}
    assert all("t0" in s and "t1" in s for s in spans)

    bundle = json.load(open(os.path.join(out_dir, fleet.BUNDLE_FILE)))
    assert bundle["schema"] == fleet.BUNDLE_SCHEMA
    assert bundle["host"]["run_id"] == "test-run"
    assert bundle["clock"]["skew_bound_seconds"] >= 0.0
    kinds = {ev["kind"] for ev in bundle["events"]}
    assert {"instant", "counter"} <= kinds
    assert bundle["ledger"] is None  # ledger off in this test


def test_ship_bundle_extra_block(telemetry, tmp_path):
    out_dir = fleet.ship_bundle(str(tmp_path), extra={"verdict": "ok"})
    bundle = json.load(open(os.path.join(out_dir, fleet.BUNDLE_FILE)))
    assert bundle["extra"] == {"verdict": "ok"}


# ---------------------------------------------------------------------------
# synthetic two-host merge
# ---------------------------------------------------------------------------


def _forge_bundle(
    run_dir,
    rank,
    *,
    offset,
    spans,
    ledger_rows=None,
    process_count=2,
    skew_bound=1e-6,
):
    """Write a forged rank bundle: ``spans`` are (name, t0, t1) in the
    host's own perf_counter base; ``offset`` is its perf→epoch shift."""
    d = fleet.host_dir(str(run_dir), rank)
    os.makedirs(d, exist_ok=True)
    host = {
        "process_index": rank, "process_count": process_count,
        "hostname": f"host-{rank}", "pid": 1000 + rank,
        "device_kind": "cpu", "local_device_count": 4,
        "global_device_count": 4 * process_count,
        "jax_version": "0.0-test", "run_id": "forged",
    }
    clock_half = {
        "offset": offset, "spread": 0.0,
        "epoch": offset + 100.0, "perf_counter": 100.0,
    }
    lines = [{"type": "telemetry", "version": 1, "spans_dropped": 0,
              "host": host}]
    for name, t0, t1 in spans:
        lines.append({
            "type": "span", "name": name, "path": name,
            "seconds": t1 - t0, "thread": "main", "attrs": {},
            "device_wait_seconds": None, "t0": t0, "t1": t1,
        })
    with open(os.path.join(d, fleet.SPANS_FILE), "w") as f:
        f.write("".join(json.dumps(x) + "\n" for x in lines))
    bundle = {
        "schema": fleet.BUNDLE_SCHEMA, "host": host,
        "clock": {"init": clock_half, "commit": clock_half,
                  "skew_bound_seconds": skew_bound},
        "metrics": {"counters": {}, "gauges": {}},
        "events": [], "events_dropped": 0, "spans_dropped": 0,
        "ledger": (
            None if ledger_rows is None else {"rows": ledger_rows}
        ),
        "health": None, "extra": {},
    }
    with open(os.path.join(d, fleet.BUNDLE_FILE), "w") as f:
        json.dump(bundle, f)
    return d


def _two_host_dir(tmp_path):
    """Two ranks with DIFFERENT perf bases joined by known offsets:
    rank 0 (offset 1000) works at local [1.0, 3.0] → epoch [1001, 1003];
    rank 1 (offset 996) at local [4.5, 8.5] → epoch [1000.5, 1004.5] —
    interleaved on the fleet clock even though their local stamps are
    disjoint."""
    run = tmp_path / "fleet"
    _forge_bundle(
        run, 0, offset=1000.0, spans=[("fit", 1.0, 3.0)],
        ledger_rows=[{"coordinate": "fixed", "phase": "fit",
                      "program": "fused_fit", "seconds": 2.0,
                      "dispatches": 4, "host_gap_seconds": 0.0}],
    )
    _forge_bundle(
        run, 1, offset=996.0, spans=[("fit", 4.5, 8.5)],
        ledger_rows=[{"coordinate": "fixed", "phase": "fit",
                      "program": "fused_fit", "seconds": 4.0,
                      "dispatches": 4, "host_gap_seconds": 0.0}],
    )
    return run


def test_merge_two_hosts_one_timeline(tmp_path):
    run = _two_host_dir(tmp_path)
    bundles, gaps = fleet.discover_bundles(str(run))
    assert [fleet._bundle_rank(b) for b in bundles] == [0, 1]
    assert gaps == []
    doc = fleet.merge_chrome_trace(bundles, gaps)
    events = doc["traceEvents"]
    pids = {ev["pid"] for ev in events}
    assert pids == {0, 1}
    # non-metadata events land in fleet-time order (ONE monotonic
    # timeline), and metadata all sorts first
    body = [ev for ev in events if ev["ph"] != "M"]
    ts = [ev["ts"] for ev in body]
    assert ts == sorted(ts)
    meta_prefix = len(events) - len(body)
    assert all(ev["ph"] == "M" for ev in events[:meta_prefix])
    # the injected offsets place rank 1's span start 0.5 s BEFORE
    # rank 0's even though its local stamp is smaller by 1000.5:
    # epoch0 = 1000.5, so rank 0's fit starts at +0.5 s, rank 1's at 0
    spans = {ev["pid"]: ev for ev in body if ev["ph"] == "X"}
    assert spans[1]["ts"] == pytest.approx(0.0, abs=1.0)
    assert spans[0]["ts"] == pytest.approx(0.5e6, rel=1e-6)
    assert doc["otherData"]["clock_skew_bound_seconds"] <= 1e-5
    assert [h["process_index"] for h in doc["otherData"]["hosts"]] == [0, 1]


def test_merged_trace_validates_on_disk(tmp_path):
    run = _two_host_dir(tmp_path)
    trace_path = tmp_path / "fleet-trace.json"
    report, doc = fleet.merge_run(str(run), trace_path=str(trace_path))
    assert trace_path.exists()
    assert validate_chrome_trace(str(trace_path)) == len(
        doc["traceEvents"]
    )
    assert report["bundles"] == 2


def test_straggler_report_names_slowest_rank(tmp_path):
    run = _two_host_dir(tmp_path)
    bundles, gaps = fleet.discover_bundles(str(run))
    report = fleet.straggler_report(bundles, gaps)
    assert report["ranks"] == [0, 1]
    assert report["missing_ranks"] == []
    # rank 1 attributed 4 s vs rank 0's 2 s
    assert report["straggler"]["process_index"] == 1
    assert report["straggler_skew_seconds"] == pytest.approx(2.0)
    # wall = slowest window (rank 1's 4 s); rank 0 waits 2 s of it →
    # fraction = 2 / (2 ranks × 4 s)
    assert report["wall_seconds"] == pytest.approx(4.0)
    per = {r["process_index"]: r for r in report["per_rank"]}
    assert per[0]["collective_wait_seconds"] == pytest.approx(2.0)
    assert per[1]["collective_wait_seconds"] == pytest.approx(0.0)
    assert report["collective_fraction"] == pytest.approx(0.25)
    # span-named program: completion-window skew on the fleet clock
    fit = report["programs"]["fit"]
    assert fit["on_all_ranks"]
    # rank 0 finishes at epoch 1003, rank 1 at 1004.5
    assert fit["window_skew_seconds"] == pytest.approx(1.5)
    # ledger-named program: per-rank attributed seconds name the slow rank
    fused = report["programs"]["fused_fit"]
    assert fused["slowest_rank"] == 1
    assert fused["seconds_skew"] == pytest.approx(2.0)


def test_ledger_off_rank_falls_back_to_span_window(tmp_path):
    run = tmp_path / "fleet"
    _forge_bundle(run, 0, offset=0.0, spans=[("fit", 1.0, 4.0)],
                  process_count=1)
    bundles, gaps = fleet.discover_bundles(str(run))
    report = fleet.straggler_report(bundles, gaps)
    assert report["per_rank"][0]["attributed_seconds"] == pytest.approx(
        3.0
    )


# ---------------------------------------------------------------------------
# degradation: torn spans, missing rank, uncommitted bundle
# ---------------------------------------------------------------------------


def test_truncated_spans_merge_partially_with_named_gap(tmp_path):
    run = _two_host_dir(tmp_path)
    spans_path = os.path.join(
        fleet.host_dir(str(run), 1), fleet.SPANS_FILE
    )
    with open(spans_path, "a") as f:
        f.write('{"type": "span", "name": "torn", "t0": 5.0, "t')
    bundles, gaps = fleet.discover_bundles(str(run))
    assert len(bundles) == 2  # the rank still merges
    assert any("truncated" in g and "obs-host-1" in g for g in gaps)
    # the torn record is dropped, the committed one survives
    r1 = [b for b in bundles if fleet._bundle_rank(b) == 1][0]
    assert [s["name"] for s in r1["spans"]] == ["fit"]
    # and the merged artifact still validates
    trace_path = tmp_path / "trace.json"
    report, _ = fleet.merge_run(str(run), trace_path=str(trace_path))
    validate_chrome_trace(str(trace_path))
    assert any("truncated" in g for g in report["gaps"])


def test_uncommitted_bundle_is_a_named_gap(tmp_path):
    run = _two_host_dir(tmp_path)
    os.remove(os.path.join(fleet.host_dir(str(run), 1),
                           fleet.BUNDLE_FILE))
    bundles, gaps = fleet.discover_bundles(str(run))
    assert len(bundles) == 1
    assert any("commit point" in g for g in gaps)
    report = fleet.straggler_report(bundles, gaps)
    assert report["missing_ranks"] == [1]
    assert any("rank 1: no bundle shipped" in g for g in report["gaps"])


def test_empty_run_dir_reports_not_raises(tmp_path):
    bundles, gaps = fleet.discover_bundles(str(tmp_path))
    assert bundles == []
    report = fleet.straggler_report(bundles, gaps)
    assert report["bundles"] == 0
    doc = fleet.merge_chrome_trace(bundles, gaps)
    assert doc["traceEvents"] == []


# ---------------------------------------------------------------------------
# fleetview CLI
# ---------------------------------------------------------------------------


def test_fleetview_cli_exit_codes(tmp_path, capsys):
    from photon_tpu.cli import fleetview

    run = _two_host_dir(tmp_path)
    rc = fleetview.main(["--run-dir", str(run), "--expect-ranks", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "slowest rank: 1" in out
    assert "rank 0" in out and "rank 1" in out

    assert fleetview.main(
        ["--run-dir", str(run), "--expect-ranks", "3"]
    ) == 1
    empty = tmp_path / "empty"
    empty.mkdir()
    assert fleetview.main(["--run-dir", str(empty)]) == 2
    capsys.readouterr()


def test_fleetview_cli_json_report(tmp_path, capsys):
    from photon_tpu.cli import fleetview

    run = _two_host_dir(tmp_path)
    out_json = tmp_path / "report.json"
    trace = tmp_path / "trace.json"
    rc = fleetview.main([
        "--run-dir", str(run), "--json", str(out_json),
        "--trace", str(trace),
    ])
    capsys.readouterr()
    assert rc == 0
    report = json.load(open(out_json))
    assert report["straggler"]["process_index"] == 1
    validate_chrome_trace(str(trace))


# ---------------------------------------------------------------------------
# monitor-port arbitration
# ---------------------------------------------------------------------------


def test_resolve_monitor_port():
    assert fleet.resolve_monitor_port(0) == 0
    assert fleet.resolve_monitor_port(-1) == -1
    assert fleet.resolve_monitor_port(9100, 0) == 9100
    assert fleet.resolve_monitor_port(9100, 3) == 9103
    # identity-based default: this process is rank 0
    assert fleet.resolve_monitor_port(9100) == 9100


def test_two_rank_exporters_coexist_on_offset_ports(telemetry):
    """Two in-process MonitorServers on rank-offset ports — the per-host
    collision the offset exists to prevent."""
    from photon_tpu.obs.monitor import MonitorServer

    for _ in range(5):
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            base = probe.getsockname()[1]
        ports = [fleet.resolve_monitor_port(base, k) for k in range(2)]
        assert ports == [base, base + 1]
        try:
            with MonitorServer(ports[0]) as m0, \
                    MonitorServer(ports[1]) as m1:
                for mon in (m0, m1):
                    resp = urllib.request.urlopen(
                        mon.url + "/metrics", timeout=5
                    )
                    assert resp.status == 200
                    resp.read()
                assert m0.port == base and m1.port == base + 1
            return
        except OSError:
            continue  # another process raced us onto base+1; retry
    pytest.skip("could not find two adjacent free ports")


# ---------------------------------------------------------------------------
# MULTICHIP row
# ---------------------------------------------------------------------------


def test_multichip_row_shape(tmp_path):
    run = _two_host_dir(tmp_path)
    report, _ = fleet.merge_run(str(run))
    row = fleet.multichip_row(report, n_devices=8)
    assert row["schema"] == 2
    assert row["ok"] is True
    assert row["n_devices"] == 8
    assert row["per_rank_dispatch_seconds"] == {
        "0": pytest.approx(2.0), "1": pytest.approx(4.0)
    }
    assert row["multichip_straggler_skew_seconds"] == pytest.approx(2.0)
    assert row["multichip_collective_fraction"] == pytest.approx(0.25)
    assert row["report"]["ranks"] == [0, 1]


def test_multichip_row_not_ok_with_gaps(tmp_path):
    run = _two_host_dir(tmp_path)
    os.remove(os.path.join(fleet.host_dir(str(run), 1),
                           fleet.BUNDLE_FILE))
    report, _ = fleet.merge_run(str(run))
    assert fleet.multichip_row(report)["ok"] is False


@pytest.mark.parametrize(
    "present, slot",
    [(["MULTICHIP_r01.json"], "MULTICHIP_r02.json"),
     ([], "MULTICHIP_r01.json")],  # the repo's own state: no row committed
    ids=["after-r01", "empty-dir"],
)
def test_write_multichip_row_takes_next_slot(tmp_path, present, slot):
    for name in present:
        (tmp_path / name).write_text("{}")
    path = fleet.write_multichip_row({"ok": True}, root=str(tmp_path))
    assert os.path.basename(path) == slot
    assert json.load(open(path)) == {"ok": True}
