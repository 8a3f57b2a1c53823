"""The readers of the program's own stage records (benchmark/stages.py
and the metrics that share it), on hand-built records, and the rehearsal
that every cell prints every one of them."""

import pytest

from benchmark import stages, xplane
from benchmark.manifest import Manifest

from conftest import (
    cell_names,
    check_a_metric_of_one_kind_lists_no_cell_of_another,
    check_a_traced_rehearsal_prints_every_metric_that_lists_the_cell,
    kinds_of_metric,
    rehearse,
)


class Rec:
    """A record as ``photon_tpu.obs`` keeps it."""

    def __init__(self, name, t0, t1, thread="MainThread", path=None,
                 seconds=None, kind="stage"):
        self.name, self.t0, self.t1, self.thread = name, t0, t1, thread
        self.path = path or name
        self.seconds = t1 - t0 if seconds is None else seconds
        self.kind = kind


# Two jobs in a window that starts at 100 s; the trace's clock runs 900 s
# ahead and the traced window is the first job, [1000, 1020].
JOB = [
    Rec("dataset", 0.0, 0.5),
    Rec("raw_transfer", 0.25, 0.5, path="dataset/raw_transfer"),
    Rec("prepare", 1.0, 4.0),
    Rec("plan", 1.1, 3.0, thread="plan_0"),
    Rec("plan", 1.2, 3.6, thread="plan_1"),
    Rec("pack", 3.0, 3.2, thread="plan_0"),
    Rec("pack", 3.7, 3.9, path="prepare/pack"),
    Rec("compile", 1.0, 3.5, thread="compile_0"),
    Rec("compile.trace", 1.0, 2.0, thread="compile_0", kind="event"),
    Rec("compile.lower", 2.0, 2.5, thread="compile_0", kind="event"),
    Rec("compile.cache_load", 2.5, 3.25, thread="compile_0", kind="event"),
    Rec("fit", 4.0, 6.0),
    Rec("fit.operands", 4.0, 4.25, path="fit/fit.operands"),
    Rec("compile_wait", 4.25, 4.5, path="fit/compile_wait"),
    Rec("fit.materialize", 4.5, 5.0, path="fit/fit.materialize"),
    Rec("fit.dispatch", 5.0, 6.0, path="fit/fit.dispatch"),
    Rec("fused_fit", 4.0, 9.0, kind="span"),  # a gated span: never read
    Rec("save", 10.0, 19.0),
    Rec("save.records", 10.0, 13.0, path="save/save.records"),
    # summed over blocks: seconds under the envelope
    Rec("save.encode", 13.0, 19.0, path="save/save.encode", seconds=4.0,
        kind="event"),
    Rec("save.write", 13.5, 19.0, path="save/save.write", seconds=2.0,
        kind="event"),
]


def _shift(rec, by):
    return Rec(rec.name, rec.t0 + by, rec.t1 + by, rec.thread, rec.path,
               rec.seconds, rec.kind)


# What the harness itself leaves in the ring once the window has closed
# (kind.answer(), the plain reference, the comparison): compiles on the
# training thread, and a stage of a program call outside any unit.
AFTER = [
    Rec("compile.trace", 139.5, 141.0, kind="event"),
    Rec("compile.lower", 141.0, 141.5, kind="event"),
    Rec("compile.cache_load", 141.5, 143.0, kind="event"),
    Rec("fit", 143.0, 144.0),
    Rec("save.records", 144.0, 145.0, path="save/save.records"),
]

RING = ([Rec("fit", 50.0, 51.0)]  # the warm-up job: before the window
        + [_shift(r, 100.0) for r in JOB]
        + [_shift(r, 120.0) for r in JOB]
        + AFTER)


class Spans:
    """The harness's own spans: the warm-up job's, then the two jobs'."""

    closed = [("bench.save", 60.0, 69.0)] + [
        (name, s + by, e + by)
        for by in (100.0, 120.0)
        for name, s, e in (
            ("bench.dataset", 0.0, 0.5), ("bench.prepare", 1.0, 4.0),
            ("bench.fit", 4.0, 9.0), ("bench.save", 10.0, 19.0))]


class Trace:
    lo, hi = 1000.0, 1020.0
    devices = {"/device:TPU:0": [("fusion.1", 1004.5, 1009.0)]}

    def first_device(self):
        return self.devices["/device:TPU:0"]


class Ctx:
    units, window_start, trace, xplane = 2, 100.0, Trace(), xplane
    spans = Spans()


@pytest.fixture
def ring(monkeypatch):
    from photon_tpu import obs

    monkeypatch.setattr(obs.TRACER, "completed", lambda: list(RING))


EXPECTED = {
    "save.records_s": 3.0,
    "save.encode_s": 4.0,
    "save.write_s": 2.0,
    "fit.host_s.retrain": 2.0,
    "fit.host_s.refit": 2.0,
    "fit.operands_s": 0.75,
    "compile.wait_s": 0.25,
    "compile.trace_lower_s": 1.5,
    "compile.cache_load_s": 0.75,
    "ingest.pack_s": 0.4,
    "ingest.plan_wall_s": 2.5,
    # Idle in [1000, 1020]: all but [1004.5, 1009] = 15.5 s. No stage of
    # the training thread covers [1000.5, 1001], [1006, 1010] (of which
    # [1006, 1009] is busy) and [1019, 1020]: 0.5 + 1 + 1 = 2.5 s.
    "device.idle_unattributed.retrain": 100.0 * 2.5 / 15.5,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_hand_built_records(ring, name):
    read = Manifest().metric_reader(name)
    assert read(Ctx()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_nothing_for_a_program_without_records(
        monkeypatch, name):
    from photon_tpu import obs

    monkeypatch.setattr(obs.TRACER, "completed", lambda: [])
    assert Manifest().metric_reader(name)(Ctx()) is None


def test_the_window_ends_with_its_last_unit(ring):
    """Records that start after the last unit (the reference's compiles,
    whatever the harness calls of the program) are no part of the window;
    neither is anything before its start."""
    assert stages.window_end(Ctx()) == 139.0
    got = stages.records(Ctx())
    assert len(got) == 2 * (len(JOB) - 1)  # all but the gated span
    assert all(100.0 <= r.t0 <= 139.0 for r in got)
    assert not set(map(id, got)) & set(map(id, AFTER))

    class NoUnit(Ctx):
        class spans:
            closed = Spans.closed[:1]  # the warm-up's alone

    assert stages.records(NoUnit()) == []


@pytest.mark.parametrize("name,planted", [
    ("compile.trace_lower_s", Rec("compile.trace", 139.5, 149.5,
                                  kind="event")),
    ("compile.cache_load_s", Rec("compile.cache_load", 140.0, 145.0,
                                 kind="event")),
    ("fit.host_s.retrain", Rec("fit", 139.25, 140.0)),
    ("save.records_s", Rec("save.records", 150.0, 151.0)),
])
def test_a_record_after_the_last_unit_moves_no_reader(
        monkeypatch, name, planted):
    from photon_tpu import obs

    monkeypatch.setattr(
        obs.TRACER, "completed", lambda: list(RING) + [planted])
    assert Manifest().metric_reader(name)(Ctx()) == pytest.approx(
        EXPECTED[name])


def test_every_new_metric_is_declared_with_its_reader():
    """Each is declared, has its reader and is a metric of the kind whose
    window it reads: it lists at least the cells that kind came with, and
    no cell of the other kind (conftest.py: the rule for a new cell)."""
    man = Manifest()
    declared = {m["name"]: m for m in man.doc["per_layer"]}
    for name in sorted(EXPECTED):
        assert name in declared and callable(man.metric_reader(name)), name
        kind = "refit" if name == "fit.host_s.refit" else "retrain_job"
        assert kinds_of_metric(declared[name]) == {kind}, name
    check_a_metric_of_one_kind_lists_no_cell_of_another(man)


def test_each_moment_goes_to_the_deepest_open_stage(ring):
    main = [r for r in stages.records(Ctx()) if r.thread == "MainThread"]
    assert all(r.kind != "span" for r in main)
    pieces = stages.deepest(
        [r for r in main if r.t1 <= 120.0 and r.kind == "stage"])
    assert pieces == [
        ("dataset", 100.0, 100.25),
        ("dataset/raw_transfer", 100.25, 100.5),
        # prepare keeps what its one child on this thread leaves: the wait
        # for the planner pool
        ("prepare", 101.0, 103.7), ("prepare/pack", 103.7, 103.9),
        ("prepare", 103.9, 104.0),
        ("fit/fit.operands", 104.0, 104.25),
        ("fit/compile_wait", 104.25, 104.5),
        ("fit/fit.materialize", 104.5, 105.0),
        ("fit/fit.dispatch", 105.0, 106.0),
        ("save/save.records", 110.0, 113.0),
        # encode and write are sums over interleaved blocks, no intervals:
        # their time stays with save
        ("save", 113.0, 119.0),
    ]
    assert stages.training_thread(Ctx()) == "MainThread"
    by = stages.idle_by_leaf(Ctx())
    assert by["prepare"] == pytest.approx(2.7 + 0.1)
    assert by["fit/fit.operands"] == pytest.approx(0.25)
    assert "fit/fit.materialize" not in by  # the device was busy under it
    assert by["save"] == pytest.approx(6.0)
    assert sum(by.values()) == pytest.approx(15.5)


@pytest.mark.parametrize("cell", cell_names(Manifest()))
def test_a_traced_rehearsal_prints_every_new_metric_of_the_cell(
        tiny_root, cell):
    man = Manifest(tiny_root)
    check_a_traced_rehearsal_prints_every_metric_that_lists_the_cell(
        man, cell, rehearse(man, cell, True, seed=2**31 + 11))
