"""The four readers ``ml25m.refit4`` brought, on hand-made records and a
hand-made four-plane trace: ``fit.programs_per_fit`` and
``mesh.placement_skew`` from the ``fit`` stage's attributes,
``kernel.collective_share_pct`` and ``device.idle_share.worst_chip`` from
the device planes; each gives nothing where there is nothing to read (the
parent's program, whose unfused loop records no ``fit`` stage; the CPU,
which has no device plane)."""

import pytest

from benchmark import xplane
from benchmark.manifest import Manifest

MAN = Manifest()
STAGE_READERS = ("fit.programs_per_fit", "mesh.placement_skew")
TRACE_READERS = ("kernel.collective_share_pct",
                 "device.idle_share.worst_chip")
ATTRS = {"coordinates": {}, "devices": 4, "programs": 110,
         "placed_bytes": [16, 10, 10, 4]}


class Rec:
    kind, thread = "stage", "MainThread"

    def __init__(self, name, t0, t1, attrs=None):
        self.name = self.path = name
        self.t0, self.t1, self.seconds = t0, t1, t1 - t0
        self.attrs = attrs


class Ctx:
    units, window_start = 2, 100.0
    trace = None
    xplane = xplane

    class spans:
        closed = [("bench.fit", 100.0, 103.0), ("bench.fit", 103.0, 106.0)]


def _ring(monkeypatch, records):
    from photon_tpu import obs

    monkeypatch.setattr(obs.TRACER, "completed", lambda: list(records))


def _window(attrs):
    return [
        Rec("fit", 50.0, 51.0, dict(ATTRS, programs=999)),
        Rec("fit", 100.0, 100.5, attrs),
        Rec("fit", 103.0, 103.5, attrs),
        Rec("fit", 110.0, 111.0, dict(ATTRS, programs=999)),
    ]


@pytest.mark.parametrize("name, value", [
    ("fit.programs_per_fit", 110.0),
    ("mesh.placement_skew", 16 * 4 / 40),  # the fullest over the mean
])
def test_a_stage_reader_gives_its_number_from_the_attributes(
        monkeypatch, name, value):
    _ring(monkeypatch, _window(ATTRS))
    assert MAN.metric_reader(name)(Ctx) == pytest.approx(value)


def test_an_even_placement_reads_one(monkeypatch):
    _ring(monkeypatch, _window(dict(ATTRS, placed_bytes=[7, 7, 7, 7])))
    assert MAN.metric_reader("mesh.placement_skew")(Ctx) == 1.0


@pytest.mark.parametrize(
    "attrs", [None, {}, {"coordinates": {"per-user": {}}, "home": None}],
    ids=["no_attrs", "empty", "the_fused_fits"])
@pytest.mark.parametrize("name", STAGE_READERS)
def test_a_stage_reader_gives_nothing_without_its_attribute(
        monkeypatch, name, attrs):
    _ring(monkeypatch, _window(attrs))
    assert MAN.metric_reader(name)(Ctx) is None


@pytest.mark.parametrize("name", STAGE_READERS)
def test_a_stage_reader_gives_nothing_without_a_fit_stage(
        monkeypatch, name):
    _ring(monkeypatch, [Rec("prepare", 100.0, 101.0)])
    assert MAN.metric_reader(name)(Ctx) is None


def _traced(devices):
    loaded = {"devices": devices,
              "host": [("bench.window", 0.0, 10.0)]}

    class Traced(Ctx):
        trace = xplane.Reduced(loaded)

    return Traced


def _four_planes():
    """Three planes busy 8 s of 10, one 5 s; the first runs 1 s of
    all-reduce (its start and done halves) and 0.5 s of all-gather inside
    a fusion of another name, which is not a collective."""
    first = [
        ("%fusion.1 = f32[8]{0} fusion(%all-gather.9)", 0.0, 6.5),
        ("%all-reduce-start.2 = f32[64]{0} all-reduce-start(%x)", 6.5, 7.0),
        ("%all-reduce-done.2 = f32[64]{0} all-reduce-done(%y)", 7.0, 7.5),
        ("%all-gather.3 = f32[64]{0} all-gather(%z)", 7.5, 8.0),
    ]
    busy = [("%fusion.1 = f32[8]{0} fusion(%p)", 0.0, 8.0)]
    idler = [("%fusion.1 = f32[8]{0} fusion(%p)", 1.0, 6.0)]
    return {"/device:TPU:0": first, "/device:TPU:1": busy,
            "/device:TPU:2": idler, "/device:TPU:3": busy}


def test_the_collectives_share_is_of_the_first_planes_busy_time():
    ctx = _traced(_four_planes())
    got = MAN.metric_reader("kernel.collective_share_pct")(ctx)
    assert got == pytest.approx(100.0 * 1.5 / 8.0)


def test_a_trace_without_a_collective_reads_zero():
    ctx = _traced({"/device:TPU:0": [("%fusion.1 = f32[8]{0} fusion(%p)",
                                      0.0, 8.0)]})
    assert MAN.metric_reader("kernel.collective_share_pct")(ctx) == 0.0


def test_the_worst_chip_is_the_plane_that_worked_least():
    ctx = _traced(_four_planes())
    got = MAN.metric_reader("device.idle_share.worst_chip")(ctx)
    assert got == pytest.approx(50.0)
    # the mean of the planes, which device.idle_share.refit reads
    assert 100.0 * (1 - ctx.trace.busy_s / 10.0) == pytest.approx(27.5)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_a_trace_reader_gives_nothing_without_a_device_plane(name):
    assert MAN.metric_reader(name)(Ctx) is None
    assert MAN.metric_reader(name)(_traced({})) is None


@pytest.mark.parametrize("name", STAGE_READERS + TRACE_READERS)
def test_the_new_metrics_list_the_four_chip_cell_alone(name):
    (metric,) = [m for m in MAN.doc["per_layer"] if m["name"] == name]
    assert metric["workloads"] == ["ml25m.refit4"]
    assert metric["layer"] == "Mesh" and metric["better"] == "lower"
    assert metric["moves"] == "train_rows_per_s"
