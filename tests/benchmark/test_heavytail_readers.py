"""The three readers of the ``fit`` stage's attributes
(benchmark/fitstage.py; ``plan.passive_row_share``, ``plan.solver_shapes``,
``solve.xla_newton_slab_share``) on hand-built records: a value where the
program wrote the attribute, nothing where it did not (the parent's
program, whose ``fit`` stage carries none)."""

import pytest

from benchmark import fitstage
from benchmark.manifest import Manifest

from conftest import check_the_cap_metrics_list_cells_whose_cap_binds

MAN = Manifest()
READERS = ("plan.passive_row_share", "plan.solver_shapes",
           "solve.xla_newton_slab_share")

# What FusedFit.run writes for a capped GLMix: per random-effect
# coordinate the planner's counts and the rungs [entities, row cap, route].
COORDINATES = {
    "per-user": {
        "active_rows": 750, "passive_rows": 250, "capped_entities": 3,
        "slab_rows": 40 * 16 + 10 * 64,
        "rungs": [[40, 16, "newton_kernel"], [10, 64, "newton_kernel"]],
    },
    "per-movie": {
        "active_rows": 400, "passive_rows": 600, "capped_entities": 2,
        "slab_rows": 5 * 128 + 2 * 2048,
        "rungs": [[5, 128, "newton_kernel"], [2, 2048, "newton_xla"]],
    },
}


class Rec:
    """A record as ``photon_tpu.obs`` keeps it."""

    kind, thread = "stage", "MainThread"

    def __init__(self, name, t0, t1, attrs=None):
        self.name = self.path = name
        self.t0, self.t1, self.seconds = t0, t1, t1 - t0
        self.attrs = attrs


class Ctx:
    units, window_start = 2, 100.0

    class spans:
        closed = [("bench.fit", 100.0, 103.0), ("bench.fit", 103.0, 106.0)]


def _ring(monkeypatch, records):
    from photon_tpu import obs

    monkeypatch.setattr(obs.TRACER, "completed", lambda: list(records))


def _window(attrs):
    return [
        Rec("fit", 50.0, 51.0, {"coordinates": {"warm-up": {}}}),
        Rec("fit", 100.0, 100.5, attrs),
        Rec("fit.dispatch", 100.1, 100.4),
        Rec("fit", 103.0, 103.5, attrs),
        # after the window's last unit: the harness's own calls
        Rec("fit", 110.0, 111.0, {"coordinates": {"after": {}}}),
    ]


def test_the_readers_take_the_windows_last_fit_stage(monkeypatch):
    _ring(monkeypatch, _window({"coordinates": COORDINATES}))
    assert fitstage.coordinates(Ctx) == COORDINATES
    assert len(fitstage.rungs(Ctx)) == 4
    assert fitstage.rungs(Ctx, "newton_xla") == [[2, 2048, "newton_xla"]]


@pytest.mark.parametrize("name, value", [
    ("plan.passive_row_share", 60.0),  # the movies': 600 of 1 000
    ("plan.solver_shapes", 4.0),
    ("solve.xla_newton_slab_share",
     100.0 * 2 * 2048 / (40 * 16 + 10 * 64 + 5 * 128 + 2 * 2048)),
])
def test_a_reader_gives_its_number_from_the_attributes(
        monkeypatch, name, value):
    _ring(monkeypatch, _window({"coordinates": COORDINATES}))
    assert MAN.metric_reader(name)(Ctx) == pytest.approx(value)


@pytest.mark.parametrize("attrs", [None, {}, {"buckets": [[16, 40]]}],
                         ids=["no_attrs", "empty", "other_attributes"])
@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_nothing_where_the_program_wrote_no_such_attribute(
        monkeypatch, name, attrs):
    _ring(monkeypatch, _window(attrs))
    assert MAN.metric_reader(name)(Ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_nothing_for_a_window_without_a_fit_stage(
        monkeypatch, name):
    _ring(monkeypatch, [Rec("prepare", 100.0, 101.0)])
    assert MAN.metric_reader(name)(Ctx) is None


def test_the_xla_share_needs_a_newton_rung(monkeypatch):
    direct = {"per-user": dict(COORDINATES["per-user"],
                               rungs=[[40, 16, "direct"]])}
    _ring(monkeypatch, _window({"coordinates": direct}))
    assert MAN.metric_reader("solve.xla_newton_slab_share")(Ctx) is None
    assert MAN.metric_reader("plan.solver_shapes")(Ctx) == 1.0


@pytest.mark.parametrize("name", READERS)
def test_the_new_metrics_list_the_new_cell_alone(name):
    """What "alone" meant (PR 32): the cell they were made for, and no
    cell in which no cap binds; a later cell whose caps bind may be
    listed beside it (conftest.py holds the rule for any manifest)."""
    check_the_cap_metrics_list_cells_whose_cap_binds(MAN, name)
