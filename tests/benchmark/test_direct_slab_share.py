"""``solve.xla_direct_slab_share`` (benchmark/metrics/): the share of the
direct route's slab rows that keep the vmapped XLA CG, from the ``fit``
stage's ``rungs``, on hand-built records and on the CPU rehearsal of a
cell it lists, where the gate of the CG kernel is closed."""

import pytest

from benchmark.manifest import Manifest

from conftest import rehearse
from test_heavytail_readers import COORDINATES, Ctx, _ring, _window

MAN = Manifest()
NAME = "solve.xla_direct_slab_share"

DIRECT = {
    "per-user": dict(COORDINATES["per-user"], rungs=[
        [1213, 512, "direct_kernel"], [4017, 256, "direct_kernel"],
        [10, 64, "direct"]]),
    "per-item": dict(COORDINATES["per-movie"], rungs=[
        [40, 2048, "direct_kernel"], [30, 128, "direct"]]),
}


def test_the_share_is_of_the_direct_rungs_slab_rows(monkeypatch):
    _ring(monkeypatch, _window({"coordinates": DIRECT}))
    xla = 10 * 64 + 30 * 128
    every = 1213 * 512 + 4017 * 256 + 40 * 2048 + xla
    assert MAN.metric_reader(NAME)(Ctx) == pytest.approx(100.0 * xla / every)


@pytest.mark.parametrize("attrs", [
    None, {"coordinates": COORDINATES},
    {"coordinates": {"per-user": dict(COORDINATES["per-user"], rungs=[])}},
], ids=["no_attributes", "newton_rungs_only", "no_rungs"])
def test_no_direct_rung_gives_no_number(monkeypatch, attrs):
    _ring(monkeypatch, _window(attrs))
    assert MAN.metric_reader(NAME)(Ctx) is None


def test_a_program_with_no_kernel_reads_every_direct_rung_as_xla(monkeypatch):
    """The parent's program names every direct rung ``direct``."""
    plain = {c: dict(a, rungs=[[b, r, "direct"] for b, r, _ in a["rungs"]])
             for c, a in DIRECT.items()}
    _ring(monkeypatch, _window({"coordinates": plain}))
    assert MAN.metric_reader(NAME)(Ctx) == 100.0


def test_the_entry_lists_the_cell_whose_rungs_are_direct():
    """``yahoo.refit`` alone: ``linear.refit`` runs the direct route too,
    but a metric that lists it is held to list every cell of its kind
    (``test_manifest.py`` pins linear.refit's metrics to the kind's), and
    the cells of the Newton route have no direct rung to read."""
    (entry,) = [m for m in MAN.doc["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "Kernels",
        "moves": "train_rows_per_s", "workloads": ["yahoo.refit"],
    }
    assert MAN.config(MAN.cell("yahoo.refit")["config"])["task"] == (
        "LINEAR_REGRESSION")


def test_the_cpu_rehearsal_reads_every_direct_rung_as_xla(tiny_root):
    man = Manifest(tiny_root)
    out = rehearse(man, "yahoo.refit", trace=1, seed=2**31 + 7)
    assert out["metrics"][NAME] == {"value": 100.0, "unit": "%"}
