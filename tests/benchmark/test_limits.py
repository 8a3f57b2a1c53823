"""Every limit of every cell lies between the two chip readings it was set
from (benchmark/limits/<cell>.json, PERF.md section 6), with room on both
sides, and the control's readings fail the committed limits."""

import pytest

from benchmark import check
from benchmark.manifest import Manifest, _read_json

MAN = Manifest()
CELLS = [w["name"] for w in MAN.doc["workloads"]]
ROOM = 1.25  # the least factor between a limit and either reading


def _numbers(cell):
    doc = _read_json(MAN.limits_path(cell))
    assert doc["cell"] == cell
    assert set(doc["readings"]) == set(doc["limits"])
    return [(name, doc["limits"][name], doc["readings"][name])
            for name in doc["limits"]]


@pytest.mark.parametrize("cell", CELLS)
def test_each_limit_lies_between_its_readings_with_room(cell):
    for name, limit, r in _numbers(cell):
        if r["upper_from"] == "exact":
            assert limit == r["lower"] == r["upper"] == 0.0, name
            continue
        assert r["lower"] * ROOM <= limit <= r["upper"] / ROOM, (name, r)


@pytest.mark.parametrize("cell", CELLS)
def test_a_control_three_times_the_lower_reading_is_the_upper_one(cell):
    for name, limit, r in _numbers(cell):
        if r["control"] is None:
            continue
        if r["control"] >= 3.0 * r["lower"]:
            assert r["upper"] <= r["control"], (name, r)
            assert limit < r["control"], (name, r)
        else:
            assert r["upper_from"] != "control", (name, r)


@pytest.mark.parametrize("cell", CELLS)
def test_the_controls_readings_fail_and_the_programs_pass(cell):
    limits = MAN.limits(cell)
    numbers = _numbers(cell)
    largest_sound = {name: r["lower"] for name, _, r in numbers}
    ok, compared = check.verdict(largest_sound, limits)
    assert ok, compared
    smallest_control = {
        name: r["lower"] if r["control"] is None else r["control"]
        for name, _, r in numbers}
    ok, compared = check.verdict(smallest_control, limits)
    assert not ok
    failed = [name for name, row in compared.items() if not row["ok"]]
    assert failed and all(
        r["upper_from"] == "control" for name, _, r in numbers
        if name in failed), failed


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_limits_have_the_keys_of_limits_and_keep_exact_ones_exact(cell):
    """The limits of the CPU rehearsal sit beside the chip's in the same
    file (tests/benchmark/conftest.py reads them), number for number."""
    doc = _read_json(MAN.limits_path(cell))
    assert list(doc["tiny_limits"]) == list(doc["limits"])
    for name, limit in doc["limits"].items():
        if name.endswith("_max_abs"):
            assert doc["tiny_limits"][name] == limit == 0.0
        else:
            assert doc["tiny_limits"][name] > 0.0
    config = MAN.config(MAN.cell(cell)["config"])
    assert config["tiny"]["rows"] < config["rows"]
    assert set(config["tiny"]["entities"]) <= {
        c["name"] for c in config["coordinates"]}
