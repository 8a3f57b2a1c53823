"""A later ``model_config`` PR's GLMix arrives as files and entries: a
configuration with coordinates of other names, power-law rows per entity
and a cap that binds, a refit cell and a retrain cell on it and each
cell's limits; and a second configuration that needs more of the
yardstick, with a reference, a generator and a builder of its own, each
named in its file, and a cell that asks for four chips: all laid over a
copy of the benchmark with no edit to any file
that was there. The grown manifest then passes every check the suite
makes of the repository's (conftest.py holds them as plain functions):
the manifest's, each configuration's, each cell's limits and each cell's
rehearsal on the CPU, for the fixture's cells and for the accepted ones;
and a fixture cell that a metric of its kind does not list fails by a
message that names both."""

import collections
import filecmp
import glob
import json
import os
import shutil

import pytest

from benchmark import costs, run
from benchmark.manifest import Manifest

from conftest import (
    CAME_WITH,
    CAP_METRICS,
    FAKE_DEVICE,
    LIMITS_CHECKS,
    MANIFEST_CHECKS,
    NAMED_KEYS,
    REPO_ROOT,
    cell_kind,
    cell_names,
    check_a_configuration_has_a_reference_and_a_generator,
    check_a_metric_of_one_kind_lists_no_cell_of_another,
    check_a_traced_rehearsal_prints_every_metric_that_lists_the_cell,
    check_every_cell_is_listed_by_every_metric_of_its_kind,
    check_rehearsal_of_a_cell,
    check_the_cap_metrics_list_cells_whose_cap_binds,
    copy_benchmark,
    rehearse,
    tiny_copy,
)

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "add_configuration")


def _read_entries():
    found = []
    for path in sorted(glob.glob(os.path.join(FIXTURE, "entries*.json"))):
        with open(path) as f:
            found.append(json.load(f))
    return found


# The fixture's entries, one file to a PR that would bring them: ``configs``
# and ``workloads`` to append, and ``reported_in``, the metrics that list
# those workloads.
ENTRIES = _read_entries()
ADDED_CONFIGS = [c["name"] for e in ENTRIES for c in e["configs"]]
ADDED_CELLS = [w["name"] for e in ENTRIES for w in e["workloads"]]
GROWN_CONFIGS = [
    c["name"] for c in Manifest().doc["configs"]] + ADDED_CONFIGS
GROWN_CELLS = cell_names(Manifest()) + ADDED_CELLS


def _files(root):
    return sorted(
        os.path.relpath(os.path.join(d, f), root)
        for d, _, names in os.walk(root) for f in names
        if "__pycache__" not in d)


def _grow(full, change=None):
    """A copy of the benchmark at its committed size in ``full``, plus
    the fixture's files and its entries in BENCHMARK.json (``change``
    edits that document last); returns the files it had before."""
    copy_benchmark(REPO_ROOT, full)
    before = _files(full)
    shutil.copytree(os.path.join(FIXTURE, "benchmark"),
                    os.path.join(full, "benchmark"), dirs_exist_ok=True)
    path = os.path.join(full, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    for entries in ENTRIES:
        doc["configs"] += entries["configs"]
        doc["workloads"] += entries["workloads"]
        for metric in doc["end_to_end"] + doc["per_layer"]:
            if metric["name"] in entries["reported_in"]:
                metric["workloads"] += [
                    w["name"] for w in entries["workloads"]]
    if change is not None:
        change(doc)
    with open(path, "w") as f:
        json.dump(doc, f)
    return before


Grown = collections.namedtuple("Grown", "full before tiny")


@pytest.fixture(scope="module")
def grown_root(tmp_path_factory):
    """The grown copy, the files it had before it grew, and the tiny copy
    of THAT, made by the function that makes every test's ``tiny_root``.
    No test writes to either."""
    tmp = tmp_path_factory.mktemp("grown")
    full = str(tmp / "full")
    before = _grow(full)
    return Grown(full, before, tiny_copy(full, str(tmp / "checkout")))


@pytest.fixture(scope="module")
def traced(grown_root):
    """``traced(cell)``: one traced rehearsal of the cell on the grown
    tiny copy, shared by the checks that read a traced run."""
    man, outs = Manifest(grown_root.tiny), {}

    def of(cell):
        if cell not in outs:
            outs[cell] = rehearse(man, cell, True, seed=2**31 + 27,
                                  seconds=0.2)
        return outs[cell]

    return of


def test_adding_a_configuration_needs_files_and_entries_only(
        grown_root, traced):
    full, before, tiny = grown_root
    added = set(_files(full)) - set(before)
    assert added == {
        f"benchmark/configs/{name}.json" for name in ADDED_CONFIGS} | {
        f"benchmark/limits/{cell}.json" for cell in ADDED_CELLS} | {
        f"benchmark/{key}s/fixture_named.py" for key in NAMED_KEYS}
    for name in before:
        if name != "BENCHMARK.json":
            assert filecmp.cmp(os.path.join(REPO_ROOT, name),
                               os.path.join(full, name), shallow=False), name
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        was = json.load(f)
    now = Manifest(full).doc
    assert [c["name"] for c in now["configs"]] == GROWN_CONFIGS
    assert cell_names(Manifest(full)) == GROWN_CELLS
    for key in ("configs", "workloads"):
        assert now[key][:len(was[key])] == was[key]
    assert now["command"] == was["command"]
    # An entry that is there changes by the names appended to its list.
    for key in ("end_to_end", "per_layer"):
        assert len(now[key]) == len(was[key])
        for m_now, m_was in zip(now[key], was[key]):
            listed = m_now.get("workloads", [])
            old = m_was.get("workloads", [])
            assert listed[:len(old)] == old
            assert set(listed[len(old):]) <= set(ADDED_CELLS)
            assert dict(m_now, workloads=None) == dict(m_was, workloads=None)

    man = Manifest(tiny)
    config = man.config("glmix_fixture_powerlaw")
    assert config["rows"] == config["tiny"]["rows"]
    assert [c["entities"] for c in config["coordinates"][1:]] == [1200, 30]
    capped = config["coordinates"][1]
    counts = man.generator(config["name"]).rows_per_entity(config, capped)
    assert counts.min() == 1
    assert capped["active_data_upper_bound"] < counts.max()

    out = traced("powerlaw.refit")
    assert out["correct"] is True, out["compared"]
    assert set(out["compared"]) == set(man.limits("powerlaw.refit"))
    # Members that train on one label have no minimiser: the reference
    # states none, and the program's margins there are held on their own.
    assert 0.0 < out["compared"]["unbounded.per-member"]["value"] < 1e-5
    assert {"plan.padding_ratio", "fit.mfu_pct"} <= set(out["metrics"])


@pytest.mark.parametrize("check", MANIFEST_CHECKS,
                         ids=lambda check: check.__name__)
def test_the_grown_manifest_passes_each_check_of_a_manifest(
        grown_root, check):
    check(Manifest(grown_root.full))


@pytest.mark.parametrize("config_name", GROWN_CONFIGS)
def test_each_configuration_of_the_grown_manifest_has_its_reference(
        grown_root, config_name):
    check_a_configuration_has_a_reference_and_a_generator(
        Manifest(grown_root.full), config_name)


@pytest.mark.parametrize("check", LIMITS_CHECKS,
                         ids=lambda check: check.__name__)
@pytest.mark.parametrize("cell", GROWN_CELLS)
def test_each_cell_of_the_grown_manifest_passes_each_check_of_its_limits(
        grown_root, cell, check):
    check(Manifest(grown_root.full), cell)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", GROWN_CELLS)
def test_rehearsal_of_each_cell_of_the_grown_manifest(
        grown_root, traced, cell, trace):
    man = Manifest(grown_root.tiny)
    out = (traced(cell) if trace
           else rehearse(man, cell, False, seed=2**31 + 28, seconds=0.2))
    check_rehearsal_of_a_cell(man, cell, trace, out)


@pytest.mark.parametrize("cell", GROWN_CELLS)
def test_a_traced_rehearsal_prints_every_metric_that_lists_each_grown_cell(
        grown_root, traced, cell):
    check_a_traced_rehearsal_prints_every_metric_that_lists_the_cell(
        Manifest(grown_root.tiny), cell, traced(cell))


NAMED_CONFIG, NAMED_CELL = "glmix_fixture_named", "named.refit4"


def test_the_named_files_are_the_ones_a_run_of_the_four_chip_cell_uses(
        grown_root, traced):
    """Each of the fixture's three named files leaves a mark when it is
    called; the cells of the other configurations leave none."""
    man = Manifest(grown_root.tiny)
    config = man.config(NAMED_CONFIG)
    assert man.cell(NAMED_CELL)["chips"] == 4 and config["mesh"] == "off"
    for key in NAMED_KEYS:
        assert config[key] == "fixture_named"
        assert getattr(man, key)(NAMED_CONFIG).__file__ == os.path.join(
            grown_root.tiny, "benchmark", key + "s", "fixture_named.py")
    out = traced(NAMED_CELL)
    assert out["correct"] is True, out["compared"]
    assert out["device"]["count"] == 4
    seed = out["window"]["seed"]
    assert (NAMED_CONFIG, seed) in man.generator(NAMED_CONFIG).CALLS
    assert NAMED_CONFIG in man.reference(NAMED_CONFIG).CALLS
    built = man.builder(NAMED_CONFIG).CALLS
    assert ("estimator", NAMED_CONFIG) in built
    assert ("dataset", config["rows"]) in built
    traced("powerlaw.refit")
    assert {name for name, _ in man.generator(NAMED_CONFIG).CALLS} == {
        NAMED_CONFIG}
    assert set(man.reference(NAMED_CONFIG).CALLS) == {NAMED_CONFIG}
    assert {what for kind, what in built if kind == "estimator"} == {
        NAMED_CONFIG}


@pytest.mark.parametrize("name, count, peak", [
    ("fit.mfu_pct", costs.fit_flops, "flops_per_s"),
    ("fit.hbm_share_pct", costs.fit_hbm_bytes, "hbm_bytes_per_s"),
])
def test_a_whole_fit_share_divides_by_the_cells_chips(
        grown_root, traced, name, count, peak):
    """At one chip the float every accepted cell read before ``chips``
    was there; at four, for the same window, a quarter of it; and the
    four-chip cell's line carries the quarter. The counts are what the
    run's data holds (``costs.shard_counts``; every shard here is
    dense)."""
    man = Manifest(grown_root.tiny)
    out = traced(NAMED_CELL)
    config = man.config(NAMED_CONFIG)
    counts = costs.shard_counts(config, man.generator(NAMED_CONFIG).generate(
        config, out["window"]["seed"]))
    peaks = costs.chip_peaks(FAKE_DEVICE["kind"])
    window = dict(config=config, units=out["attempted"], costs=costs,
                  counts=counts, window_s=out["window"]["window_s"],
                  peaks=peaks)
    read = man.metric_reader(name)
    one = read(run.Reading(**window, chips=1))
    assert one == read(run.Reading(**window))
    assert one == 100.0 * count(config, counts) * out["attempted"] / (
        out["window"]["window_s"] * peaks[peak])
    four = read(run.Reading(**window, chips=4))
    assert four == one / 4.0
    assert out["metrics"][name]["value"] == four
    # The one-chip fixture cell on the same configuration's sizes reads
    # against one chip's peak.
    other = traced("powerlaw.refit")
    powerlaw = man.config("glmix_fixture_powerlaw")
    powerlaw_counts = costs.shard_counts(
        powerlaw, man.generator("glmix_fixture_powerlaw").generate(
            powerlaw, other["window"]["seed"]))
    assert other["metrics"][name]["value"] == (
        100.0 * count(powerlaw, powerlaw_counts)
        * other["attempted"]
        / (other["window"]["window_s"] * peaks[peak]))


@pytest.mark.parametrize("name", CAP_METRICS)
def test_the_cap_metrics_may_list_a_second_cell_whose_cap_binds(
        grown_root, tmp_path, name):
    man = Manifest(grown_root.full)
    (metric,) = [m for m in man.doc["per_layer"] if m["name"] == name]
    assert metric["workloads"] == ["heavytail.refit", NAMED_CELL]
    check_the_cap_metrics_list_cells_whose_cap_binds(man, name)

    # A cell in which no cap binds is not theirs to list.
    def relist(doc):
        for m in doc["per_layer"]:
            if m["name"] == name:
                m["workloads"].append("linear.refit")

    full = str(tmp_path / "full")
    _grow(full, relist)
    with pytest.raises(AssertionError, match="linear.refit"):
        check_the_cap_metrics_list_cells_whose_cap_binds(
            Manifest(full), name)


def test_the_fixture_brings_a_cell_of_each_kind_that_has_metrics(grown_root):
    man = Manifest(grown_root.full)
    assert {cell_kind(man, cell) for cell in ADDED_CELLS} == set(CAME_WITH)


def _kind_rule(man):
    check_a_metric_of_one_kind_lists_no_cell_of_another(man)
    check_every_cell_is_listed_by_every_metric_of_its_kind(man)


@pytest.mark.parametrize("cell, metric, how", [
    # PR 25's tests let a new cell through only if it was NOT listed (and
    # then failed its own case): being listed is the requirement.
    ("powerlaw.refit", "fit.host_s.refit", "remove"),
    ("powerlaw.refit", "fit.hbm_share_pct", "remove"),
    ("powerlaw.retrain", "save.encode_s", "remove"),
    ("powerlaw.retrain", "retrain_s", "remove"),
    ("powerlaw.refit", "save.encode_s", "append"),
    ("powerlaw.retrain", "fit.host_s.refit", "append"),
], ids=["refit_cell_unlisted", "refit_cell_out_of_a_metric_no_stage_feeds",
        "job_cell_unlisted", "job_cell_out_of_its_end_to_end_metric",
        "refit_cell_in_a_job_metric", "job_cell_in_a_refit_metric"])
def test_a_new_cell_the_metrics_of_its_kind_do_not_list_is_refused(
        grown_root, tmp_path, cell, metric, how):
    def relist(doc):
        for m in doc["end_to_end"] + doc["per_layer"]:
            if m["name"] == metric:
                getattr(m["workloads"], how)(cell)

    _kind_rule(Manifest(grown_root.full))  # as the fixture states it
    full = str(tmp_path / "full")
    _grow(full, relist)
    with pytest.raises(AssertionError) as refused:
        _kind_rule(Manifest(full))
    assert repr(cell) in str(refused.value)
    assert repr(metric) in str(refused.value)


def test_the_added_cell_fails_with_a_cap_the_reference_does_not_keep(
        grown_root, monkeypatch):
    """The cap is part of the comparison: a reference that trains every
    entity on all of its rows is not the capped program's reference."""
    man = Manifest(grown_root.tiny)
    reference = man.reference("glmix_fixture_powerlaw")
    real = reference.kept_rows
    monkeypatch.setattr(
        reference, "kept_rows",
        lambda ids, entities, upper, id_tag: real(ids, entities, None, id_tag))
    out = rehearse(man, "powerlaw.refit", False, seed=5, seconds=0.2)
    assert out["correct"] is False
    assert not out["compared"]["coef.per-member"]["ok"]


def test_the_added_cell_fails_with_a_one_label_member_left_untrained(
        grown_root, monkeypatch):
    """The members without a minimiser are in no coef.* and no score_rms;
    ``unbounded.per-member`` alone sees one of them come back untrained."""
    import numpy as np

    from benchmark import sut

    man = Manifest(grown_root.tiny)
    config = man.config("glmix_fixture_powerlaw")
    seed = 5
    data = man.generator(config["name"]).generate(config, seed)
    ref = man.reference(config["name"]).fit(config, data)["per-member"]
    one_label = np.flatnonzero(np.isinf(ref[:, -1]))
    assert 100 < one_label.size < 600
    real = sut.model_tables

    def untrained(model, cfg):
        tables = real(model, cfg)
        tables["per-member"][one_label[0]] = 0.0
        return tables

    monkeypatch.setattr(sut, "model_tables", untrained)
    out = rehearse(man, "powerlaw.refit", False, seed=seed, seconds=0.2)
    assert out["correct"] is False
    failed = [k for k, row in out["compared"].items() if not row["ok"]]
    assert failed == ["unbounded.per-member"], out["compared"]
