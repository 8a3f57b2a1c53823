"""A later ``model_config`` PR's GLMix arrives as files and entries: a
configuration with coordinates of other names, power-law rows per entity
and a cap that binds, its cell and the cell's limits, laid over a copy of
the benchmark with no edit to any file that was there, and rehearsed to
``correct`` on the CPU through the same fixtures as the cells that are."""

import filecmp
import json
import os
import shutil

import pytest

from benchmark import run
from benchmark.manifest import Manifest

from conftest import FAKE_DEVICE, REPO_ROOT, copy_benchmark, tiny_copy

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "add_configuration")


def _files(root):
    return sorted(
        os.path.relpath(os.path.join(d, f), root)
        for d, _, names in os.walk(root) for f in names
        if "__pycache__" not in d)


@pytest.fixture()
def grown_root(tmp_path):
    """A copy of the benchmark at its committed size, plus the fixture's
    files and its entries in BENCHMARK.json; then the tiny copy of THAT,
    made by the function that makes every test's ``tiny_root``."""
    full = copy_benchmark(REPO_ROOT, str(tmp_path / "full"))
    before = _files(full)
    shutil.copytree(os.path.join(FIXTURE, "benchmark"),
                    os.path.join(full, "benchmark"), dirs_exist_ok=True)
    with open(os.path.join(FIXTURE, "entries.json")) as f:
        entries = json.load(f)
    path = os.path.join(full, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"] += entries["configs"]
    doc["workloads"] += entries["workloads"]
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if metric["name"] in entries["reported_in"]:
            metric["workloads"] += [w["name"] for w in entries["workloads"]]
    with open(path, "w") as f:
        json.dump(doc, f)
    return full, before, tiny_copy(full, str(tmp_path / "checkout"))


def test_adding_a_configuration_needs_files_and_entries_only(grown_root):
    full, before, tiny = grown_root
    added = set(_files(full)) - set(before)
    assert added == {"benchmark/configs/glmix_fixture_powerlaw.json",
                     "benchmark/limits/powerlaw.refit.json"}
    for name in before:
        if name != "BENCHMARK.json":
            assert filecmp.cmp(os.path.join(REPO_ROOT, name),
                               os.path.join(full, name), shallow=False), name
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        was = json.load(f)
    now = Manifest(full).doc
    for key in ("configs", "workloads"):
        assert now[key][:-1] == was[key] and len(now[key]) == len(was[key]) + 1
    assert now["command"] == was["command"]

    man = Manifest(tiny)
    config = man.config("glmix_fixture_powerlaw")
    assert config["rows"] == config["tiny"]["rows"]
    assert [c["entities"] for c in config["coordinates"][1:]] == [1200, 30]
    capped = config["coordinates"][1]
    counts = man.generator(config["name"]).rows_per_entity(config, capped)
    assert counts.min() == 1
    assert capped["active_data_upper_bound"] < counts.max()

    out = run.run_cell(man, man.cell("powerlaw.refit"), seed=2**31 + 27,
                       seconds=0.2, trace=True, device=dict(FAKE_DEVICE))
    assert out["correct"] is True, out["compared"]
    assert set(out["compared"]) == set(man.limits("powerlaw.refit"))
    # Members that train on one label have no minimiser: the reference
    # states none, and the program's margins there are held on their own.
    assert 0.0 < out["compared"]["unbounded.per-member"]["value"] < 1e-5
    assert {"plan.padding_ratio", "fit.mfu_pct"} <= set(out["metrics"])


def test_the_added_cell_fails_with_a_cap_the_reference_does_not_keep(
        grown_root, monkeypatch):
    """The cap is part of the comparison: a reference that trains every
    entity on all of its rows is not the capped program's reference."""
    _, _, tiny = grown_root
    man = Manifest(tiny)
    reference = man.reference("glmix_fixture_powerlaw")
    real = reference.kept_rows
    monkeypatch.setattr(
        reference, "kept_rows",
        lambda ids, entities, upper, id_tag: real(ids, entities, None, id_tag))
    out = run.run_cell(man, man.cell("powerlaw.refit"), seed=5,
                       seconds=0.2, trace=False, device=dict(FAKE_DEVICE))
    assert out["correct"] is False
    assert not out["compared"]["coef.per-member"]["ok"]


def test_the_added_cell_fails_with_a_one_label_member_left_untrained(
        grown_root, monkeypatch):
    """The members without a minimiser are in no coef.* and no score_rms;
    ``unbounded.per-member`` alone sees one of them come back untrained."""
    import numpy as np

    from benchmark import sut

    _, _, tiny = grown_root
    man = Manifest(tiny)
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
    out = run.run_cell(man, man.cell("powerlaw.refit"), seed=seed,
                       seconds=0.2, trace=False, device=dict(FAKE_DEVICE))
    assert out["correct"] is False
    failed = [k for k, row in out["compared"].items() if not row["ok"]]
    assert failed == ["unbounded.per-member"], out["compared"]
