"""BENCHMARK.json against the contract's shape, and every name in it
against the file it stands for."""

import json
import os
import re
import shutil

import pytest

from benchmark.manifest import Manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return Manifest()


def test_top_level_keys_and_limits(man):
    doc = man.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51
    assert doc["paths"] == ["benchmark", "tests/benchmark"]
    assert all(isinstance(w, str) and 0 < len(w) <= 200
               for w in doc["command"])
    size = os.path.getsize(os.path.join(man.root, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_and_units_hold_only_allowed_characters(man):
    doc = man.doc
    names = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in doc[key]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((key in ("end_to_end", "per_layer"), entry["name"]))
    assert len(set(names)) == len(names)
    for w in doc["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert "setup_s" in {m["name"] for m in doc["end_to_end"]}


def test_every_cell_resolves_to_its_files(man):
    pairs = set()
    for w in man.doc["workloads"]:
        config = man.config(w["config"])
        assert config["name"] == w["config"]
        traffic = man.traffic(w["traffic"])
        assert callable(man.kind(traffic["kind"]))
        assert man.limits(w["name"]), "a cell needs limits to be correct"
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(man.doc["workloads"])
    used = {w["config"] for w in man.doc["workloads"]}
    files = set()
    for c in man.doc["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        assert os.path.exists(os.path.join(man.root, c["file"]))
        assert c["source"].startswith("https://")
        assert set(c["reduced"]) <= set(man.config(c["name"]))
        assert all(NAME.match(key) for key in c["reduced"])


def test_every_metric_has_a_reader_and_cells_that_report_what_it_moves(man):
    cells = {w["name"] for w in man.doc["workloads"]}
    end_to_end = {m["name"] for m in man.doc["end_to_end"]}
    for m in man.doc["per_layer"]:
        assert callable(man.metric_reader(m["name"])), m["name"]
        assert m["moves"] in end_to_end and m["moves"] != "setup_s"
        for cell in m.get("workloads", cells):
            assert cell in cells
            reported = {e["name"] for e in man.end_to_end(cell)}
            assert m["moves"] in reported, (m["name"], cell)
    layers = {m["layer"] for m in man.doc["per_layer"]}
    with open(os.path.join(man.root, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_every_cell_reports_setup_one_more_and_a_layer_metric(man):
    for w in man.doc["workloads"]:
        reported = [m["name"] for m in man.end_to_end(w["name"])]
        assert "setup_s" in reported and len(reported) >= 2
        assert man.per_layer(w["name"])
    with_mfu = [m for m in man.doc["per_layer"] if "mfu" in m["name"]]
    roofs = [m for m in man.doc["per_layer"] if "roofline" in m["name"]]
    for roof in roofs:
        assert any(m["moves"] == roof["moves"] for m in with_mfu)


def test_adding_a_cell_needs_files_and_entries_only(tiny_root):
    """A later PR's cell: one new traffic file, one new metric reader, one
    limits file and three entries; no file that exists is edited."""
    bench = os.path.join(tiny_root, "benchmark")
    with open(os.path.join(bench, "traffic", "refit_cold.json"), "w") as f:
        json.dump({"kind": "refit", "warmup_fits": 2, "min_units": 1,
                   "trace_units": 1}, f)
    with open(os.path.join(bench, "metrics", "fit.units.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.units)\n")
    shutil.copy(os.path.join(bench, "limits", "linear.refit.json"),
                os.path.join(bench, "limits", "linear.refit_cold.json"))
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["workloads"].append({
        "name": "linear.refit_cold", "config": "glmix_ml_linear",
        "traffic": "refit_cold", "chips": 1, "why": "fixture"})
    doc["per_layer"].append({
        "name": "fit.units", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Fit, fused",
        "moves": "train_rows_per_s", "workloads": ["linear.refit_cold"]})
    for m in doc["end_to_end"]:
        if m["name"] == "train_rows_per_s":
            m["workloads"].append("linear.refit_cold")
    with open(path, "w") as f:
        json.dump(doc, f)

    man = Manifest(tiny_root)
    cell = man.cell("linear.refit_cold")
    assert man.traffic(cell["traffic"])["warmup_fits"] == 2
    names = [m["name"] for m in man.per_layer("linear.refit_cold")]
    assert names == ["fit.units"]
    assert [m["name"] for m in man.end_to_end("linear.refit_cold")] == [
        "train_rows_per_s", "setup_s"]

    class Ctx:
        units = 3

    assert man.metric_reader("fit.units")(Ctx()) == 3.0


def test_adding_a_traffic_kind_needs_a_file_only(tiny_root):
    """A later PR's kind: one file under benchmark/kinds/, found by the
    name its traffic file gives; what its report() names, its metric's
    reader sees. The harness drives it as it drives the others."""
    from benchmark import run

    from conftest import FAKE_DEVICE

    bench = os.path.join(tiny_root, "benchmark")
    with open(os.path.join(bench, "kinds", "refit_pairs.py"), "w") as f:
        f.write(
            "from benchmark.kinds.refit import Kind as Refit\n\n\n"
            "class Kind(Refit):\n"
            "    unit_name = 'pairs'\n\n"
            "    def unit(self, k):\n"
            "        super().unit(2 * k)\n"
            "        super().unit(2 * k + 1)\n\n"
            "    def report(self):\n"
            "        return dict(super().report(), fits_per_unit=2)\n")
    with open(os.path.join(bench, "traffic", "refit_pairs.json"), "w") as f:
        json.dump({"kind": "refit_pairs", "warmup_fits": 1, "min_units": 1,
                   "trace_units": 1}, f)
    with open(os.path.join(bench, "metrics", "fit.per_unit.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.fits_per_unit)\n")
    shutil.copy(os.path.join(bench, "limits", "linear.refit.json"),
                os.path.join(bench, "limits", "linear.pairs.json"))
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["workloads"].append({
        "name": "linear.pairs", "config": "glmix_ml_linear",
        "traffic": "refit_pairs", "chips": 1, "why": "fixture"})
    doc["per_layer"].append({
        "name": "fit.per_unit", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "Fit, fused",
        "moves": "train_rows_per_s", "workloads": ["linear.pairs"]})
    for m in doc["end_to_end"]:
        if m["name"] == "train_rows_per_s":
            m["workloads"].append("linear.pairs")
    with open(path, "w") as f:
        json.dump(doc, f)

    man = Manifest(tiny_root)
    out = run.run_cell(man, man.cell("linear.pairs"), seed=5, seconds=0.1,
                       trace=True, device=dict(FAKE_DEVICE))
    assert out["correct"] is True, out["compared"]
    assert out["window"]["pairs"] == out["attempted"] >= 1
    assert out["metrics"]["fit.per_unit"]["value"] == 2.0


def test_unknown_names_are_errors(man):
    with pytest.raises(KeyError):
        man.cell("no.such.cell")
    with pytest.raises(KeyError):
        man.config("no_such_config")
    with pytest.raises(FileNotFoundError):
        man.traffic("no_such_traffic")
    with pytest.raises(FileNotFoundError):
        man.kind("no_such_kind")
