"""BENCHMARK.json against the contract's shape, and every name in it
against the file it stands for. The checks of a manifest are conftest.py's,
which test_adding_a_configuration.py also makes of one that has grown."""

import json
import os
import shutil

import pytest

from benchmark.manifest import Manifest

from conftest import MANIFEST_CHECKS, metrics_of_kind


@pytest.fixture(scope="module")
def man():
    return Manifest()


def _test_of(check):
    def test(man):
        check(man)
    return test


# One test to a check of conftest.py's list, named after it: test_<what>
# for check_<what>.
for _check in MANIFEST_CHECKS:
    globals()["test_" + _check.__name__.removeprefix("check_")] = _test_of(
        _check)


def test_adding_a_cell_needs_files_and_entries_only(tiny_root):
    """A later PR's cell: one new traffic file, one new metric reader, one
    limits file and its entries; no file that exists is edited. Its kind
    is one that has cells: the metrics of that kind list it."""
    bench = os.path.join(tiny_root, "benchmark")
    with open(os.path.join(bench, "traffic", "refit_cold.json"), "w") as f:
        json.dump({"kind": "refit", "warmup_fits": 2, "min_units": 1,
                   "trace_units": 1}, f)
    with open(os.path.join(bench, "metrics", "fit.units.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.units)\n")
    shutil.copy(os.path.join(bench, "limits", "linear.refit.json"),
                os.path.join(bench, "limits", "linear.refit_cold.json"))
    of_its_kind = metrics_of_kind(Manifest(tiny_root), "refit")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["workloads"].append({
        "name": "linear.refit_cold", "config": "glmix_ml_linear",
        "traffic": "refit_cold", "chips": 1, "why": "fixture"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] in of_its_kind:
            m["workloads"].append("linear.refit_cold")
    doc["per_layer"].append({
        "name": "fit.units", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Fit, fused",
        "moves": "train_rows_per_s", "workloads": ["linear.refit_cold"]})
    with open(path, "w") as f:
        json.dump(doc, f)

    man = Manifest(tiny_root)
    for check in MANIFEST_CHECKS:
        check(man)
    cell = man.cell("linear.refit_cold")
    assert man.traffic(cell["traffic"])["warmup_fits"] == 2
    names = {m["name"] for m in man.per_layer("linear.refit_cold")}
    assert "fit.units" in names and "kernel.newton_roofline_pct" not in names
    assert names == {m["name"] for m in man.per_layer("linear.refit")} | {
        "fit.units"}
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
    # A kind that came with no cell of the repository's: no metric is OF
    # it, and an accepted kind's end-to-end metric may list its cell.
    for check in MANIFEST_CHECKS:
        check(man)
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
