"""Fixtures of the benchmark's own tests: a temporary copy of the
manifest and its data files, cut to a size the CPU holds; and the checks
the suite makes of a manifest, of each of its cells and of each of its
configurations, as plain functions of ``(man)``, ``(man, cell)`` and
``(man, config_name)``. The tests call them on the repository's manifest,
case for case, and ``test_adding_a_configuration.py`` calls every one of
them on a manifest that has grown by a configuration and two cells: what
a check expects of a cell it reads from the manifest and from the kind
its traffic file states, never from a cell's name."""

import json
import os
import re
import shutil

import pytest

from benchmark import check, run
from benchmark.manifest import Manifest, _read_json

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# A device the peaks table knows, handed to run_cell in place of the look
# for a chip (the CPU does the work; no number of these runs is a device
# number).
FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def shrink(config: dict) -> dict:
    """The configuration at the CPU size its own ``tiny`` block states:
    rows and, by coordinate name, entities."""
    tiny = config["tiny"]
    config["rows"] = tiny["rows"]
    for c in config["coordinates"]:
        if c["name"] in tiny["entities"]:
            c["entities"] = tiny["entities"][c["name"]]
    return config


def _rewrite(path: str, change) -> None:
    with open(path) as f:
        doc = json.load(f)
    with open(path, "w") as f:
        json.dump(change(doc), f)


def copy_benchmark(source_root: str, root: str) -> str:
    """``source_root``'s BENCHMARK.json and benchmark files in a new
    checkout-shaped directory ``root``."""
    os.makedirs(root)
    shutil.copy(os.path.join(source_root, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(source_root, "benchmark"),
        os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"))
    return root


def tiny_copy(source_root: str, root: str) -> str:
    """``copy_benchmark`` with every configuration cut to its ``tiny``
    size and every cell's limits replaced by its ``tiny_limits``: all of
    it read from the data files, whatever they are named."""
    man = Manifest(copy_benchmark(source_root, root))
    for entry in man.doc["configs"]:
        _rewrite(os.path.join(root, entry["file"]), shrink)
    for cell in man.doc["workloads"]:
        _rewrite(man.limits_path(cell["name"]),
                 lambda limits: dict(limits, limits=limits["tiny_limits"]))
    return root


@pytest.fixture(autouse=True)
def _restore_matmul_precision():
    """A configuration may set JAX's process-wide matmul precision
    (benchmark/sut.py configure); the suite's other tests must not
    inherit it."""
    import jax

    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


@pytest.fixture()
def tiny_root(tmp_path):
    """``tiny_copy`` of this repository's benchmark."""
    return tiny_copy(REPO_ROOT, str(tmp_path / "checkout"))


# ---- a manifest and its cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

# The cells each kind of traffic came with (PR 24). What a cell of a kind
# reports is read from the manifest: a metric OF a kind is one whose
# ``workloads`` hold every cell the kind came with. The rule for a later
# cell: one whose traffic is of that kind is listed by each of them, and by
# no metric of the other kind.
CAME_WITH = {
    "refit": {"logistic.refit", "linear.refit"},
    "retrain_job": {"linear.retrain"},
}


def kinds_of_metric(metric: dict) -> set:
    listed = set(metric.get("workloads", ()))
    return {kind for kind, cells in CAME_WITH.items() if cells <= listed}


def metrics_of_kind(man, kind: str) -> set:
    """End-to-end and per-layer; a kind that came with no cell has none."""
    return {m["name"] for m in man.doc["end_to_end"] + man.doc["per_layer"]
            if kind in kinds_of_metric(m)}


# A share of the device has no reading without a device plane: the CPU
# rehearsal leaves these out of the line and prints every other metric.
NEEDS_A_DEVICE = ("device.idle_share", "kernel.")


def cell_names(man) -> list:
    return [w["name"] for w in man.doc["workloads"]]


def cell_kind(man, cell: str) -> str:
    """The kind the cell's traffic file states."""
    return man.traffic(man.cell(cell)["traffic"])["kind"]


def check_top_level_keys_and_limits(man):
    doc = man.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51
    assert doc["paths"] == ["benchmark", "tests/benchmark"]
    assert all(isinstance(w, str) and 0 < len(w) <= 200
               for w in doc["command"])
    size = os.path.getsize(os.path.join(man.root, "BENCHMARK.json"))
    assert size <= 64 * 1024


def check_names_and_units_hold_only_allowed_characters(man):
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


def check_every_cell_resolves_to_its_files(man):
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


def check_every_metric_has_a_reader_and_cells_that_report_what_it_moves(
        man):
    """The layers are looked up in the REPOSITORY's PERF.md, whichever
    copy of the benchmark ``man`` reads: a new ``layer`` is named there."""
    cells = set(cell_names(man))
    end_to_end = {m["name"] for m in man.doc["end_to_end"]}
    for m in man.doc["per_layer"]:
        assert callable(man.metric_reader(m["name"])), m["name"]
        assert m["moves"] in end_to_end and m["moves"] != "setup_s"
        for cell in m.get("workloads", cells):
            assert cell in cells
            reported = {e["name"] for e in man.end_to_end(cell)}
            assert m["moves"] in reported, (m["name"], cell)
    layers = {m["layer"] for m in man.doc["per_layer"]}
    with open(os.path.join(REPO_ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def check_every_cell_reports_setup_one_more_and_a_layer_metric(man):
    for cell in cell_names(man):
        reported = [m["name"] for m in man.end_to_end(cell)]
        assert "setup_s" in reported and len(reported) >= 2
        assert man.per_layer(cell)
    with_mfu = [m for m in man.doc["per_layer"] if "mfu" in m["name"]]
    roofs = [m for m in man.doc["per_layer"] if "roofline" in m["name"]]
    for roof in roofs:
        assert any(m["moves"] == roof["moves"] for m in with_mfu)


def check_a_metric_of_one_kind_lists_no_cell_of_another(man):
    """A cell of a kind that came with no cell (a later PR's kind) is held
    by its rehearsal alone: every metric that lists it has to print."""
    for kind, cells in CAME_WITH.items():
        assert all(cell_kind(man, cell) == kind for cell in cells)
        assert metrics_of_kind(man, kind), kind
    for m in man.doc["end_to_end"] + man.doc["per_layer"]:
        for kind in sorted(kinds_of_metric(m)):
            for cell in m["workloads"]:
                other = cell_kind(man, cell)
                assert other == kind or other not in CAME_WITH, (
                    f"metric {m['name']!r} is reported by the cells of kind "
                    f"{kind!r} and lists cell {cell!r}, whose traffic is of "
                    f"kind {other!r}")


def check_the_cell_is_listed_by_every_metric_of_its_kind(man, cell):
    """The requirement on a new cell: the kind its traffic states decides
    which metrics list it, whatever it is called."""
    kind = cell_kind(man, cell)
    listing = {m["name"]
               for m in man.end_to_end(cell) + man.per_layer(cell)}
    for name in sorted(metrics_of_kind(man, kind)):
        assert name in listing, (
            f"cell {cell!r} has traffic of kind {kind!r} and is not listed "
            f"by {name!r}: a new cell appends its name to that metric's "
            "workloads in BENCHMARK.json")


def check_every_cell_is_listed_by_every_metric_of_its_kind(man):
    for cell in cell_names(man):
        check_the_cell_is_listed_by_every_metric_of_its_kind(man, cell)


MANIFEST_CHECKS = [
    check_top_level_keys_and_limits,
    check_names_and_units_hold_only_allowed_characters,
    check_every_cell_resolves_to_its_files,
    check_every_metric_has_a_reader_and_cells_that_report_what_it_moves,
    check_every_cell_reports_setup_one_more_and_a_layer_metric,
    check_a_metric_of_one_kind_lists_no_cell_of_another,
    check_every_cell_is_listed_by_every_metric_of_its_kind,
]


# The keys by which a configuration's file names a file of its own:
# benchmark/<key>s/<name>.py.
NAMED_KEYS = ("reference", "generator", "builder")


def check_a_configuration_has_a_reference_and_a_generator(man, config_name):
    """And, where it names one, a builder that gives something."""
    reference = man.reference(config_name)
    assert callable(reference.fit) and callable(reference.predict)
    generator = man.generator(config_name)
    assert callable(generator.generate)
    assert callable(generator.rows_per_entity)
    builder = man.builder(config_name)
    assert (builder is None) == ("builder" not in man.config(config_name))
    if builder is not None:
        gives = [getattr(builder, name, None)
                 for name in ("build_estimator", "build_dataset")]
        assert any(gives) and all(g is None or callable(g) for g in gives)


# The metrics PR 32 made for a configuration whose reservoir caps bind.
CAP_METRICS = ("plan.passive_row_share", "plan.solver_shapes",
               "solve.xla_newton_slab_share")


def a_cap_binds(man, config_name: str) -> bool:
    """Some random coordinate has an entity with more rows than its
    ``active_data_upper_bound``: by the generator's law, no data made."""
    config = man.config(config_name)
    rows_per_entity = man.generator(config_name).rows_per_entity
    return any(
        c["active_data_upper_bound"] is not None
        and rows_per_entity(config, c).max() > c["active_data_upper_bound"]
        for c in config["coordinates"] if c["kind"] == "random")


def check_the_cap_metrics_list_cells_whose_cap_binds(man, name):
    (metric,) = [m for m in man.doc["per_layer"] if m["name"] == name]
    assert "heavytail.refit" in metric["workloads"]
    for cell in metric["workloads"]:
        assert a_cap_binds(man, man.cell(cell)["config"]), (name, cell)
    assert metric["moves"] == "train_rows_per_s"
    assert metric["source"] == "program_counter"
    assert metric["better"] == "lower"


# ---- a cell's rehearsal on the CPU, on a tiny copy

def rehearse(man, cell: str, trace, seed: int, seconds: float = 0.5) -> dict:
    """The device handed over has the chips the cell asks for (the
    suite's CPU has 8 host devices; tests/conftest.py)."""
    entry = man.cell(cell)
    return run.run_cell(man, entry, seed=seed, seconds=seconds,
                        trace=bool(trace),
                        device=dict(FAKE_DEVICE, count=entry["chips"]))


def check_rehearsal_of_a_cell(man, cell, trace, out):
    """``out``: a rehearsal of that cell with that ``trace``."""
    assert list(out)[-1] == "compared"
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0
    traffic = man.traffic(man.cell(cell)["traffic"])
    assert out["attempted"] >= traffic["min_units"]
    json.dumps(out)
    names = set(out["metrics"])
    if not trace:
        assert names == {m["name"] for m in man.end_to_end(cell)}
        assert all(v["value"] > 0 for v in out["metrics"].values())
        assert "busy_s" not in out["device"]
    else:
        declared = {m["name"] for m in man.per_layer(cell)}
        assert names <= declared
        # No device plane on the CPU: a share of the device is left out of
        # the line, never printed as 0 or 100.
        assert not any(n.startswith(NEEDS_A_DEVICE) for n in names)
        assert declared - names <= {
            n for n in declared if n.startswith(NEEDS_A_DEVICE)}
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def check_a_traced_rehearsal_prints_every_metric_that_lists_the_cell(
        man, cell, out):
    """``out``: a traced rehearsal of that cell."""
    check_the_cell_is_listed_by_every_metric_of_its_kind(man, cell)
    listing = {m["name"] for m in man.per_layer(cell)
               if not m["name"].startswith(NEEDS_A_DEVICE)}
    assert listing <= set(out["metrics"])
    m = {n: v["value"] for n, v in out["metrics"].items()}
    assert all(m[n] >= 0.0 for n in listing)
    if cell_kind(man, cell) == "retrain_job":
        # The split adds up inside what the harness times from outside.
        assert (m["save.records_s"] + m["save.encode_s"] + m["save.write_s"]
                <= m["save.model_s"])
        assert m["fit.host_s.retrain"] <= m["job.fit_s"]
        assert m["fit.operands_s"] + m["compile.wait_s"] <= (
            m["fit.host_s.retrain"])
        assert m["ingest.plan_wall_s"] <= m["ingest.prepare_s"]


# ---- a cell's limits file (benchmark/limits/<cell>.json)

ROOM = 1.25  # the least factor between a limit and either reading


def _limit_numbers(man, cell):
    doc = _read_json(man.limits_path(cell))
    assert doc["cell"] == cell
    assert set(doc["readings"]) == set(doc["limits"])
    return [(name, doc["limits"][name], doc["readings"][name])
            for name in doc["limits"]]


def check_each_limit_lies_between_its_readings_with_room(man, cell):
    for name, limit, r in _limit_numbers(man, cell):
        if r["upper_from"] == "exact":
            assert limit == r["lower"] == r["upper"] == 0.0, name
            continue
        assert r["lower"] * ROOM <= limit <= r["upper"] / ROOM, (name, r)


def check_a_control_three_times_the_lower_reading_is_the_upper_one(
        man, cell):
    for name, limit, r in _limit_numbers(man, cell):
        if r["control"] is None:
            continue
        if r["control"] >= 3.0 * r["lower"]:
            assert r["upper"] <= r["control"], (name, r)
            assert limit < r["control"], (name, r)
        else:
            assert r["upper_from"] != "control", (name, r)


def check_the_controls_readings_fail_and_the_programs_pass(man, cell):
    limits = man.limits(cell)
    numbers = _limit_numbers(man, cell)
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


def check_tiny_limits_have_the_keys_of_limits_and_keep_exact_ones_exact(
        man, cell):
    """The limits of the CPU rehearsal sit beside the chip's in the same
    file (``tiny_copy`` reads them), number for number. ``repeat_max_abs``
    and ``saved_max_abs`` are benchmark/check.py's own and exact in every
    cell, whatever its file says; a file may state more exact numbers."""
    doc = _read_json(man.limits_path(cell))
    assert list(doc["tiny_limits"]) == list(doc["limits"])
    for name, limit in doc["limits"].items():
        exact = doc["readings"][name]["upper_from"] == "exact"
        if name.endswith("_max_abs"):
            assert exact, name
        assert exact == (limit == 0.0), name
        if exact:
            assert doc["tiny_limits"][name] == 0.0
        else:
            assert doc["tiny_limits"][name] > 0.0
    config = man.config(man.cell(cell)["config"])
    assert config["tiny"]["rows"] < config["rows"]
    assert set(config["tiny"]["entities"]) <= {
        c["name"] for c in config["coordinates"]}


LIMITS_CHECKS = [
    check_each_limit_lies_between_its_readings_with_room,
    check_a_control_three_times_the_lower_reading_is_the_upper_one,
    check_the_controls_readings_fail_and_the_programs_pass,
    check_tiny_limits_have_the_keys_of_limits_and_keep_exact_ones_exact,
]
