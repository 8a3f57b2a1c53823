"""Where the harness ends: ``benchmark/sut.py`` and the builders alone
import the program, and a configuration's plain reference, data generator
and builder are files found by the name its configuration file gives.
Every case that writes such a file runs twice: on the tree as it is, and
on one whose ``benchmark/references/``, ``generators/`` and ``builders/``
are already there with another PR's file in each."""

import json
import os
import re
import shutil

import pytest

from benchmark import run
from benchmark.manifest import Manifest

from conftest import (
    FAKE_DEVICE,
    NAMED_KEYS,
    REPO_ROOT,
    check_a_configuration_has_a_reference_and_a_generator,
)

IMPORTS_THE_PROGRAM = re.compile(
    r"^\s*(import photon_tpu|from photon_tpu)\b", re.MULTILINE)

NAMED = {
    # A reference that is off by one in the fixed effect: a run that asks
    # the manifest for it cannot come out correct.
    "reference": (
        "from benchmark.reference import predict  # noqa: F401\n"
        "from benchmark import reference as plain\n\n"
        "MARK = 'mine'\n\n\n"
        "def fit(config, data):\n"
        "    tables = plain.fit(config, data)\n"
        "    tables['global'] = tables['global'] + 1.0\n"
        "    return tables\n"),
    "generator": (
        "MARK = 'mine'\n\n\n"
        "def generate(config, seed):\n"
        "    raise RuntimeError(f'the named generator, seed {seed}')\n"),
    # A builder that gives the data set only, with half of the rows left
    # out: a run it builds cannot come out correct, and the estimator is
    # sut.py's.
    "builder": (
        "from benchmark import generator, sut\n\n"
        "MARK = 'mine'\n\n\n"
        "def build_dataset(data):\n"
        "    n = data.labels.shape[0] // 2\n"
        "    return sut.plain_dataset(generator.GlmixData(\n"
        "        labels=data.labels[:n],\n"
        "        features={k: v[:n] for k, v in data.features.items()},\n"
        "        ids={k: v[:n] for k, v in data.ids.items()}))\n"),
}
assert sorted(NAMED) == sorted(NAMED_KEYS)

# The file name the tests write in the three named directories. No
# committed reference, generator or builder may take it.
TESTS_OWN = "mine"

SOMEONE_ELSES = "MARK = 'a later PR\'s'\n"


@pytest.fixture(params=["tree_as_it_is", "named_directories_there"])
def tiny_root(request, tiny_root):
    """conftest's ``tiny_root``, and the same with the three directories
    already in the checkout, a foreign file in each: what the tree looks
    like once a PR has committed a named reference, generator or
    builder."""
    if request.param == "named_directories_there":
        for key in NAMED:
            folder = os.path.join(tiny_root, "benchmark", key + "s")
            os.makedirs(folder, exist_ok=True)
            with open(os.path.join(folder, "someone_elses.py"), "w") as f:
                f.write(SOMEONE_ELSES)
    return tiny_root


def _importers(bench):
    found = []
    for folder, _, names in os.walk(bench):
        for name in names:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                with open(path) as f:
                    if IMPORTS_THE_PROGRAM.search(f.read()):
                        found.append(os.path.relpath(path, bench))
    return sorted(found)


def test_sut_and_the_builders_are_the_files_that_import_the_program():
    allowed = re.compile(r"^(sut|builders/[^/]+)\.py$")
    importers = _importers(os.path.join(REPO_ROOT, "benchmark"))
    assert "sut.py" in importers
    assert [p for p in importers if not allowed.match(p)] == []


def test_an_importer_outside_sut_and_the_builders_is_found(tiny_root):
    bench = os.path.join(tiny_root, "benchmark")
    for name in ("builders/wide.py", "references/wide.py", "kinds/wide.py"):
        os.makedirs(os.path.dirname(os.path.join(bench, name)),
                    exist_ok=True)
        with open(os.path.join(bench, name), "w") as f:
            f.write("from photon_tpu import optim  # noqa: F401\n")
    assert _importers(bench) == [
        "builders/wide.py", "kinds/wide.py", "references/wide.py", "sut.py"]


@pytest.mark.parametrize("key", sorted(NAMED))
def test_no_committed_file_takes_the_name_the_tests_write(key):
    folder = os.path.join(REPO_ROOT, "benchmark", key + "s")
    assert not os.path.exists(os.path.join(folder, TESTS_OWN + ".py"))


@pytest.mark.parametrize(
    "config_name", [c["name"] for c in Manifest().doc["configs"]])
def test_every_configuration_has_a_reference_and_a_generator(config_name):
    check_a_configuration_has_a_reference_and_a_generator(
        Manifest(), config_name)


def _write_named(root, key):
    """``benchmark/<key>s/mine.py``, in a folder that may be there
    already; returns its path."""
    folder = os.path.join(root, "benchmark", key + "s")
    os.makedirs(folder, exist_ok=True)
    path = os.path.join(folder, TESTS_OWN + ".py")
    with open(path, "w") as f:
        f.write(NAMED[key])
    return path


def _add_named_config(root, **keys):
    """A configuration ``named``: the linear one's file with the keys,
    one entry in BENCHMARK.json and a cell ``named.refit`` on it."""
    man = Manifest(root)
    config = dict(man.config("glmix_ml_linear"), name="named", **keys)
    with open(os.path.join(root, "benchmark", "configs", "named.json"),
              "w") as f:
        json.dump(config, f)
    shutil.copy(man.limits_path("linear.refit"),
                man.limits_path("named.refit"))
    doc = man.doc
    doc["configs"].append({
        "name": "named", "source": "https://example.org/fixture",
        "file": "benchmark/configs/named.json", "reduced": [],
        "why": "fixture"})
    doc["workloads"].append({
        "name": "named.refit", "config": "named", "traffic": "refit",
        "chips": 1, "why": "fixture"})
    for m in doc["end_to_end"]:
        if m["name"] == "train_rows_per_s":
            m["workloads"].append("named.refit")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return Manifest(root)


@pytest.mark.parametrize("key", sorted(NAMED))
def test_a_named_module_is_its_file_and_no_name_is_the_one_that_is_there(
        tiny_root, key):
    path = _write_named(tiny_root, key)
    man = _add_named_config(tiny_root, **{key: "mine"})
    find = getattr(man, key)
    assert find("named").MARK == "mine"
    assert find("named").__file__ == path
    # Without the key, whatever the configuration is called: the file of
    # THIS checkout, not sys.path's, and one module for all of them; for
    # a builder, none: sut.py's pair builds it.
    plain = [c["name"] for c in man.doc["configs"]
             if key not in man.config(c["name"])]
    assert len(plain) >= 2 and "named" not in plain
    for name in plain:
        if key == "builder":
            assert find(name) is None
            continue
        assert find(name).__file__ == os.path.join(
            tiny_root, "benchmark", key + ".py")
        assert find(name) is find(plain[0])


@pytest.mark.parametrize("key", sorted(NAMED))
def test_an_unknown_reference_generator_or_builder_is_an_error(
        tiny_root, key):
    man = _add_named_config(tiny_root, **{key: "nowhere"})
    with pytest.raises(FileNotFoundError):
        getattr(man, key)("named")
    with pytest.raises(KeyError):
        getattr(man, key)("no_such_config")
    with pytest.raises(FileNotFoundError):
        run.run_cell(man, man.cell("named.refit"), seed=1, seconds=0.1,
                     trace=False, device=dict(FAKE_DEVICE))


def test_a_run_takes_reference_and_generator_from_the_manifest(tiny_root):
    for key in NAMED:
        _write_named(tiny_root, key)
    man = _add_named_config(tiny_root, reference="mine")
    out = run.run_cell(man, man.cell("named.refit"), seed=1, seconds=0.1,
                       trace=False, device=dict(FAKE_DEVICE))
    assert out["correct"] is False
    assert not out["compared"]["coef.global"]["ok"]
    assert out["compared"]["coef.per-user"]["ok"]

    path = os.path.join(tiny_root, "benchmark", "configs", "named.json")
    with open(path) as f:
        config = json.load(f)
    with open(path, "w") as f:
        json.dump(dict(config, generator="mine"), f)
    with pytest.raises(RuntimeError, match="the named generator, seed 9"):
        run.run_cell(man, man.cell("named.refit"), seed=9, seconds=0.1,
                     trace=False, device=dict(FAKE_DEVICE))


def test_a_run_is_built_by_the_builder_its_configuration_names(
        tiny_root, monkeypatch):
    from benchmark import sut

    _write_named(tiny_root, "builder")
    built = []
    real = sut.plain_estimator
    monkeypatch.setattr(
        sut, "plain_estimator",
        lambda config, precision=None: built.append(config["name"])
        or real(config, precision))

    man = _add_named_config(tiny_root, builder="mine")
    out = run.run_cell(man, man.cell("named.refit"), seed=1, seconds=0.1,
                       trace=False, device=dict(FAKE_DEVICE))
    # The named file's data set (half of the rows), sut.py's estimator,
    # which the file does not give; and no builder is left in use.
    assert out["correct"] is False
    assert not out["compared"]["score_rms"]["ok"]
    assert built == ["named"]
    assert sut._builder is None

    # A configuration without the key: sut.py's pair, as before.
    out = run.run_cell(man, man.cell("linear.refit"), seed=1, seconds=0.1,
                       trace=False, device=dict(FAKE_DEVICE))
    assert out["correct"] is True, out["compared"]
    assert built == ["named", "glmix_ml_linear"]


def test_a_builder_may_give_the_estimator_and_is_told_the_precision(
        tiny_root):
    from benchmark import sut

    folder = os.path.join(tiny_root, "benchmark", "builders")
    os.makedirs(folder, exist_ok=True)
    with open(os.path.join(folder, TESTS_OWN + ".py"), "w") as f:
        f.write(
            "from benchmark import sut\n\nASKED = []\n\n\n"
            "def build_estimator(config, precision=None):\n"
            "    ASKED.append(precision)\n"
            "    return sut.plain_estimator(config, precision)\n")
    man = _add_named_config(tiny_root, builder="mine")
    config = man.config("named")
    with sut.using_builder(man.builder("named")):
        est = sut.build_estimator(config, precision="float32")
        assert sut.build_estimator(config).precision == config["precision"]
    assert est.precision == "float32" and sut._builder is None
    assert man.builder("named").ASKED == ["float32", None]


def test_a_builder_in_use_inside_another_leaves_the_outer_one_in_use():
    from benchmark import sut

    outer = object()
    with sut.using_builder(outer):
        with sut.using_builder(None):
            assert sut._builder is None
        assert sut._builder is outer
        with pytest.raises(RuntimeError), sut.using_builder(None):
            raise RuntimeError("a run that fails")
        assert sut._builder is outer
    assert sut._builder is None
