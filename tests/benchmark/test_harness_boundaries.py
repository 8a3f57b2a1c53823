"""Where the harness ends: ``benchmark/sut.py`` alone imports the
program, and a configuration's plain reference and data generator are
files found by the name its configuration file gives."""

import json
import os
import re
import shutil

import pytest

from benchmark import run
from benchmark.manifest import Manifest

from conftest import (
    FAKE_DEVICE,
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
}


def test_sut_is_the_one_module_that_imports_the_program():
    importers = []
    bench = os.path.join(REPO_ROOT, "benchmark")
    for folder, _, names in os.walk(bench):
        for name in names:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                with open(path) as f:
                    if IMPORTS_THE_PROGRAM.search(f.read()):
                        importers.append(os.path.relpath(path, bench))
    assert importers == ["sut.py"]


@pytest.mark.parametrize(
    "config_name", [c["name"] for c in Manifest().doc["configs"]])
def test_every_configuration_has_a_reference_and_a_generator(config_name):
    check_a_configuration_has_a_reference_and_a_generator(
        Manifest(), config_name)


def _write_named(root, key):
    """``benchmark/<key>s/mine.py``; returns its path."""
    folder = os.path.join(root, "benchmark", key + "s")
    os.makedirs(folder)
    path = os.path.join(folder, "mine.py")
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
    # THIS checkout, not sys.path's, and one module for all of them.
    plain = [c["name"] for c in man.doc["configs"]
             if key not in man.config(c["name"])]
    assert len(plain) >= 2 and "named" not in plain
    for name in plain:
        assert find(name).__file__ == os.path.join(
            tiny_root, "benchmark", key + ".py")
        assert find(name) is find(plain[0])


@pytest.mark.parametrize("key", sorted(NAMED))
def test_an_unknown_reference_or_generator_is_an_error(tiny_root, key):
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
