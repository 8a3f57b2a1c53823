"""Fixtures of the benchmark's own tests: a temporary copy of the
manifest and its data files, cut to a size the CPU holds."""

import json
import os
import shutil

import pytest

from benchmark.manifest import Manifest

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
