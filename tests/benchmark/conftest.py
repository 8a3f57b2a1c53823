"""Fixtures of the benchmark's own tests: a temporary copy of the
manifest and its data files, cut to a size the CPU holds."""

import json
import os
import shutil

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# A device the peaks table knows, handed to run_cell in place of the look
# for a chip (the CPU does the work; no number of these runs is a device
# number).
FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

# Limits at the TEST size, from readings on the CPU (x64 on, as the suite
# runs): the program reads 1e-4..5e-4 on the float32 configuration and the
# bf16 control 2e-3 on the random effects; the bf16 configuration reads
# 1e-3..3e-3 and its fp8 control 2e-2..5e-2.
TINY_LIMITS = {
    "glmix_ml_logistic": {"coef.global": 2e-3, "coef.per-user": 8e-4,
                          "coef.per-movie": 8e-4, "score_rms": 6e-4,
                          "entity_max.per-user": 0.02,
                          "entity_max.per-movie": 0.02},
    "glmix_ml_linear": {"coef.global": 6e-4, "coef.per-user": 8e-3,
                        "coef.per-movie": 6e-3, "score_rms": 4e-3,
                        "entity_max.per-user": 0.05,
                        "entity_max.per-movie": 0.05},
}
TINY_SIZES = {"rows": 12000, "per-user": 200, "per-movie": 40}


def shrink(config: dict) -> dict:
    config["rows"] = TINY_SIZES["rows"]
    for c in config["coordinates"]:
        if c["name"] in TINY_SIZES:
            c["entities"] = TINY_SIZES[c["name"]]
    return config


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
    """A checkout-shaped directory with BENCHMARK.json and the benchmark's
    data files, its configurations cut to TINY_SIZES and its limits to
    TINY_LIMITS."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO_ROOT, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(REPO_ROOT, "benchmark"),
        os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for entry in doc["configs"]:
        path = os.path.join(root, entry["file"])
        with open(path) as f:
            config = shrink(json.load(f))
        with open(path, "w") as f:
            json.dump(config, f)
    for cell in doc["workloads"]:
        path = os.path.join(root, "benchmark", "limits",
                            cell["name"] + ".json")
        with open(path) as f:
            limits = json.load(f)
        exact = {k: v for k, v in limits["limits"].items()
                 if k.endswith("_max_abs")}
        limits["limits"] = dict(TINY_LIMITS[cell["config"]], **exact)
        with open(path, "w") as f:
            json.dump(limits, f)
    return root
