"""A CPU rehearsal of each cell at a tiny size, and the refusals: no
device metric is printed without a chip."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.manifest import Manifest

from conftest import FAKE_DEVICE, REPO_ROOT

CELLS = [w["name"] for w in Manifest().doc["workloads"]]


def _run_command(cwd, cell, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    command = Manifest().doc["command"] + [
        "--workload", cell, "--seed", "3", "--seconds", "1", "--trace", "0"]
    return subprocess.run(
        command, cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


@pytest.mark.parametrize("cell", CELLS)
def test_the_command_refuses_the_cpu_and_prints_no_result(cell):
    done = _run_command(REPO_ROOT, cell)
    assert done.returncode == run.EXIT_NO_CHIP
    assert done.stdout.strip() == ""
    assert "no accelerator" in done.stderr


def test_the_command_refuses_a_directory_without_the_program(tiny_root):
    done = _run_command(tiny_root, CELLS[0], {"PYTHONPATH": ""})
    assert done.returncode == run.EXIT_NO_PROGRAM
    assert done.stdout.strip() == ""


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_a_cell(tiny_root, cell, trace):
    man = Manifest(tiny_root)
    out = run.run_cell(man, man.cell(cell), seed=2**31 + 5, seconds=0.5,
                       trace=bool(trace), device=dict(FAKE_DEVICE))
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
        assert not any(n.startswith(("device.idle_share", "kernel."))
                       for n in names)
        assert declared - names <= {
            n for n in declared
            if n.startswith(("device.idle_share", "kernel."))}
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
