"""A CPU rehearsal of each cell at a tiny size, and the refusals: no
device metric is printed without a chip."""

import os
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.manifest import Manifest

from conftest import REPO_ROOT, check_rehearsal_of_a_cell, rehearse

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
    check_rehearsal_of_a_cell(
        man, cell, trace, rehearse(man, cell, trace, seed=2**31 + 5))
