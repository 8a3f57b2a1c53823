"""``fit.fixed_iterations``: the fixed effect's optimizer iterations of a
fit, from what the program hands back with it; nothing where it hands back
nothing. It stands beside ``train_rows_per_s`` so that a rate that moved
with the L-BFGS's stop reads as that (PERF.md section 2, item 21)."""

import types

import pytest

from benchmark import generator, run, sut
from benchmark.manifest import Manifest

from conftest import cell_kind, cell_names, rehearse, shrink

MAN = Manifest()
NAME = "fit.fixed_iterations"
REFIT_CELLS = [c for c in cell_names(MAN) if cell_kind(MAN, c) == "refit"]


def _record(iterations):
    return types.SimpleNamespace(
        diagnostics=types.SimpleNamespace(iterations=iterations))


def test_the_count_is_the_sum_over_the_fixed_effects_updates():
    history = (_record(4), _record(None), _record(5),
               types.SimpleNamespace(diagnostics=object()))
    result = types.SimpleNamespace(
        descent=types.SimpleNamespace(history=history))
    assert sut.fixed_effect_iterations(result) == 9


@pytest.mark.parametrize("result", [
    None,
    types.SimpleNamespace(descent=None),
    types.SimpleNamespace(descent=types.SimpleNamespace(history=())),
    types.SimpleNamespace(descent=types.SimpleNamespace(
        history=(_record(None),))),
])
def test_a_fit_that_hands_back_no_count_gives_none(result):
    assert sut.fixed_effect_iterations(result) is None


def test_the_reader_gives_the_count_or_nothing():
    read = MAN.metric_reader(NAME)
    assert read(run.Reading(fixed_iterations=17)) == 17.0
    assert read(run.Reading()) is None


def test_a_real_fit_counts_every_coordinate_descent_iteration():
    """At least one L-BFGS iteration an update of the fixed effect, the
    same count from two fits of one prepared data set."""
    config = shrink(MAN.config("glmix_ml_logistic"))
    sut.configure(config)
    dataset = sut.build_dataset(generator.generate(config, 3))
    est = sut.build_estimator(config)
    est.prepare(dataset)
    counts = [sut.fixed_effect_iterations(sut.fit_blocking(est, dataset))
              for _ in range(2)]
    assert counts[0] == counts[1] >= int(config["num_iterations"])


def test_the_metric_is_the_refit_kinds_and_names_its_layer():
    (metric,) = [m for m in MAN.doc["per_layer"] if m["name"] == NAME]
    assert metric["workloads"] == REFIT_CELLS
    assert (metric["moves"], metric["source"], metric["better"]) == (
        "train_rows_per_s", "program_counter", "lower")
    assert metric["layer"] == "Fit, fused"


@pytest.mark.parametrize("cell", REFIT_CELLS)
def test_every_refit_cell_prints_it_in_the_rehearsal(tiny_root, cell):
    man = Manifest(tiny_root)
    out = rehearse(man, cell, True, seed=2**31 + 35, seconds=0.2)
    iterations = int(man.config(man.cell(cell)["config"])["num_iterations"])
    assert out["metrics"][NAME]["value"] >= iterations
    assert out["metrics"][NAME]["unit"] == "count"
