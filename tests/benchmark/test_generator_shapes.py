"""Data shapes come from the configuration, data from its ``data_seed`` (from
the seed where that is null); the
yardstick's FLOP and byte counts see neither padding nor the seed."""

import copy
import json
import os

import numpy as np
import pytest

from benchmark import costs, generator, sut
from benchmark.manifest import Manifest

from conftest import shrink


@pytest.fixture(scope="module")
def config():
    man = Manifest()
    return shrink(man.config("glmix_ml_linear"))


def _plan(config, seed):
    data = generator.generate(config, seed)
    est = sut.build_estimator(config)
    datasets, _ = est.prepare(sut.build_dataset(data))
    warm_compile = getattr(est, "_aot_future", None)
    if warm_compile is not None:  # started by prepare, consumed by no fit
        warm_compile.result()
    return data, sut.plan_shapes(datasets)


def test_two_seeds_equal_plan_shapes_and_the_configurations_data(config):
    """A stated ``data_seed`` makes the data set the configuration's: two
    seeds train on the same arrays, names included, so every run of a
    cell does the same work (PERF.md section 2: a renaming by ``--seed``
    moved the fused fit's work)."""
    a, shapes_a = _plan(config, 7)
    b, shapes_b = _plan(config, 2**31 + 11)
    assert shapes_a == shapes_b and shapes_a
    assert set(a.ids) == {"userId", "movieId"}
    for tag in a.ids:
        assert np.array_equal(a.ids[tag], b.ids[tag])
    assert np.array_equal(a.labels, b.labels)
    assert all(np.array_equal(a.features[k], b.features[k])
               for k in a.features)


def test_the_names_are_a_draw_and_not_the_order_of_making(config):
    """Which id owns which row set is drawn (from ``data_seed``), so an
    entity's id says nothing of its size or of when it was made."""
    data = generator.generate(config, 7)
    other = generator.generate(
        dict(config, data_seed=config["data_seed"] + 1), 7)
    for coord in config["coordinates"][1:]:
        counts = generator.rows_per_entity(config, coord)
        seen = np.bincount(data.ids[coord["id"]], minlength=counts.size)
        assert not np.array_equal(seen, counts)
        assert np.array_equal(np.sort(seen), np.sort(counts))
        assert not np.array_equal(
            seen, np.bincount(other.ids[coord["id"]], minlength=counts.size))


def test_another_data_seed_gives_other_values(config):
    other = dict(config, data_seed=config["data_seed"] + 1)
    a = generator.generate(config, 7)
    b = generator.generate(other, 7)
    assert not np.array_equal(a.labels, b.labels)
    # The names are the data seed's since PR 35: the same row counts,
    # owned by other ids.
    assert np.array_equal(np.sort(np.bincount(a.ids["userId"])),
                          np.sort(np.bincount(b.ids["userId"])))


def test_a_null_data_seed_draws_values_and_names_from_the_seed(config):
    free = dict(config, data_seed=None)
    a = generator.generate(free, 7)
    b = generator.generate(free, 8)
    again = generator.generate(free, 7)
    assert not np.array_equal(a.labels, b.labels)
    assert np.array_equal(a.labels, again.labels)
    for tag in a.ids:
        # Other owners of the same row counts: the ids differ, the
        # multiset of rows per entity does not.
        assert not np.array_equal(a.ids[tag], b.ids[tag])
        assert np.array_equal(a.ids[tag], again.ids[tag])
        assert np.array_equal(np.sort(np.bincount(a.ids[tag])),
                              np.sort(np.bincount(b.ids[tag])))


def test_same_seed_same_data(config):
    a = generator.generate(config, 5)
    b = generator.generate(config, 5)
    assert np.array_equal(a.labels, b.labels)
    assert all(np.array_equal(a.features[k], b.features[k])
               for k in a.features)


def test_rows_per_entity_ignore_the_seed_and_add_up(config):
    for coord in config["coordinates"][1:]:
        counts = generator.rows_per_entity(config, coord)
        assert counts.sum() == config["rows"]
        assert counts.shape == (coord["entities"],)


def test_the_uniform_law_is_one_multinomial_draw(config):
    """What the committed configurations' data was measured on: the law's
    general form leaves the uniform draw as it was."""
    for position, coord in enumerate(config["coordinates"]):
        if coord["kind"] == "fixed":
            continue
        rng = np.random.default_rng(
            [config["shape_seed"], generator._SHAPE_STREAM, position])
        entities = coord["entities"]
        assert np.array_equal(
            generator.rows_per_entity(config, coord),
            rng.multinomial(config["rows"],
                            np.full(entities, 1.0 / entities)))


@pytest.mark.parametrize("law", [
    {"law": "power", "exponent": 1.0, "min_rows": 4},
    {"law": "power", "exponent": 0.5},
    {"law": "uniform", "min_rows": 30},
])
def test_rows_per_entity_laws_are_data(config, law):
    """A heavy-tailed configuration is a file: the law, its exponent and
    its floor are keys of the coordinate; shapes still ignore the seed."""
    heavy = copy.deepcopy(config)
    for c in heavy["coordinates"][1:]:
        c["rows_per_entity"] = law
    for coord in heavy["coordinates"][1:]:
        counts = generator.rows_per_entity(heavy, coord)
        assert counts.sum() == heavy["rows"]
        assert counts.min() >= law.get("min_rows", 0)
        if law["law"] == "power":
            tenth = max(1, coord["entities"] // 10)
            assert counts[:tenth].sum() > counts[-tenth:].sum() * 2
    _, shapes_a = _plan(heavy, 7)
    _, shapes_b = _plan(heavy, 2**31 + 11)
    assert shapes_a == shapes_b and shapes_a


def test_an_unknown_law_or_an_impossible_floor_is_an_error(config):
    bad = copy.deepcopy(config)
    bad["coordinates"][1]["rows_per_entity"] = {"law": "lognormal"}
    with pytest.raises(ValueError, match="no rows_per_entity law"):
        generator.rows_per_entity(bad, bad["coordinates"][1])
    bad["coordinates"][1]["rows_per_entity"] = {
        "law": "uniform", "min_rows": bad["rows"]}
    with pytest.raises(ValueError, match="min_rows"):
        generator.rows_per_entity(bad, bad["coordinates"][1])


def test_costs_unchanged_by_padding(config):
    merged = copy.deepcopy(config)
    for c in merged["coordinates"][1:]:
        c["min_bucket_entities"] = 10**6  # every bucket merges upward
    _, loose = _plan(merged, 7)
    _, tight = _plan(config, 7)
    padded = {k: sum(b * r for b, r in v) for k, v in loose.items()}
    packed = {k: sum(b * r for b, r in v) for k, v in tight.items()}
    assert any(padded[k] > packed[k] for k in packed)
    assert costs.fit_flops(merged) == costs.fit_flops(config)
    assert costs.fit_hbm_bytes(merged) == costs.fit_hbm_bytes(config)


def test_costs_by_hand():
    cfg = {"rows": 10, "num_iterations": 2, "precision": "bfloat16",
           "coordinates": [{"features": 3}, {"features": 2}]}
    assert costs.fit_flops(cfg) == 2 * (2 * 10 * 3 * 5 + 2 * 10 * 2 * 4)
    assert costs.fit_hbm_bytes(cfg) == 2 * (
        10 * 3 * 2 + 120 + 10 * 2 * 2 + 120)
    flops, bytes_ = costs.newton_step_cost(rows=4, dim=3, lanes=2)
    assert flops == 2 * (2 * 4 * 3 * 5 + 9.0)
    assert bytes_ == 2 * 4 * (12 + 12 + 6)


def test_a_device_without_peaks_is_an_error():
    assert costs.chip_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        costs.chip_peaks("cpu")
    seconds, bound = costs.least_seconds(
        197e12, 819e9 * 2, costs.chip_peaks("TPU v5 lite"))
    assert (seconds, bound) == (pytest.approx(2.0), "hbm")
