"""The plain reference packs entities by size and keeps the active-data
cap: against the one dense slab it replaced (every entity in ONE group),
against a byte budget, and against the planner's own covered rows."""

import copy

import numpy as np
import pytest

from benchmark import generator, reference, sut
from benchmark.manifest import Manifest

from conftest import shrink

MAN = Manifest()
# What the dense packing of the power-law data below would hold at once,
# and the most the grouped one may (bytes of features, index, mask, labels
# and offsets: entities x width x (4 d + 24)).
SLAB_BUDGET_BYTES = 4_000_000


def _tiny(config_name, users=None, **changes):
    config = shrink(MAN.config(config_name))
    for c in config["coordinates"][1:]:
        c.update(copy.deepcopy(changes))
    if users:
        config["coordinates"][1]["entities"] = users
    return config


def _one_group(kept):
    """The dense slab: every entity that trains, in one batch as wide as
    the largest."""
    return [np.flatnonzero(kept > 0)]


def _slab_bytes(groups, kept, d):
    return max(len(g) * int(kept[g].max()) * (4 * d + 24) for g in groups)


# The mathematics of an entity's problem does not know its packing: only
# how many zeros pad it changes, and with them the rounding of float32
# sums. The Newton steps go on until the GRADIENT's norm stops falling, so
# both packings arrive at the minimiser to that rounding, logistic blocks
# too (while the objective had to fall they stopped up to 1e-3 apart).
TOLERANCE = 2e-6
FEW_ROWS = {"law": "power", "exponent": 0.8, "min_rows": 1}


@pytest.mark.parametrize("config_name, law, cap", [
    ("glmix_ml_linear", {"law": "uniform"}, None),
    ("glmix_ml_logistic", {"law": "uniform"}, None),
    ("glmix_ml_linear", {"law": "power", "exponent": 1.2}, None),
    ("glmix_ml_linear", {"law": "power", "exponent": 1.2}, 64),
    ("glmix_ml_logistic", {"law": "power", "exponent": 1.2}, 64),
    ("glmix_ml_logistic", FEW_ROWS, 16),
])  # the last one has entities that train on one label
def test_size_groups_fit_what_the_dense_slab_fits(
        monkeypatch, config_name, law, cap):
    config = _tiny(config_name, rows_per_entity=law,
                   active_data_upper_bound=cap)
    data = generator.generate(config, 7)
    grouped = reference.fit(config, data)
    monkeypatch.setattr(reference, "size_groups", _one_group)
    dense = reference.fit(config, data)
    for name in dense:
        stated = np.isfinite(dense[name])
        assert np.array_equal(np.isfinite(grouped[name]), stated)
        gap = np.max(np.abs(grouped[name][stated] - dense[name][stated]))
        assert gap <= TOLERANCE * max(1.0, np.max(np.abs(dense[name][stated])))


def test_a_power_law_fits_where_the_dense_slab_is_over_budget():
    config = _tiny("glmix_ml_linear",
                   rows_per_entity={"law": "power", "exponent": 1.2},
                   active_data_upper_bound=None)
    for coord in config["coordinates"][1:]:
        kept = generator.rows_per_entity(config, coord)
        d = coord["features"]
        groups = reference.size_groups(kept)
        assert _slab_bytes(_one_group(kept), kept, d) > SLAB_BUDGET_BYTES
        assert _slab_bytes(groups, kept, d) < SLAB_BUDGET_BYTES
        # Over all groups, under twice the real rows.
        assert sum(len(g) * int(kept[g].max()) for g in groups) < (
            2 * kept.sum())
    tables = reference.fit(config, generator.generate(config, 7))
    assert all(np.all(np.isfinite(t)) for t in tables.values())


@pytest.mark.parametrize("kept", [
    [0, 1, 1, 2, 3, 4, 5, 9, 10, 21, 0, 1000],
    [7],
    [0, 0],
    list(range(40)),
])
def test_a_group_is_at_most_twice_as_wide_as_its_narrowest_member(kept):
    kept = np.asarray(kept)
    groups = reference.size_groups(kept)
    members = np.concatenate(groups) if groups else np.empty(0, int)
    assert sorted(members) == list(np.flatnonzero(kept > 0))
    for g in groups:
        assert kept[g].max() <= 2 * kept[g].min()
    for a, b in zip(groups, groups[1:]):
        assert kept[b].min() > 2 * kept[a].min()


def test_an_entity_without_rows_keeps_a_zero_table_row():
    config = _tiny("glmix_ml_linear",
                   rows_per_entity={"law": "power", "exponent": 2.5},
                   active_data_upper_bound=None)
    counts = generator.rows_per_entity(config, config["coordinates"][1])
    assert (counts == 0).any()
    data = generator.generate(config, 3)
    table = reference.fit(config, data)["per-user"]
    empty = np.bincount(data.ids["userId"], minlength=len(counts)) == 0
    assert np.all(table[empty] == 0.0) and np.all(np.isfinite(table))
    assert np.all(np.any(table[~empty] != 0.0, axis=1))


@pytest.mark.parametrize("cap, binds", [(16, True), (48, True),
                                        (4096, False), (None, False)])
def test_the_references_kept_rows_are_the_planners_covered_rows(cap, binds):
    """The cap's rule is written out twice, in the program's planner and
    in the reference from its description; on seeded ids they keep the
    same rows, whether the cap binds or not."""
    config = _tiny("glmix_ml_logistic",
                   rows_per_entity={"law": "power", "exponent": 0.8,
                                    "min_rows": 1},
                   active_data_upper_bound=cap)
    data = generator.generate(config, 2**31 + 3)
    est = sut.build_estimator(config)
    datasets, _ = est.prepare(sut.build_dataset(data))
    warm_compile = getattr(est, "_aot_future", None)
    if warm_compile is not None:  # started by prepare, consumed by no fit
        warm_compile.result()
    for coord in config["coordinates"][1:]:
        ids = data.ids[coord["id"]]
        order, starts, kept = reference.kept_rows(
            ids, coord["entities"], cap, coord["id"])
        mine = np.zeros(ids.shape[0], bool)
        for e in range(coord["entities"]):
            mine[order[starts[e]:starts[e] + kept[e]]] = True
        assert (np.bincount(ids).max() > (cap or np.inf)) == binds
        assert mine.sum() == kept.sum()
        assert binds == (not mine.all())
        covered, passive = datasets[coord["name"]].covered_row_partition()
        assert np.array_equal(np.asarray(covered), mine), coord["name"]
        assert passive.shape[0] == (~mine).sum()


def test_one_label_side_reads_the_rows_an_entity_trains_on():
    """Entity 0 keeps two rows of its three (the cap), both of label 1,
    and its passive row carries 0; entity 1 has both labels, entity 2 one
    row of label 0, entity 3 no row."""
    ids = np.array([0, 1, 0, 1, 2, 0])
    labels = np.array([1, 1, 1, 0, 0, 0], np.float32)
    for upper, want in ((2, None), (None, [0, 0, -1, 0])):
        order, starts, kept = reference.kept_rows(ids, 4, upper, "userId")
        side = reference.one_label_side(labels, ids, order, starts, kept)
        if want is None:  # whichever two rows the keys keep
            ones = labels[order[starts[0]:starts[0] + 2]].sum()
            want = [int(ones == 2) - int(ones == 0), 0, -1, 0]
        assert list(side) == want


@pytest.mark.parametrize("config_name, some", [
    ("glmix_ml_logistic", True), ("glmix_ml_linear", False)])
def test_an_entity_without_a_minimiser_gets_zeros_and_an_infinite_intercept(
        config_name, some):
    """A logistic entity that trains on one label has no minimiser (its
    unpenalised intercept runs off, the penalised coefficients to 0): the
    reference states that point, on the label's side, and ordinary rows
    for everyone else; a squared loss always has its minimiser."""
    config = _tiny(config_name, users=1500, rows_per_entity=FEW_ROWS,
                   active_data_upper_bound=16)
    data = generator.generate(config, 11)
    steps = []
    tables = reference.fit(config, data, steps_taken=steps)
    assert max(steps) < reference.NEWTON_MAX_STEPS
    assert np.all(np.isfinite(tables["global"]))
    found = 0
    for coord in config["coordinates"][1:]:
        ids, table = data.ids[coord["id"]], tables[coord["name"]]
        order, starts, kept = reference.kept_rows(
            ids, coord["entities"], 16, coord["id"])
        for e in range(coord["entities"]):
            seen = set(data.labels[order[starts[e]:starts[e] + kept[e]]])
            if some and len(seen) == 1:
                found += 1
                assert np.all(table[e, :-1] == 0.0)
                assert table[e, -1] == (np.inf if seen == {1.0} else -np.inf)
            else:
                assert np.all(np.isfinite(table[e]))
    assert (found > 50) == some
    z = reference.predict(config, data, tables)
    member = config["coordinates"][1]
    open_rows = np.isinf(tables[member["name"]][data.ids[member["id"]], -1])
    assert np.array_equal(np.isinf(z), open_rows) and not np.isnan(z).any()


def test_compare_leaves_unstated_entities_out_and_holds_their_margins():
    from benchmark import check

    config = _tiny("glmix_ml_logistic", users=1500,
                   rows_per_entity=FEW_ROWS, active_data_upper_bound=16)
    data = generator.generate(config, 11)
    ref = reference.fit(config, data)
    far = {k: np.where(np.isinf(v), 20.0 * np.sign(v), v)
           for k, v in ref.items()}
    numbers = check.compare(config, data, {"tables": far}, ref,
                            reference.predict)
    assert numbers.pop("unbounded.per-user") == pytest.approx(np.exp(-20.0))
    assert all(v < 1e-6 for v in numbers.values()), numbers
    # One such entity comes back untrained.
    e = int(np.flatnonzero(np.isinf(ref["per-user"][:, -1]))[0])
    far["per-user"][e] = 0.0
    numbers = check.compare(config, data, {"tables": far}, ref,
                            reference.predict)
    assert numbers.pop("unbounded.per-user") == 1.0
    assert all(v < 1e-6 for v in numbers.values()), numbers
    # Nothing unstated, no such number.
    whole = {k: np.where(np.isinf(v), 0.0, v) for k, v in ref.items()}
    assert "unbounded.per-user" not in check.compare(
        config, data, {"tables": far}, whole, reference.predict)
