"""``glmix_ml_heavytail`` at both of its sizes, from the generator's and
the reference's own counts: the caps bind, the ladder has rungs enough and
some users train on one label, so the CPU rehearsal of ``heavytail.refit``
(test_rehearsal.py, test_limits.py) runs the reservoir, the passive rows'
scoring and ``unbounded.per-user``, as the chip run does."""

import copy

import numpy as np
import pytest

from benchmark.manifest import Manifest

from conftest import rehearse, shrink, tiny_copy, REPO_ROOT

MAN = Manifest()
NAME = "glmix_ml_heavytail"
CELL = "heavytail.refit"
GENERATOR = MAN.generator(NAME)
REFERENCE = MAN.reference(NAME)


def _config(size):
    config = copy.deepcopy(MAN.config(NAME))
    return shrink(config) if size == "tiny" else config


def _random(config):
    return [c for c in config["coordinates"] if c["kind"] == "random"]


def _rungs(kept):
    """The powers of two from 16 up that hold an entity (no tail rule:
    the least a planner of that ladder solves)."""
    caps = np.maximum(16, 2 ** np.ceil(np.log2(np.maximum(kept, 1))))
    return np.unique(caps[kept > 0])


def test_it_differs_from_the_logistic_configuration_in_rows_and_laws_only():
    ours, theirs = MAN.config(NAME), MAN.config("glmix_ml_logistic")
    free = {"name", "source", "source_note", "rows", "tiny", "assumed",
            "coordinates"}
    assert {k for k in set(ours) | set(theirs)
            if ours.get(k) != theirs.get(k)} <= free
    for mine, other in zip(ours["coordinates"], theirs["coordinates"]):
        assert {k for k in set(mine) | set(other)
                if mine.get(k) != other.get(k)} <= {"rows_per_entity"}
    assert "reference" not in ours and "generator" not in ours
    (entry,) = [c for c in MAN.doc["configs"] if c["name"] == NAME]
    assert entry["source"] == ours["source"]
    assert entry["reduced"] == ["matmul_precision"]


@pytest.mark.parametrize("size", ["stated", "tiny"])
def test_both_caps_bind_and_each_ladder_has_four_rungs(size):
    config = _config(size)
    for c in _random(config):
        counts = GENERATOR.rows_per_entity(config, c)
        cap = c["active_data_upper_bound"]
        assert counts.sum() == config["rows"]
        assert counts.max() > cap, (c["name"], int(counts.max()))
        assert len(_rungs(np.minimum(counts, cap))) >= 4, c["name"]


def test_the_stated_size_is_the_one_the_cell_was_planned_for():
    """ISSUE 32's counts: 1 442 users and 376 movies over their caps,
    27.6 % and 52.5 % of the rows passive, rungs 16..512 and 32..2048."""
    config = _config("stated")
    users, movies = _random(config)
    seen = {}
    for c in (users, movies):
        counts = GENERATOR.rows_per_entity(config, c)
        kept = np.minimum(counts, c["active_data_upper_bound"])
        seen[c["name"]] = (
            int(np.count_nonzero(counts > kept)),
            round(100.0 * (counts - kept).sum() / config["rows"], 1),
            [int(r) for r in _rungs(kept)])
    assert seen["per-user"] == (1442, 27.6, [16, 32, 64, 128, 256, 512])
    assert seen["per-movie"] == (
        376, 52.5, [32, 64, 128, 256, 512, 1024, 2048])


@pytest.fixture(scope="module")
def tiny_data():
    config = _config("tiny")
    return config, GENERATOR.generate(config, 2**31 + 5)


def test_some_tiny_users_train_on_one_label_and_no_movie_does(tiny_data):
    config, data = tiny_data
    users, movies = _random(config)
    sides = {}
    for c in (users, movies):
        order, starts, kept = REFERENCE.kept_rows(
            data.ids[c["id"]], c["entities"], c["active_data_upper_bound"],
            c["id"])
        assert kept.max() == c["active_data_upper_bound"]
        sides[c["name"]] = REFERENCE.one_label_side(
            data.labels, data.ids[c["id"]], order, starts, kept)
    assert np.count_nonzero(sides["per-user"]) >= 50
    assert np.count_nonzero(sides["per-movie"]) == 0
    limits = MAN.limits(CELL)
    assert "unbounded.per-user" in limits
    assert "unbounded.per-movie" not in limits


@pytest.fixture(scope="module")
def traced_rehearsal(tmp_path_factory):
    root = tiny_copy(REPO_ROOT, str(tmp_path_factory.mktemp("ht") / "co"))
    return rehearse(Manifest(root), CELL, True, seed=2**31 + 32, seconds=0.2)


def test_the_rehearsal_compares_the_one_label_users(traced_rehearsal):
    compared = traced_rehearsal["compared"]
    assert traced_rehearsal["correct"] is True, compared
    assert set(compared) == set(MAN.limits(CELL))
    assert 0.0 < compared["unbounded.per-user"]["value"] < 1e-4


def test_the_rehearsal_prints_what_the_caps_did(traced_rehearsal):
    m = {k: v["value"] for k, v in traced_rehearsal["metrics"].items()}
    config = _config("tiny")
    shares, rungs = [], 0
    for c in _random(config):
        counts = GENERATOR.rows_per_entity(config, c)
        kept = np.minimum(counts, c["active_data_upper_bound"])
        shares.append(100.0 * (counts - kept).sum() / config["rows"])
        rungs += len(_rungs(kept))
    assert m["plan.passive_row_share"] == pytest.approx(max(shares))
    assert max(shares) > 5.0
    # The planner's tail rule may join a thin rung to the next, never more.
    assert 8 <= m["plan.solver_shapes"] <= rungs
    # No Pallas step on the CPU: every Newton rung takes the XLA step.
    assert m["solve.xla_newton_slab_share"] == 100.0
    assert m["plan.padding_ratio"] < 2.5
