"""``glmix_ml25m`` at both of its sizes, and its own files: the caps bind
and the ladder has rungs enough at the stated and at the tiny size; at the
tiny size the row count and some buckets' entity counts are no multiple of
four, so the inert padding of a four-device mesh is exercised; the named
reference gives ``benchmark/reference.py``'s tables; the program on four
devices meets the named reference under the tiny limits, and the program
on one device within half of them."""

import copy

import numpy as np
import pytest

from benchmark import check, sut
from benchmark.manifest import Manifest

from conftest import REPO_ROOT, rehearse, shrink, tiny_copy

MAN = Manifest()
NAME = "glmix_ml25m"
CELL = "ml25m.refit4"
GENERATOR = MAN.generator(NAME)
REFERENCE = MAN.reference(NAME)


def _config(size):
    config = copy.deepcopy(MAN.config(NAME))
    return shrink(config) if size == "tiny" else config


def _random(config):
    return [c for c in config["coordinates"] if c["kind"] == "random"]


def _rungs(kept):
    caps = np.maximum(16, 2 ** np.ceil(np.log2(np.maximum(kept, 1))))
    return np.unique(caps[kept > 0])


def test_it_is_the_logistic_configuration_at_another_scale_on_a_mesh():
    ours, theirs = MAN.config(NAME), MAN.config("glmix_ml_logistic")
    free = {"name", "source", "source_note", "rows", "tiny", "assumed",
            "coordinates", "mesh", "shape_seed", "data_seed", "reference",
            "builder"}
    assert {k for k in set(ours) | set(theirs)
            if ours.get(k) != theirs.get(k)} <= free
    for mine, other in zip(ours["coordinates"], theirs["coordinates"]):
        assert {k for k in set(mine) | set(other)
                if mine.get(k) != other.get(k)} <= {
                    "rows_per_entity", "entities"}
    assert ours["mesh"] == 4 and ours["rows"] == 25_000_095
    assert ours["shape_seed"] == ours["data_seed"]
    assert [c["entities"] for c in _random(ours)] == [162_541, 59_047]
    assert ours["reference"] == ours["builder"] == NAME
    assert "generator" not in ours
    (entry,) = [c for c in MAN.doc["configs"] if c["name"] == NAME]
    assert entry["source"].startswith(ours["source"])
    assert entry["reduced"] == ["matmul_precision"]
    assert MAN.cell(CELL)["chips"] == 4


@pytest.mark.parametrize("size", ["stated", "tiny"])
def test_both_caps_bind_and_each_ladder_has_three_rungs(size):
    config = _config(size)
    for c in _random(config):
        counts = GENERATOR.rows_per_entity(config, c)
        cap = c["active_data_upper_bound"]
        assert counts.sum() == config["rows"]
        assert counts.max() > cap, (c["name"], int(counts.max()))
        assert len(_rungs(np.minimum(counts, cap))) >= 3, c["name"]


def test_the_stated_size_is_the_one_the_cell_was_planned_for():
    """ISSUE 36's counts: 3 030 users and 795 movies over their caps,
    5.79 % and 7.40 % of the rows passive, every user at least 20 rows
    (the data set's own floor) and, by the law, no small movie."""
    config = _config("stated")
    seen = {}
    for c in _random(config):
        counts = GENERATOR.rows_per_entity(config, c)
        kept = np.minimum(counts, c["active_data_upper_bound"])
        seen[c["name"]] = (
            int(counts.max()), int(counts.min()),
            int(np.count_nonzero(counts > kept)),
            round(100.0 * (counts - kept).sum() / config["rows"], 2),
            [int(r) for r in _rungs(kept)])
    assert seen["per-user"] == (27434, 58, 3030, 5.79, [64, 128, 256, 512])
    assert seen["per-movie"] == (
        79594, 155, 795, 7.4, [256, 512, 1024, 2048])


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration, its data, the named reference's tables and
    the program's on four devices, through the configuration's builder."""
    config = _config("tiny")
    data = GENERATOR.generate(config, 2**31 + 5)
    sut.configure(config)
    with sut.using_builder(MAN.builder(NAME)):
        dataset = sut.build_dataset(data)
        est = sut.build_estimator(config)
        datasets, _ = est.prepare(dataset)
        result = sut.fit_blocking(est, dataset)
    return {
        "config": config, "data": data, "datasets": datasets,
        "reference": REFERENCE.fit(config, data),
        "mesh": sut.model_tables(result.model, config),
    }


def test_the_tiny_size_pads_rows_and_entities_to_the_mesh(tiny):
    assert tiny["config"]["rows"] % 4
    odd = 0
    for name, ds in tiny["datasets"].items():
        for i, codes in enumerate(getattr(ds, "block_codes_np", ())):
            assert len(codes) % 4 == 0
            odd += int(ds.real_entity_mask(i).sum()) % 4 != 0
    assert odd >= 2
    # The fixed effect's rows: padded to the mesh, a quarter a device.
    batch = tiny["datasets"]["global"]
    assert batch.num_samples == tiny["config"]["rows"] + 1
    assert len(batch.labels.sharding.device_set) == 4


def test_the_named_reference_gives_the_plain_references_tables(tiny):
    from benchmark import reference as plain

    want = plain.fit(tiny["config"], tiny["data"])
    for name, table in tiny["reference"].items():
        assert np.array_equal(np.isinf(table), np.isinf(want[name]))
        stated = np.isfinite(want[name])
        np.testing.assert_allclose(
            table[stated], want[name][stated], rtol=0, atol=2e-6)
    z = REFERENCE.predict(tiny["config"], tiny["data"], want)
    np.testing.assert_allclose(
        z, plain.predict(tiny["config"], tiny["data"], want),
        rtol=0, atol=1e-6)


def _tiny_limits():
    from benchmark.manifest import _read_json

    return _read_json(MAN.limits_path(CELL))["tiny_limits"]


def test_the_program_on_four_devices_meets_the_named_reference(tiny):
    numbers = check.compare(
        tiny["config"], tiny["data"], {"tables": tiny["mesh"]},
        tiny["reference"], REFERENCE.predict)
    limits = {k: v for k, v in _tiny_limits().items()
              if k != "repeat_max_abs"}
    ok, compared = check.verdict(numbers, limits)
    assert ok, compared


def test_the_program_on_one_device_is_within_half_the_tiny_limits(tiny):
    config = dict(tiny["config"], mesh="off")
    est = sut.plain_estimator(config)
    result = sut.fit_blocking(est, sut.plain_dataset(tiny["data"]))
    one = sut.model_tables(result.model, config)
    numbers = check.compare(
        tiny["config"], tiny["data"], {"tables": tiny["mesh"]}, one,
        REFERENCE.predict)
    for name, limit in _tiny_limits().items():
        if name != "repeat_max_abs":
            assert numbers[name] <= 0.5 * limit, (name, numbers[name])


@pytest.fixture(scope="module")
def traced_rehearsal(tmp_path_factory):
    root = tiny_copy(REPO_ROOT, str(tmp_path_factory.mktemp("ml") / "co"))
    return rehearse(Manifest(root), CELL, True, seed=2**31 + 36, seconds=0.2)


def test_the_rehearsal_prints_what_the_mesh_did(traced_rehearsal):
    assert traced_rehearsal["correct"] is True, traced_rehearsal["compared"]
    assert set(traced_rehearsal["compared"]) == set(MAN.limits(CELL))
    m = {k: v["value"] for k, v in traced_rehearsal["metrics"].items()}
    assert m["fit.programs_per_fit"] >= 4 * 3 * 2
    assert 1.0 <= m["mesh.placement_skew"] < 1.25
    assert m["fit.host_s.refit"] > 0.0
    assert 1.0 < m["plan.padding_ratio"] < 2.0
    assert "kernel.collective_share_pct" not in m
    assert "device.idle_share.worst_chip" not in m


def test_the_loops_fit_stage_says_what_the_cap_metrics_would_read(
        traced_rehearsal):
    """The three cap metrics do not list this cell, though both of its
    caps bind: test_adding_a_configuration.py pins their lists to
    ``heavytail.refit`` and its own fixture cell, and is not this PR's to
    edit. What they would read is on the unfused loop's ``fit`` stage all
    the same, for the PR that may list the cell."""
    from photon_tpu import obs

    from conftest import CAP_METRICS, a_cap_binds

    assert a_cap_binds(MAN, NAME)
    for name in CAP_METRICS:
        (metric,) = [x for x in MAN.doc["per_layer"] if x["name"] == name]
        assert CELL not in metric["workloads"]
        assert name not in traced_rehearsal["metrics"]
    attrs = [r.attrs for r in obs.TRACER.completed()
             if r.name == "fit" and r.attrs and "programs" in r.attrs][-1]
    rungs = [rung for c in attrs["coordinates"].values()
             for rung in c["rungs"]]
    assert len(rungs) == 8
    # A mesh closes the Pallas step: every Newton rung takes the XLA one.
    assert {route for _, _, route in rungs} == {"newton_xla"}
    shares = [100.0 * c["passive_rows"] / (c["active_rows"] + c["passive_rows"])
              for c in attrs["coordinates"].values()]
    assert 0.0 < max(shares) < 10.0
