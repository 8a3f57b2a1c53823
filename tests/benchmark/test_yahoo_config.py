"""``glmix_yahoo_music`` at both of its sizes, and its own files: the
stated size is the deployment's scale cut in proportion; shapes come from
``shape_seed`` and the genre bags from ``data_seed``; the bag is ELL as
``SparseFeatures`` holds it; at the tiny size a user's subspace is wider
than the lazy layout takes and both caps bind, so the CPU rehearsal plans
the layout the chip runs; the ``dataset`` and ``plan`` stages count what
a brute-force pass over the bags counts; the program's per-user models are
the exact solves in each user's subspace; the program meets the named
reference under the tiny limits and the bf16 control does not; the
rehearsal prints both new metrics."""

import copy

import numpy as np
import pytest

from benchmark import check, costs, sut
from benchmark import reference as plain
from benchmark.manifest import Manifest, _read_json

from conftest import FAKE_DEVICE, REPO_ROOT, rehearse, shrink, tiny_copy

MAN = Manifest()
NAME = "glmix_yahoo_music"
CELL = "yahoo.refit"
GENERATOR = MAN.generator(NAME)
REFERENCE = MAN.reference(NAME)
RATINGS, USERS, ITEMS = 252_800_275, 1_000_990, 624_961


def _config(size="stated"):
    config = copy.deepcopy(MAN.config(NAME))
    return shrink(config) if size == "tiny" else config


def _coordinate(config, name):
    (c,) = [c for c in config["coordinates"] if c["name"] == name]
    return c


def _widths(config, data):
    """Each user's subspace width, by brute force: the set of features
    with a nonzero value on the rows the cap keeps."""
    c = _coordinate(config, "per-user")
    ids, bag = data.ids[c["id"]], data.features[c["shard"]]
    order, starts, kept = plain.kept_rows(
        ids, c["entities"], c["active_data_upper_bound"], c["id"])
    seen = [set() for _ in range(c["entities"])]
    for e in range(c["entities"]):
        for row in order[starts[e]:starts[e] + kept[e]]:
            seen[e].update(bag.indices[row][bag.values[row] != 0].tolist())
    return np.array([len(s) for s in seen]), kept, seen


def test_it_is_the_source_cut_in_proportion():
    config = _config()
    assert (config["task"], config["precision"], config["matmul_precision"],
            config["mesh"], config["num_iterations"]) == (
        "LINEAR_REGRESSION", "float32", "highest", "off", 4)
    assert config["rows"] == 10_000_000
    assert config["shape_seed"] is not None and config["data_seed"] is not None
    share = config["rows"] / RATINGS
    assert round(100 * share, 3) == 3.956
    users, items = (_coordinate(config, n)["entities"]
                    for n in ("per-user", "per-item"))
    assert (users, items) == (39_597, 24_722)
    assert abs(users - USERS * share) < 1 and abs(items - ITEMS * share) < 1
    assert [c["features"] for c in config["coordinates"]] == [64, 993, 1]
    assert _coordinate(config, "per-user")["bag"]["genres"] == 992
    assert config["reference"] == config["generator"] == config["builder"]
    (entry,) = [c for c in MAN.doc["configs"] if c["name"] == NAME]
    assert entry["reduced"] == ["rows", "coordinates", "matmul_precision"]
    assert MAN.cell(CELL)["chips"] == 1


def test_the_stated_size_is_the_one_the_cell_was_planned_for():
    """Counts of the rows per entity the cell runs on (the bags, and so the
    subspaces, are in PERF.md: they take the whole data set to count)."""
    config = _config()
    seen = {}
    for name in ("per-user", "per-item"):
        c = _coordinate(config, name)
        counts = GENERATOR.rows_per_entity(config, c)
        kept = np.minimum(counts, c["active_data_upper_bound"])
        assert counts.sum() == config["rows"]
        seen[name] = (int(counts.max()), int(np.median(counts)),
                      int(counts.min()), int(np.count_nonzero(counts > kept)),
                      round(100.0 * (counts - kept).sum() / config["rows"], 2))
    assert seen["per-user"] == (24458, 183, 99, 2345, 11.38)
    assert seen["per-item"] == (299934, 160, 66, 507, 28.41)


@pytest.fixture(scope="module")
def tiny_data():
    config = _config("tiny")
    return config, GENERATOR.generate(config, 2**31 + 7)


def test_the_tiny_size_plans_the_layout_of_the_stated_one(tiny_data):
    """A user wider than the lazy layout takes (so the coordinate is
    planned materialized, as at 10 M rows), and an entity over each cap."""
    config, data = tiny_data
    widths, _, _ = _widths(config, data)
    assert widths.max() > 128
    for name in ("per-user", "per-item"):
        c = _coordinate(config, name)
        counts = np.bincount(data.ids[c["id"]], minlength=c["entities"])
        assert counts.max() > c["active_data_upper_bound"], name


def test_shapes_come_from_shape_seed_alone_and_bags_from_data_seed(
        tiny_data):
    config, a = tiny_data
    b = GENERATOR.generate(config, 11)
    assert np.array_equal(a.labels, b.labels)
    for tag in a.ids:
        assert np.array_equal(a.ids[tag], b.ids[tag])
    bag_a, bag_b = a.features["genreShard"], b.features["genreShard"]
    assert np.array_equal(bag_a.indices, bag_b.indices)

    other = GENERATOR.generate(dict(config, data_seed=config["data_seed"] + 1),
                               7)
    bag_o = other.features["genreShard"]
    assert not np.array_equal(bag_a.indices, bag_o.indices)
    assert not np.array_equal(a.features["global"], other.features["global"])
    for c in config["coordinates"][1:]:
        counts = GENERATOR.rows_per_entity(config, c)
        assert np.array_equal(
            counts, GENERATOR.rows_per_entity(
                dict(config, data_seed=config["data_seed"] + 1), c))
        assert not np.array_equal(
            counts, GENERATOR.rows_per_entity(
                dict(config, shape_seed=config["shape_seed"] + 1), c))
        for data in (a, other):
            assert np.array_equal(
                np.sort(np.bincount(data.ids[c["id"]],
                                    minlength=c["entities"])),
                np.sort(counts))


def test_the_bag_is_ell_as_sparse_features_holds_it(tiny_data):
    config, data = tiny_data
    bag = data.features["genreShard"]
    spec = _coordinate(config, "per-user")["bag"]
    n = config["rows"]
    assert bag.d == 993 and bag.shape == (n, 993)
    assert bag.indices.shape == bag.values.shape == (n, spec["max_genres"] + 1)
    assert bag.indices.dtype == np.int32 and bag.values.dtype == np.float32
    used = bag.values != 0
    assert set(np.unique(bag.values)) == {0.0, 1.0}
    # Used slots first, padding (0, 0.0) after them.
    assert np.all(used[:, :-1] | ~used[:, 1:])
    assert np.all(bag.indices[~used] == 0)
    width = used.sum(axis=1)
    rows = np.arange(n)
    assert np.all(bag.indices[rows, width - 1] == spec["genres"])
    assert 2 <= width.min() and width.max() <= spec["max_genres"] + 1
    genres = np.where(used, bag.indices, 10_000)[:, :-1]
    genres = np.where(np.arange(genres.shape[1]) < width[:, None] - 1,
                      genres, 10_000)
    ordered = np.diff(genres, axis=1)
    assert np.all((ordered > 0) | (genres[:, 1:] == 10_000))
    assert genres[genres < 10_000].max() < spec["genres"]


@pytest.fixture(scope="module")
def tiny_fit(tiny_data):
    """The program on the tiny data through the configuration's builder,
    with the stages it recorded, and the named reference's tables."""
    import time

    from photon_tpu import obs

    config, data = tiny_data
    sut.configure(config)
    start = time.perf_counter()
    with sut.using_builder(MAN.builder(NAME)):
        dataset = sut.build_dataset(data)
        est = sut.build_estimator(config)
        datasets, _ = est.prepare(dataset)
        result = sut.fit_blocking(est, dataset)
    return {
        "datasets": datasets,
        "records": [r for r in obs.TRACER.completed() if r.t0 >= start],
        "tables": sut.model_tables(result.model, config),
        "reference": REFERENCE.fit(config, data),
    }


def test_the_dataset_and_plan_stages_count_what_brute_force_counts(
        tiny_data, tiny_fit):
    config, data = tiny_data
    bag = data.features["genreShard"]
    (dataset,) = [r for r in tiny_fit["records"] if r.name == "dataset"]
    assert dataset.attrs["sparse_shards"] == {"genreShard": {
        "ell_slots": bag.indices.shape[1],
        "nnz": int(np.count_nonzero(bag.values))}}
    (plan,) = [r for r in tiny_fit["records"] if r.name == "plan"
               and r.attrs["shard_form"] == "ell"]
    widths, kept, _ = _widths(config, data)
    attrs = plan.attrs
    assert (attrs["layout"], attrs["layout_rule"]) == (
        "materialized", "wide_subspace")
    assert attrs["max_sub_dim"] == widths.max()
    assert attrs["active_subspace_elements"] == int((kept * widths).sum())
    ds = tiny_fit["datasets"]["per-user"]
    keys = np.asarray([int(k) for k in ds.entity_keys])
    by_cap = {}
    for block, codes in zip(ds.blocks, ds.block_codes_np):
        by_cap.setdefault(int(block.row_ids.shape[1]), []).extend(
            keys[codes].tolist())
    want = [[cap, len(ids), int(widths[ids].max())]
            for cap, ids in sorted(by_cap.items())]
    assert attrs["subspaces"] == want
    (item_plan,) = [r for r in tiny_fit["records"] if r.name == "plan"
                    and r.attrs["shard_form"] == "dense"]
    assert (item_plan.attrs["layout"], item_plan.attrs["max_sub_dim"]) == (
        "lazy", 1)
    fit = [r for r in tiny_fit["records"] if r.name == "fit"][-1]
    per_user = fit.attrs["coordinates"]["per-user"]
    assert per_user["layout"] == "materialized"
    assert per_user["subspaces"] == want
    assert per_user["score_route"] == "table"


def test_the_program_meets_the_named_reference_and_the_control_does_not(
        tiny_data, tiny_fit):
    import jax.numpy as jnp

    config, data = tiny_data
    limits = {k: v for k, v in
              _read_json(MAN.limits_path(CELL))["tiny_limits"].items()
              if k != "repeat_max_abs"}
    numbers = check.compare(config, data, {"tables": tiny_fit["tables"]},
                            tiny_fit["reference"], REFERENCE.predict)
    ok, compared = check.verdict(numbers, limits)
    assert ok, compared
    control = REFERENCE.fit(config, data, storage=jnp.bfloat16)
    ok, compared = check.verdict(
        check.compare(config, data, {"tables": control},
                      tiny_fit["reference"], REFERENCE.predict), limits)
    assert not ok, compared


def test_untrained_item_biases_move_the_global_model_past_its_limit(
        tiny_data, tiny_fit, monkeypatch):
    """The fault that gives ``coef.global`` its upper reading (the bf16
    control reads under the program there): the reference fit with the
    per-item coordinate left untrained through every iteration, its biases
    zero and its rows scored at zero, the other coordinates fitted around
    it. It is not correct, and ``coef.global`` alone fails its limit at
    both sizes."""
    config, data = tiny_data
    real = plain.Coordinate.solve

    def untrained(self, y, off, task, steps_taken=None):
        if self.name != "per-item":
            real(self, y, off, task, steps_taken)

    monkeypatch.setattr(plain.Coordinate, "solve", untrained)
    fault = REFERENCE.fit(config, data)
    assert not np.any(fault["per-item"])
    doc = _read_json(MAN.limits_path(CELL))
    numbers = check.compare(config, data, {"tables": fault},
                            tiny_fit["reference"], REFERENCE.predict)
    for limits in (doc["tiny_limits"], doc["limits"]):
        ok, compared = check.verdict(
            numbers, {k: v for k, v in limits.items()
                      if k != "repeat_max_abs"})
        assert not ok
        assert not compared["coef.global"]["ok"], compared


def test_the_users_models_are_the_exact_solves_in_their_subspaces(
        tiny_data):
    """One CD iteration of the per-user coordinate alone: each user's
    model is the ridge solve (intercept unpenalised) over the features of
    its kept rows, here in float64 numpy, and zero elsewhere."""
    config, data = tiny_data
    config = dict(config, num_iterations=1, coordinates=[
        _coordinate(config, "per-user")])
    c = config["coordinates"][0]
    with sut.using_builder(MAN.builder(NAME)):
        dataset = sut.build_dataset(data)
    result = sut.fit_blocking(sut.plain_estimator(config), dataset)
    got = sut.model_tables(result.model, config)["per-user"]
    _, _, seen = _widths(config, data)
    bag, ids = data.features["genreShard"], data.ids["userId"]
    order, starts, kept = plain.kept_rows(
        ids, c["entities"], c["active_data_upper_bound"], c["id"])
    want = np.zeros_like(got, dtype=np.float64)
    for e in range(c["entities"]):
        rows = order[starts[e]:starts[e] + kept[e]]
        feats = np.array(sorted(seen[e]))
        x = np.zeros((rows.size, feats.size))
        for j, f in enumerate(feats):
            x[:, j] = np.sum(np.where(bag.indices[rows] == f,
                                      bag.values[rows], 0.0), axis=1)
        penalty = np.where(feats == bag.d - 1, 0.0, c["l2"])
        want[e, feats] = np.linalg.solve(
            x.T @ x + np.diag(penalty), x.T @ data.labels[rows])
    assert np.all(got[want == 0.0] == 0.0)
    scale = np.sqrt(np.mean(np.sum(want ** 2, axis=1)))
    assert np.max(np.abs(got - want)) < 1e-4 * scale


@pytest.fixture(scope="module")
def traced_rehearsal(tmp_path_factory):
    root = tiny_copy(REPO_ROOT, str(tmp_path_factory.mktemp("ya") / "co"))
    return rehearse(Manifest(root), CELL, True, seed=2**31 + 40, seconds=0.2)


def test_the_rehearsal_prints_the_subspace_metrics(traced_rehearsal):
    assert traced_rehearsal["correct"] is True, traced_rehearsal["compared"]
    m = {k: v["value"] for k, v in traced_rehearsal["metrics"].items()}
    assert m["plan.materialized_row_share_pct"] == 100.0
    assert 1.0 < m["plan.subspace_pad_ratio"] < 4.0
    assert 1.0 < m["plan.padding_ratio"] < 2.0
    assert m["fit.fixed_iterations"] > 0
    # Squared loss runs no Newton kernel: nothing for its roofline.
    assert "kernel.newton_roofline_pct" not in m


@pytest.mark.parametrize("name, count, peak", [
    ("fit.mfu_pct", costs.fit_flops, "flops_per_s"),
    ("fit.hbm_share_pct", costs.fit_hbm_bytes, "hbm_bytes_per_s"),
])
def test_the_rehearsal_counts_the_bag_by_its_nonzeros(
        traced_rehearsal, name, count, peak):
    """The whole-fit shares the rehearsal prints are the yardstick's counts
    of what the tiny data set holds, over window x peak: the bag by its
    stored nonzeros (135 209, their squares a row summed 508 785: a count
    of the generated bags block by block), the dense shards by their
    shapes. Counted by its 993 columns the bag would read over ten times
    more."""
    config = _config("tiny")
    counts = costs.shard_counts(
        config, GENERATOR.generate(config, 2**31 + 40))
    assert counts["per-user"] == {
        "rows": 40_000, "nonzeros": 135_209, "square_nonzeros": 508_785}
    out = traced_rehearsal
    peaks = costs.chip_peaks(FAKE_DEVICE["kind"])
    want = 100.0 * (count(config, counts) * out["attempted"]) / (
        out["window"]["window_s"] * (1 * peaks[peak]))
    assert out["metrics"][name]["value"] == want
    as_dense = dict(counts, **{"per-user": {
        "rows": 40_000, "shape": (40_000, 993)}})
    assert count(config, as_dense) > 10 * count(config, counts)


@pytest.mark.parametrize("name", ["plan.subspace_pad_ratio",
                                  "plan.materialized_row_share_pct"])
def test_the_new_metrics_list_this_cell_alone(name):
    (metric,) = [m for m in MAN.doc["per_layer"] if m["name"] == name]
    assert metric["workloads"] == [CELL]
    assert metric["layer"] == "Ingest planner"
    assert metric["better"] == "lower"
    assert metric["moves"] == "train_rows_per_s"


def test_the_builder_refuses_a_program_that_stores_a_bucket_whole(
        monkeypatch):
    """A program without ``MATERIALIZED_BLOCK_ELEMENTS`` cannot run the
    cell on one chip: its builder fails as it is loaded, before any data
    is made."""
    import importlib.util
    import os

    from photon_tpu.data import random_effect

    monkeypatch.delattr(random_effect, "MATERIALIZED_BLOCK_ELEMENTS")
    path = os.path.join(REPO_ROOT, "benchmark", "builders", NAME + ".py")
    spec = importlib.util.spec_from_file_location("yahoo_builder", path)
    with pytest.raises(AttributeError, match="MATERIALIZED_BLOCK_ELEMENTS"):
        spec.loader.exec_module(importlib.util.module_from_spec(spec))
