"""The yardstick counts a coordinate from what its shard holds: a dense
``[n, d]`` shard exactly as it was counted before ELL shards were (every
accepted configuration's dense coordinates, bit for bit), an ELL shard by
its stored nonzeros and never by its width, so that a wide hashed-feature
coordinate (LIBSVM's ``kdd12``: 54 686 452 features, 11 to a row) reads
under 100 % of the peaks at a fit the chip could run."""

import copy
import types

import numpy as np
import pytest

from benchmark import costs, run
from benchmark.manifest import Manifest

from conftest import FAKE_DEVICE, shrink

MAN = Manifest()
CONFIGS = [c["name"] for c in MAN.doc["configs"]]
SHARES = ("fit.mfu_pct", "fit.hbm_share_pct")


def _flops_before(config, c):
    """One coordinate's term of ``fit_flops`` as every accepted reading
    was counted, from the configuration's rows and width."""
    n = float(config["rows"])
    d = float(c["features"])
    return 2.0 * n * d * (2.0 + d)


def _bytes_before(config, c):
    n = float(config["rows"])
    width = {"float32": 4, "bfloat16": 2}[config["precision"]]
    return n * float(c["features"]) * width + 3.0 * 4.0 * n


def _fit_before(config, term):
    per_sweep = sum(term(config, c) for c in config["coordinates"])
    return per_sweep * float(config["num_iterations"])


@pytest.mark.parametrize("name", CONFIGS)
def test_a_dense_coordinate_counts_as_it_did(name):
    """Which shards are dense is read from the configuration's own data at
    its tiny size; their counts at the stated size equal (==) the
    expression every reading before ELL shards were counted was taken
    with."""
    config = MAN.config(name)
    tiny = shrink(copy.deepcopy(config))
    held = costs.shard_counts(tiny, MAN.generator(name).generate(tiny, 3))
    dense = [c for c in config["coordinates"] if "shape" in held[c["name"]]]
    for c in dense:
        assert held[c["name"]] == {
            "rows": tiny["rows"], "shape": (tiny["rows"], c["features"])}
    stated = dict(config, coordinates=dense)
    data = types.SimpleNamespace(features={
        c["shard"]: np.broadcast_to(np.float32(1.0),
                                    (config["rows"], c["features"]))
        for c in dense})
    counts = costs.shard_counts(stated, data)
    width = {"float32": 4, "bfloat16": 2}[config["precision"]]
    for c in dense:
        assert costs.coordinate_flops(counts[c["name"]]) == _flops_before(
            config, c)
        assert costs.coordinate_hbm_bytes(
            counts[c["name"]], width) == _bytes_before(config, c)
    assert costs.fit_flops(stated, counts) == _fit_before(
        stated, _flops_before)
    assert costs.fit_hbm_bytes(stated, counts) == _fit_before(
        stated, _bytes_before)


# LIBSVM's binary data page, kdd12 (KDD Cup 2012 Track 2): 54 686 452
# hashed binary features, one for each of 11 fields a row; plus the
# intercept.
KDD12_FEATURES = 54_686_452 + 1
KDD12_NONZEROS_A_ROW = 11 + 1


def _share(name, config, counts, window_s=1.0):
    return MAN.metric_reader(name)(run.Reading(
        config=config, counts=counts, costs=costs, units=1,
        window_s=window_s, peaks=costs.chip_peaks(FAKE_DEVICE["kind"])))


def test_a_kdd12_wide_coordinate_reads_under_the_peaks():
    """At kdd12's width, 4 M rows and 12 stored nonzeros a row, a fit of
    one CD iteration in one second reads under 100 % of both v5e peaks
    when counted by its nonzeros; counted by its width it would read over
    105 %, which no run may print."""
    rows, k = 4_000_000, KDD12_NONZEROS_A_ROW
    config = {"rows": rows, "num_iterations": 1, "precision": "float32",
              "coordinates": [{"name": "global", "shard": "global",
                               "features": KDD12_FEATURES}]}
    sparse = {"global": {"rows": rows, "nonzeros": k * rows,
                         "square_nonzeros": k * k * rows}}
    dense = {"global": {"rows": rows, "shape": (rows, KDD12_FEATURES)}}
    assert costs.fit_flops(config, sparse) == 4 * k * rows + 2 * k * k * rows
    assert costs.fit_hbm_bytes(config, sparse) == k * rows * 8 + 12 * rows
    for name in SHARES:
        assert _share(name, config, sparse) < 100.0, name
        assert _share(name, config, dense) > 105.0, name
    # No cut in rows lets the dense count through: one row alone.
    one_row = dict(config, rows=1)
    assert _share("fit.mfu_pct", one_row,
                  {"global": {"rows": 1, "shape": (1, KDD12_FEATURES)}}) > 105


YAHOO = shrink(copy.deepcopy(MAN.config("glmix_yahoo_music")))


@pytest.mark.parametrize("counts", [
    None,
    {},
    {"global": {"rows": 10, "shape": (10, 64)},
     "per-item": {"rows": 10, "shape": (10, 1)}},
    {"global": {"rows": 10, "shape": (10, 64)},
     "per-user": {"rows": 10},
     "per-item": {"rows": 10, "shape": (10, 1)}},
], ids=["none", "empty", "no_user_count", "rows_only"])
@pytest.mark.parametrize("count", [costs.fit_flops, costs.fit_hbm_bytes])
def test_an_ell_shard_without_its_counts_raises(count, counts):
    with pytest.raises(ValueError, match="no count of what coordinate"):
        count(YAHOO, counts)


@pytest.mark.parametrize("count", [costs.fit_flops, costs.fit_hbm_bytes])
def test_the_count_takes_no_configuration_alone(count):
    with pytest.raises(TypeError):
        count(YAHOO)
