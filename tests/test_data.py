"""Data layer: libsvm ingest, index maps, ELL packing, synthetic generators."""

import time

import numpy as np
import pytest

from photon_tpu.data.dataset import SparseFeatures, rows_to_ell
from photon_tpu.data.index_map import IndexMap
from photon_tpu.data.libsvm import read_libsvm
from photon_tpu.data.synthetic import generate_binary, generate_game_data
from photon_tpu.types import INTERCEPT_KEY


def test_libsvm_round_trip(tmp_path):
    content = """\
+1 1:0.5 3:-1.25
-1 2:2.0
+1 1:1.0 2:1.0 3:1.0
"""
    p = tmp_path / "tiny.libsvm"
    p.write_text(content)
    batch = read_libsvm(p)
    # 3 features + intercept
    assert batch.num_features == 4
    assert batch.num_samples == 3
    np.testing.assert_array_equal(batch.labels, [1.0, 0.0, 1.0])
    feats = batch.features
    assert isinstance(feats, SparseFeatures)
    dense = np.zeros((3, 4))
    for i in range(3):
        for j in range(feats.indices.shape[1]):
            dense[i, int(feats.indices[i, j])] += float(feats.values[i, j])
    np.testing.assert_allclose(
        dense,
        [[0.5, 0.0, -1.25, 1.0], [0.0, 2.0, 0.0, 1.0], [1.0, 1.0, 1.0, 1.0]],
    )


def test_libsvm_num_features_override(tmp_path):
    p = tmp_path / "t.libsvm"
    p.write_text("1 1:1.0\n")
    batch = read_libsvm(p, num_features=10, add_intercept=False)
    assert batch.num_features == 10
    with pytest.raises(ValueError):
        read_libsvm(p, num_features=0, add_intercept=False)


def test_index_map_from_names():
    im = IndexMap.from_feature_names(["b", "a", "c", "a"])
    assert len(im) == 4  # 3 + intercept
    assert im.get_index("a") == 0 and im.get_index("c") == 2
    assert im.intercept_index == 3
    assert im.get_feature_name(0) == "a"
    assert "missing" not in im


def test_index_map_identity_and_save_load(tmp_path):
    im = IndexMap.identity(5, add_intercept=True)
    assert im.get_index("3") == 3
    assert im.intercept_index == 5
    path = tmp_path / "vocab.json"
    im.save(path)
    im2 = IndexMap.load(path)
    assert im2.get_index(INTERCEPT_KEY) == 5
    assert len(im2) == len(im)


def test_rows_to_ell_validation():
    with pytest.raises(ValueError):
        rows_to_ell([[(5, 1.0)]], num_features=3)
    with pytest.raises(ValueError):
        rows_to_ell([[(0, 1.0), (1, 1.0)]], num_features=3, capacity=1)
    idx, val = rows_to_ell([[(0, 1.0)], []], num_features=3)
    assert idx.shape == (2, 1)
    assert val[1, 0] == 0.0


def test_generators_deterministic():
    x1, y1, w1 = generate_binary(7, 50, 4)
    x2, y2, w2 = generate_binary(7, 50, 4)
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_array_equal(y1, y2)
    assert np.all(x1[:, -1] == 1.0)  # intercept column


def test_game_data_generator():
    data = generate_game_data(
        3, 200, 5, {"user": (20, 3), "item": (10, 4)}, task="linear")
    assert data.x_global.shape == (200, 5)
    assert set(data.entity_ids) == {"user", "item"}
    assert data.re_models["user"].shape == (20, 3)
    assert data.re_features["item"].shape == (200, 4)
    assert data.entity_ids["user"].max() < 20
    # power-law skew: most common entity should dominate
    counts = np.bincount(data.entity_ids["user"], minlength=20)
    assert counts[0] == counts.max()


# ---------------------------------------------------------------------------
# IdTag.from_raw: small integer ids are coded by counting, the rest by sort
# ---------------------------------------------------------------------------


def _tag_by_sort(raw):
    """The ``np.unique`` form ``from_raw`` had for every column."""
    uniq, codes = np.unique(np.asarray(raw), return_inverse=True)
    keys = tuple(str(k.item()) for k in uniq)
    return codes.astype(np.int32), keys, {k: i for i, k in enumerate(keys)}


def _assert_tag_is(tag, raw):
    codes, keys, vocab = _tag_by_sort(raw)
    np.testing.assert_array_equal(tag.host_codes(), codes)
    np.testing.assert_array_equal(np.asarray(tag.codes), codes)
    assert tag.host_codes().dtype == np.int32
    assert tag.codes.dtype == np.int32
    assert tag.inverse == keys
    assert tag.vocab == vocab
    assert list(tag.vocab) == list(vocab)  # the same insertion order too


_COUNTED_IDS = {
    "dense": lambda rng: rng.integers(0, 50, size=400),
    "holes": lambda rng: rng.integers(0, 40, size=400) * 3 + 7,
    "one": lambda rng: np.full(9, 113),
}


@pytest.mark.parametrize("dtype", [np.uint8, np.int32, np.int64, np.uint64])
@pytest.mark.parametrize("ids", sorted(_COUNTED_IDS))
def test_from_raw_codes_small_integer_ids_by_counting(ids, dtype):
    from photon_tpu.data.game_data import IdTag

    raw = _COUNTED_IDS[ids](np.random.default_rng(5)).astype(dtype)
    tag = IdTag.from_raw(raw)
    assert tag.grouping == "count"
    _assert_tag_is(tag, raw)


_SORTED_IDS = {
    "strings": np.array(["u7", "u3", "u7", "u10"]),
    "negative": np.array([4, -1, 4, 2], np.int64),
    # At or over max(4 n, 2**20): a table of counts would outgrow the ids.
    "over_the_span": np.array([3, 1 << 20, 3, 0], np.int64),
    "uint64_over_2_63": np.array([5, (1 << 63) + 9, 5], np.uint64),
    "empty": np.empty(0, np.int64),
}


@pytest.mark.parametrize("case", sorted(_SORTED_IDS))
def test_from_raw_takes_the_sort_for_every_other_column(case):
    """Asserted through the ``dataset`` stage's ``id_grouping``."""
    from photon_tpu import obs
    from photon_tpu.data.dataset import DenseFeatures
    from photon_tpu.data.game_data import make_game_dataset

    raw = _SORTED_IDS[case]
    n = raw.shape[0]
    small = np.arange(n, dtype=np.int32) % 3
    start = time.perf_counter()
    data = make_game_dataset(
        np.zeros(n, np.float32),
        {"s": DenseFeatures(np.ones((n, 2), np.float32))},
        id_tags={"g": raw, "small": small},
    )
    (rec,) = [r for r in obs.TRACER.completed()
              if r.name == "dataset" and r.t0 >= start]
    assert rec.attrs == {"id_grouping": {
        "g": "sort", "small": "count" if n else "sort"}}
    _assert_tag_is(data.id_tags["g"], raw)
    _assert_tag_is(data.id_tags["small"], small)
