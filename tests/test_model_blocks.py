"""The model writer's blocks, encoded from a coordinate's arrays, against
the plain reference: one dict per coefficient walked through the
interpreter (``avro._encode``), which is what the writer did before and
what every other writer of the package still does."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.index_map import IndexMap
from photon_tpu.io import avro
from photon_tpu.io.model_io import (
    _LOSS_CLASS,
    _MODEL_CLASS,
    BAYESIAN_LINEAR_MODEL_SCHEMA,
    _model_blocks,
    load_game_model,
    save_game_model,
)
from photon_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.types import TaskType, make_feature_key, split_feature_key


def _name_term_values(values, indices, index_map, threshold):
    out = []
    for idx, v in zip(indices, values):
        if abs(float(v)) <= threshold:
            continue
        key = index_map.get_feature_name(int(idx))
        if key is None:
            raise KeyError(f"feature index {idx} not in index map")
        name, term = split_feature_key(key)
        out.append({"name": name, "term": term, "value": float(v)})
    return out


def _reference_records(ids, means, variances, indices, index_map, task,
                       threshold):
    """One BayesianLinearModelAvro datum per entity with a valid slot."""
    records = []
    for e, model_id in enumerate(ids):
        valid = indices[e] >= 0
        if not valid.any():
            continue
        support = indices[e, valid]
        records.append({
            "modelId": model_id,
            "modelClass": _MODEL_CLASS[task],
            "means": _name_term_values(
                means[e, valid], support, index_map, threshold),
            # Variances keep the full support (threshold -1).
            "variances": None if variances is None else _name_term_values(
                variances[e, valid], support, index_map, -1.0),
            "lossFunction": _LOSS_CLASS[task],
        })
    return records


def _case(ids, means, indices=None, *, variances=None, index_map=None,
          task=TaskType.LINEAR_REGRESSION, threshold=0.0, sync_interval=4000,
          blocks=None, raises=None):
    means = np.asarray(means)
    if indices is None:
        indices = np.broadcast_to(np.arange(means.shape[1]), means.shape)
    indices = np.asarray(indices)
    if index_map is None:
        index_map = IndexMap.identity(int(indices.max(initial=0)) + 1)
    return dict(
        ids=list(ids), means=means, indices=indices,
        variances=None if variances is None else np.asarray(variances),
        index_map=index_map, task=task, threshold=threshold,
        sync_interval=sync_interval, blocks=blocks, raises=raises)


def _ramp(shape, dtype=np.float64):
    """Distinct non-zero values that every table dtype holds exactly."""
    n = int(np.prod(shape))
    return ((np.arange(n) % 251 - 125.5) / 64.0).reshape(shape).astype(dtype)


def _tables_case(dtype):
    table = _ramp((9, 3))
    table[0, 1], table[1, 0], table[2, 2], table[3, 1] = (
        np.nan, -np.inf, 0.0, -0.0)
    table = jnp.asarray(table, dtype=dtype)
    proj = np.stack([np.arange(9) % 2, 2 + np.arange(9) % 3,
                     np.where(np.arange(9) % 4, 5, -1)], axis=1)
    return _case([f"u{i}" for i in range(9)], np.asarray(table), proj,
                 variances=np.asarray(table * table))


_NAMED = IndexMap({
    make_feature_key("age", "18-25"): 0,
    "bare-key-without-a-term": 3,
    make_feature_key("n" * 70, "t" * 130): 4,
    make_feature_key("größe", "日本"): 7,
    make_feature_key("", "only-a-term"): 9,
    make_feature_key("x", ""): 12,
})

CASES = {
    "zeros_dropped_nan_inf_kept": lambda: _case(
        ["a", "b"],
        [[0.0, -0.0, np.nan, np.inf], [-np.inf, 1.5, 0.0, -np.nan]]),
    "sparsity_threshold_above_zero": lambda: _case(
        ["a", "b"],
        [[0.25, -0.25, 0.2500001, np.nan], [-0.3, 0.1, -0.0, np.inf]],
        threshold=0.25, task=TaskType.LOGISTIC_REGRESSION),
    "ragged_supports_an_empty_entity_and_one_all_dropped": lambda: _case(
        ["head", "middle", "none", "dropped", "tail"],
        [[1.0, 2.0, 3.0], [4.0, 5.0, 6.0], [7.0, 8.0, 9.0],
         [0.0, -0.0, 5.0], [1.0, 0.0, 2.0]],
        [[0, 1, -1], [2, -1, 0], [-1, -1, -1], [1, 2, -1], [-1, -1, 2]]),
    "variances_keep_zero_means_slots": lambda: _case(
        ["a", "b", "c"],
        [[0.0, 1.0, 0.0], [0.0, 0.0, 0.0], [2.0, -0.0, 3.0]],
        [[0, 1, 2], [1, -1, 0], [-1, 2, -1]],
        variances=[[0.5, 0.0, np.nan], [1.0, 9.0, 0.0], [7.0, -0.0, 7.0]],
        task=TaskType.POISSON_REGRESSION),
    "fixed_effect_of_64_means_two_byte_count": lambda: _case(
        ["global"], _ramp((1, 64))),
    "fixed_effect_of_200_means_with_variances": lambda: _case(
        ["global"], _ramp((1, 200)), variances=_ramp((1, 200)) ** 2,
        task=TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM),
    "ids_of_0_63_64_200_bytes_and_non_ascii": lambda: _case(
        ["", "i" * 63, "j" * 64, "k" * 200, "ü" * 32, "用户7", "u1"],
        _ramp((7, 2))),
    "feature_keys_with_and_without_a_delimiter": lambda: _case(
        ["a", "b", "c"], _ramp((3, 4)),
        [[0, 3, 4, 7], [9, 12, -1, -1], [12, 7, 0, 3]],
        variances=_ramp((3, 4)) ** 2, index_map=_NAMED),
    "missing_index_raises": lambda: _case(
        ["a", "b"], _ramp((2, 2)), [[0, 3], [5, -1]], index_map=_NAMED,
        raises="feature index 5 not in index map"),
    "float32_tables": lambda: _tables_case(jnp.float32),
    "bfloat16_tables": lambda: _tables_case(jnp.bfloat16),
    "float64_tables": lambda: _tables_case(jnp.float64),
    "8001_entities_in_three_blocks": lambda: _case(
        [f"u{i}" for i in range(8001)], _ramp((8001, 3)),
        np.where(np.arange(8001 * 3).reshape(8001, 3) % 7 == 0, -1,
                 np.arange(8001 * 3).reshape(8001, 3) % 5),
        blocks=[4000, 4000, 1]),
    "entities_without_slots_fill_no_block": lambda: _case(
        [f"u{i}" for i in range(12)], _ramp((12, 2)),
        np.where(np.arange(12)[:, None] % 3 == 0, -1, [[0, 1]]),
        sync_interval=4, blocks=[4, 4]),
    "no_entity_a_header_and_no_block": lambda: _case(
        [], np.zeros((0, 3)), np.zeros((0, 3), np.int64), blocks=[]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_array_blocks_equal_the_interpreters(name, tmp_path, monkeypatch):
    case = CASES[name]()
    args = (case["ids"], case["means"], case["variances"], case["indices"],
            case["index_map"], case["task"], case["threshold"])
    if case["raises"]:
        with pytest.raises(KeyError, match=case["raises"]):
            _reference_records(*args)
        # Raised by the call, before a writer could have opened the file.
        with pytest.raises(KeyError, match=case["raises"]):
            _model_blocks(*args)
        return
    records = _reference_records(*args)
    step = case["sync_interval"]
    want = [(len(records[lo:lo + step]),
             avro.encode_records(BAYESIAN_LINEAR_MODEL_SCHEMA,
                                 records[lo:lo + step]))
            for lo in range(0, len(records), step)]
    got = list(_model_blocks(*args, sync_interval=step))
    assert [count for count, _ in got] == [count for count, _ in want]
    assert got == want
    if case["blocks"] is not None:
        assert [count for count, _ in got] == case["blocks"]

    # And the files: one framing around either producer.
    monkeypatch.setattr(os, "urandom", lambda n: bytes(range(n)))
    by_dicts, by_arrays = str(tmp_path / "dicts"), str(tmp_path / "arrays")
    avro.write_container(by_dicts, BAYESIAN_LINEAR_MODEL_SCHEMA, records,
                         sync_interval=step)
    written = avro.write_blocks(by_arrays, BAYESIAN_LINEAR_MODEL_SCHEMA,
                                iter(got))
    with open(by_dicts, "rb") as f, open(by_arrays, "rb") as g:
        assert f.read() == g.read()
    assert written["records"] == len(records)
    assert written["bytes_raw"] == sum(len(raw) for _, raw in want)
    assert len(avro.read_container(by_arrays)[1]) == len(records)


def _glmix(dtype):
    """The benchmark cell's shape class, small: a 64-feature fixed effect
    and two random effects of 16 + 1 and 8 + 1 slots."""
    def table(shape, scale):
        rng = np.random.default_rng(shape[0])
        return jnp.asarray(scale * rng.standard_normal(shape), dtype=dtype)

    def random_effect(kind, entities, slots, prefix):
        return RandomEffectModel(
            coefficients=table((entities, slots), 0.3),
            random_effect_type=kind, feature_shard_id=kind,
            task=TaskType.LINEAR_REGRESSION,
            proj_all=np.broadcast_to(np.arange(slots), (entities, slots)),
            entity_keys=tuple(f"{prefix}{i}" for i in range(entities)))

    fixed = FixedEffectModel(
        GeneralizedLinearModel(Coefficients(means=table((64,), 1.0)),
                               TaskType.LINEAR_REGRESSION), "global")
    model = GameModel({
        "global": fixed,
        "per-user": random_effect("userId", 4100, 17, "u"),
        "per-movie": random_effect("movieId", 300, 9, "m")})
    index_maps = {"global": IndexMap.identity(64),
                  "userId": IndexMap.identity(17),
                  "movieId": IndexMap.identity(9)}
    return model, index_maps


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_saved_glmix_model_reads_back_to_the_last_bit(dtype, tmp_path):
    model, index_maps = _glmix(jnp.dtype(dtype))
    out = str(tmp_path / "model")
    save_game_model(model, out, index_maps)
    with jax.enable_x64(True):
        loaded, _ = load_game_model(out, index_maps)
    for name, saved in model.items():
        back = loaded[name]
        if isinstance(saved, FixedEffectModel):
            saved, back = (saved.model.coefficients.means,
                           back.model.coefficients.means)
        else:
            assert back.entity_keys == saved.entity_keys
            np.testing.assert_array_equal(back.proj_all, saved.proj_all)
            saved, back = saved.coefficients, back.coefficients
        assert saved.dtype == dtype and np.asarray(saved).all()
        # float64 holds every float32 and bfloat16: equal, not close.
        np.testing.assert_array_equal(
            np.asarray(back), np.asarray(saved).astype(np.float64))
