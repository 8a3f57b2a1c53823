"""GAME model save/load in the reference's Avro directory layout.

TPU-native counterpart of ModelProcessingUtils (photon-client
data/avro/ModelProcessingUtils.scala:59): ``saveGameModelToHDFS`` (:77-130)
writes

    <dir>/model-metadata.json
    <dir>/fixed-effect/<name>/id-info                  (one line: shard id)
    <dir>/fixed-effect/<name>/coefficients/part-00000.avro
    <dir>/random-effect/<name>/id-info                 (REType, shard id)
    <dir>/random-effect/<name>/coefficients/part-*.avro

with one BayesianLinearModelAvro record per GLM (per entity for random
effects), means/variances as NameTermValueAvro lists keyed by the feature
index map, and the model/loss class names of the reference JVM classes so
files round-trip with the reference loader (AvroUtils.scala
convertGLMModelToBayesianLinearModelAvro). Sparsity threshold semantics
match saveModelToHDFS: zero coefficients are dropped on save.

A fast native checkpoint (``save_checkpoint``/``load_checkpoint``) stores the
same GameModel as one .npz + JSON manifest for warm start / resume without
the name-keyed Avro round trip.
"""

from __future__ import annotations

import hashlib
import io
import json
import os

import jax.numpy as jnp
import numpy as np

from photon_tpu.data.index_map import IndexMap
from photon_tpu.io import avro
from photon_tpu.resilience.errors import CorruptModelError
from photon_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.types import TaskType, make_feature_key, split_feature_key

ID_INFO = "id-info"
METADATA_FILE = "model-metadata.json"
FIXED_EFFECT = "fixed-effect"
RANDOM_EFFECT = "random-effect"
COEFFICIENTS = "coefficients"
DEFAULT_AVRO_FILE = "part-00000.avro"

# Reference JVM class names (the loader dispatches on them,
# ModelProcessingUtils.scala:371-391).
_MODEL_CLASS = {
    TaskType.LOGISTIC_REGRESSION:
        "com.linkedin.photon.ml.supervised.classification.LogisticRegressionModel",
    TaskType.LINEAR_REGRESSION:
        "com.linkedin.photon.ml.supervised.regression.LinearRegressionModel",
    TaskType.POISSON_REGRESSION:
        "com.linkedin.photon.ml.supervised.regression.PoissonRegressionModel",
    TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM:
        "com.linkedin.photon.ml.supervised.classification.SmoothedHingeLossLinearSVMModel",
}
_CLASS_TO_TASK = {v: k for k, v in _MODEL_CLASS.items()}
_LOSS_CLASS = {
    TaskType.LOGISTIC_REGRESSION:
        "com.linkedin.photon.ml.function.LogisticLossFunction",
    TaskType.LINEAR_REGRESSION:
        "com.linkedin.photon.ml.function.SquaredLossFunction",
    TaskType.POISSON_REGRESSION:
        "com.linkedin.photon.ml.function.PoissonLossFunction",
    TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM:
        "com.linkedin.photon.ml.function.SmoothedHingeLossFunction",
}

NAME_TERM_VALUE_SCHEMA = {
    "name": "NameTermValueAvro",
    "namespace": "com.linkedin.photon.avro.generated",
    "type": "record",
    "fields": [
        {"name": "name", "type": "string"},
        {"name": "term", "type": "string"},
        {"name": "value", "type": "double"},
    ],
}
BAYESIAN_LINEAR_MODEL_SCHEMA = {
    "name": "BayesianLinearModelAvro",
    "namespace": "com.linkedin.photon.avro.generated",
    "type": "record",
    "fields": [
        {"name": "modelId", "type": "string"},
        {"name": "modelClass", "type": ["null", "string"], "default": None},
        {"name": "means",
         "type": {"items": NAME_TERM_VALUE_SCHEMA, "type": "array"}},
        {"name": "variances", "default": None,
         "type": ["null", {"items": "NameTermValueAvro", "type": "array"}]},
        {"name": "lossFunction", "type": ["null", "string"], "default": None},
    ],
}
SCORING_RESULT_SCHEMA = {
    "name": "ScoringResultAvro",
    "namespace": "com.linkedin.photon.avro.generated",
    "type": "record",
    "fields": [
        {"name": "uid", "type": ["null", "string"], "default": None},
        {"name": "label", "type": ["null", "double"], "default": None},
        {"name": "modelId", "type": "string"},
        {"name": "predictionScore", "type": "double"},
        {"name": "weight", "type": ["null", "double"], "default": None},
        {"name": "metadataMap", "default": None,
         "type": ["null", {"type": "map", "values": "string"}]},
    ],
}


def _resolve_index(index_map: IndexMap, name: str, term: str) -> int | None:
    """Inverse of the save-side split_feature_key: keys WITHOUT a delimiter
    serialize as (name, term="") (types.py split_feature_key), so an empty
    term must also try the bare name — identity index maps ("0", "1", ...)
    would otherwise silently drop every feature on load."""
    idx = index_map.get_index(make_feature_key(name, term))
    if idx is None and term == "":
        idx = index_map.get_index(name)
    return idx


def _varints(n: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Avro longs (zigzag varints) of non-negative ``n [N]``: their bytes
    ``[N, W]`` padded to the widest, and their widths ``[N]``."""
    z = n.astype(np.int64) << 1
    width = max(1, -(-int(z.max(initial=0)).bit_length() // 7))
    groups = z[:, None] >> (7 * np.arange(width))
    widths = np.maximum((groups != 0).sum(axis=1), 1)
    more = np.arange(width) < widths[:, None] - 1
    return ((groups & 0x7F) | (more << 7)).astype(np.uint8), widths


def _model_blocks(
    entity_ids,
    means: np.ndarray,
    variances: np.ndarray | None,
    indices: np.ndarray,
    index_map: IndexMap,
    task: TaskType,
    sparsity_threshold: float,
    sync_interval: int = 4000,
):
    """Raw Avro blocks of BayesianLinearModelAvro records, straight from a
    coordinate's arrays, for ``avro.write_blocks``.

    ``entity_ids`` (E str), ``means [E, S]``, ``variances [E, S]`` or None,
    ``indices [E, S]``: the feature index of each slot, -1 for an empty
    one. A mean is written unless ``abs(v) <= sparsity_threshold`` (a NaN
    is); variances keep every valid slot, so that an exact-zero mean (L1)
    keeps its variance. An entity with no valid slot gets no record.

    The call itself prepares what is per coordinate: every entity's
    encoded id, the encoded name + term of every distinct feature index (a
    ``KeyError`` for one the index map lacks), the constant runs. The
    returned iterator then lays one block of ``sync_interval`` records at
    a time out as a padded byte matrix ``[records, L]`` with a validity
    mask, so memory is bounded by the block."""
    rows = np.flatnonzero((indices >= 0).any(axis=1))
    ids = [entity_ids[e].encode("utf-8") for e in rows]
    id_lens = np.fromiter(map(len, ids), np.int64, len(ids))
    id_starts = np.cumsum(id_lens) - id_lens
    id_bytes = np.frombuffer(b"".join(ids), np.uint8)

    features = np.unique(indices)
    features = features[features >= 0]
    prefixes = []
    for idx in features.tolist():
        key = index_map.get_feature_name(idx)
        if key is None:
            raise KeyError(f"feature index {idx} not in index map")
        prefixes.append(avro.encode_records("string", split_feature_key(key)))
    prefix_lens = np.fromiter(map(len, prefixes), np.int64, len(prefixes))
    prefix_width = int(prefix_lens.max(initial=0))
    prefix_bytes = np.zeros((len(prefixes), prefix_width), np.uint8)
    for k, raw in enumerate(prefixes):
        prefix_bytes[k, :len(raw)] = np.frombuffer(raw, np.uint8)

    optional_string = ["null", "string"]
    model_class = avro.encode_records(optional_string, [_MODEL_CLASS[task]])
    loss_class = avro.encode_records(optional_string, [_LOSS_CLASS[task]])

    def constant(raw: bytes, count: int):
        run = np.broadcast_to(np.frombuffer(raw, np.uint8), (count, len(raw)))
        return run, np.ones(run.shape, bool)

    def varints(n, keep=True):
        raw, widths = _varints(n)
        return raw, (np.arange(raw.shape[1]) < widths[:, None]) & keep

    def items(values, keep, slot_prefix, slot_prefix_lens):
        """One array of NameTermValueAvro a record: count (none when the
        array is empty), each kept slot's name + term + double, end."""
        count, slots = keep.shape
        kept = keep.sum(axis=1)
        doubles = values.view(np.uint8).reshape(count, slots, 8)
        body = np.concatenate([slot_prefix, doubles], axis=2)
        body_mask = np.concatenate([
            np.arange(prefix_width) < slot_prefix_lens[:, :, None],
            np.ones((count, slots, 8), bool)], axis=2) & keep[:, :, None]
        return [
            varints(kept, (kept > 0)[:, None]),
            (body.reshape(count, -1), body_mask.reshape(count, -1)),
            constant(b"\0", count),
        ]

    def blocks():
        for lo in range(0, len(rows), sync_interval):
            block = rows[lo:lo + sync_interval]
            count = len(block)
            slot = indices[block]
            valid = slot >= 0
            which = np.searchsorted(features, np.where(valid, slot, 0))
            slot_prefix = prefix_bytes[which]
            slot_prefix_lens = prefix_lens[which]
            # Widened first: the rule compares, and the file holds, float64.
            mean = means[block].astype("<f8")
            lens = id_lens[lo:lo + count]
            at = np.arange(int(lens.max()))
            id_mask = at < lens[:, None]
            id_at = np.where(id_mask, id_starts[lo:lo + count, None] + at, 0)
            parts = [
                varints(lens),
                (id_bytes[id_at], id_mask),
                constant(model_class, count),
                *items(mean, valid & ~(np.abs(mean) <= sparsity_threshold),
                       slot_prefix, slot_prefix_lens),
            ]
            if variances is None:
                parts.append(constant(b"\0", count))
            else:
                parts.append(constant(b"\2", count))
                parts += items(variances[block].astype("<f8"), valid,
                               slot_prefix, slot_prefix_lens)
            parts.append(constant(loss_class, count))
            layout = np.concatenate([raw for raw, _ in parts], axis=1)
            mask = np.concatenate([keep for _, keep in parts], axis=1)
            yield count, layout[mask].tobytes()

    return blocks()


def _record_to_coefficients(
    rec: dict, index_map: IndexMap, dim: int
) -> tuple[Coefficients, TaskType | None]:
    means = np.zeros(dim)
    for ntv in rec["means"]:
        idx = _resolve_index(index_map, ntv["name"], ntv["term"])
        if idx is not None:
            means[idx] = ntv["value"]
    variances = None
    if rec.get("variances"):
        variances = np.zeros(dim)
        for ntv in rec["variances"]:
            idx = _resolve_index(index_map, ntv["name"], ntv["term"])
            if idx is not None:
                variances[idx] = ntv["value"]
    task = _CLASS_TO_TASK.get(rec.get("modelClass") or "")
    return Coefficients(
        means=jnp.asarray(means),
        variances=None if variances is None else jnp.asarray(variances),
    ), task


def save_game_model(
    model: GameModel,
    output_dir: str,
    index_maps: dict[str, IndexMap],
    *,
    task: TaskType | None = None,
    optimization_configurations: dict | None = None,
    sparsity_threshold: float = 0.0,
) -> None:
    """saveGameModelToHDFS equivalent (ModelProcessingUtils.scala:77-130).

    Always-recorded stages (``obs.stage``): ``save`` around the call and,
    per coordinate (attr ``coordinate``), ``save.records`` (device ->
    host pull, the encoded entity ids and feature names) and
    ``save.encode`` / ``save.write``, summed over the blocks
    ``avro.write_blocks`` interleaves; ``save.encode`` also carries what
    was written: ``records``, ``bytes_raw``, ``bytes_written``."""
    from photon_tpu import obs

    with obs.stage("save"):
        _save_game_model(
            model, output_dir, index_maps, task,
            optimization_configurations, sparsity_threshold,
        )


def _save_game_model(
    model, output_dir, index_maps, task, optimization_configurations,
    sparsity_threshold,
) -> None:
    from photon_tpu import obs

    os.makedirs(output_dir, exist_ok=True)
    task = task if task is not None else model.task
    with open(os.path.join(output_dir, METADATA_FILE), "w") as f:
        json.dump({
            "modelType": task.value,
            "optimizationConfigurations":
                optimization_configurations or {},
        }, f, indent=2)

    for name, sub in model.items():
        if isinstance(sub, FixedEffectModel):
            base = os.path.join(output_dir, FIXED_EFFECT, name)
            os.makedirs(os.path.join(base, COEFFICIENTS), exist_ok=True)
            with open(os.path.join(base, ID_INFO), "w") as f:
                f.write(sub.feature_shard_id + "\n")
            with obs.stage("save.records", coordinate=name):
                # One entity, the coordinate's name, with every feature.
                coefs = sub.model.coefficients
                means = np.asarray(coefs.means)[None]
                blocks = _model_blocks(
                    [name],
                    means,
                    None if coefs.variances is None
                    else np.asarray(coefs.variances)[None],
                    np.arange(means.shape[1])[None],
                    index_maps[sub.feature_shard_id],
                    sub.model.task,
                    sparsity_threshold,
                )
        elif isinstance(sub, RandomEffectModel):
            base = os.path.join(output_dir, RANDOM_EFFECT, name)
            os.makedirs(os.path.join(base, COEFFICIENTS), exist_ok=True)
            with open(os.path.join(base, ID_INFO), "w") as f:
                f.write(sub.random_effect_type + "\n")
                f.write(sub.feature_shard_id + "\n")
            with obs.stage("save.records", coordinate=name):
                blocks = _model_blocks(
                    [str(key) for key in sub.entity_keys]
                    or [str(e) for e in range(sub.num_entities)],
                    np.asarray(sub.coefficients),
                    None if sub.variances is None
                    else np.asarray(sub.variances),
                    sub.proj_all,
                    index_maps[sub.feature_shard_id],
                    sub.task,
                    sparsity_threshold,
                )
        else:
            raise TypeError(f"unknown sub-model type for {name!r}")
        # Encode and write interleave block by block inside the writer:
        # one record each per coordinate, summed over the blocks.
        encoding = obs.stage_sum("save.encode", coordinate=name)
        writing = obs.stage_sum("save.write", coordinate=name)
        try:
            encoding.attrs.update(avro.write_blocks(
                os.path.join(base, COEFFICIENTS, DEFAULT_AVRO_FILE),
                BAYESIAN_LINEAR_MODEL_SCHEMA,
                blocks,
                encoding=encoding,
                writing=writing,
            ))
        finally:
            encoding.close()
            writing.close()


def model_feature_shard_ids(model_dir: str) -> set[str]:
    """The feature shard ids a saved model directory references.

    Reads each sub-model's ``id-info`` (shard id is the LAST line —
    fixed effects write one line, random effects two). Shared by the
    scoring/serving drivers to decide which index maps a load needs.
    """
    shards: set[str] = set()
    for kind in (FIXED_EFFECT, RANDOM_EFFECT):
        base = os.path.join(model_dir, kind)
        if not os.path.isdir(base):
            continue
        for name in os.listdir(base):
            with open(os.path.join(base, name, ID_INFO)) as f:
                shards.add(f.read().strip().splitlines()[-1])
    return shards


def _read_coefficients_dir(coef_dir: str, what: str) -> list:
    """Avro coefficient read with codec failures translated.

    A truncated upload / torn copy otherwise surfaces as a bare
    ``EOFError("truncated varint")`` with no hint WHICH of the model's
    many part files is bad; every decode failure becomes a
    ``CorruptModelError`` naming the directory and the cause.
    """
    try:
        return avro.read_container_dir(coef_dir)
    except (ValueError, EOFError, KeyError) as exc:
        raise CorruptModelError(
            f"{what} coefficients under {coef_dir}: Avro decode failed "
            f"({type(exc).__name__}: {exc}) — the file is truncated or "
            "not a BayesianLinearModelAvro container"
        ) from exc


def load_game_model(
    input_dir: str,
    index_maps: dict[str, IndexMap],
) -> tuple[GameModel, dict]:
    """loadGameModelFromHDFS equivalent (ModelProcessingUtils.scala:143-240).

    Returns (model, metadata). Random-effect models are reassembled into the
    padded-matrix layout with per-entity projectors derived from each
    entity's saved support.
    """
    meta_path = os.path.join(input_dir, METADATA_FILE)
    try:
        with open(meta_path) as f:
            metadata = json.load(f)
    except json.JSONDecodeError as exc:
        raise CorruptModelError(
            f"model metadata {meta_path}: not valid JSON ({exc})"
        ) from exc
    task = TaskType(metadata["modelType"])
    models: dict[str, object] = {}

    fe_dir = os.path.join(input_dir, FIXED_EFFECT)
    if os.path.isdir(fe_dir):
        for name in sorted(os.listdir(fe_dir)):
            base = os.path.join(fe_dir, name)
            with open(os.path.join(base, ID_INFO)) as f:
                shard = f.read().strip().splitlines()[0]
            imap = index_maps[shard]
            records = _read_coefficients_dir(
                os.path.join(base, COEFFICIENTS),
                f"fixed-effect model {name!r}",
            )
            if len(records) != 1:
                raise ValueError(
                    f"fixed-effect model {name!r}: expected 1 record, "
                    f"got {len(records)}"
                )
            coefs, rec_task = _record_to_coefficients(
                records[0], imap, len(imap)
            )
            models[name] = FixedEffectModel(
                GeneralizedLinearModel(coefs, rec_task or task), shard
            )

    re_dir = os.path.join(input_dir, RANDOM_EFFECT)
    if os.path.isdir(re_dir):
        for name in sorted(os.listdir(re_dir)):
            base = os.path.join(re_dir, name)
            lines = open(os.path.join(base, ID_INFO)).read().strip().splitlines()
            re_type, shard = lines[0], lines[1]
            coef_dir = os.path.join(base, COEFFICIENTS)
            # Partial-retrain fixtures ship id-info with no coefficients
            # (reference GameIntegTest/retrainModels); an absent dir is an
            # empty model set, matching the reference's empty-RDD load (and
            # needs no index map for its shard).
            records = (
                _read_coefficients_dir(
                    coef_dir, f"random-effect model {name!r}"
                )
                if os.path.isdir(coef_dir) else []
            )
            imap = index_maps[shard] if records else None
            entity_ids = []
            supports = []
            means_list = []
            var_list = []
            any_var = False
            for rec in records:
                entity_ids.append(rec["modelId"])
                mmap: dict[int, float] = {}
                for ntv in rec["means"]:
                    idx = _resolve_index(imap, ntv["name"], ntv["term"])
                    if idx is not None:
                        mmap[idx] = ntv["value"]
                vmap: dict[int, float] = {}
                if rec.get("variances"):
                    for ntv in rec["variances"]:
                        idx = _resolve_index(imap, ntv["name"], ntv["term"])
                        if idx is not None:
                            vmap[idx] = ntv["value"]
                    any_var = True
                # Support = union of means and variances: L1 solutions carry
                # exact-zero means whose variances must survive the round
                # trip.
                idxs = np.asarray(
                    sorted(set(mmap) | set(vmap)), dtype=np.int64
                )
                supports.append(idxs)
                means_list.append(
                    np.array([mmap.get(int(i), 0.0) for i in idxs])
                )
                var_list.append(
                    np.array([vmap.get(int(i), 0.0) for i in idxs])
                    if vmap else None
                )
            e_cnt = len(records)
            s_max = max((s.size for s in supports), default=1)
            s_max = max(s_max, 1)
            w = np.zeros((e_cnt, s_max))
            v = np.zeros((e_cnt, s_max)) if any_var else None
            proj = np.full((e_cnt, s_max), -1, dtype=np.int64)
            for e in range(e_cnt):
                k = supports[e].size
                proj[e, :k] = supports[e]
                w[e, :k] = means_list[e]
                if v is not None and var_list[e] is not None:
                    v[e, :k] = var_list[e]
            rec_task = _CLASS_TO_TASK.get(
                (records[0].get("modelClass") or "") if records else ""
            )
            models[name] = RandomEffectModel(
                coefficients=jnp.asarray(w),
                random_effect_type=re_type,
                feature_shard_id=shard,
                task=rec_task or task,
                proj_all=proj,
                variances=None if v is None else jnp.asarray(v),
                entity_keys=tuple(entity_ids),
            )

    if not models:
        raise ValueError(f"no models found under {input_dir}")
    return GameModel(models), metadata


def save_scores(
    path: str,
    scores: np.ndarray,
    *,
    model_id: str = "",
    uids: np.ndarray | None = None,
    labels: np.ndarray | None = None,
    weights: np.ndarray | None = None,
) -> None:
    """ScoringResultAvro writer (ScoreProcessingUtils.scala:88)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    scores = np.asarray(scores)

    def rec(i):
        return {
            "uid": None if uids is None else str(uids[i]),
            "label": None if labels is None else float(labels[i]),
            "modelId": model_id,
            "predictionScore": float(scores[i]),
            "weight": None if weights is None else float(weights[i]),
            "metadataMap": None,
        }

    avro.write_container(
        path, SCORING_RESULT_SCHEMA, (rec(i) for i in range(scores.shape[0]))
    )


FEATURE_SUMMARIZATION_SCHEMA = {
    "name": "FeatureSummarizationResultAvro",
    "namespace": "com.linkedin.photon.avro.generated",
    "type": "record",
    "fields": [
        {"name": "featureName", "type": "string"},
        {"name": "featureTerm", "type": "string"},
        {"name": "metrics", "type": {"type": "map", "values": "double"}},
    ],
}


def save_feature_stats(path: str, stats, index_map: IndexMap) -> None:
    """Per-feature summary artifact (one record per non-intercept feature).

    Reference: ModelProcessingUtils.writeBasicStatistics (photon-client
    data/avro/ModelProcessingUtils.scala:514-560) — the metrics map carries
    max/min/mean/normL1/normL2/numNonzeros/variance per (name, term), with
    the intercept filtered out; written under
    ``<dataSummaryDirectory>/<shardId>`` by the training driver
    (GameTrainingDriver.calculateAndSaveFeatureShardStats :616-627).
    """
    from photon_tpu.types import split_feature_key

    os.makedirs(path, exist_ok=True)
    skip = stats.intercept_index
    zeros = np.zeros(stats.dim)
    l1 = zeros if stats.norm_l1 is None else stats.norm_l1
    l2 = zeros if stats.norm_l2 is None else stats.norm_l2

    def records():
        for idx in range(stats.dim):
            if idx == skip:
                continue
            key = index_map.get_feature_name(idx)
            if key is None:
                continue
            name, term = split_feature_key(key)
            yield {
                "featureName": name,
                "featureTerm": term,
                "metrics": {
                    "max": float(stats.max[idx]),
                    "min": float(stats.min[idx]),
                    "mean": float(stats.mean[idx]),
                    "normL1": float(l1[idx]),
                    "normL2": float(l2[idx]),
                    "numNonzeros": float(stats.num_nonzeros[idx]),
                    "variance": float(stats.variance[idx]),
                },
            }

    avro.write_container(
        os.path.join(path, "part-00000.avro"),
        FEATURE_SUMMARIZATION_SCHEMA,
        records(),
    )


def load_feature_stats(path: str) -> dict[str, dict[str, float]]:
    """Read a stats artifact back: feature key -> metrics map."""
    from photon_tpu.types import make_feature_key

    out: dict[str, dict[str, float]] = {}
    for rec in avro.read_container_dir(path):
        out[make_feature_key(rec["featureName"], rec["featureTerm"])] = {
            k: float(v) for k, v in rec["metrics"].items()
        }
    return out


# --------------------------------------------------------------------------
# native checkpoint (fast path; no Avro name-keying)
# --------------------------------------------------------------------------


def _ckpt_path(path: str) -> str:
    """np.savez appends .npz; normalize so save/load stay symmetric."""
    return path if path.endswith(".npz") else path + ".npz"


def fsync_dir(path: str) -> None:
    """Durably commit a rename: fsync the containing directory (the
    rename itself is atomic; the DIRECTORY entry still needs a sync to
    survive power loss)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:  # pragma: no cover — exotic filesystems
        return
    try:
        os.fsync(fd)
    except OSError:  # pragma: no cover
        pass
    finally:
        os.close(fd)


def atomic_write_bytes(
    path: str, data: bytes | memoryview, *, fault_point: str | None = None
) -> None:
    """The one atomic-commit dance every durable artifact goes through:
    bytes land in a temp sibling that is fsynced, ``os.replace``d over
    ``path``, and the directory entry is fsynced — a crash at any step
    leaves either the previous file or the committed new one, never a
    torn write, and the rename survives power loss. ``fault_point``
    names an injection point fired in the mid-write crash window (bytes
    down, rename not yet done) so chaos tests can prove exactly that.
    Temp debris is removed on any failure."""
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        if fault_point is not None:
            from photon_tpu.resilience import faults

            faults.check(fault_point)
        os.replace(tmp, path)
    except BaseException:
        # Never leave tmp debris for a directory listing to confuse
        # with a committed artifact.
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise
    fsync_dir(os.path.dirname(path) or ".")


_META_KEY = "__meta__"


def save_checkpoint(
    model: GameModel,
    path: str,
    *,
    extra_meta: dict | None = None,
    fault_point: str | None = "checkpoint.write",
) -> str:
    """One-file native GameModel checkpoint (.npz + JSON manifest).

    The write is ATOMIC: bytes land in a temp file that is fsynced and
    ``os.replace``d over ``path``, so a crash (or the injected
    ``checkpoint.write`` fault) mid-write leaves any previous file at
    ``path`` untouched and loadable. ``extra_meta`` rides inside the
    npz under a reserved key — the training checkpointer stores its
    loop state (config/iteration cursor, static key) there so the
    artifact is self-contained; read it back with
    ``load_checkpoint_meta``. ``fault_point`` names the injection point
    fired in the mid-write crash window (default the training
    checkpointer's ``checkpoint.write``; the pilot's generation ring
    passes its own ``pilot.promote`` so chaos CI can kill exactly
    between the ring commit and the serving reload).

    Returns the sha256 hex digest of the committed bytes, hashed from
    the in-memory serialization — callers recording content hashes
    (the training checkpointer's manifest) never re-read the file.
    """
    path = _ckpt_path(path)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    arrays: dict[str, np.ndarray] = {}
    manifest: dict[str, dict] = {}
    for name, sub in model.items():
        if isinstance(sub, FixedEffectModel):
            arrays[f"{name}/means"] = np.asarray(sub.model.coefficients.means)
            if sub.model.coefficients.variances is not None:
                arrays[f"{name}/variances"] = np.asarray(
                    sub.model.coefficients.variances
                )
            manifest[name] = {
                "kind": "fixed",
                "shard": sub.feature_shard_id,
                "task": sub.model.task.value,
            }
        elif isinstance(sub, RandomEffectModel):
            arrays[f"{name}/coefficients"] = np.asarray(sub.coefficients)
            arrays[f"{name}/proj_all"] = sub.proj_all
            if sub.variances is not None:
                arrays[f"{name}/variances"] = np.asarray(sub.variances)
            manifest[name] = {
                "kind": "random",
                "re_type": sub.random_effect_type,
                "shard": sub.feature_shard_id,
                "task": sub.task.value,
                "entity_keys": [str(k) for k in sub.entity_keys],
            }
        else:
            raise TypeError(f"unknown sub-model type for {name!r}")
    if _META_KEY in manifest:
        raise ValueError(
            f"model coordinate name {_META_KEY!r} collides with the "
            "checkpoint metadata key")
    if extra_meta is not None:
        manifest[_META_KEY] = dict(extra_meta)
    arrays["__manifest__"] = np.frombuffer(
        json.dumps(manifest).encode(), dtype=np.uint8
    )
    # Serialize in memory first: np.savez's zip writer seeks back to
    # patch member headers, so the only way to hash the exact committed
    # bytes in one pass is to hash the finished buffer.
    buf = io.BytesIO()
    np.savez_compressed(buf, **arrays)
    data = buf.getbuffer()  # zero-copy view; getvalue() would double peak RSS
    digest = hashlib.sha256(data).hexdigest()
    atomic_write_bytes(path, data, fault_point=fault_point)
    return digest


def artifact_digest(path: str) -> str:
    """Stable sha256 identity of a model artifact — a checkpoint npz's
    content hash, or (for an Avro model DIRECTORY) the hash of every
    file's (relative name, content hash) pair in sorted order. The
    training checkpointer records this for the run's init model so a
    resumed day-over-day retrain can prove it is warm-starting from the
    SAME yesterday-model the interrupted run used."""
    h = hashlib.sha256()
    if os.path.isfile(path):
        with open(path, "rb") as f:
            for block in iter(lambda: f.read(1 << 20), b""):
                h.update(block)
        return h.hexdigest()
    for root, dirs, files in os.walk(path):
        dirs.sort()
        for name in sorted(files):
            full = os.path.join(root, name)
            rel = os.path.relpath(full, path)
            h.update(rel.encode())
            with open(full, "rb") as f:
                for block in iter(lambda: f.read(1 << 20), b""):
                    h.update(block)
    return h.hexdigest()


def load_initial_model(
    path: str, index_maps: dict[str, IndexMap] | None = None
) -> tuple[GameModel, str]:
    """Load a warm-start model from either artifact form.

    ``path`` may be a native checkpoint (``.npz``, self-contained) or a
    reference Avro model directory (needs ``index_maps`` to key the
    name+term records). Returns ``(model, digest)`` — the digest is the
    ``artifact_digest`` identity the training checkpointer records so
    an ingest-then-descent resume can verify its warm start.
    """
    if os.path.isfile(path) or path.endswith(".npz"):
        return load_checkpoint(path), artifact_digest(_ckpt_path(path))
    if os.path.isfile(os.path.join(path, METADATA_FILE)):
        if index_maps is None:
            raise ValueError(
                f"init model {path} is an Avro model directory; loading "
                "it needs the feature index maps (name+term keyed "
                "records) — pass index_maps, or point at a native "
                ".npz checkpoint instead")
        model, _ = load_game_model(path, index_maps)
        return model, artifact_digest(path)
    raise FileNotFoundError(
        f"init model {path}: neither a checkpoint npz nor an Avro "
        f"model directory (no {METADATA_FILE})")


def load_checkpoint(path: str) -> GameModel:
    """Load a native checkpoint; see ``load_checkpoint_meta`` for the
    embedded loop-state metadata."""
    return load_checkpoint_meta(path)[0]


def load_checkpoint_meta(path: str) -> tuple[GameModel, dict | None]:
    """Load a native checkpoint plus its ``extra_meta`` (None when the
    file predates metadata). A truncated / torn npz raises
    ``CorruptModelError`` naming the file instead of leaking
    ``zipfile.BadZipFile`` from three layers down."""
    import zipfile

    path = _ckpt_path(path)
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    try:
        return _load_checkpoint_impl(path)
    except (zipfile.BadZipFile, ValueError, KeyError, EOFError,
            json.JSONDecodeError) as exc:
        # Deliberately NOT OSError: EACCES / transient filesystem errors
        # mean the file may be intact — reporting them as corruption
        # would steer the operator toward deleting a good checkpoint.
        raise CorruptModelError(
            f"checkpoint {path}: failed to decode "
            f"({type(exc).__name__}: {exc}) — the npz is truncated or "
            "not a photon_tpu checkpoint"
        ) from exc


def _load_checkpoint_impl(path: str) -> tuple[GameModel, dict | None]:
    with np.load(path) as z:
        manifest = json.loads(bytes(z["__manifest__"]).decode())
        meta = manifest.pop(_META_KEY, None)
        models: dict[str, object] = {}
        for name, info in manifest.items():
            task = TaskType(info["task"])
            if info["kind"] == "fixed":
                var_key = f"{name}/variances"
                coefs = Coefficients(
                    means=jnp.asarray(z[f"{name}/means"]),
                    variances=(jnp.asarray(z[var_key])
                               if var_key in z else None),
                )
                models[name] = FixedEffectModel(
                    GeneralizedLinearModel(coefs, task), info["shard"]
                )
            else:
                var_key = f"{name}/variances"
                models[name] = RandomEffectModel(
                    coefficients=jnp.asarray(z[f"{name}/coefficients"]),
                    random_effect_type=info["re_type"],
                    feature_shard_id=info["shard"],
                    task=task,
                    proj_all=z[f"{name}/proj_all"],
                    variances=(jnp.asarray(z[var_key])
                               if var_key in z else None),
                    entity_keys=tuple(info["entity_keys"]),
                )
    return GameModel(models), meta
