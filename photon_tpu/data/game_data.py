"""GameDataset: the canonical columnar table every coordinate trains against.

TPU-native counterpart of the reference's ``RDD[(UniqueSampleId, GameDatum)]``
(photon-api data/GameDatum.scala:37, GameConverters.scala:28): response /
offset / weight columns, one feature matrix per feature shard, and integer-
coded id tags (the ``idTagToValueMap``: random-effect grouping columns and
evaluation grouping columns).

Because every array shares one canonical row order fixed at ingest, all of
the reference's join/groupByKey plumbing (keying by uid, routing residuals by
REId) reduces to index arithmetic: a coordinate's scores are a [n] device
array aligned with this table (the CoordinateDataScores equivalent,
data/scoring/CoordinateDataScores.scala:30).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.data.dataset import (
    DenseFeatures,
    DualEllFeatures,
    Features,
    GLMBatch,
    SparseFeatures,
)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class IdTag:
    """One grouping column: dense int codes + the key vocabulary."""

    codes: Array  # [n] int32
    vocab: dict  # str key -> code
    inverse: tuple  # code -> str key
    # Host mirror of ``codes``: the ingest planner (entity grouping,
    # reservoir sampling) is host-side numpy; keeping the codes it was built
    # from avoids a device->host round trip per dataset build.
    codes_np: np.ndarray | None = None
    # How ``from_raw`` found the vocabulary ("count" | "sort"); the
    # ``dataset`` stage reports it.
    grouping: str | None = None

    @property
    def num_groups(self) -> int:
        return len(self.inverse)

    def host_codes(self) -> np.ndarray:
        if self.codes_np is not None:
            return self.codes_np
        return np.asarray(self.codes)

    @staticmethod
    def from_raw(raw_ids, *, place: bool = True) -> "IdTag":
        # Entity keys are normalized to str at ingest: the Avro model format
        # stores modelId as a string (BayesianLinearModelAvro), so keeping
        # numeric keys here would make every vocab lookup after a model
        # reload miss silently ('5' vs np.int64(5)).
        uniq, codes, how = _unique_inverse(np.asarray(raw_ids))
        if uniq.dtype.kind in "iu":  # ``tolist`` is ``item`` in bulk
            keys = tuple(map(str, uniq.tolist()))
        else:
            keys = tuple(
                str(k.item() if hasattr(k, "item") else k) for k in uniq
            )
        if len(set(keys)) != len(keys):
            raise ValueError(
                "id tag keys collide after str normalization"
            )
        return IdTag(
            codes=jnp.asarray(codes) if place else codes,
            vocab={k: i for i, k in enumerate(keys)},
            inverse=keys,
            codes_np=codes,
            grouping=how,
        )


# Integer ids are coded by counting while their maximum is under this
# multiple of the row count (or under 2**20): the transient is then at
# most that many int64 counts.
_COUNT_IDS_SPAN = 4


def _unique_inverse(raw: np.ndarray) -> tuple[np.ndarray, np.ndarray, str]:
    """``np.unique(raw, return_inverse=True)`` with int32 codes, and how it
    was found: ``count`` where the ids are small non-negative integers
    (grouping by a bounded integer key is a counting problem, O(n)), else
    ``sort`` (strings, negative or hashed ids, an empty column)."""
    n = raw.size
    if raw.ndim == 1 and n and raw.dtype.kind in "iu":
        top = int(raw.max())
        if raw.min() >= 0 and top < max(_COUNT_IDS_SPAN * n, 1 << 20):
            ids = raw.astype(np.intp, copy=False)
            seen = np.bincount(ids, minlength=top + 1) > 0
            code_of = np.cumsum(seen, dtype=np.int32)
            code_of -= 1
            return np.flatnonzero(seen), code_of[ids], "count"
    uniq, codes = np.unique(raw, return_inverse=True)
    return uniq, codes.astype(np.int32), "sort"


@dataclasses.dataclass(frozen=True)
class GameDataset:
    """Columnar GAME table in canonical row order."""

    labels: Array  # [n]
    offsets: Array  # [n]
    weights: Array  # [n]
    feature_shards: dict[str, Features]
    id_tags: dict[str, IdTag]
    uids: np.ndarray | None = None  # host-side original row ids, optional
    # Host numpy mirrors captured at ingest (``make_game_dataset`` stashes
    # the numpy inputs before pushing them to the device). The dataset-build
    # planner works entirely on these, so ingest never pulls device arrays
    # back over the (potentially slow) host<->device link. Keys:
    # "labels"/"offsets"/"weights" -> [n] column arrays;
    # ("shard", <name>) -> the ELL view of ``host_shard_coo``;
    # ("tail", <name>) -> the COO overflow of ``host_shard_tail``.
    # Shard names live in their own tuple namespace so a shard named,
    # say, "weights" cannot clobber the column mirror.
    host: dict | None = None

    @property
    def num_samples(self) -> int:
        return int(self.labels.shape[0])

    def host_column(self, name: str) -> np.ndarray:
        """Host view of labels/offsets/weights (mirror or cached pull)."""
        if self.host is not None and name in self.host:
            return self.host[name]
        view = np.asarray(getattr(self, name))
        if self.host is not None:
            self.host[name] = view
        return view

    def host_shard_coo(self, shard_id: str):
        """Host-side ``(indices [n, k], values [n, k], d)`` ELL view of a
        feature shard, preferring the ingest-time mirror. Computed views are
        cached into the mirror dict so repeated planning passes pull the
        device data at most once.

        For ``DualEllFeatures`` this is the bounded-width SLAB only — the
        overflow entries live in ``host_shard_tail`` (re-widening the slab
        to the widest row would reintroduce exactly the memory hazard the
        dual-ELL layout bounds, SURVEY §7.3)."""
        key = ("shard", shard_id)
        if self.host is not None and key in self.host:
            return self.host[key]
        feats = self.feature_shards[shard_id]
        if isinstance(feats, DenseFeatures):
            x = np.asarray(feats.x)
            n, d = x.shape
            idx = np.broadcast_to(np.arange(d, dtype=np.int32), (n, d))
            view = (idx, x, d)
        elif isinstance(feats, (SparseFeatures, DualEllFeatures)):
            view = (
                np.asarray(feats.indices), np.asarray(feats.values), feats.d
            )
        else:
            raise TypeError(
                f"shard {shard_id!r}: no host COO view for "
                f"{type(feats).__name__}"
            )
        if self.host is not None:
            self.host[key] = view
        return view

    def host_shard_tail(self, shard_id: str):
        """Host ``(rows, indices, values)`` COO overflow of a DualEll shard
        (rows sorted ascending), or None for rectangular layouts."""
        feats = self.feature_shards[shard_id]
        if not isinstance(feats, DualEllFeatures):
            return None
        key = ("tail", shard_id)
        if self.host is not None and key in self.host:
            return self.host[key]
        tail = (
            np.asarray(feats.tail_rows),
            np.asarray(feats.tail_indices),
            np.asarray(feats.tail_values),
        )
        if tail[0].size == 0:
            tail = None
        if self.host is not None:
            self.host[key] = tail
        return tail

    def shard_batch(self, shard_id: str) -> GLMBatch:
        """A GLMBatch view for one feature shard (FixedEffectDataset
        equivalent, data/FixedEffectDataset.scala:32)."""
        return GLMBatch(
            features=self.feature_shards[shard_id],
            labels=self.labels,
            offsets=self.offsets,
            weights=self.weights,
        )

    def tag_codes(self, tag: str) -> tuple[Array, int]:
        t = self.id_tags[tag]
        return t.codes, t.num_groups

    @property
    def on_host(self) -> bool:
        """Whether the columns and raw shards are still host arrays
        (``make_host_game_dataset``): an estimator's ``prepare`` places
        them."""
        return isinstance(self.labels, np.ndarray)

    def on_device(self) -> "GameDataset":
        """This table with its columns, raw shards and id codes on the
        default device: itself unless it was left on the host."""
        if not self.on_host:
            return self
        placed = jax.device_put
        return dataclasses.replace(
            self,
            labels=placed(self.labels),
            offsets=placed(self.offsets),
            weights=placed(self.weights),
            feature_shards={
                k: jax.tree.map(placed, f)
                for k, f in self.feature_shards.items()},
            id_tags={
                k: dataclasses.replace(t, codes=placed(t.codes))
                for k, t in self.id_tags.items()},
        )


def make_game_dataset(
    labels,
    feature_shards: dict[str, Features],
    *,
    offsets=None,
    weights=None,
    id_tags: dict[str, np.ndarray] | None = None,
    uids=None,
    dtype=jnp.float32,
) -> GameDataset:
    """Host arrays -> GameDataset with the raw shards put to the device.
    An always-recorded stage, ``dataset`` (the dtype conversions, the id
    vocabularies, and inside it ``raw_transfer``, the enqueue of the one
    ``device_put``; the copy itself is asynchronous and outlasts it)."""
    return _dataset_stage(
        labels, feature_shards, offsets, weights, id_tags, uids, dtype,
        place=True)


def make_host_game_dataset(
    labels,
    feature_shards: dict[str, Features],
    *,
    offsets=None,
    weights=None,
    id_tags: dict[str, np.ndarray] | None = None,
    uids=None,
    dtype=jnp.float32,
) -> GameDataset:
    """``make_game_dataset`` with every column, raw shard and id code LEFT
    ON THE HOST (host shards only): for a table that no single device
    should hold. ``GameEstimator.prepare`` places each leaf from the host
    where its mesh's partition rules put it (parallel/mesh.py: a quarter
    of a fixed effect's rows a device, a random effect's raw shard on
    every device), so no device ever holds a whole copy; without a mesh
    it places the table on the default device (``on_device``). The same
    ``dataset`` stage, with no ``raw_transfer`` inside it."""
    return _dataset_stage(
        labels, feature_shards, offsets, weights, id_tags, uids, dtype,
        place=False)


def _dataset_stage(*args, place: bool) -> GameDataset:
    from photon_tpu import obs

    with obs.stage("dataset") as stage:
        data = _make_game_dataset(*args, place=place)
        stage.attrs = dict(
            id_grouping={k: t.grouping for k, t in data.id_tags.items()})
        return data


def _make_game_dataset(
    labels, feature_shards, offsets, weights, id_tags, uids, dtype,
    place: bool = True,
) -> GameDataset:
    np_dtype = np.dtype(dtype)
    labels_np = np.asarray(labels, dtype=np_dtype)
    n = labels_np.shape[0]
    offsets_np = (
        np.zeros(n, np_dtype) if offsets is None
        else np.asarray(offsets, dtype=np_dtype)
    )
    weights_np = (
        np.ones(n, np_dtype) if weights is None
        else np.asarray(weights, dtype=np_dtype)
    )
    host: dict = {
        "labels": labels_np, "offsets": offsets_np, "weights": weights_np,
    }
    # Feature shards may arrive with host numpy arrays inside (the cheap way
    # to ingest: the dataset build plans on the numpy mirror and the device
    # copy is pushed exactly once, here). Device-backed shards pass through
    # untouched (no mirror; host views fall back to a one-time pull).
    # jax.device_put moves large host buffers ~2x faster than jnp.asarray
    # (no trace/convert layer), and EVERY push — all shards' arrays plus
    # the three columns — batches into ONE device_put call, enqueued
    # asynchronously so the ingest planner starts on the host mirrors
    # while the raw data is still crossing the link (the transfer time is
    # accounted in PIPELINE_STATS as "raw_transfer").
    from photon_tpu.data.pipeline import PIPELINE_STATS

    staged: list[np.ndarray] = []

    def stage_arr(arr: np.ndarray) -> int:
        staged.append(arr)
        return len(staged) - 1

    specs: dict[str, tuple] = {}
    shards: dict[str, Features] = {}
    for name, feats in feature_shards.items():
        rows = (feats.x.shape[0] if hasattr(feats, "x") else feats.indices.shape[0])
        if rows != n:
            raise ValueError(
                f"feature shard {name!r} has {rows} rows, expected {n}")
        if isinstance(feats, DenseFeatures) and isinstance(feats.x, np.ndarray):
            x = np.asarray(feats.x, dtype=np_dtype)
            d = x.shape[1]
            host[("shard", name)] = (
                np.broadcast_to(np.arange(d, dtype=np.int32), x.shape), x, d,
            )
            specs[name] = ("dense", stage_arr(x))
        elif isinstance(feats, SparseFeatures) and isinstance(
            feats.indices, np.ndarray
        ):
            idx = np.asarray(feats.indices, dtype=np.int32)
            val = np.asarray(feats.values, dtype=np_dtype)
            host[("shard", name)] = (idx, val, feats.d)
            specs[name] = ("sparse", stage_arr(idx), stage_arr(val), feats.d)
        elif not place:
            raise TypeError(
                f"feature shard {name!r}: a data set left on the host "
                "takes host Dense or Sparse shards, got "
                f"{type(feats).__name__}")
        shards[name] = feats
    i_lab = stage_arr(labels_np)
    i_off = stage_arr(offsets_np)
    i_wt = stage_arr(weights_np)
    if place:
        with PIPELINE_STATS.stage("raw_transfer"):
            devs = jax.device_put(staged)
    else:
        devs = staged
    for name, spec in specs.items():
        if spec[0] == "dense":
            shards[name] = DenseFeatures(devs[spec[1]])
        else:
            shards[name] = SparseFeatures(
                devs[spec[1]], devs[spec[2]], spec[3]
            )
    return GameDataset(
        labels=devs[i_lab],
        offsets=devs[i_off],
        weights=devs[i_wt],
        feature_shards=shards,
        id_tags={k: IdTag.from_raw(v, place=place)
                 for k, v in (id_tags or {}).items()},
        uids=None if uids is None else np.asarray(uids),
        host=host,
    )
