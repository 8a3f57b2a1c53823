"""Device-side dataset representations: dense and padded-sparse (ELL) batches.

TPU-native counterpart of the reference's ``LabeledPoint`` / RDD row
partitions (photon-lib data/LabeledPoint.scala:30, photon-api
data/FixedEffectDataset.scala:32). Instead of millions of JVM objects, a
dataset is a struct-of-arrays batch resident in HBM:

- ``DenseBatch``: features ``[n, d]`` — right for small/medium d where the
  MXU eats the matvec directly.
- ``SparseBatch``: ELL/padded-row layout ``indices[n, k]``, ``values[n, k]``
  with a fixed per-row capacity k = max nnz. Padding slots point at a valid
  column with value 0, so ``matvec`` is a gather + fused multiply-reduce and
  ``rmatvec`` a scatter-add — both static-shape, both XLA-tileable. This is
  the TPU answer to Breeze sparse vectors: bag-of-features data (the
  reference's domain) is hash-sparse with bounded row nnz, so ELL padding is
  cheap and every shape is static.

Rows carry (label, offset, weight) exactly like ``LabeledPoint``; weight 0
removes a row from every aggregation, which is how padding rows added for
even device sharding stay inert.
"""

from __future__ import annotations

import dataclasses
from typing import Protocol, Union

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class FeatureMatrix(Protocol):
    """The two matvecs every GLM computation is built from."""

    num_features: int

    def matvec(self, w: Array) -> Array:
        """X @ w -> [n] margins."""

    def rmatvec(self, g: Array) -> Array:
        """X^T @ g -> [d] aggregation."""

    def rmatvec_sq(self, g: Array) -> Array:
        """(X*X)^T @ g -> [d]; Hessian-diagonal helper."""


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseFeatures:
    x: Array  # [n, d]

    @property
    def num_features(self) -> int:
        return self.x.shape[-1]

    def matvec(self, w: Array) -> Array:
        return self.x @ w

    def rmatvec(self, g: Array) -> Array:
        return self.x.T @ g

    def rmatvec_sq(self, g: Array) -> Array:
        return (self.x * self.x).T @ g

    def gram(self, c: Array) -> Array:
        """X^T diag(c) X, [d, d]: the Hessian of a FULL variance."""
        return self.x.T @ (c[:, None] * self.x)


# ``DenseFeatures``' products read through ``xt``, the [d, n] transpose of
# its ``x``: the operands the other way round, the same dtypes.
def _fm_matvec(xt: Array, w: Array) -> Array:
    return w @ xt


def _fm_rmatvec(xt: Array, g: Array) -> Array:
    return xt @ g


def _fm_rmatvec_sq(xt: Array, g: Array) -> Array:
    return (xt * xt) @ g


def _fm_gram(xt: Array, c: Array) -> Array:
    return (xt * c[None, :]) @ xt.T


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FeatureMajorFeatures:
    """Dense features read feature-major: ``xt`` is ``[d, n]``, the
    transpose of the stored ``x``, taken inside a program before its loops
    (``feature_major``).

    Why: the TPU stores a ``[n, d]`` array of small d with its rows on
    the lanes, so ``x.T`` is a bitcast of it. A program that reads ``x``
    row-major has XLA relay it out first, d padded to 128 lanes (twice
    the bytes at d = 64), and every pass then reads the padded copy. The
    CPU pads nothing: there each product reads ``x`` as ``DenseFeatures``
    does (``xt`` is then dead and compiled away), so its arithmetic stays
    what it was. The platform is chosen when the program is lowered.
    """

    x: Array  # [n, d], as stored
    xt: Array  # [d, n]

    @property
    def num_features(self) -> int:
        return self.xt.shape[0]

    def _product(self, row_major, feature_major, v: Array) -> Array:
        """``row_major`` (a ``DenseFeatures`` method) on the CPU,
        ``feature_major`` of ``xt`` on any other platform."""
        return jax.lax.platform_dependent(
            self.x, self.xt, v,
            cpu=lambda x, xt, v: row_major(DenseFeatures(x), v),
            default=lambda x, xt, v: feature_major(xt, v))

    def matvec(self, w: Array) -> Array:
        return self._product(DenseFeatures.matvec, _fm_matvec, w)

    def rmatvec(self, g: Array) -> Array:
        return self._product(DenseFeatures.rmatvec, _fm_rmatvec, g)

    def rmatvec_sq(self, g: Array) -> Array:
        return self._product(DenseFeatures.rmatvec_sq, _fm_rmatvec_sq, g)

    def gram(self, c: Array) -> Array:
        return self._product(DenseFeatures.gram, _fm_gram, c)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseFeatures:
    """ELL layout: per-row index/value slabs with static capacity.

    ``indices`` entries for padding slots MUST be valid column ids (0 is
    fine) with ``values`` 0 — gathers stay in-bounds and scatters add zeros.
    """

    indices: Array  # [n, k] int32
    values: Array  # [n, k]
    d: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_features(self) -> int:
        return self.d

    def matvec(self, w: Array) -> Array:
        return jnp.sum(self.values * w[self.indices], axis=-1)

    def rmatvec(self, g: Array) -> Array:
        contrib = self.values * g[:, None]
        return jnp.zeros(self.d, dtype=contrib.dtype).at[self.indices].add(contrib)

    def rmatvec_sq(self, g: Array) -> Array:
        contrib = self.values * self.values * g[:, None]
        return jnp.zeros(self.d, dtype=contrib.dtype).at[self.indices].add(contrib)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DualEllFeatures:
    """Bounded-width ELL slab + COO overflow tail.

    Plain ELL sizes every row at the GLOBAL max nnz — one dense row inflates
    the whole table (the SURVEY §7.3 width hazard). Here the slab width is
    capped; entries beyond the cap spill into a COO tail whose contributions
    are segment-summed back per row. Storage is O(n * cap + overflow) instead
    of O(n * max_nnz), which is what makes heavy-tailed bag-of-features data
    (the reference's domain) storable at scale.

    ``tail_rows`` MUST be sorted ascending (segment_sum indices_are_sorted).
    """

    indices: Array  # [n, cap] int32; padding -> (0, value 0)
    values: Array  # [n, cap]
    tail_rows: Array  # [t] int32 row id per overflow entry, sorted
    tail_indices: Array  # [t] int32
    tail_values: Array  # [t]
    d: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_features(self) -> int:
        return self.d

    @property
    def num_rows(self) -> int:
        return self.indices.shape[0]

    def matvec(self, w: Array) -> Array:
        base = jnp.sum(self.values * w[self.indices], axis=-1)
        tail = self.tail_values * w[self.tail_indices]
        return base + jax.ops.segment_sum(
            tail, self.tail_rows, num_segments=self.num_rows,
            indices_are_sorted=True,
        )

    def rmatvec(self, g: Array) -> Array:
        contrib = self.values * g[:, None]
        out = jnp.zeros(self.d, dtype=contrib.dtype).at[self.indices].add(
            contrib)
        return out.at[self.tail_indices].add(
            self.tail_values * g[self.tail_rows])

    def rmatvec_sq(self, g: Array) -> Array:
        contrib = self.values * self.values * g[:, None]
        out = jnp.zeros(self.d, dtype=contrib.dtype).at[self.indices].add(
            contrib)
        return out.at[self.tail_indices].add(
            self.tail_values * self.tail_values * g[self.tail_rows])


def ell_to_dual_ell(
    indices: np.ndarray,  # [n, k] host-side
    values: np.ndarray,  # [n, k]
    num_features: int,
    width_cap: int,
    dtype=np.float32,
) -> DualEllFeatures:
    """Split an ELL slab at ``width_cap``: widest entries spill to the tail."""
    n, k = indices.shape
    cap = max(min(width_cap, k), 1)
    present = values != 0.0
    # Compact valid entries left so the first `cap` slots hold real entries.
    order = np.argsort(~present, axis=1, kind="stable")
    idx_c = np.take_along_axis(np.where(present, indices, 0), order, axis=1)
    val_c = np.take_along_axis(np.where(present, values, 0.0), order, axis=1)
    tail_mask = val_c[:, cap:] != 0.0
    rows = np.broadcast_to(
        np.arange(n, dtype=np.int64)[:, None], tail_mask.shape)
    return DualEllFeatures(
        indices=jnp.asarray(idx_c[:, :cap].astype(np.int32)),
        values=jnp.asarray(val_c[:, :cap], dtype=dtype),
        tail_rows=jnp.asarray(rows[tail_mask].astype(np.int32)),
        tail_indices=jnp.asarray(
            idx_c[:, cap:][tail_mask].astype(np.int32)),
        tail_values=jnp.asarray(val_c[:, cap:][tail_mask], dtype=dtype),
        d=num_features,
    )


Features = Union[
    DenseFeatures, FeatureMajorFeatures, SparseFeatures, DualEllFeatures]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GLMBatch:
    """One coordinate's training slab: features + (label, offset, weight).

    The reference's ``FixedEffectDataset`` is an RDD of these rows plus
    persistence choreography; here the whole dataset is one pytree, and
    "persistence" is just the arrays living in HBM (optionally sharded over
    the mesh's data axis by the caller via NamedSharding).
    """

    features: Features
    labels: Array  # [n]
    offsets: Array  # [n]
    weights: Array  # [n]

    @property
    def num_samples(self) -> int:
        return self.labels.shape[-1]

    @property
    def num_features(self) -> int:
        return self.features.num_features

    def with_offsets(self, offsets: Array) -> "GLMBatch":
        """Functional offset update — the residual-score plumbing of
        coordinate descent (Coordinate.scala:52-53 addScoresToOffsets)."""
        return dataclasses.replace(self, offsets=offsets)

    def with_weights(self, weights: Array) -> "GLMBatch":
        """Functional weight update (down-sampling masks)."""
        return dataclasses.replace(self, weights=weights)

    def weighted_count(self) -> Array:
        return jnp.sum(self.weights)


def _has_view(features) -> bool:
    return isinstance(features, DenseFeatures) and features.x.ndim == 2


def feature_layout(batch: GLMBatch) -> str:
    """How a solve reads the batch's features: ``"feature_major"`` where
    ``feature_major`` gives the ``[d, n]`` view, ``"row_major"`` where it
    leaves the batch as it is. From types and ranks alone."""
    f = batch.features
    if isinstance(f, FeatureMajorFeatures) or _has_view(f):
        return "feature_major"
    return "row_major"


def feature_major(batch: GLMBatch) -> GLMBatch:
    """The batch with its 2-D ``DenseFeatures`` read through the
    feature-major view (``FeatureMajorFeatures``); any other batch as it
    is. Meant for inside a program, where the transpose is free on the
    TPU; outside one it dispatches a transpose."""
    if _has_view(batch.features):
        x = batch.features.x
        return dataclasses.replace(
            batch, features=FeatureMajorFeatures(x, x.T))
    return batch


def make_dense_batch(
    x: np.ndarray,
    labels: np.ndarray,
    offsets: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    dtype=jnp.float32,
) -> GLMBatch:
    n = x.shape[0]
    return GLMBatch(
        features=DenseFeatures(jnp.asarray(x, dtype=dtype)),
        labels=jnp.asarray(labels, dtype=dtype),
        offsets=jnp.zeros(n, dtype=dtype) if offsets is None else jnp.asarray(offsets, dtype=dtype),
        weights=jnp.ones(n, dtype=dtype) if weights is None else jnp.asarray(weights, dtype=dtype),
    )


def rows_to_ell(
    rows: list[list[tuple[int, float]]],
    num_features: int,
    *,
    capacity: int | None = None,
    dtype=np.float32,
) -> tuple[np.ndarray, np.ndarray]:
    """Pack per-row (index, value) lists into ELL index/value slabs."""
    k = capacity if capacity is not None else max((len(r) for r in rows), default=1)
    k = max(k, 1)
    n = len(rows)
    indices = np.zeros((n, k), dtype=np.int32)
    values = np.zeros((n, k), dtype=dtype)
    for i, row in enumerate(rows):
        if len(row) > k:
            raise ValueError(f"row {i} has {len(row)} nnz > capacity {k}")
        for j, (idx, val) in enumerate(row):
            if not (0 <= idx < num_features):
                raise ValueError(f"feature index {idx} out of range [0, {num_features})")
            indices[i, j] = idx
            values[i, j] = val
    return indices, values


def make_sparse_batch(
    rows: list[list[tuple[int, float]]],
    num_features: int,
    labels: np.ndarray,
    offsets: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    capacity: int | None = None,
    dtype=jnp.float32,
) -> GLMBatch:
    indices, values = rows_to_ell(
        rows, num_features, capacity=capacity, dtype=np.dtype(dtype)
    )
    n = len(rows)
    return GLMBatch(
        features=SparseFeatures(jnp.asarray(indices), jnp.asarray(values, dtype=dtype), num_features),
        labels=jnp.asarray(labels, dtype=dtype),
        offsets=jnp.zeros(n, dtype=dtype) if offsets is None else jnp.asarray(offsets, dtype=dtype),
        weights=jnp.ones(n, dtype=dtype) if weights is None else jnp.asarray(weights, dtype=dtype),
    )


def pad_batch(batch: GLMBatch, multiple: int) -> GLMBatch:
    """Pad the sample axis to a multiple (for even device sharding) with
    weight-0 rows; padding rows contribute exactly zero to every aggregate."""
    n = batch.num_samples
    rem = (-n) % multiple
    if rem == 0:
        return batch

    def pad1(a):
        return jnp.concatenate([a, jnp.zeros((rem,) + a.shape[1:], dtype=a.dtype)])

    feats = batch.features
    if isinstance(feats, DenseFeatures):
        feats = DenseFeatures(pad1(feats.x))
    elif isinstance(feats, SparseFeatures):
        feats = SparseFeatures(pad1(feats.indices), pad1(feats.values), feats.d)
    else:
        raise TypeError(
            "pad_batch/shard_batch do not support DualEllFeatures: the COO "
            "tail is not row-aligned, so row sharding would misroute it. "
            "Use plain SparseFeatures for data-axis sharding, or "
            "FeatureShardedSparse for the feature axis.")
    return GLMBatch(
        features=feats,
        labels=pad1(batch.labels),
        offsets=pad1(batch.offsets),
        weights=pad1(batch.weights),  # zeros: inert rows
    )
