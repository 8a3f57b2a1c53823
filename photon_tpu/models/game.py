"""GAME model objects: fixed-effect, random-effect, and composite models.

TPU-native counterpart of photon-api model/FixedEffectModel.scala:33 (a
broadcast GLM + feature shard id), model/RandomEffectModel.scala:36 (an
RDD[(REId, GLM)] + REType + shard; ``score`` :70 joins game data by REId) and
photon-lib model/GameModel.scala:32 (ordered map coordinate id -> sub-model;
scores sum across sub-models via ModelDataScores ``+``).

The RDD-of-models becomes ONE padded coefficient matrix ``[num_entities,
max_sub_dim]`` in entity-subspace coordinates: scoring is a two-level gather
(entity row, subspace slot) fused with the multiply-reduce — the join by REId
is index arithmetic. Entities with no trained model (below the active-data
lower bound) occupy all-zero rows, matching the reference's behavior of
contributing no score for unknown entities.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from photon_tpu.data.random_effect import BlockPlan, RandomEffectDataset
from photon_tpu.models.glm import GeneralizedLinearModel
from photon_tpu.ops import placement
from photon_tpu.ops import precision as precision_mod
from photon_tpu.ops import segment_reduce
from photon_tpu.types import TaskType

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class FixedEffectModel:
    """Global GLM + the feature shard it scores against.

    Reference: model/FixedEffectModel.scala:33.
    """

    model: GeneralizedLinearModel
    feature_shard_id: str

    @property
    def task(self) -> TaskType:
        return self.model.task


@dataclasses.dataclass(frozen=True)
class RandomEffectModel:
    """All per-entity GLMs of one random-effect type, as a padded matrix.

    ``coefficients[e, s]`` is entity e's coefficient for its subspace slot s;
    ``proj_all[e, s]`` (host-side) names the original feature id of that slot
    (-1 padding). Reference: model/RandomEffectModel.scala:36.
    """

    coefficients: Array  # [E, S]
    random_effect_type: str
    feature_shard_id: str
    task: TaskType
    proj_all: np.ndarray  # [E, S] original feature ids; -1 pad
    variances: Array | None = None  # [E, S]
    entity_keys: tuple = ()

    @property
    def num_entities(self) -> int:
        return self.coefficients.shape[0]

    @property
    def sub_dim(self) -> int:
        return self.coefficients.shape[1]

    def score_table(
        self, codes: Array, indices: Array, values: Array
    ) -> Array:
        """Scores for rows given subspace-remapped ELL arrays.

        z_i = sum_j values[i, j] * W[codes[i], indices[i, j]] — the
        RandomEffectModel.score join (:70) as a fused two-level gather.
        """
        return score_entity_table(self.coefficients, codes, indices, values)

    def score_dataset(self, dataset: RandomEffectDataset) -> Array:
        if dataset.is_lazy:
            z = _score_via_buckets(self.coefficients, dataset)
            if z is not None:
                return z
            return score_raw_features(
                self.coefficients,
                dataset.score_codes,
                dataset.raw,
                dataset.proj_device(),
            )
        tail = None
        if dataset.score_tail_rows is not None:
            tail = (
                dataset.score_tail_rows,
                dataset.score_tail_indices,
                dataset.score_tail_values,
            )
        return score_entity_table_with_tail(
            self.coefficients,
            dataset.score_codes,
            dataset.score_indices,
            dataset.score_values,
            tail,
            tail_multiplicity=getattr(dataset, "score_tail_mult", None),
        )


@functools.partial(jax.jit, static_argnames=("spmd",))
def _bucket_score_add(z, x_slab, row_ids, row_counts, codes, w, *,
                      spmd: bool = False):
    """Add one bucket's kept-row scores into the canonical [n] vector.

    The slab-side formulation replaces the per-row gather scorer for
    covered rows: z = bmm(slab, W[codes]) reads the materialized slab at
    streaming bandwidth instead of 4-byte-granular row gathers (~17x
    faster measured at 4M rows). Mesh sentinel codes have row_counts 0, so
    their lanes are masked before the scatter. ``spmd`` (operands span a
    mesh, ops/placement.py) keeps the XLA scatter: GSPMD cannot
    partition the Pallas reduce.
    """
    r = row_ids.shape[1]
    s = x_slab.shape[-1]
    valid = jnp.arange(r, dtype=jnp.int32)[None, :] < row_counts[:, None]
    we = jnp.take(w, codes, axis=0, mode="clip")[:, :s].astype(x_slab.dtype)
    # f32 accumulator whenever the slab is stored bf16 (ops/precision.py
    # mixed-precision invariant); on f32 slabs this is the plain einsum.
    zb = precision_mod.acc_einsum("brs,bs->br", x_slab, we)
    if segment_reduce.kernel_supported(
        int(np.prod(row_ids.shape)), int(z.shape[0]), zb.dtype, spmd=spmd
    ):
        # Tiled segment-reduce instead of the serialized scatter-add:
        # valid row ids are distinct within one bucket (each kept row
        # belongs to exactly one entity), so multiplicity is 1.
        return segment_reduce.scatter_add_rows(z, row_ids, zb, valid)
    zb = jnp.where(valid, zb, 0.0)
    return z.at[row_ids].add(zb.astype(z.dtype))


def _score_via_buckets(w: Array, ds: RandomEffectDataset) -> Array | None:
    """Bucket-slab scoring for lazy datasets, or None when not applicable.

    Covered (active kept) rows score from the cached materialized slabs;
    the passive remainder (beyond the reservoir cap / inactive entities)
    scores through the raw-gather path on its row SUBSET — the
    active/passive split of RandomEffectDataset.scala:631-640 as device
    index arithmetic. Applicable when every bucket materialized to a
    subspace-dense slab (the common small-sub_dim case). The gather route
    returns the inverse map's rows: on a mesh the canonical rows padded
    to the device count (``score_rows``), sharded by rows.
    """
    from photon_tpu.data.dataset import DenseFeatures, SparseFeatures

    route = score_route(ds)
    if route == "raw":
        return None
    plans = ds.device_plans()
    blocks = ds.device_blocks()
    if route == "gather":
        # Scatter-free path (same contract as the fused fit's scorer):
        # bucket score blocks + passive scores concatenate into one flat
        # vector that a single gather distributes — TPU scatter-adds of
        # the same pass measured ~4x slower. Empty datasets (no buckets,
        # no passive rows) take the zeros below.
        inv = ds.score_inv_device()
        slabs = tuple(eb.x_values for eb in blocks)
        codes = tuple(p.entity_codes for p in plans)
        args = (w, slabs, codes, inv, ds.passive_rows_device(),
                ds.score_codes, ds.raw, ds.proj_device())
        if placement.spans_devices(inv):
            return _gather_score_mesh(*args, mesh=inv.sharding.mesh)
        return _gather_score(*args)
    _, passive = ds.covered_row_partition()
    z = jnp.zeros(ds.num_rows, dtype=w.dtype)
    for plan, eb in zip(plans, blocks):
        z = _bucket_score_add(
            z, eb.x_values, plan.row_ids, plan.row_counts,
            plan.entity_codes, w,
            spmd=placement.spans_devices((eb.x_values, plan.row_ids, w)),
        )
    if passive.size:
        pr = ds.passive_rows_device()
        feats = ds.raw
        if isinstance(feats, DenseFeatures):
            z = _passive_score_set_dense(
                z, pr, ds.score_codes, feats.x, w, ds.proj_device()
            )
        else:
            z = _passive_score_set_sparse(
                z, pr, ds.score_codes, feats.indices, feats.values,
                w, ds.proj_device(),
            )
    return z


def score_route(ds: RandomEffectDataset, slabs=None) -> str:
    """Which branch of ``RandomEffectModel.score_dataset`` scores ``ds``,
    decided from what the data set holds, not run: ``"table"`` (the
    materialized score table), ``"raw"`` (a bucket left lazy or in ELL
    form: every row from the raw features), ``"gather"`` (one gather
    through the inverse score map, on a mesh each device its own rows) or
    ``"scatter"`` (an add a bucket into an ``[n]`` vector, where no map
    exists). ``slabs``: the buckets as a fit solves them (by default the
    data set's ``device_blocks``); only their form is read, so a caller
    that passes them places and materializes nothing."""
    if not ds.is_lazy:
        return "table"
    if slabs is None:
        slabs = ds.device_blocks()
    if any(isinstance(b, BlockPlan) or b.x_indices is not None
           for b in slabs):
        return "raw"
    _, passive = ds.covered_row_partition()
    if ds.has_score_inv and (slabs or passive.size):
        return "gather"
    return "scatter"


def score_programs(ds: RandomEffectDataset) -> int:
    """How many programs ``score_dataset`` dispatches on ``ds``: the
    branches of ``_score_via_buckets`` counted, not run (a zeros vector
    to add into is one of JAX's one-primitive helpers and not counted)."""
    if score_route(ds) != "scatter":
        return 1
    # One add a bucket and the passive rows' set.
    _, passive = ds.covered_row_partition()
    return len(ds.device_blocks()) + bool(passive.size)


def score_rows(ds: RandomEffectDataset) -> int:
    """The length of the vector ``score_dataset`` returns on ``ds``: the
    inverse map's on the gather route (on a mesh the rows padded to the
    device count), the data set's rows on every other."""
    if score_route(ds) == "gather":
        return int(ds.score_inv_device().shape[0])
    return ds.num_rows


def bucket_score_parts(w, slabs, codes):
    """Per-bucket flat [B*cap] score vectors (slab GEMM per bucket).

    bf16-stored slabs accumulate their score reduction in f32
    (ops/precision.py); the parts come back f32 either way."""
    parts = []
    for xv, cd in zip(slabs, codes):
        we = jnp.take(w, cd, axis=0, mode="clip")[:, :xv.shape[-1]].astype(
            xv.dtype)
        parts.append(
            precision_mod.acc_einsum("brs,bs->br", xv, we).reshape(-1)
        )
    return parts


def passive_raw_scores(w, pr, score_codes, feats, proj_dev):
    """Raw-feature scores for the passive row subset ``pr`` (traceable).

    Computed in the COEFFICIENT dtype — passive rows must not round
    through a lower slab dtype on their way into the final gather."""
    from photon_tpu.data.dataset import DenseFeatures

    codes_p = jnp.take(score_codes, pr)
    if isinstance(feats, DenseFeatures):
        zp = _score_raw_dense(
            w, codes_p, jnp.take(feats.x, pr, axis=0), proj_dev)
    else:
        zp = _score_raw_sparse(
            w, codes_p, jnp.take(feats.indices, pr, axis=0),
            jnp.take(feats.values, pr, axis=0), proj_dev,
        )
    return zp.astype(w.dtype)


@jax.jit
def _gather_score(w, slabs, codes, inv, pr, score_codes, feats, proj_dev):
    """ONE gather distributes concatenated bucket + passive scores to
    canonical rows (the scatter-free scoring contract; shared shape with
    fused_fit._re_score)."""
    parts = bucket_score_parts(w, slabs, codes)
    if pr is not None:
        parts.append(passive_raw_scores(w, pr, score_codes, feats,
                                        proj_dev))
    return jnp.take(
        jnp.concatenate(parts), inv, mode="clip").astype(w.dtype)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _gather_score_mesh(w, slabs, codes, inv, pr, score_codes, feats,
                       proj_dev, *, mesh):
    """``_gather_score`` on a mesh, its communication written out: each
    device scores its own entities' slab rows and its share of the
    passive rows, the flat parts are all-gathered in bucket order (the
    layout the padded inverse map points into), and each device gathers
    its own rows of the map. Row-sharded ``[len(inv)]``."""
    axis = mesh.axis_names[0]
    rows, whole = P(axis), P()

    def local(w, slabs, codes, inv, pr, score_codes, feats, proj_dev):
        parts = bucket_score_parts(w, slabs, codes)
        if pr is not None:
            parts.append(passive_raw_scores(w, pr, score_codes, feats,
                                            proj_dev))
        flat = jnp.concatenate(
            [jax.lax.all_gather(p, axis, tiled=True) for p in parts])
        return jnp.take(flat, inv, mode="clip").astype(w.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(whole, rows, rows, rows, rows, whole, whole, whole),
        out_specs=rows,
    )(w, slabs, codes, inv, pr, score_codes, feats, proj_dev)


@jax.jit
def _passive_score_set_dense(z, pr, score_codes, x, w, proj_dev):
    """Scatter passive-row scores into z as ONE program: the row-subset
    gathers, the raw-feature score, and the set-scatter each compile as
    separate eager programs otherwise."""
    codes_p = jnp.take(score_codes, pr)
    zp = _score_raw_dense(w, codes_p, jnp.take(x, pr, axis=0), proj_dev)
    return z.at[pr].set(zp.astype(z.dtype))


@jax.jit
def _passive_score_set_sparse(z, pr, score_codes, indices, values, w,
                              proj_dev):
    codes_p = jnp.take(score_codes, pr)
    zp = _score_raw_sparse(
        w, codes_p, jnp.take(indices, pr, axis=0),
        jnp.take(values, pr, axis=0), proj_dev,
    )
    return z.at[pr].set(zp.astype(z.dtype))


def score_entity_table(
    w: Array, codes: Array, indices: Array, values: Array
) -> Array:
    """z_i = sum_j values[i,j] * w[codes[i], indices[i,j]] (jit-friendly)."""
    if w.shape[0] == 0:
        # Empty model set (e.g. a partial-retrain dir with no coefficients):
        # every row is an unknown entity and scores 0 (the reference's
        # left-join-with-no-match semantics).
        return jnp.zeros(codes.shape[0], dtype=values.dtype)
    s = w.shape[1]
    n, k = indices.shape
    rows = jnp.take(w, codes, axis=0)  # [n, S]
    from photon_tpu.data.random_effect import DENSE_SUB_DIM_MAX

    # One-hot contraction instead of take_along_axis: batched gathers
    # compile ~40x slower on TPU than the equivalent matmul. Bounded by
    # total one-hot elements so a width-capped table (k << S chosen to
    # bound memory) never inflates by a factor of S.
    if s <= DENSE_SUB_DIM_MAX and n * k * s <= (1 << 28):
        onehot = (
            indices[:, :, None]
            == jnp.arange(s, dtype=indices.dtype)[None, None, :]
        ).astype(rows.dtype)  # [n, k, S]
        picked = jnp.einsum("nks,ns->nk", onehot, rows)
    else:
        picked = jnp.take_along_axis(rows, indices, axis=-1)  # [n, k]
    return precision_mod.acc_sum(
        precision_mod.like_storage(values, picked) * picked, axis=-1
    )


@jax.jit
def _score_raw_dense(w: Array, codes: Array, x: Array, proj: Array) -> Array:
    """Fused dense-shard scoring: scatter each entity's subspace
    coefficients into original feature space ([E, d], small), then one
    gather-dot per row against the HBM-resident raw matrix. No [n, k]
    scoring table ever exists."""
    e, s = w.shape
    d = x.shape[1]
    # -1 projector pads scatter into a spill column that is sliced away.
    pr = jnp.where(proj >= 0, proj, d)
    w_orig = jnp.zeros((e, d + 1), w.dtype)
    w_orig = w_orig.at[
        jnp.arange(e, dtype=jnp.int32)[:, None], pr
    ].set(jnp.where(proj >= 0, w, 0.0))[:, :d]
    # Unseen entities (code -1) drop to zero rows. NOTE: jnp.take wraps
    # negative indices numpy-style BEFORE the out-of-bounds fill check, so
    # -1 must be masked explicitly, not left to mode="fill".
    rows = jnp.take(
        w_orig, jnp.maximum(codes, 0), axis=0, mode="fill", fill_value=0
    )
    rows = jnp.where((codes >= 0)[:, None], rows, 0)
    # Row-axis reduction: f32 accumulator when the table is stored bf16
    # (the serving precision path); identical to the plain sum at f32.
    return precision_mod.acc_sum(x.astype(w.dtype) * rows, axis=-1)


@jax.jit
def _score_raw_sparse(
    w: Array, codes: Array, indices: Array, values: Array, proj: Array
) -> Array:
    """Fused ELL-shard scoring against the owning entity's projector.

    Small subspaces use a one-hot contraction (feature-id match feeding a
    matmul); larger ones fall back to binary search + take_along_axis.
    Batched gather ops compile ~40x slower on TPU than the one-hot einsum,
    so the contraction is the default for every realistic sub_dim.
    """
    from photon_tpu.data.random_effect import DENSE_SUB_DIM_MAX

    s = w.shape[1]
    # Unseen entities (code -1): jnp.take wraps negative indices
    # numpy-style before the fill check, so mask them explicitly.
    safe = jnp.maximum(codes, 0)
    known = codes >= 0
    wrows = jnp.take(w, safe, axis=0, mode="fill", fill_value=0)  # [n, S]
    n, k = indices.shape
    if s <= DENSE_SUB_DIM_MAX and n * k * s <= (1 << 28):
        prows = jnp.take(proj, safe, axis=0)  # [n, S]; -1 pads never match
        onehot = (
            indices[:, :, None] == prows[:, None, :]
        ).astype(values.dtype)  # [n, k, S]
        contrib = jnp.einsum("nk,nks->ns", values, onehot)
        return jnp.where(
            known,
            precision_mod.acc_einsum(
                "ns,ns->n", precision_mod.like_storage(contrib, wrows),
                wrows,
            ),
            0.0,
        )
    sentinel = jnp.iinfo(jnp.int32).max
    psort = jnp.where(proj >= 0, proj, sentinel)  # [E, S], stays ascending
    prows = jnp.take(
        psort, safe, axis=0, mode="fill", fill_value=sentinel
    )  # [n, S]
    slot = jax.vmap(jnp.searchsorted)(prows, indices)
    slot = jnp.minimum(slot, s - 1)
    hit = (jnp.take_along_axis(prows, slot, axis=1) == indices) & known[:, None]
    picked = jnp.take_along_axis(wrows, slot, axis=1)
    return precision_mod.acc_sum(
        jnp.where(
            hit, precision_mod.like_storage(values, picked) * picked, 0.0
        ),
        axis=-1,
    )


def score_raw_features(
    w: Array, codes: Array, feats, proj_dev: Array
) -> Array:
    """Lazy-layout scoring straight off the raw feature arrays.

    The materialized equivalent (``score_entity_table``) reads a
    pre-remapped [n, k] table; this fuses the remap into the score so the
    only per-row state in HBM is the raw shard itself (shared with every
    other consumer). ``proj_dev`` is the device [E, S] projector matrix.
    """
    from photon_tpu.data.dataset import DenseFeatures, SparseFeatures

    if w.shape[0] == 0:
        n = (
            feats.x.shape[0]
            if isinstance(feats, DenseFeatures)
            else feats.indices.shape[0]
        )
        return jnp.zeros(n, dtype=w.dtype)
    if isinstance(feats, DenseFeatures):
        return _score_raw_dense(w, codes, feats.x, proj_dev)
    if isinstance(feats, SparseFeatures):
        return _score_raw_sparse(
            w, codes, feats.indices, feats.values, proj_dev
        )
    raise TypeError(
        f"lazy scoring expects Dense or Sparse features, got "
        f"{type(feats).__name__}"
    )


def score_entity_table_with_tail(
    w: Array,
    codes: Array,
    indices: Array,
    values: Array,
    tail: tuple[Array, Array, Array] | None,
    tail_multiplicity: int | None = None,
) -> Array:
    """score_entity_table plus a width-capped table's COO overflow tail
    (rows sorted ascending; see RandomEffectDataConfiguration
    .score_table_width_cap).

    ``tail_multiplicity`` is the host-computed max tail entries per row
    (RandomEffectDataset.score_tail_mult): with it, the sorted tail
    reduction runs through the tiled Pallas segment-reduce where
    supported instead of the XLA scatter lowering of ``segment_sum``.
    """
    base = score_entity_table(w, codes, indices, values)
    if tail is None or w.shape[0] == 0:
        return base
    tr, ti, tv = tail
    # Flattened 1-D take instead of a two-vector gather (compile cost).
    flat = jnp.take(codes, tr) * w.shape[1] + ti
    picked = jnp.take(w.reshape(-1), flat)
    contrib = precision_mod.like_storage(tv, picked) * picked
    n = base.shape[0]
    if tail_multiplicity is not None and segment_reduce.kernel_supported(
        int(tr.shape[0]), int(n), contrib.dtype,
        spmd=placement.spans_devices((w, codes, values, tail)),
    ):
        summed = segment_reduce.sorted_segment_sum(
            contrib, tr.astype(jnp.int32), n,
            multiplicity=int(tail_multiplicity),
            site="segment_reduce/score_tail",
        )
    else:
        if contrib.dtype == jnp.bfloat16:
            contrib = contrib.astype(jnp.float32)  # f32 accumulator
        summed = jax.ops.segment_sum(
            contrib, tr, num_segments=n, indices_are_sorted=True
        )
    return base + summed.astype(base.dtype)


@dataclasses.dataclass(frozen=True)
class GameModel:
    """Ordered composite of coordinate sub-models (model/GameModel.scala:32).

    Iteration order is the coordinate update sequence; total score is the sum
    of per-coordinate scores (DataScores ``+`` algebra).
    """

    models: dict[str, FixedEffectModel | RandomEffectModel]

    def __getitem__(self, coordinate_id: str):
        return self.models[coordinate_id]

    def __contains__(self, coordinate_id: str) -> bool:
        return coordinate_id in self.models

    def items(self):
        return self.models.items()

    def updated(self, coordinate_id: str, model) -> "GameModel":
        new = dict(self.models)
        new[coordinate_id] = model
        return GameModel(new)

    @property
    def task(self) -> TaskType:
        for m in self.models.values():
            return m.task
        raise ValueError("empty GAME model")


def remap_random_effect_model(
    model: RandomEffectModel,
    *,
    entity_keys: tuple,
    proj_all: np.ndarray,
) -> RandomEffectModel:
    """Re-layout a RandomEffectModel onto a different dataset layout.

    Used when an externally loaded model (warm start / partial retrain,
    GameTrainingDriver.scala:395-404) meets a freshly built
    RandomEffectDataset whose entity vocabulary and per-entity subspace slot
    order differ from the model's. Coefficients are routed by (entity key,
    original feature id); entities/features absent from the new layout are
    dropped, new ones start at zero — the fullOuterJoin warm-start semantics
    of RandomEffectCoordinate.scala:200.
    """
    e_new, s_new = proj_all.shape
    w_old = np.asarray(model.coefficients)
    v_old = None if model.variances is None else np.asarray(model.variances)
    dtype = w_old.dtype
    w = np.zeros((e_new, s_new), dtype=dtype)
    v = None if v_old is None else np.zeros((e_new, s_new), dtype=dtype)
    old_vocab = {str(k): i for i, k in enumerate(model.entity_keys)}
    n_hit = sum(1 for k in entity_keys if str(k) in old_vocab)
    if entity_keys and model.entity_keys and n_hit == 0:
        import warnings

        warnings.warn(
            f"remap_random_effect_model({model.random_effect_type!r}): none "
            f"of {len(entity_keys)} dataset entities match the "
            f"{len(model.entity_keys)} model entities — the warm start is "
            "effectively a zero model",
            stacklevel=2,
        )
    max_feat = 0
    if proj_all.size:
        max_feat = max(max_feat, int(proj_all.max(initial=0)))
    if model.proj_all.size:
        max_feat = max(max_feat, int(model.proj_all.max(initial=0)))
    lut = np.full(max_feat + 1, -1, dtype=np.int64)
    for en, key in enumerate(entity_keys):
        eo = old_vocab.get(str(key))
        if eo is None:
            continue
        old_p = model.proj_all[eo]
        old_valid = old_p >= 0
        lut[old_p[old_valid]] = np.nonzero(old_valid)[0]
        new_p = proj_all[en]
        new_valid = new_p >= 0
        src = lut[new_p[new_valid]]
        dst = np.nonzero(new_valid)[0]
        hit = src >= 0
        w[en, dst[hit]] = w_old[eo, src[hit]]
        if v is not None:
            v[en, dst[hit]] = v_old[eo, src[hit]]
        lut[old_p[old_valid]] = -1
    return dataclasses.replace(
        model,
        coefficients=jnp.asarray(w),
        variances=None if v is None else jnp.asarray(v),
        proj_all=proj_all,
        entity_keys=entity_keys,
    )
