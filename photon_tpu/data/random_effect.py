"""RandomEffectDataset: per-entity data as size-bucketed device blocks.

TPU-native counterpart of the heart of GLMix scaling (photon-api
data/RandomEffectDataset.scala:54, apply :264-354). The reference's build
pipeline — key by REId, per-entity ``LinearSubspaceProjector`` from the union
of active feature indices (:390-426), deterministic reservoir-sampling cap
(groupDataByKeyAndSample :468-527 with byteswap64 hash keys :510), feature
projection to the subspace (:538-550), optional Pearson-correlation feature
selection (:562-576), active-data lower-bound filter (:586-606) and passive
data as the leftovers (:631-640) — happens ONCE at ingest, in two stages:

1. **Plan (host)**: a fully vectorized numpy pass over the id codes — one
   ``(entity, hash)`` lexsort gives the deterministic reservoir order, one
   global ``unique`` over (entity, feature) pairs gives every subspace
   projector, and one global ``searchsorted`` against the concatenated
   projector key table remaps any (entity, feature) pair to its subspace
   slot. There are NO per-entity Python loops; the reference's shuffles
   (RandomEffectDataset.scala:264-354) become O(n log n) host sorts.
2. **Device placement**: by default the plan is *lazy* — only the small
   index arrays (bucket membership ``row_ids``, projector tables) are
   pushed; the big per-bucket feature slabs and the scoring table are
   **gathered on device, inside the already-jitted solver/scorer, from the
   raw feature arrays resident in HBM**. The raw data crosses the
   host->device link exactly once (at ``make_game_dataset``), and HBM
   bandwidth — not the host link — feeds the MXU. ``lazy=False`` keeps the
   fully materialized layout (used for ``DualEllFeatures`` shards and by
   layout-introspection tests).

- **EntityBlocks / BlockPlan** (training): entities grouped into size
  buckets; each bucket materializes to a ``[B, R, k]`` ELL slab plus
  per-entity projector index arrays, so one vmapped solver call fits all B
  entities simultaneously. This replaces the reference's per-partition
  ``mapValues`` local solves (RandomEffectCoordinate.scala:243-292) and its
  partitioner bin-packing (RandomEffectDatasetPartitioner.scala:44): padding
  buckets instead of packing bins.
- **Scoring** (active + passive rows): every canonical row scores against
  the ``[num_entities, max_sub_dim]`` coefficient matrix — lazily as a fused
  remap-gather-reduce over the raw features (models/game.py
  score_raw_features), or through the materialized width-capped table with
  COO tail. Features outside an entity's subspace contribute nothing (the
  projector drop semantics of LinearSubspaceProjector.projectForward).

Residual routing (addScoresToOffsets :83-110) reduces to gathering the
canonical offsets vector through each block's ``row_ids``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.data.dataset import (
    DenseFeatures,
    Features,
    SparseFeatures,
)
from photon_tpu.data.game_data import GameDataset
from photon_tpu.ops import segment_reduce
from photon_tpu.data.pipeline import (
    PIPELINE_STATS,
    bincount_chunked,
    group_rows,
    chunk_executor,
    consume_futures,
    map_chunked,
    packed_device_put,
)

Array = jax.Array

# Row-count caps for entity size buckets: entities are padded up to the next
# cap, so worst-case padding waste is bounded within a bucket (SURVEY §7.3).
# Ratio 2: a slab holds under twice its entity's rows, the rule
# ``_assign_buckets`` applies above the largest cap too. Padding rows carry
# weight 0 but are not free: every fit gathers the row residuals into the
# padded slabs, and the slab build the features, and on the chip (TPU v5e)
# an element gather costs about 7 ns an index (116-151 M slab rows/s)
# whatever a row holds, so its time goes with the SLAB rows (PERF.md
# section 6, PR 29: a ratio-4 ladder held 1.75 x the slab rows on the
# benchmark's GLMix and a fit took 1.45-1.6 x as long). The fused fit's home
# coordinate moves its rows without a gather (PR 33, ops/ragged.py: a pass
# over the slab's slots); every other coordinate still pays the gather.
DEFAULT_BUCKET_CAPS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
# The price of a fine ladder: each occupied rung is a solver instance of the
# fused program, 2-3.5 s of trace on the chip's host in EVERY process (a
# Newton-kernel rung of 9 / 17 features) and a compile in every new checkout.
# So ``_assign_buckets`` merges a rung upward when that adds under
# 1 / _THIN_RUNG of the coordinate's slab rows: 1 / 256 more slab rows cost a
# 1.3 s fit about 3 ms of gathers, and a daily job is one fit a process, a
# tuning sweep 16-32.
_THIN_RUNG = 256


@dataclasses.dataclass(frozen=True)
class RandomEffectDataConfiguration:
    """Per-coordinate random-effect data config.

    Reference: RandomEffectDataConfiguration in
    data/CoordinateDataConfiguration.scala:77 — REType, feature shard, active
    data bounds, features-to-samples ratio (Pearson filter).
    """

    random_effect_type: str
    feature_shard_id: str
    active_data_upper_bound: int | None = None
    active_data_lower_bound: int | None = None
    features_to_samples_ratio: float | None = None
    bucket_caps: tuple[int, ...] = DEFAULT_BUCKET_CAPS
    # Scoring-table ELL width bound (SURVEY §7.3 width hazard) for the
    # MATERIALIZED layout: rows with more nnz spill into a COO tail instead
    # of inflating every row's slab. The lazy layout reads the raw feature
    # arrays directly and never builds a table, so the cap is moot there.
    score_table_width_cap: int | None = None
    # Entity-bucket batching: buckets with fewer member entities than
    # this merge UPWARD into the next-larger row cap (more padding, but
    # fewer/fatter solver programs — a bucket-tail of a handful of
    # entities otherwise dispatches its own program per warm refit and
    # instantiates its own solver inside the fused sweep). 0 leaves the
    # planner's own rule alone (``_assign_buckets``: a rung whose merge
    # pads the slabs by under 1 / ``_THIN_RUNG`` rides up). Shared with the
    # ingest pipeline's shape oracle through ``_assign_buckets`` so
    # predicted block shapes can never drift from built ones.
    min_bucket_entities: int = 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EntityBlocks:
    """One size bucket of entities, padded to common shapes (materialized).

    Training slab for a vmapped per-entity solver: leading axis B is the
    entity axis. Padding rows carry weight 0; padded subspace slots have
    ``proj == -1`` and never receive data gradient.
    """

    entity_codes: Array  # [B] int32 — global entity code per slot
    # Feature slabs, one of two layouts:
    # - ELL: x_indices [B, R, k] int32 subspace slots + x_values [B, R, k]
    # - subspace-dense: x_indices is None, x_values [B, R, S] holds the
    #   densified per-entity design matrix. Preferred for small sub_dims:
    #   it keeps every downstream op a matmul (MXU) and avoids batched
    #   gather/scatter lowerings, which compile catastrophically slowly on
    #   TPU (tens of seconds per shape vs <1s for the one-hot einsum).
    x_indices: Array | None
    x_values: Array  # [B, R, k] or [B, R, S]
    labels: Array  # [B, R]
    offsets: Array  # [B, R] base offsets (residuals added per train call)
    weights: Array  # [B, R]; 0 for padding rows
    row_ids: Array  # [B, R] int32 canonical row ids; 0 for padding (weight 0)
    proj: Array  # [B, S] int32 original feature id per subspace slot; -1 pad
    penalty_mask: Array  # [B, S] 1.0 for penalized slots (valid, non-intercept)
    valid_mask: Array  # [B, S] 1.0 for valid subspace slots
    intercept_slots: Array  # [B] int32 subspace slot of intercept; -1 if none

    @property
    def num_entities(self) -> int:
        return self.entity_codes.shape[0]

    @property
    def sub_dim(self) -> int:
        return self.proj.shape[-1]

    @property
    def is_dense(self) -> bool:
        return self.x_indices is None


# Subspace-dense materialization bound: up to this sub_dim the [B, R, S]
# dense slab (built by one-hot einsum, no gather/scatter) is both the
# fastest-compiling and the most MXU-friendly layout. Above it, the one-hot
# tensors get large and blocks stay in ELL form.
DENSE_SUB_DIM_MAX = 128
# Element budget for materialized one-hot operands (the dot_general operand
# is NOT fused away): beyond this, fall back to gather/scatter lowerings,
# which compile slowly but keep memory at the ELL slab's order.
ONE_HOT_ELEMENT_BUDGET = 1 << 28


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """One size bucket in lazy form: plan indices + raw-data references.

    ``materialize`` runs INSIDE the jitted solver, so the [B, R, k] slabs are
    gathered from HBM-resident raw arrays by the compiled program — they
    never exist on the host and never cross the host<->device link. The raw
    leaves (``raw``/``labels``/``offsets``/``weights``) are shared references
    to the GameDataset's arrays: every bucket's jit call sees the same
    buffers.
    """

    entity_codes: Array  # [B] int32
    row_ids: Array  # [B, R] int32 canonical rows; 0 for padding slots
    row_counts: Array  # [B] int32 valid rows per entity
    proj: Array  # [B, S] int32 sorted feature ids; -1 pads (trailing)
    intercept_slots: Array  # [B] int32; -1 if none
    raw: Features  # device-resident feature shard (Dense or Sparse ELL)
    raw_labels: Array  # [n] shared
    raw_offsets: Array  # [n] shared (base offsets)
    raw_weights: Array  # [n] shared

    @property
    def num_entities(self) -> int:
        return self.entity_codes.shape[0]

    @property
    def sub_dim(self) -> int:
        return self.proj.shape[-1]

    @property
    def dense_slab(self) -> bool:
        """Whether ``materialize`` builds this bucket's feature slab
        subspace-DENSE (``x_indices is None``): shapes alone decide."""
        b, r = self.row_ids.shape
        s = self.proj.shape[-1]
        if isinstance(self.raw, DenseFeatures):
            width = self.raw.x.shape[1]
        else:
            width = r * self.raw.indices.shape[1]
        return (
            s <= DENSE_SUB_DIM_MAX
            and b * width * s <= ONE_HOT_ELEMENT_BUDGET
        )

    def materialize(
        self, residuals: Array | None = None, *, gathered: dict | None = None,
    ) -> EntityBlocks:
        """Gather the bucket's training slabs (traceable; runs in jit).

        ``gathered``: the rows of a DENSE shard already in slab layout,
        ``labels`` / ``offsets`` / ``weights`` as ``[B, R]`` and ``x`` as
        ``[B, R, d]``; left out, they are gathered through ``row_ids``.
        The fused fit hands its home coordinate's over, moved there
        contiguously (``algorithm/fused_fit.py``). Padding slots may hold
        anything: every slab is masked by ``row_counts`` below.

        Returns an ``EntityBlocks`` whose ``offsets`` already include the
        coordinate-descent residuals. For sub_dims up to
        ``DENSE_SUB_DIM_MAX`` (within the one-hot element budget) the
        feature slab comes out subspace-DENSE, built by one-hot
        selections (comparisons feeding a contraction) — row gathers are
        plain ``jnp.take``; no batched gather/scatter, because those lower
        to pathologically slow-compiling programs on TPU while the one-hot
        contraction compiles in under a second. Wider subspaces (or
        over-budget one-hot operands) fall back to ELL form via gather
        lowerings: slower compiles, bounded memory.

        A dense shard's selection is a sum of elementwise products, NOT
        a ``dot_general``: a slab must hold the raw features exactly,
        whatever JAX's matmul precision is. As a matmul it held them
        rounded to bf16 at the TPU's default precision, and at
        ``highest`` the 12-slab program of a heavy-tailed GLMix came back
        from XLA with one slab wrong (every column of 3 119 of a
        [4 868, 256, 17] slab's entities a copy of the first; PERF.md
        section 6, PR 32).
        """
        b, r = self.row_ids.shape
        s = self.proj.shape[-1]
        rows = self.row_ids
        if gathered is None:
            gathered = {
                "labels": jnp.take(self.raw_labels, rows),
                "weights": jnp.take(self.raw_weights, rows),
                "offsets": jnp.take(self.raw_offsets, rows),
            }
            if isinstance(self.raw, DenseFeatures):
                gathered["x"] = jnp.take(self.raw.x, rows, axis=0)
        dtype = self.raw_weights.dtype
        row_mask = jnp.arange(r, dtype=jnp.int32)[None, :] < (
            self.row_counts[:, None]
        )
        labels = gathered["labels"]
        weights = jnp.where(row_mask, gathered["weights"], 0)
        offs = gathered["offsets"]
        if residuals is not None:
            offs = offs + jnp.take(residuals, rows)
        offs = jnp.where(row_mask, offs, 0)

        proj = self.proj
        valid = (proj >= 0).astype(dtype)
        iota_s = jnp.arange(s, dtype=jnp.int32)[None, :]
        penalty = jnp.where(
            iota_s == self.intercept_slots[:, None], 0.0, valid
        ).astype(dtype)

        if isinstance(self.raw, DenseFeatures):
            d = self.raw.x.shape[1]
            xr = gathered["x"]  # [B, R, d]
            if self.dense_slab:
                # Feature->slot one-hot per entity:
                # M[b, f, s] = proj[b,s] == f; -1 pads never match.
                onehot = (
                    proj[:, None, :]
                    == jnp.arange(d, dtype=proj.dtype)[None, :, None]
                ).astype(dtype)  # [B, d, S]
                x_values = jnp.sum(
                    xr[:, :, :, None] * onehot[:, None, :, :], axis=2)
                x_values = jnp.where(row_mask[:, :, None], x_values, 0)
                x_indices = None
            else:
                # Guarded fallback: LUT gather keeps memory at O(B d + B R d)
                # at the cost of a slow-compiling batched scatter/gather.
                pr = jnp.where(proj >= 0, proj, d)
                lut = jnp.full((b, d + 1), -1, jnp.int32)
                lut = lut.at[
                    jnp.arange(b, dtype=jnp.int32)[:, None], pr
                ].set(jnp.broadcast_to(iota_s, (b, s)))
                lut = lut[:, :d]  # [B, d]
                x_indices = jnp.broadcast_to(
                    jnp.maximum(lut, 0)[:, None, :], (b, r, d)
                )
                x_values = jnp.where(
                    (lut >= 0)[:, None, :] & row_mask[:, :, None], xr, 0
                )
        else:
            idx = jnp.take(self.raw.indices, rows, axis=0)  # [B, R, k]
            val = jnp.take(self.raw.values, rows, axis=0)
            val = jnp.where(row_mask[:, :, None], val, 0)
            k = idx.shape[-1]
            if self.dense_slab:
                # Slot one-hot: idx[b,r,k] == proj[b,s]; the contraction
                # densifies without any gather/scatter.
                onehot = (
                    idx[:, :, :, None] == proj[:, None, None, :]
                ).astype(dtype)  # [B, R, k, S]
                x_values = jnp.einsum("brk,brks->brs", val, onehot)
                x_indices = None
            else:
                # Guarded fallback: binary-search remap keeps ELL form
                # (O(B R k) memory, slow-compiling batched gathers).
                sentinel = jnp.iinfo(jnp.int32).max
                psort = jnp.where(proj >= 0, proj, sentinel)  # ascending
                flat = idx.reshape(b, r * k)
                slot = jax.vmap(jnp.searchsorted)(psort, flat)
                slot = jnp.minimum(slot, s - 1)
                hit = jnp.take_along_axis(psort, slot, axis=1) == flat
                slot = slot.reshape(b, r, k).astype(jnp.int32)
                ok = hit.reshape(b, r, k) & (val != 0)
                x_indices = jnp.where(ok, slot, 0)
                x_values = jnp.where(ok, val, 0)

        return EntityBlocks(
            entity_codes=self.entity_codes,
            x_indices=x_indices,
            x_values=x_values,
            labels=labels,
            offsets=offs,
            weights=weights,
            row_ids=jnp.where(row_mask, rows, 0),
            proj=proj,
            penalty_mask=penalty,
            valid_mask=valid,
            intercept_slots=self.intercept_slots,
        )

    # Eager conveniences so layout introspection (tests, debugging) works on
    # either block form. Each access re-gathers; not for hot paths.
    @property
    def weights(self) -> Array:
        return self.materialize().weights

    @property
    def labels(self) -> Array:
        return self.materialize().labels

    @property
    def offsets(self) -> Array:
        return self.materialize().offsets

    @property
    def x_values(self) -> Array:
        return self.materialize().x_values

    @property
    def x_indices(self) -> Array:
        return self.materialize().x_indices

    @property
    def valid_mask(self) -> Array:
        return self.materialize().valid_mask

    @property
    def penalty_mask(self) -> Array:
        return self.materialize().penalty_mask


@dataclasses.dataclass(frozen=True)
class RandomEffectDataset:
    """All device-resident state for one random-effect coordinate."""

    config: RandomEffectDataConfiguration
    num_entities: int
    entity_keys: tuple  # code -> raw entity key
    blocks: tuple  # active data, size-bucketed: EntityBlocks | BlockPlan
    max_sub_dim: int
    sub_dims: np.ndarray  # [E] host-side subspace dims
    proj_all: np.ndarray  # [E, max_sub_dim] original feature ids; -1 pad
    num_features: int  # original feature-space dim of the shard
    dtype: object = np.float32
    # Scoring state, lazy form: owning-entity code per canonical row plus
    # the device projector table; scores fuse against ``raw`` in HBM.
    score_codes: Array | None = None  # [n] int32
    raw: Features | None = None  # device raw shard (lazy mode)
    proj_dev: Array | None = None  # [E, max_sub_dim] device; -1 pad
    # Scoring state, materialized form (score_indices is None in lazy mode):
    score_indices: Array | None = None  # [n, k] int32 subspace-remapped
    score_values: Array | None = None  # [n, k]; 0 where outside the subspace
    # COO overflow tail for rows wider than the configured score-table cap
    # (empty arrays when uncapped); tail rows are sorted ascending.
    score_tail_rows: Array | None = None  # [t] int32
    score_tail_indices: Array | None = None  # [t] int32 subspace slots
    score_tail_values: Array | None = None  # [t]
    # Host-computed max tail entries per row: the static multiplicity
    # bound the tiled segment-reduce kernel needs (ops/segment_reduce).
    score_tail_mult: int | None = None
    # Host mirrors of small per-block plan arrays (one per ``blocks`` entry)
    # so per-fit bookkeeping never pulls from the device.
    block_codes_np: tuple = ()
    block_intercepts_np: tuple = ()
    # Per-bucket (grad_mult, hess_mult) WINDOW bounds for the direct ELL
    # gram route (ops/segment_reduce.ell_gram_supported documents the
    # currency), or None per bucket when the route cannot engage there
    # (small subspaces densify up front; over-budget pair passes).
    # Empty for lazy datasets — their slabs never exist on the host, so
    # there is nothing to bound at plan time.
    block_gram_mults: tuple = ()
    # [n] bool host mask: rows kept into some training block (built from the
    # planner's rows_flat, so no device work is needed to derive it).
    covered_np: np.ndarray | None = None
    # Lazy device placement: every plan array of the build rides ONE packed
    # int32 device buffer (one transfer-shape setup for the whole ingest,
    # ~65ms instead of ~30 x 65ms on remote links); the fused fit slices it
    # IN-TRACE (zero extra programs), while eager consumers split it once
    # through ``device_plans()``. ``blocks`` carries host-numpy plan leaves
    # when this is set.
    packed_view: object | None = None
    # Host integers of the plan this dataset was built from
    # (``_plan_counts``): the `fit` stage's attributes read them, so a
    # fit never counts anything. None on an AOT skeleton.
    plan_counts: dict | None = None
    # The inverse score map where no packed buffer carries it: the host
    # array of a build whose plan stayed on the host, then, once a mesh
    # has placed it (parallel/mesh.py shard_random_effect_dataset), its
    # row-sharded device array in the padded buckets' layout.
    score_inv: object | None = None

    @property
    def num_rows(self) -> int:
        """Canonical row count of the table this dataset was built from."""
        return int(self.score_codes.shape[0])

    def device_plans(self) -> tuple:
        """``blocks`` with DEVICE plan arrays (cached).

        Lazy-packed datasets split the packed buffer with one jitted
        program on first need — only the unfused training/scoring paths
        pay it; the fused fit slices the buffer inside its own programs.
        """
        cached = getattr(self, "_device_plans", None)
        if cached is not None:
            return cached
        first = self.blocks[0] if self.blocks else None
        if first is None or not isinstance(first, BlockPlan) or isinstance(
            first.entity_codes, jax.Array
        ):
            out = self.blocks  # already device-resident (or materialized)
        elif self.packed_view is not None:
            devs = self.packed_view.device_arrays()
            out = tuple(
                dataclasses.replace(
                    b,
                    entity_codes=devs[PLAN_ARRAYS_PER_BUCKET * i],
                    row_ids=devs[PLAN_ARRAYS_PER_BUCKET * i + 1],
                    row_counts=devs[PLAN_ARRAYS_PER_BUCKET * i + 2],
                    proj=devs[PLAN_ARRAYS_PER_BUCKET * i + 3],
                    intercept_slots=devs[PLAN_ARRAYS_PER_BUCKET * i + 4],
                )
                for i, b in enumerate(self.blocks)
            )
        else:
            leaves = jax.device_put([
                arr for b in self.blocks
                for arr in (b.entity_codes, b.row_ids, b.row_counts,
                            b.proj, b.intercept_slots)
            ])
            out = tuple(
                dataclasses.replace(
                    b,
                    entity_codes=leaves[PLAN_ARRAYS_PER_BUCKET * i],
                    row_ids=leaves[PLAN_ARRAYS_PER_BUCKET * i + 1],
                    row_counts=leaves[PLAN_ARRAYS_PER_BUCKET * i + 2],
                    proj=leaves[PLAN_ARRAYS_PER_BUCKET * i + 3],
                    intercept_slots=leaves[PLAN_ARRAYS_PER_BUCKET * i + 4],
                )
                for i, b in enumerate(self.blocks)
            )
        object.__setattr__(self, "_device_plans", out)
        return out

    @property
    def has_score_inv(self) -> bool:
        """Whether an inverse score map exists, read without a device."""
        return self.score_inv is not None or (
            self.packed_view is not None
            and len(self.packed_view) == packed_len_with_score_inv(
                len(self.blocks)))

    def score_inv_device(self) -> Array | None:
        """[n] int32 inverse score map (device), or None when absent.

        Maps each canonical row to its flat position in the concatenation
        of all buckets' [B, cap] score blocks followed by the passive-row
        score vector — the scatter-free scoring contract (trailing array
        of the packed plan layout, or the mesh's placed map)."""
        if isinstance(self.score_inv, jax.Array):
            return self.score_inv
        if self.packed_view is None or not self.has_score_inv:
            return None  # none, or a pre-score-map packed layout
        n_blocks = len(self.blocks)
        cached = getattr(self, "_score_inv_cache", None)
        if cached is None:
            cached = self.packed_view.device_arrays()[
                packed_score_inv_index(n_blocks)]
            object.__setattr__(self, "_score_inv_cache", cached)
        return cached

    def proj_device(self) -> Array:
        """[E, max_sub_dim] int32 device projector table (cached)."""
        if self.proj_dev is not None:
            return self.proj_dev
        cached = getattr(self, "_proj_dev_cache", None)
        if cached is None:
            if self.packed_view is not None:
                cached = self.packed_view.device_arrays()[
                    packed_proj_index(len(self.blocks))]
            else:
                cached = jnp.asarray(self.proj_all.astype(np.int32))
            object.__setattr__(self, "_proj_dev_cache", cached)
        return cached

    def device_blocks(self) -> tuple:
        """Training blocks with feature slabs materialized ON DEVICE (cached).

        Lazy ``BlockPlan`` buckets re-gather their [B, R, S] feature slab
        from the raw arrays on EVERY solve call; the slab is
        residual-independent, so materializing it once per dataset cuts the
        per-solve gather traffic to the [B, R] residual rows (~S x less).
        The one-time cost is HBM for the slabs — gated by
        ``_DEVICE_SLAB_BUDGET_BYTES`` A DEVICE, beyond which the lazy form
        is kept (gather per solve, bounded memory): a bucket whose entity
        axis is sharded over a mesh costs each device its shard of the
        slab, read from the placement of the bucket's own ``row_ids``.
        Materialization runs as one jitted program per bucket, so slabs
        never touch the host.
        """
        cached = getattr(self, "_device_blocks", None)
        if cached is not None:
            return cached
        out = []
        spent = 0  # the budget bounds the TOTAL cached bytes, not per block
        itemsize = np.dtype(self.dtype).itemsize
        for b in self.device_plans():
            if isinstance(b, BlockPlan):
                # The entities one device holds: the shard of a placed
                # plan array, the whole bucket anywhere else.
                sharding = getattr(b.row_ids, "sharding", None)
                bb, r = (
                    b.row_ids.shape if sharding is None
                    else sharding.shard_shape(b.row_ids.shape))
                s = b.proj.shape[-1]
                # Conservative estimate of the materialized layout: the
                # subspace-dense [B, R, S] slab, or the ELL fallback's
                # values + int32 slot indices at the raw row width.
                k_raw = (
                    b.raw.indices.shape[1]
                    if isinstance(b.raw, SparseFeatures)
                    else b.raw.x.shape[1]
                )
                slab_bytes = max(
                    itemsize * bb * r * s,
                    (itemsize + 4) * bb * r * min(k_raw, s),
                )
                if spent + slab_bytes <= _DEVICE_SLAB_BUDGET_BYTES:
                    spent += slab_bytes
                    b = _materialize_block_jit(b)
            out.append(b)
        out = tuple(out)
        object.__setattr__(self, "_device_blocks", out)
        return out

    def covered_row_partition(self):
        """(covered_mask [n] bool HOST array, passive_rows host int32 array).

        "Covered" rows appear in some training block (the active kept
        rows); "passive" rows — beyond the reservoir cap or owned by
        inactive entities with a trained model — still need scoring
        (RandomEffectDataset's activeData/passiveData split, :631-640).
        Cached per dataset.

        Derived ENTIRELY on the host: the planner's kept-row lists are host
        arrays, and the former device derivation (per-bucket eager
        iota/compare/scatter-max at 4M-row shapes) paid a string of
        one-off XLA compiles per fit (cost not measured on this chip).
        """
        cached = getattr(self, "_covered", None)
        if cached is not None:
            return cached
        assert self.is_lazy, "row partition is defined for lazy datasets"
        if self.covered_np is not None:
            covered = self.covered_np
        else:
            # Fallback for datasets built before covered_np existed (e.g.
            # dataclasses.replace-based shims in tests): one host pass over
            # the block plans. A real row with data weight 0 is still
            # covered and must score.
            covered = np.zeros(self.num_rows, dtype=bool)
            for b in self.blocks:
                rows = np.asarray(b.row_ids)
                counts = np.asarray(b.row_counts)
                r = rows.shape[1]
                valid = np.arange(r, dtype=np.int32)[None, :] < counts[:, None]
                covered[rows[valid]] = True
        passive = np.nonzero(~covered)[0].astype(np.int32)
        result = (covered, passive)
        object.__setattr__(self, "_covered", result)
        return result

    def passive_rows_device(self) -> Array | None:
        """The passive rows' numbers on the device (cached); None without
        any. Where ``score_codes`` spans a mesh they are sharded over its
        axis, padded to the device count with the last passive row again:
        the gather scorer never reads the padding's scores, and a scatter
        that sets them writes that row's own score twice."""
        cached = getattr(self, "_passive_dev", None)
        if cached is None:
            _, passive = self.covered_row_partition()
            if not passive.size:
                return None
            sharding = getattr(self.score_codes, "sharding", None)
            if sharding is not None and len(sharding.device_set) > 1:
                from photon_tpu.parallel.mesh import row_sharding

                mesh = sharding.mesh
                axis = mesh.axis_names[0]
                pad = (-passive.size) % mesh.shape[axis]
                cached = jax.device_put(
                    np.pad(passive, (0, pad), mode="edge"),
                    row_sharding(mesh, 1, axis_name=axis))
            else:
                cached = jnp.asarray(passive)
            object.__setattr__(self, "_passive_dev", cached)
        return cached

    def device_leaves(self) -> tuple:
        """Everything of this data set that lives on a device, as the
        unfused loop holds it: plans, cached slabs, scoring state."""
        return (
            self.device_plans(), self.device_blocks(), self.score_codes,
            self.raw, self.proj_dev,
            self.passive_rows_device() if self.is_lazy else None,
            self.score_inv_device() if self.is_lazy else None,
            self.score_indices, self.score_values, self.score_tail_rows,
            self.score_tail_indices, self.score_tail_values,
        )

    @property
    def is_lazy(self) -> bool:
        return self.score_indices is None

    def real_entity_mask(self, block_index: int) -> np.ndarray:
        """[B] bool — True for real entities of block ``block_index``.
        Mesh-sharded blocks pad the entity axis with inert entities whose
        code is ``num_entities`` (parallel/mesh.py
        shard_random_effect_dataset); this helper owns that convention."""
        return self.block_codes_np[block_index] < self.num_entities

    @property
    def num_active_entities(self) -> int:
        return sum(
            int(self.real_entity_mask(i).sum())
            for i in range(len(self.blocks))
        )


# Total-HBM budget for cached materialized feature slabs (device_blocks):
# datasets whose slabs exceed this stay lazy (gather per solve).
_DEVICE_SLAB_BUDGET_BYTES = 2 << 30


@jax.jit
def _materialize_block_jit(block):
    """One bucket's residual-independent slabs, gathered on device."""
    return block.materialize(None)


def _stable_type_seed(re_type: str) -> np.uint64:
    """Deterministic 64-bit seed from the REType name (the reference XORs
    ``REType.hashCode`` into the sample key, RandomEffectDataset.scala:510)."""
    import zlib

    return np.uint64(zlib.crc32(re_type.encode()) | (0x9E3779B9 << 32))


def _byteswap64_mix(uids: np.ndarray, seed: np.uint64) -> np.ndarray:
    """splitmix64-style deterministic hash of sample ids — the moral
    equivalent of the reference's ``byteswap64(hash ^ uid)`` reservoir keys:
    a fixed pseudo-random total order over samples, reproducible across
    re-ingests (SURVEY §5.2 determinism requirement)."""
    z = uids.astype(np.uint64) ^ seed
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _pearson_select(
    values: np.ndarray,  # [r, k] ELL values for one entity's active rows
    indices: np.ndarray,  # [r, k]
    labels: np.ndarray,  # [r]
    active_features: np.ndarray,  # sorted original ids
    keep: int,
    intercept_index: int | None,
    num_features: int,
) -> np.ndarray:
    """Rank an entity's active features by |Pearson corr with the label| and
    keep the top ``keep`` (intercept always kept).

    Reference: LocalDataset.filterFeaturesByPearsonCorrelationScore
    (data/LocalDataset.scala:103, stableComputePearsonCorrelationScore :132):
    features with near-constant columns get score ~0 except the intercept,
    which is always retained.
    """
    if keep >= active_features.size:
        return active_features
    r = labels.shape[0]
    pos = np.full(num_features, -1, dtype=np.int64)
    pos[active_features] = np.arange(active_features.size)
    sub = pos[indices]
    valid = (values != 0.0) & (sub >= 0)
    rows = np.broadcast_to(np.arange(r)[:, None], indices.shape)
    cols = np.zeros((r, active_features.size), dtype=np.float64)
    cols[rows[valid], sub[valid]] = values[valid]
    y = labels.astype(np.float64)
    yc = y - y.mean()
    xc = cols - cols.mean(axis=0, keepdims=True)
    num = xc.T @ yc
    den = np.sqrt((xc * xc).sum(axis=0) * (yc * yc).sum()) + 1e-12
    score = np.abs(num / den)
    if intercept_index is not None and pos[intercept_index] >= 0:
        score[pos[intercept_index]] = np.inf  # always keep the intercept
    order = np.argsort(-score, kind="stable")[:keep]
    return np.sort(active_features[order])


@dataclasses.dataclass(frozen=True)
class _ProjectorTable:
    """Flat per-entity subspace projectors (all host numpy).

    ``keys`` is ``entity * stride + feature`` for every (entity, feature)
    pair in any subspace, globally sorted — so ONE ``np.searchsorted``
    resolves any batch of pairs to subspace slots (``slot = pos -
    offsets[entity]``). This replaces the reference's per-entity
    LinearSubspaceProjector maps (projector/LinearSubspaceProjector.scala:36)
    with index arithmetic.
    """

    keys: np.ndarray  # [total] int64, sorted
    offsets: np.ndarray  # [E + 1] int64
    stride: int
    num_entities: int

    @property
    def sub_dims(self) -> np.ndarray:
        return np.diff(self.offsets)

    def features_of(self, e: int) -> np.ndarray:
        return self.keys[self.offsets[e]:self.offsets[e + 1]] % self.stride

    def lookup(
        self, codes: np.ndarray, feats: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Vectorized (entity, feature) -> (slot, found). Any shape; codes
        broadcastable to feats. Negative codes never match."""
        codes = np.broadcast_to(codes, feats.shape)
        keys = (
            np.maximum(codes, 0).astype(np.int64) * self.stride
            + feats.astype(np.int64)
        )
        if self.keys.size == 0:
            z = np.zeros(feats.shape, dtype=np.int64)
            return z, np.zeros(feats.shape, dtype=bool)
        pos = np.searchsorted(self.keys, keys)
        pos_c = np.minimum(pos, self.keys.size - 1)
        found = (self.keys[pos_c] == keys) & (codes >= 0)
        slot = pos_c - self.offsets[np.maximum(codes, 0)]
        return np.where(found, slot, 0), found

    @staticmethod
    def from_lists(
        projs: list[np.ndarray], stride: int
    ) -> "_ProjectorTable":
        e = len(projs)
        sizes = np.array([p.size for p in projs], dtype=np.int64)
        offsets = np.zeros(e + 1, dtype=np.int64)
        np.cumsum(sizes, out=offsets[1:])
        if e and offsets[-1]:
            ids = np.repeat(np.arange(e, dtype=np.int64), sizes)
            keys = ids * stride + np.concatenate(
                [p.astype(np.int64) for p in projs if p.size]
            )
        else:
            keys = np.empty(0, dtype=np.int64)
        return _ProjectorTable(keys, offsets, stride, e)


def _subset_rows_widened(
    ell_idx: np.ndarray,
    ell_val: np.ndarray,
    tail,  # (rows, indices, values) sorted by row, or None
    rows: np.ndarray,  # unique row ids to take
) -> tuple[np.ndarray, np.ndarray]:
    """ELL view of a row subset with the rows' COO tail entries appended as
    extra columns. Width grows only to the widest row IN THE SUBSET, so
    per-bucket / per-entity widening stays bounded by that group's own
    content — never by the single widest row of the whole table."""
    si = ell_idx[rows]
    sv = ell_val[rows]
    if tail is None:
        return si, sv
    tr, ti, tv = tail
    n = ell_idx.shape[0]
    m = rows.shape[0]
    inv = np.full(n, -1, dtype=np.int64)
    inv[rows] = np.arange(m)
    sel = inv[tr] >= 0
    if not sel.any():
        return si, sv
    # Global within-row rank of tail entries (tail rows sorted ascending).
    g_starts = np.searchsorted(tr, np.arange(n))
    g_rank = np.arange(tr.size) - g_starts[tr]
    r_of = inv[tr[sel]]
    kx = int(g_rank[sel].max()) + 1
    k0 = si.shape[1]
    out_i = np.zeros((m, k0 + kx), dtype=si.dtype)
    out_v = np.zeros((m, k0 + kx), dtype=sv.dtype)
    out_i[:, :k0] = si
    out_v[:, :k0] = sv
    out_i[r_of, k0 + g_rank[sel]] = ti[sel]
    out_v[r_of, k0 + g_rank[sel]] = tv[sel]
    return out_i, out_v


def _compact_left(
    slot: np.ndarray, val: np.ndarray, found: np.ndarray, k_out: int
) -> tuple[np.ndarray, np.ndarray]:
    """Left-compact valid ELL entries per row; truncate/pad to ``k_out``."""
    order = np.argsort(~found, axis=1, kind="stable")
    slot_c = np.take_along_axis(np.where(found, slot, 0), order, axis=1)
    val_c = np.take_along_axis(np.where(found, val, 0.0), order, axis=1)
    n, k = slot_c.shape
    if k_out > k:
        slot_c = np.pad(slot_c, ((0, 0), (0, k_out - k)))
        val_c = np.pad(val_c, ((0, 0), (0, k_out - k)))
    return slot_c[:, :k_out].astype(np.int32), val_c[:, :k_out]


# A dense shard with at most n / _FEW_ZEROS exact zeros is planned from the
# zeros alone; above that their sort would cost what the gather does.
_FEW_ZEROS = 16


def _dense_presence_by_scan(
    values: np.ndarray, codes: np.ndarray, kept: np.ndarray,
    all_rows_kept: bool,
) -> np.ndarray | None:
    """[E, d] per-entity feature presence of a dense shard from ONE
    streaming pass over it, or None where that cannot say. ``kept`` [E] is
    how many of an entity's rows count (0: it trains no model). With no
    exact zero in the shard, every entity with a row has every feature;
    with a few, and ``all_rows_kept`` (no cap binds, so each row of such
    an entity counts), an entity lacks a feature only where it has as many
    zeros there as rows."""
    n, d = values.shape
    zero = values == 0.0
    holes = int(np.count_nonzero(zero))
    if holes and not (all_rows_kept and holes * _FEW_ZEROS <= n):
        return None
    presence = np.repeat((kept > 0)[:, None], d, axis=1)
    if holes:
        at = np.flatnonzero(zero.reshape(-1))  # 1-D: a 2-D nonzero is 4x
        pairs, zeros = np.unique(
            codes[at // d] * d + at % d, return_counts=True)
        presence.reshape(-1)[pairs] = kept[pairs // d] > zeros
    return presence


def _dense_presence_by_gather(
    ell_val: np.ndarray, rows_p: np.ndarray, pair_codes: np.ndarray,
    num_entities: int,
) -> np.ndarray:
    """[E, d] per-entity feature presence of a dense shard: every row
    touches every column, so the per-entity active-feature union is one
    segment-OR over the kept rows ``rows_p`` in entity order
    (``pair_codes``: their entities, ascending) — no 17M-key sort (the
    reference amortizes the equivalent union across the cluster's
    foldByKey, RandomEffectDataset.scala:390-426)."""
    # Compare/gather in whichever order moves fewer bytes: when most
    # rows are kept, compare first (the bool matrix is 4x narrower
    # than the floats, so the fancy-index moves 4x fewer bytes); when
    # the reservoir cap discards most rows, gather the kept rows
    # first and compare only those.
    if rows_p.size * 2 > ell_val.shape[0]:
        present = (ell_val != 0.0)[rows_p]  # [m, d]
    else:
        present = ell_val[rows_p] != 0.0
    presence = np.zeros((num_entities, ell_val.shape[1]), dtype=bool)
    if present.all():
        # Fully dense kept rows (their zeros lie in rows not kept): every
        # entity's subspace is the whole feature set — skip the
        # segment-OR entirely.
        presence[np.unique(pair_codes)] = True
        return presence
    m = rows_p.shape[0]
    seg_starts = np.searchsorted(pair_codes, np.arange(num_entities))
    seg_ends = np.append(seg_starts[1:], m)
    nonempty = seg_starts < seg_ends
    # reduceat over the NONEMPTY starts only: consecutive empty
    # segments share their successor's start, so a naive clamp of
    # trailing starts to m-1 would shave the last row off the
    # preceding entity's union. Nonempty starts partition [0, m)
    # exactly (each spans to the next nonempty start).
    if nonempty.any():
        presence[nonempty] = np.logical_or.reduceat(
            present, seg_starts[nonempty], axis=0
        )
    return presence


@dataclasses.dataclass
class _Plan:
    """Host-side build plan: everything downstream layout needs, no loops."""

    codes: np.ndarray  # [n] int64 owning-entity code per row
    perm: np.ndarray  # [n] rows sorted by (entity, reservoir hash)
    sorted_codes: np.ndarray  # [n] codes[perm] (computed once; hoisted
    # out of the per-bucket row selection, which used to re-gather it
    # per bucket — the round-5 ingest-floor bisect's actual culprit)
    starts: np.ndarray  # [E]
    counts_full: np.ndarray  # [E] rows per entity
    counts: np.ndarray  # [E] kept (reservoir-capped) rows per entity
    keep_sorted: np.ndarray  # [n] bool mask in sorted order
    rank_sorted: np.ndarray  # [n] within-entity rank in sorted order
    active: np.ndarray  # [E] bool — trains a model
    table: _ProjectorTable
    proj_all: np.ndarray  # [E, S] feature ids, -1 pad
    sub_dims: np.ndarray  # [E]
    max_sub_dim: int
    intercept_slots_all: np.ndarray  # [E] int32; -1 none
    bucket_members: dict  # cap -> np.ndarray of entity codes
    num_features: int
    # How the pass engaged (the ``plan`` stage's attributes): the form of
    # ``group_rows`` (``sort`` where the cap binds), and whether one scan
    # of a dense shard answered the presence test (``scan``) or the kept
    # rows' presence was gathered in entity order (``gather``).
    grouping: str
    presence: str


def _plan_random_effect(
    game_data: GameDataset,
    config: RandomEffectDataConfiguration,
    *,
    intercept_index: int | None,
    extra_features: dict[int, np.ndarray] | None,
) -> _Plan:
    """Vectorized host planning pass (see module docstring, stage 1)."""
    tag = game_data.id_tags[config.random_effect_type]
    codes = tag.host_codes().astype(np.int64, copy=False)
    num_entities = tag.num_groups
    n = codes.shape[0]
    ell_idx, ell_val, num_features = game_data.host_shard_coo(
        config.feature_shard_id
    )
    labels_np = game_data.host_column("labels")

    # --- 1. deterministic reservoir cap: per entity keep the
    # active_data_upper_bound rows with smallest hash keys -----------------
    # Chunked passes (bincount partial sums, elementwise hash mixing) are
    # EXACT: the parallel planner's output is bit-identical to serial.
    counts_full = bincount_chunked(codes, num_entities).astype(
        np.int64, copy=False
    )
    upper = config.active_data_upper_bound
    lower = config.active_data_lower_bound
    cap_binds = upper is not None and bool(
        counts_full.max(initial=0) > upper
    )
    if cap_binds:
        uids = (
            game_data.uids.astype(np.int64)
            if game_data.uids is not None
            else np.arange(n, dtype=np.int64)
        )
        seed = _stable_type_seed(config.random_effect_type)
        order_keys = map_chunked(
            lambda u: _byteswap64_mix(u, seed),
            np.empty(n, dtype=np.uint64),
            uids,
        )
        # Group-by-entity, ordered by hash within the group. A two-key
        # lexsort costs two comparison sorts; packing (code, high hash
        # bits) into one int64 makes it one. (Still a comparison sort:
        # numpy's stable argsort is a radix sort for 8- and 16-bit keys
        # only.) Within-entity ties on the truncated hash
        # fall back to stable row order — still a deterministic uniform
        # reservoir (the hash bits kept exceed 2x log2(n) for any E below
        # 2^20, so ties are vanishing).
        code_bits = max(int(num_entities - 1).bit_length(), 1)
        if code_bits <= 40:
            hash_bits = 63 - code_bits
            key = map_chunked(
                lambda c, k: (c << hash_bits) | (
                    k >> np.uint64(64 - hash_bits)
                ).astype(np.int64),
                np.empty(n, dtype=np.int64),
                codes, order_keys,
            )
            perm = np.argsort(key, kind="stable")
        else:  # pathological entity counts: keep the exact two-key sort
            perm = np.lexsort((order_keys, codes))
        grouping = "sort"
    else:
        # No entity exceeds the cap (or no cap): the reservoir keeps every
        # row, so within-entity order is row order — group by entity
        # alone, by counting, and skip the hashing pass.
        perm, grouping = group_rows(codes, num_entities)
    # perm groups the rows by ascending entity on either branch, so what
    # follows the grouping streams over the counts.
    starts = np.cumsum(counts_full) - counts_full
    sorted_codes = np.repeat(
        np.arange(num_entities, dtype=np.int64), counts_full)
    counts = (
        counts_full if upper is None else np.minimum(counts_full, upper)
    )
    # Within-entity rank of each sorted position (0 = smallest hash key).
    rank_sorted = np.arange(n, dtype=np.int64) - np.repeat(
        starts, counts_full
    ) if n else np.empty(0, dtype=np.int64)
    keep_sorted = (
        rank_sorted < upper if cap_binds else np.ones(n, dtype=bool)
    )
    # Lower-bound filter: too-small entities train no model (their rows
    # still score via the zero row of the coefficient matrix).
    active = counts >= (lower or 1)

    # --- 2. per-entity subspace projectors (one global unique) ------------
    stride = num_features
    if extra_features:
        for arr in extra_features.values():
            a = np.asarray(arr)
            if a.size:
                stride = max(stride, int(a.max()) + 1)
    tail = game_data.host_shard_tail(config.feature_shard_id)
    proj_mask = keep_sorted & active[sorted_codes]
    has_rows = bool(proj_mask.any())
    dense_only = tail is None and isinstance(
        game_data.feature_shards[config.feature_shard_id], DenseFeatures
    )
    scan = False
    if has_rows and dense_only:
        # Ask a dense shard before gathering from it: where one streaming
        # pass answers, nothing needs the rows in entity order.
        presence = _dense_presence_by_scan(
            ell_val, codes, np.where(active, counts, 0), not cap_binds)
        scan = presence is not None
        if not scan:
            presence = _dense_presence_by_gather(
                ell_val, perm[proj_mask], sorted_codes[proj_mask],
                num_entities)
        rows_e, cols_f = np.nonzero(presence)
        # Row-major nonzero order == ascending key order (stride >= d).
        uniq = rows_e.astype(np.int64) * np.int64(stride) + cols_f
    elif has_rows:
        rows_p = perm[proj_mask]
        pair_codes = sorted_codes[proj_mask]
        iv = ell_idx[rows_p]
        present = ell_val[rows_p] != 0.0
        pair_keys = (
            np.broadcast_to(pair_codes[:, None], iv.shape)[present]
            * np.int64(stride)
            + iv[present].astype(np.int64)
        )
        if tail is not None:
            # Dual-ELL overflow entries contribute subspace features too.
            mask_rows = np.zeros(n, dtype=bool)
            mask_rows[rows_p] = True
            tr, ti, tv = tail
            sel = mask_rows[tr] & (tv != 0.0)
            if sel.any():
                tail_keys = (
                    codes[tr[sel]] * np.int64(stride)
                    + ti[sel].astype(np.int64)
                )
                pair_keys = np.concatenate([pair_keys, tail_keys])
        uniq = np.unique(pair_keys)
    else:
        uniq = np.empty(0, dtype=np.int64)

    needs_rework = bool(extra_features) or (
        config.features_to_samples_ratio is not None
    )
    if needs_rework:
        e_of = uniq // stride
        f_of = uniq % stride
        e_starts = np.searchsorted(e_of, np.arange(num_entities))
        e_ends = np.searchsorted(
            e_of, np.arange(num_entities), side="right"
        )
        projs = [f_of[e_starts[e]:e_ends[e]] for e in range(num_entities)]
        ratio = config.features_to_samples_ratio
        active_ids = np.nonzero(active)[0]
        for e in active_ids:
            act = projs[e]
            if ratio is not None:
                # Kept rows are the first counts[e] of the entity's sorted
                # span (rank < upper by construction) — O(rows_e), not a
                # full-array scan.
                rows_e = perm[starts[e]:starts[e] + counts[e]]
                keep = max(int(ratio * rows_e.size), 1)
                pe_i, pe_v = _subset_rows_widened(
                    ell_idx, ell_val, tail, rows_e
                )
                act = _pearson_select(
                    pe_v, pe_i, labels_np[rows_e],
                    act, keep, intercept_index, num_features,
                )
            # Prior-model support is unioned AFTER the Pearson filter:
            # features a warm-start model depends on must stay in the
            # subspace even when inactive/filtered in the current data
            # (RandomEffectDataset.scala:390-426 unions unconditionally).
            if extra_features and e in extra_features:
                act = np.union1d(
                    act, np.asarray(extra_features[e], dtype=act.dtype)
                )
            projs[e] = act
        table = _ProjectorTable.from_lists(projs, stride)
    else:
        offsets = np.zeros(num_entities + 1, dtype=np.int64)
        e_of = uniq // stride
        offsets[1:] = np.searchsorted(
            e_of, np.arange(num_entities), side="right"
        )
        table = _ProjectorTable(uniq, offsets, stride, num_entities)

    sub_dims = table.sub_dims
    max_sub_dim = max(int(sub_dims.max()) if num_entities else 1, 1)
    # proj_all scatter-fill: one flat write.
    proj_all = np.full((num_entities, max_sub_dim), -1, dtype=np.int64)
    if table.keys.size:
        row_of = np.repeat(np.arange(num_entities), sub_dims)
        col_of = np.arange(table.keys.size) - np.repeat(
            table.offsets[:-1], sub_dims
        )
        proj_all[row_of, col_of] = table.keys % stride

    # Intercept slot per entity (vectorized projector lookup).
    if intercept_index is not None and num_entities:
        slots, found = table.lookup(
            np.arange(num_entities),
            np.full(num_entities, intercept_index, dtype=np.int64),
        )
        intercept_slots_all = np.where(found, slots, -1).astype(np.int32)
    else:
        intercept_slots_all = np.full(num_entities, -1, dtype=np.int32)

    # --- 3. size-bucket membership ----------------------------------------
    bucket_members = _assign_buckets(
        counts, active, config.bucket_caps, config.min_bucket_entities
    )
    return _Plan(
        codes=codes,
        perm=perm,
        sorted_codes=sorted_codes,
        starts=starts,
        counts_full=counts_full,
        counts=counts,
        keep_sorted=keep_sorted,
        rank_sorted=rank_sorted,
        active=active,
        table=table,
        proj_all=proj_all,
        sub_dims=sub_dims,
        max_sub_dim=max_sub_dim,
        intercept_slots_all=intercept_slots_all,
        bucket_members=bucket_members,
        num_features=num_features,
        grouping=grouping,
        presence="scan" if scan else "gather",
    )


def _assign_buckets(
    counts: np.ndarray,
    active: np.ndarray,
    bucket_caps: tuple,
    min_bucket_entities: int = 0,
) -> dict:
    """cap -> member entity codes (ascending), shared between the planner
    and the ingest pipeline's shape oracle (``predict_plan_shapes``) so
    predicted block shapes can never drift from the built ones.

    Undersized buckets merge UPWARD into the next occupied cap: those
    with fewer than ``min_bucket_entities`` members, and always those
    whose merge pads the slabs by under 1 / ``_THIN_RUNG`` of the
    coordinate's slab rows. A warm refit then dispatches fewer, fatter
    programs instead of paying one solver instance per bucket-tail. The
    largest bucket never merges (nothing above holds its rows); merging
    only ever widens padding, never drops rows."""
    caps = np.asarray(sorted(bucket_caps), dtype=np.int64)
    active_ids = np.nonzero(active)[0]
    r = counts[active_ids]
    pos = np.searchsorted(caps, r)
    # Entities above the largest cap round up to the next power of two so
    # heavy-tailed size distributions share padded shapes (and jit compiles
    # of the solver) instead of one shape per distinct size.
    pow2 = np.left_shift(
        np.int64(1),
        np.ceil(np.log2(np.maximum(r, 1).astype(np.float64))).astype(
            np.int64
        ),
    )
    cap_of = np.where(pos < caps.size, caps[np.minimum(pos, caps.size - 1)],
                      pow2)
    members = {
        int(c): active_ids[cap_of == c] for c in np.unique(cap_of)
    }
    if len(members) > 1:
        floor = int(min_bucket_entities or 0)
        occupied = sorted(members)
        # The planner's own tail rule: a rung that saves the coordinate
        # under 1 / _THIN_RUNG of its slab rows does not pay for the
        # solver instance it is, and rides up.
        budget = sum(c * members[c].size for c in occupied) // _THIN_RUNG
        merged: dict[int, np.ndarray] = {}
        pending: np.ndarray | None = None
        for i, cap in enumerate(occupied):
            ids = members[cap]
            if pending is not None:
                ids = np.union1d(pending, ids)
                pending = None
            if i < len(occupied) - 1 and (
                ids.size < floor
                or ids.size * (occupied[i + 1] - cap) < budget
            ):
                pending = ids  # tail rides up into the next bucket
            else:
                # The largest bucket always lands here (its cap holds
                # every smaller entity's rows), so no tail is dropped.
                merged[cap] = ids
        members = merged
    return members


def _split_packed_impl(buf, shapes):
    out = []
    o = 0
    for s in shapes:
        n = int(np.prod(s)) if s else 1
        out.append(jax.lax.slice_in_dim(buf, o, o + n).reshape(s))
        o += n
    return tuple(out)


_split_packed = jax.jit(_split_packed_impl, static_argnames=("shapes",))


# Packed-plan layout contract (build_random_effect_dataset's lazy branch):
# PLAN_ARRAYS_PER_BUCKET arrays per bucket (members, row_ids, counts, proj,
# intercepts), then the [E, S] projector table, then the score gather map.
# Every consumer (device_plans, proj_device, score_inv_device, the fused
# materialization program) indexes through these helpers.
PLAN_ARRAYS_PER_BUCKET = 5


def packed_proj_index(n_blocks: int) -> int:
    return PLAN_ARRAYS_PER_BUCKET * n_blocks


def packed_score_inv_index(n_blocks: int) -> int:
    return PLAN_ARRAYS_PER_BUCKET * n_blocks + 1


def packed_len_with_score_inv(n_blocks: int) -> int:
    return PLAN_ARRAYS_PER_BUCKET * n_blocks + 2


class PackedPlanArrays:
    """Every plan array of a build in ONE granule-padded int32 device
    buffer.

    ~30 distinct plan-array shapes are ~30 host-to-device transfers,
    each with its own set-up (per-transfer cost not measured on this
    chip). One packed buffer is ONE transfer, and nothing else happens
    at ingest time:

    - the fused fit slices the buffer INSIDE its own traced programs
      (``slice_in_trace`` — zero additional XLA programs, zero transfers);
    - eager consumers (the unfused loop, tests, mesh sharding) split it
      once through ``device_arrays()``, paying the splitter program's
      compile only when that fallback path actually runs.
    """

    def __init__(self, buf: Array, shapes: tuple):
        self.buf = buf
        self.shapes = tuple(tuple(s) for s in shapes)
        sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        offs = np.cumsum([0] + sizes)
        self.offsets = tuple(int(o) for o in offs[:-1])
        self._split: tuple | None = None

    def __len__(self) -> int:
        return len(self.shapes)

    def view(self, lo: int, hi: int) -> "_PackedPlanView":
        return _PackedPlanView(self, lo, hi)

    @property
    def buffer(self) -> Array:
        return self.buf

    def static_slices(self) -> tuple:
        """((element offset, shape), ...) — THE layout contract for
        traced consumers: slice ``buffer`` at these static offsets inside
        a jit (the fused fit's materialization program does)."""
        return tuple(zip(self.offsets, self.shapes))

    def device_arrays(self) -> tuple:
        if self._split is None:
            self._split = _split_packed(self.buf, shapes=self.shapes)
        return self._split


class _PackedPlanView:
    """Subrange of a PackedPlanArrays (one dataset's arrays of a multi-
    coordinate batch transfer)."""

    def __init__(self, packed: PackedPlanArrays, lo: int, hi: int):
        self.packed = packed
        self.lo = lo
        self.hi = hi

    def __len__(self) -> int:
        return self.hi - self.lo

    @property
    def buffer(self) -> Array:
        return self.packed.buf

    def static_slices(self) -> tuple:
        return self.packed.static_slices()[self.lo:self.hi]

    def device_arrays(self) -> tuple:
        return self.packed.device_arrays()[self.lo:self.hi]


class _ListPlanArrays:
    """Plain per-array placement fallback for non-int32 plan arrays.

    ``static_slices`` is None: traced consumers fall back to taking the
    per-array device handles as operands."""

    static_slices = staticmethod(lambda: None)

    def __init__(self, arrays):
        self._arrays = None
        self._host = list(arrays)

    def __len__(self) -> int:
        return len(self._host)

    def view(self, lo: int, hi: int):
        out = _ListPlanArrays(self._host[lo:hi])
        return out

    def device_arrays(self) -> tuple:
        if self._arrays is None:
            self._arrays = tuple(jax.device_put(self._host))
        return self._arrays


def _plan_arrays_to_device(arrays: list[np.ndarray]):
    """Stage host plan arrays for device use: ONE packed buffer.

    Returns a PackedPlanArrays (or a _ListPlanArrays fallback when dtypes
    are mixed). Device placement goes through the ingest pipeline's
    chunked double-buffered transfer (``pipeline.packed_device_put``):
    below one chunk it is the legacy single staging fill + one
    ``device_put``; above it, granule-aligned chunks stream out
    asynchronously while the host fills the next chunk, and an
    in-trace concatenate restores the one contiguous buffer — the packed
    layout contract (``static_slices``) is byte-identical either way.
    """
    if any(a.dtype != np.int32 for a in arrays):
        return _ListPlanArrays(arrays)
    buf, shapes = packed_device_put(arrays)
    return PackedPlanArrays(buf, shapes)


def _bucket_rows(plan: _Plan, members: np.ndarray, cap: int):
    """Vectorized bucket row layout: (rows_flat, t_of, r_of, counts_b).

    ``rows_flat`` are the kept canonical rows of all member entities,
    grouped by entity (reservoir hash order within); ``t_of``/``r_of`` are
    their (bucket slot, within-entity rank) coordinates.

    Pure span arithmetic over the sorted order: each member entity's kept
    rows are exactly the FIRST ``counts[e]`` positions of its sorted span
    (the reservoir keeps the ``upper`` smallest hash keys, which the
    planner's sort puts first), so the selection is O(member rows). The
    previous form re-gathered ``codes[perm]`` and boolean-scanned the
    FULL row table once PER BUCKET — O(n x buckets) host passes that the
    round-5 ingest-floor bisect identified as the planner's real
    regression (the suspected ``cache_stats()`` dir scan never runs in
    the prepare path). Output is bit-identical (pinned by
    tests/test_ingest_pipeline.py against the full-scan reference).
    """
    m_starts = plan.starts[members]
    m_counts = plan.counts[members]
    total = int(m_counts.sum())
    if total == 0:
        empty = np.empty(0, dtype=np.int64)
        return empty, empty.copy(), empty.copy(), m_counts
    t_of = np.repeat(np.arange(members.size, dtype=np.int64), m_counts)
    span_base = np.cumsum(m_counts) - m_counts
    r_of = np.arange(total, dtype=np.int64) - span_base[t_of]
    rows_flat = plan.perm[m_starts[t_of] + r_of]
    return rows_flat, t_of, r_of, m_counts


def _score_table_arrays(
    codes: np.ndarray,
    ell_idx: np.ndarray,
    ell_val: np.ndarray,
    table: _ProjectorTable,
    width_cap: int | None,
    tail_in=None,  # input COO overflow of a DualEll shard, or None
):
    """Materialized scoring-table remap for ALL rows (vectorized).

    Returns (si, sv, tail) where tail is None when uncapped, else
    (rows, indices, values) sorted by row — entries beyond the slab cap
    stream into a COO tail so one dense row never inflates every row's slab
    (SURVEY §7.3 width hazard). ``tail_in`` overflow entries of a dual-ELL
    input stay in COO form end to end when a cap is set; only an uncapped
    build widens them into the rectangular output.
    """
    if tail_in is not None and width_cap is None:
        # Rectangular output was explicitly requested without a bound:
        # widen (old behavior). Width-hazard data should set the cap.
        ell_idx, ell_val = _subset_rows_widened(
            ell_idx, ell_val, tail_in, np.arange(codes.shape[0])
        )
        tail_in = None
    slot, found = table.lookup(codes[:, None], ell_idx)
    found = found & (ell_val != 0.0)
    k_comp = max(int(found.sum(axis=1).max(initial=0)), 1)
    if width_cap is None:
        si, sv = _compact_left(slot, ell_val, found, k_comp)
        return si, sv, None
    k_slab = max(min(width_cap, k_comp), 1)
    si_f, sv_f = _compact_left(slot, ell_val, found, k_comp)
    si, sv = si_f[:, :k_slab], sv_f[:, :k_slab]
    over_i, over_v = si_f[:, k_slab:], sv_f[:, k_slab:]
    mask = over_v != 0.0
    parts_r, parts_i, parts_v = [], [], []
    if mask.any():
        row_of = np.broadcast_to(
            np.arange(codes.shape[0], dtype=np.int64)[:, None], mask.shape
        )
        parts_r.append(row_of[mask])
        parts_i.append(over_i[mask].astype(np.int64))
        parts_v.append(over_v[mask])
    if tail_in is not None:
        tr_in, ti_in, tv_in = tail_in
        slot_t, found_t = table.lookup(codes[tr_in], ti_in)
        ok = found_t & (tv_in != 0.0)
        if ok.any():
            parts_r.append(tr_in[ok].astype(np.int64))
            parts_i.append(slot_t[ok].astype(np.int64))
            parts_v.append(tv_in[ok])
    if parts_r:
        tr = np.concatenate(parts_r)
        ti = np.concatenate(parts_i)
        tv = np.concatenate(parts_v)
        o = np.argsort(tr, kind="stable")  # segment_sum wants sorted rows
        tail = (tr[o], ti[o], tv[o])
    else:
        tail = (
            np.empty(0, np.int64), np.empty(0, np.int64),
            np.empty(0, ell_val.dtype),
        )
    return si, sv, tail


def remap_for_scoring(
    game_data: GameDataset,
    *,
    re_type: str,
    feature_shard_id: str,
    entity_keys: tuple,
    proj_all: np.ndarray,  # [E, S] original feature ids; -1 pad
    dtype=None,
    width_cap: int | None = None,
) -> tuple[Array, Array, Array, tuple[Array, Array, Array] | None]:
    """Remap an arbitrary GameDataset's rows into trained entity subspaces.

    Returns (codes, indices, values, tail) consumable by
    ``score_entity_table_with_tail`` — the materialized scoring path for
    validation / test data (RandomEffectModel.score :70 joins new data by
    REId; entities unseen at training time contribute score 0, matching the
    reference's left-join semantics where rows without a model get no
    score). ``tail`` is None when ``width_cap`` is unset, else device
    (rows, indices, values) arrays for the capped table's COO overflow.
    """
    if dtype is None:
        dtype = game_data.labels.dtype
    codes = scoring_codes(game_data, re_type, entity_keys)
    ell_idx, ell_val, num_features = game_data.host_shard_coo(
        feature_shard_id
    )
    table = projector_table_from_proj_all(proj_all, num_features)
    si, sv, tail = _score_table_arrays(
        codes, ell_idx, ell_val, table, width_cap,
        tail_in=game_data.host_shard_tail(feature_shard_id),
    )
    # Unseen entities: clamp the code and zero the values -> score 0.
    unseen = codes < 0
    sv[unseen] = 0.0
    codes_safe = np.maximum(codes, 0)
    tail_out = None
    if tail is not None:
        tr, ti, tv = tail
        # Invariant: negative-code rows never produce projector hits, so
        # the tail only holds rows of KNOWN entities.
        assert not unseen[tr].any()
        tail_out = (
            jnp.asarray(tr.astype(np.int32)),
            jnp.asarray(ti.astype(np.int32)),
            jnp.asarray(tv, dtype=dtype),
        )
    return (
        jnp.asarray(codes_safe.astype(np.int32)),
        jnp.asarray(si),
        jnp.asarray(sv, dtype=dtype),
        tail_out,
    )


def scoring_codes(
    game_data: GameDataset, re_type: str, entity_keys: tuple
) -> np.ndarray:
    """[n] trained-entity code per row of ``game_data`` (-1 = unseen)."""
    tag = game_data.id_tags[re_type]
    vocab = {str(k): i for i, k in enumerate(entity_keys)}
    code_map = np.array(
        [vocab.get(str(k), -1) for k in tag.inverse], dtype=np.int64
    )
    if len(tag.inverse) and len(entity_keys) and (code_map < 0).all():
        import warnings

        warnings.warn(
            f"scoring remap({re_type!r}): none of {len(tag.inverse)} "
            f"dataset entities match the {len(entity_keys)} model entities "
            "— every random-effect score will be 0",
            stacklevel=2,
        )
    return code_map[tag.host_codes()]


def projector_table_from_proj_all(
    proj_all: np.ndarray, num_features: int
) -> _ProjectorTable:
    """Rebuild the flat projector table from a [E, S] proj matrix.

    A trained model's projectors may reference feature ids beyond a new
    dataset's shard dimension; the stride covers both so unknown features
    are dropped, not crashed on."""
    e, s = proj_all.shape if proj_all.ndim == 2 else (0, 0)
    stride = num_features
    if proj_all.size:
        stride = max(stride, int(proj_all.max(initial=0)) + 1)
    valid = proj_all >= 0
    sizes = valid.sum(axis=1).astype(np.int64) if e else np.empty(0, np.int64)
    offsets = np.zeros(e + 1, dtype=np.int64)
    np.cumsum(sizes, out=offsets[1:])
    if e and offsets[-1]:
        row_of = np.repeat(np.arange(e, dtype=np.int64), sizes)
        keys = row_of * stride + proj_all[valid].astype(np.int64)
    else:
        keys = np.empty(0, dtype=np.int64)
    return _ProjectorTable(keys, offsets, stride, e)


@dataclasses.dataclass
class PendingRandomEffectDataset:
    """A lazy-layout build whose device placement is deferred.

    ``flat`` lists the int32 plan arrays awaiting transfer; ``finalize``
    consumes their device arrays (same order) and returns the dataset. The
    estimator batches every coordinate's transfer into ONE packed push —
    one transfer-path setup and one cached split program for the whole fit
    instead of one per coordinate (`_plan_arrays_to_device`).
    """

    flat: list
    finalize: object  # Callable[[list], RandomEffectDataset]


def predict_plan_shapes(
    game_data: GameDataset,
    config: RandomEffectDataConfiguration,
) -> dict | None:
    """Predict every padded block shape of a build from configs + entity
    counts alone — the ingest pipeline's shape oracle.

    The full planner needs the expensive sorted passes; the SHAPES need
    only the per-entity row counts (one chunked bincount) plus the dense
    shard width: a fully dense shard's active entities all span the whole
    feature set, so every bucket's projector width is ``d``. That lets the
    estimator kick off the fused-fit AOT compile while planning is still
    running. Returns None when shapes can't be predicted without planning
    (sparse shards, Pearson filtering, width caps, wide subspaces) — and a
    WRONG prediction (a dense shard with exact zeros) only wastes the
    background compile: the real fit falls back to the normal jit path,
    never to wrong results.
    """
    feats = game_data.feature_shards.get(config.feature_shard_id)
    if not isinstance(feats, DenseFeatures):
        return None
    if config.features_to_samples_ratio is not None:
        return None
    if config.score_table_width_cap is not None:
        return None
    d = int(feats.x.shape[1])
    if d > DENSE_SUB_DIM_MAX:
        return None  # auto-lazy would refuse; the fused path needs lazy
    tag = game_data.id_tags[config.random_effect_type]
    codes = tag.host_codes()
    num_entities = tag.num_groups
    n = int(codes.shape[0])
    counts_full = bincount_chunked(codes, num_entities).astype(
        np.int64, copy=False
    )
    upper = config.active_data_upper_bound
    lower = config.active_data_lower_bound
    counts = (
        counts_full if upper is None else np.minimum(counts_full, upper)
    )
    active = counts >= (lower or 1)
    bucket_members = _assign_buckets(
        counts, active, config.bucket_caps, config.min_bucket_entities
    )
    any_active = bool(active.any())
    max_sub_dim = d if any_active else 1
    buckets = [
        (cap, int(bucket_members[cap].size), d)
        for cap in sorted(bucket_members)
    ]
    shapes: list[tuple] = []
    for cap, b, s in buckets:
        shapes += [(b,), (b, cap), (b,), (b, s), (b,)]
    shapes.append((num_entities, max_sub_dim))  # projector table
    shapes.append((n,))  # inverse score map
    kept_total = int(counts[active].sum())
    return dict(
        num_entities=num_entities,
        num_rows=n,
        num_features=d,
        max_sub_dim=max_sub_dim,
        buckets=buckets,
        packed_shapes=tuple(shapes),
        kept_total=kept_total,
    )


def skeleton_random_effect_dataset(
    game_data: GameDataset,
    config: RandomEffectDataConfiguration,
) -> RandomEffectDataset | None:
    """A shape-faithful stand-in for one coordinate's lazy dataset.

    Plan leaves are zero host arrays at the PREDICTED shapes; the raw
    feature / label / offset / weight leaves are the REAL device arrays
    (already resident from ``make_game_dataset``), and the packed view
    carries a ``ShapeDtypeStruct`` buffer — enough for ``FusedFit`` to
    trace, lower, and AOT-compile the exact production programs while the
    real planner is still running. Never used to train: only the compiled
    executables (keyed by the fused static key + operand avals) survive.
    """
    import jax as _jax

    from photon_tpu.data.pipeline import padded_len

    pred = predict_plan_shapes(game_data, config)
    if pred is None:
        return None
    tag = game_data.id_tags[config.random_effect_type]
    feats = game_data.feature_shards[config.feature_shard_id]
    e = pred["num_entities"]
    n = pred["num_rows"]
    s_all = pred["max_sub_dim"]
    blocks = []
    for cap, b, s in pred["buckets"]:
        blocks.append(BlockPlan(
            entity_codes=np.zeros(b, np.int32),
            row_ids=np.zeros((b, cap), np.int32),
            row_counts=np.zeros(b, np.int32),
            proj=np.zeros((b, s), np.int32),
            intercept_slots=np.zeros(b, np.int32),
            raw=feats,
            raw_labels=game_data.labels,
            raw_offsets=game_data.offsets,
            raw_weights=game_data.weights,
        ))
    total = sum(
        int(np.prod(sh)) if sh else 1 for sh in pred["packed_shapes"]
    )
    n_pad = padded_len(total)
    packed = PackedPlanArrays(
        _jax.ShapeDtypeStruct((n_pad,), np.int32), pred["packed_shapes"]
    )
    covered = np.zeros(n, dtype=bool)
    covered[:pred["kept_total"]] = True
    sub_dims = np.zeros(e, dtype=np.int64)
    sub_dims[:] = pred["num_features"]
    return RandomEffectDataset(
        config=config,
        num_entities=e,
        entity_keys=tag.inverse,
        blocks=tuple(blocks),
        max_sub_dim=s_all,
        sub_dims=sub_dims,
        proj_all=np.full((e, s_all), -1, dtype=np.int64),
        num_features=pred["num_features"],
        dtype=game_data.labels.dtype,
        score_codes=tag.codes,
        raw=feats,
        proj_dev=None,
        block_codes_np=tuple(
            np.zeros(b, np.int32) for _, b, _ in pred["buckets"]
        ),
        block_intercepts_np=tuple(
            np.zeros(b, np.int32) for _, b, _ in pred["buckets"]
        ),
        covered_np=covered,
        packed_view=packed,
    )


def share_skeleton_packing(datasets: dict[str, object]) -> dict:
    """Re-view the standalone random-effect skeletons among
    ``datasets`` (other values pass through) onto ONE predicted packed
    buffer.

    The real build places EVERY coordinate's plan arrays with one packed
    transfer (``GameEstimator._resolve_pending``): coordinate k's arrays
    sit after coordinates 0..k-1's, and all coordinates share the one
    buffer. The materialization program slices that buffer at STATIC
    offsets, so a skeleton that kept its standalone layout (offsets
    from 0, a buffer of its own) compiles a program that is right for
    the first random-effect coordinate only — and below one transfer
    granule the buffer avals still match, so nothing would refuse it.
    Same order as the real build: the dict's (coordinate config) order.
    """
    import jax as _jax

    from photon_tpu.data.pipeline import padded_len

    shapes: list[tuple] = []
    spans: dict[str, tuple[int, int]] = {}
    for cid, ds in datasets.items():
        if isinstance(ds, RandomEffectDataset):
            spans[cid] = (len(shapes), len(shapes) + len(ds.packed_view))
            shapes.extend(ds.packed_view.shapes)
    total = sum(int(np.prod(sh)) if sh else 1 for sh in shapes)
    packed = PackedPlanArrays(
        _jax.ShapeDtypeStruct((padded_len(total),), np.int32), shapes
    )
    return {
        cid: (
            dataclasses.replace(ds, packed_view=packed.view(*spans[cid]))
            if cid in spans else ds
        )
        for cid, ds in datasets.items()
    }


def _gram_window_bounds(
    bi: np.ndarray, bv: np.ndarray, sub_dim: int
) -> tuple | None:
    """HOST (grad_mult, hess_mult) window bounds for one bucket's ELL
    slabs — the static coverage key of the direct gram route
    (algorithm/random_effect._solve_direct_gram) — or None when that
    route can never engage for this bucket.

    Counts only NONZERO entries (the device side remaps zero products to
    the drop segment, so device counts are always <= these), binned into
    the kernel's output windows via ``segment_reduce.window_counts_np``.
    A uniform per-segment bound would be useless: the intercept slot
    co-occurs with every row of its entity, putting the per-SEGMENT
    multiplicity at the row count while whole windows stay cheap.
    Entity-axis PADDING (parallel/mesh) appends inert zero-weight
    entities after these ids, so the bounds survive mesh sharding.
    """
    b, cap, k = bi.shape
    s = int(sub_dim)
    if (
        s <= DENSE_SUB_DIM_MAX
        and b * cap * k * s <= ONE_HOT_ELEMENT_BUDGET
    ):
        return None  # bucket densifies up front; the gram route is moot
    if b * cap * k * k > segment_reduce.GRAM_ELEMENT_BUDGET:
        return None  # pair pass over budget on device and host alike
    nz = bv != 0.0
    grad_counts = hess_counts = None
    # Chunk over the entity axis: the pair-id tensor is
    # [chunk, cap, k, k] int64, a bounded transient for any bucket size.
    step = max(1, (1 << 22) // max(cap * k * k, 1))
    for lo in range(0, b, step):
        hi = min(lo + step, b)
        ent = np.arange(lo, hi, dtype=np.int64)[:, None, None]
        nzc = nz[lo:hi]
        bic = bi[lo:hi].astype(np.int64)
        gids = (ent * s + bic)[nzc]
        gc = segment_reduce.window_counts_np(gids, b * s)
        grad_counts = gc if grad_counts is None else grad_counts + gc
        pair_nz = nzc[:, :, :, None] & nzc[:, :, None, :]
        pids = (
            ent[..., None] * (s * s)
            + bic[:, :, :, None] * s
            + bic[:, :, None, :]
        )[pair_nz]
        hc = segment_reduce.window_counts_np(pids, b * s * s)
        hess_counts = hc if hess_counts is None else hess_counts + hc
    return (
        segment_reduce.window_bound_from_counts(grad_counts.max()),
        segment_reduce.window_bound_from_counts(hess_counts.max()),
    )


def build_random_effect_dataset(
    game_data: GameDataset,
    config: RandomEffectDataConfiguration,
    *,
    intercept_index: int | None = None,
    extra_features: dict[int, np.ndarray] | None = None,
    dtype=None,
    lazy: bool | None = None,
    defer_transfer: bool = False,
) -> RandomEffectDataset:
    """One-shot host-side ingest of a random-effect coordinate's data.

    ``extra_features`` maps entity code -> original feature ids that must be
    in the entity's subspace even if inactive in the data — the prior-model
    support used for warm-start/incremental training
    (RandomEffectDataset.scala:390-426 unions the existing model's features).

    ``lazy`` (default: auto) selects the device layout: lazy BlockPlans that
    materialize inside the jitted solver (Dense/Sparse shards), or fully
    materialized EntityBlocks + scoring table (always used for
    ``DualEllFeatures`` shards, whose COO tail is not row-gatherable).
    """
    requested_dtype = dtype
    if dtype is None:
        dtype = game_data.labels.dtype
    feats = game_data.feature_shards[config.feature_shard_id]
    lazy_capable = isinstance(feats, (DenseFeatures, SparseFeatures))
    # The lazy layout trains straight off the raw device arrays, so it
    # cannot honor a dtype different from the data's.
    dtype_matches = (
        requested_dtype is None
        or jnp.dtype(requested_dtype) == jnp.dtype(game_data.labels.dtype)
    )
    with PIPELINE_STATS.stage("plan") as plan_stage:
        plan = _plan_random_effect(
            game_data, config,
            intercept_index=intercept_index, extra_features=extra_features,
        )
        # How the bucket ladder and the grouping engaged, for a trace's
        # reader: slab_rows over real_rows is this coordinate's padding
        # ratio.
        buckets = [
            [cap, int(plan.bucket_members[cap].size)]
            for cap in sorted(plan.bucket_members)
        ]
        plan_stage.attrs = dict(
            buckets=buckets,
            slab_rows=sum(cap * b for cap, b in buckets),
            real_rows=int(plan.counts[plan.active].sum()),
            grouping=plan.grouping,
            presence=plan.presence,
        )
    if lazy is None:
        # An explicit score-table width cap is a signal that max_sub_dim is
        # dominated by heavy entities (SURVEY §7.3): the lazy scorer's
        # [n, S] gather intermediates would recreate exactly the hazard the
        # cap bounds, so honor it with the materialized dual-ELL table.
        # Very wide subspaces likewise stay materialized: the lazy path's
        # one-hot densification is sized for small sub_dims.
        lazy = (
            lazy_capable
            and dtype_matches
            and config.score_table_width_cap is None
            and plan.max_sub_dim <= DENSE_SUB_DIM_MAX
        )
    if lazy and not lazy_capable:
        raise TypeError(
            "lazy random-effect layout requires Dense or Sparse (ELL) "
            f"features, got {type(feats).__name__}"
        )
    if lazy and not dtype_matches:
        raise ValueError(
            f"lazy random-effect layout cannot retype the raw data "
            f"({game_data.labels.dtype} -> {requested_dtype}); pass "
            "lazy=False or build the GameDataset in the target dtype"
        )
    tag = game_data.id_tags[config.random_effect_type]
    num_entities = tag.num_groups

    # Per-bucket plan arrays (all vectorized scatters). Buckets are
    # independent, so they build concurrently on the chunk pool; the
    # ordered wait keeps bucket_host in ascending-cap order, identical to
    # the serial loop.
    def _build_bucket(cap: int) -> dict:
        members = plan.bucket_members[cap]
        rows_flat, t_of, r_of, counts_b = _bucket_rows(plan, members, cap)
        b = members.size
        brow = np.zeros((b, cap), dtype=np.int32)
        brow[t_of, r_of] = rows_flat
        sub = plan.sub_dims[members]
        s = max(int(sub.max(initial=0)), 1)
        bproj = plan.proj_all[members][:, :s].astype(np.int32)
        return dict(
            cap=cap,
            members=members.astype(np.int32),
            brow=brow,
            counts=counts_b.astype(np.int32),
            proj=bproj,
            intercepts=plan.intercept_slots_all[members],
            rows_flat=rows_flat,
            t_of=t_of,
            r_of=r_of,
        )

    with PIPELINE_STATS.stage("pack"):
        # consume_futures: every bucket thunk's exception is observed
        # even when an earlier bucket already failed.
        bucket_host = consume_futures(
            [
                chunk_executor.submit(_build_bucket, cap)
                for cap in sorted(plan.bucket_members)
            ]
        )

    covered_np = np.zeros(plan.codes.shape[0], dtype=bool)
    for bh in bucket_host:
        covered_np[bh["rows_flat"]] = True

    ell_idx = ell_val = ell_tail = None
    if not lazy:
        ell_idx, ell_val, _ = game_data.host_shard_coo(
            config.feature_shard_id
        )
        ell_tail = game_data.host_shard_tail(config.feature_shard_id)
    labels_np = game_data.host_column("labels")
    offsets_np = game_data.host_column("offsets")
    weights_np = game_data.host_column("weights")

    if lazy:
        # Inverse score map: canonical row -> flat position in the
        # concatenation of all buckets' [B, cap] score blocks followed by
        # the passive-row score vector. Scoring then becomes ONE gather —
        # scatter-adds of bucket scores into [n] cost ~4x more on TPU
        # (measured 51ms vs 13ms per pass at bench shapes). Lazy-path
        # only: the materialized layout scores through its remapped table.
        score_inv_np = np.empty(plan.codes.shape[0], dtype=np.int32)
        base = 0
        for bh in bucket_host:
            cap = bh["brow"].shape[1]
            score_inv_np[bh["rows_flat"]] = (
                base + bh["t_of"] * cap + bh["r_of"]
            ).astype(np.int32)
            base += bh["brow"].size
        passive_rows = np.nonzero(~covered_np)[0]
        # base counts PADDED bucket blocks (B*cap per bucket, larger than
        # the row count), so it can cross 2^31 well before n does; past
        # that the int32 map silently wraps and corrupts scoring.
        if base + passive_rows.size >= 2**31:
            raise OverflowError(
                "flat score layout has "
                f"{base + passive_rows.size} elements, which overflows the "
                "int32 inverse score map; shard the random effect wider "
                "(smaller buckets) or reduce score_table_width_cap"
            )
        score_inv_np[passive_rows] = base + np.arange(
            passive_rows.size, dtype=np.int32)

        # ONE batched device_put for every plan array of every bucket.
        # Layout contract (device_plans / proj_device / the fused mat
        # program all index it): 5 arrays per bucket, then the [E, S]
        # projector table at 5*n_buckets, then the score gather map.
        flat: list[np.ndarray] = []
        for bh in bucket_host:
            flat += [bh["members"], bh["brow"], bh["counts"], bh["proj"],
                     bh["intercepts"]]
        proj_dev_np = plan.proj_all.astype(np.int32)
        flat.append(proj_dev_np)
        flat.append(score_inv_np)

        def finalize(devs):
            return _finalize_lazy(
                devs, bucket_host, feats, game_data, config, num_entities,
                tag, plan, dtype, covered_np, score_inv_np,
            )

        if defer_transfer:
            return PendingRandomEffectDataset(flat=flat, finalize=finalize)
        return finalize(_plan_arrays_to_device(flat))

    # ---- materialized layout (DualEll shards, introspection) -------------
    blocks = []
    gram_mults_list = []
    for bh in bucket_host:
        members = bh["members"]
        b, cap = bh["brow"].shape
        rows_flat, t_of, r_of = bh["rows_flat"], bh["t_of"], bh["r_of"]
        s = bh["proj"].shape[1]
        # Remap every member row's ELL entries in one vectorized pass
        # (dual-ELL tails widen only to this bucket's own widest row).
        wi, wv = _subset_rows_widened(ell_idx, ell_val, ell_tail, rows_flat)
        slot, found = plan.table.lookup(plan.codes[rows_flat][:, None], wi)
        found = found & (wv != 0.0)
        k = max(int(found.sum(axis=1).max(initial=0)), 1)
        ri, rv = _compact_left(slot, wv, found, k)
        bi = np.zeros((b, cap, k), dtype=np.int32)
        bv = np.zeros((b, cap, k), dtype=ell_val.dtype)
        bi[t_of, r_of] = ri
        bv[t_of, r_of] = rv
        # Static coverage bounds for the direct ELL gram route (priced
        # here, at plan time, like score_tail_mult below): None when
        # this bucket can never take it.
        gram_mults_list.append(_gram_window_bounds(bi, bv, s))
        bl = np.zeros((b, cap), dtype=labels_np.dtype)
        bo = np.zeros((b, cap), dtype=offsets_np.dtype)
        bw = np.zeros((b, cap), dtype=weights_np.dtype)
        brow_arr = bh["brow"]
        bl[t_of, r_of] = labels_np[rows_flat]
        bo[t_of, r_of] = offsets_np[rows_flat]
        bw[t_of, r_of] = weights_np[rows_flat]
        bint = bh["intercepts"]
        slot_iota = np.arange(s)[None, :]
        valid = (slot_iota < plan.sub_dims[members][:, None]).astype(
            np.float32
        )
        penalty = valid.copy()
        has_int = bint >= 0
        penalty[has_int, bint[has_int]] = 0.0
        blocks.append(EntityBlocks(
            entity_codes=jnp.asarray(members),
            x_indices=jnp.asarray(bi),
            x_values=jnp.asarray(bv, dtype=dtype),
            labels=jnp.asarray(bl, dtype=dtype),
            offsets=jnp.asarray(bo, dtype=dtype),
            weights=jnp.asarray(bw, dtype=dtype),
            row_ids=jnp.asarray(brow_arr),
            proj=jnp.asarray(bh["proj"]),
            penalty_mask=jnp.asarray(penalty, dtype=dtype),
            valid_mask=jnp.asarray(valid, dtype=dtype),
            intercept_slots=jnp.asarray(bint),
        ))

    si, sv, tail = _score_table_arrays(
        plan.codes, ell_idx, ell_val, plan.table,
        config.score_table_width_cap, tail_in=ell_tail,
    )
    tail_r = tail_i = tail_v = None
    tail_mult = None
    if tail is not None:
        tail_r = jnp.asarray(tail[0].astype(np.int32))
        tail_i = jnp.asarray(tail[1].astype(np.int32))
        tail_v = jnp.asarray(tail[2], dtype=dtype)
        # Static per-row multiplicity bound for the tiled segment-reduce
        # (tail rows are sorted, so one bincount prices the worst row).
        tail_mult = (
            int(np.bincount(tail[0]).max()) if tail[0].size else 1
        )

    return RandomEffectDataset(
        config=config,
        num_entities=num_entities,
        entity_keys=tag.inverse,
        blocks=tuple(blocks),
        max_sub_dim=plan.max_sub_dim,
        sub_dims=plan.sub_dims,
        proj_all=plan.proj_all,
        num_features=plan.num_features,
        dtype=dtype,
        score_codes=jnp.asarray(plan.codes.astype(np.int32)),
        score_indices=jnp.asarray(si),
        score_values=jnp.asarray(sv, dtype=dtype),
        score_tail_rows=tail_r,
        score_tail_indices=tail_i,
        score_tail_values=tail_v,
        score_tail_mult=tail_mult,
        block_codes_np=tuple(bh["members"] for bh in bucket_host),
        block_intercepts_np=tuple(bh["intercepts"] for bh in bucket_host),
        block_gram_mults=tuple(gram_mults_list),
        covered_np=covered_np,
        plan_counts=_plan_counts(plan),
    )


def _plan_counts(plan: _Plan) -> dict:
    """What the reservoir cap did, as host integers: rows that train
    (the kept rows of entities that train a model), rows that are only
    scored (a capped entity's other rows, and every row of an entity
    under the lower bound), and entities with more rows than they keep."""
    active_rows = int(plan.counts[plan.active].sum())
    return dict(
        active_rows=active_rows,
        passive_rows=int(plan.codes.shape[0]) - active_rows,
        capped_entities=int(np.count_nonzero(plan.counts_full > plan.counts)),
    )


def _finalize_lazy(
    devs, bucket_host, feats, game_data, config, num_entities, tag, plan,
    dtype, covered_np=None, score_inv_np=None,
):
    """Assemble the lazy RandomEffectDataset around the packed plan view.

    ``devs`` is a PackedPlanArrays/_PackedPlanView: the plan arrays stay
    HOST numpy on the BlockPlan leaves (free), and device placement
    resolves lazily — in-trace slices for the fused fit, one split
    program via ``device_plans()`` for eager consumers. ``devs`` None
    (a mesh places the plan from the host): the data set keeps the host
    inverse score map for the mesh to place."""
    blocks = []
    for bh in bucket_host:
        blocks.append(BlockPlan(
            entity_codes=bh["members"],
            row_ids=bh["brow"],
            row_counts=bh["counts"],
            proj=bh["proj"],
            intercept_slots=bh["intercepts"],
            raw=feats,
            raw_labels=game_data.labels,
            raw_offsets=game_data.offsets,
            raw_weights=game_data.weights,
        ))
    return RandomEffectDataset(
        config=config,
        num_entities=num_entities,
        entity_keys=tag.inverse,
        blocks=tuple(blocks),
        max_sub_dim=plan.max_sub_dim,
        sub_dims=plan.sub_dims,
        proj_all=plan.proj_all,
        num_features=plan.num_features,
        dtype=dtype,
        score_codes=tag.codes,
        raw=feats,
        proj_dev=None,
        block_codes_np=tuple(bh["members"] for bh in bucket_host),
        block_intercepts_np=tuple(
            bh["intercepts"] for bh in bucket_host
        ),
        covered_np=covered_np,
        packed_view=devs,
        plan_counts=_plan_counts(plan),
        score_inv=score_inv_np if devs is None else None,
    )
