"""Device mesh construction and dataset sharding rules.

The reference's "communication backend" is Spark: broadcast coefficients out,
treeAggregate gradients back, partitioner-aligned shuffles for routing
(SURVEY §5.8). The TPU-native backend is a ``jax.sharding.Mesh`` plus
NamedSharding annotations: coefficients live replicated in HBM, data rows are
sharded over the ``data`` axis, and XLA inserts the psum/all-gather
collectives over ICI (DCN for multi-slice) wherever the GLM objective's
reductions cross the sharded axis. There is no per-iteration broadcast and no
host round trip.

Mirrors (in spirit) SparkSessionConfiguration (photon-api
SparkSessionConfiguration.scala:109) and LongHashPartitioner
(util/LongHashPartitioner.scala:24): session setup becomes mesh construction,
row partitioning becomes an even row split.
"""

from __future__ import annotations

import dataclasses as _dataclasses
import re as _re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_tpu.data.dataset import GLMBatch, pad_batch

DATA_AXIS = "data"

# Program contract (audited by `python -m photon_tpu.analysis --semantic`;
# machinery in analysis/program.py): every hot-loop operand of a sharded
# fixed-effect batch carries the DATA_AXIS NamedSharding; random-effect
# plan arrays shard their entity axis while the shared raw leaves stay
# replicated; and the lowered data-parallel objective's only collective is
# the gradient all-reduce — an all-gather appearing here means sharding
# propagation broke and every dispatch pays a cross-device transfer.
PROGRAM_AUDIT = dict(
    name="mesh-sharding",
    entry="parallel.mesh.shard_batch / shard_random_effect_dataset "
    "+ ops.glm objective",
    builder="build_mesh_sharding",
    hot_loop=True,
    sharded_operands=(
        "features", "labels", "offsets", "weights",
        "re_entity_codes", "re_row_ids",
    ),
    replicated_operands=("re_raw",),
    axis=DATA_AXIS,
    allowed_collectives=("all-reduce",),
)

# SPMD contract (audited by `python -m photon_tpu.analysis --spmd`;
# machinery in analysis/spmd.py): the sharded objective must trace to
# byte-identical jaxprs on every simulated host, its compiled HLO must
# carry the same ordered collective sequence on every host and nothing
# beyond the gradient all-reduce, and every placed leaf must be covered
# by exactly one PARTITION_RULES entry whose spec the placement agrees
# with. This is the acceptance harness for the pjit/NamedSharding mesh
# rebuild (ROADMAP item 1): the rebuild lands when it passes this
# contract, and `covers` pins the tier-2 census to the tier-6 one so
# the two audits cannot drift.
SPMD_AUDIT = dict(
    name="mesh-spmd",
    entry="parallel.mesh.shard_batch / shard_random_effect_dataset "
    "+ ops.glm objective",
    builder="build_mesh_spmd",
    hosts=2,
    ordered_collectives=("all-reduce",),
    partition_rules="PARTITION_RULES",
    covers=("mesh-sharding",),
)

# The regex partition-rule tree for every leaf the mesh places, in the
# match_partition_rules shape (first match wins; the SPMD auditor holds
# the stronger line that exactly one rule matches each leaf). Leaf names
# are slash-joined pytree paths: "fe/<field>" for the fixed-effect
# batch, "re/block<i>/<field>" for random-effect plan arrays,
# "re/raw*"/"re/score_*" for the shared scoring tables, "coef/*" for
# coefficient vectors. The pjit rebuild (ROADMAP item 1) feeds these
# specs to pjit instead of per-leaf device_put calls; until then they
# document — and the auditor verifies — what the placement code does.
PARTITION_RULES = (
    # Fixed-effect batch leaves: rows sharded over the data axis
    # (shard_batch pads to the device count first).
    (r"^fe/(features|labels|offsets|weights|uids)$", P(DATA_AXIS)),
    # Random-effect plan arrays: entity axis sharded — the per-entity
    # solves are embarrassingly parallel (shard_random_effect_dataset).
    (
        r"^re/block\d+/(entity_codes|row_ids|row_counts|proj"
        r"|intercept_slots)$",
        P(DATA_AXIS),
    ),
    # Shared raw leaves: replicated — BlockPlans gather arbitrary rows,
    # so every device needs the full table (the memory-for-zero-shuffle
    # tradeoff documented on shard_random_effect_dataset).
    (r"^re/raw(/|$)", P()),
    # Residual-scorer tables: per-row work, rows sharded when divisible.
    (r"^re/score_(codes|indices|values)$", P(DATA_AXIS)),
    # The lazy scorer's inverse map and passive row numbers: rows sharded,
    # padded to the device count (each device gathers its own rows'
    # scores and scores its share of the passive rows, models/game.py).
    (r"^re/(score_inv|passive_rows)$", P(DATA_AXIS)),
    # Coefficients: replicated in HBM; gradients all-reduce into them.
    (r"^coef(/|$)", P()),
)


def match_partition_rules(rules, leaves: dict):
    """Map named leaves to PartitionSpecs via first-match regex rules.

    ``leaves`` maps slash-joined pytree path names to arrays (anything
    with ``ndim``). Scalars take ``P()`` without consuming a rule; an
    array leaf no rule matches raises — silence here would mean a slab
    lands wherever jit defaults put it. Returns ``(specs, matches)``
    where ``matches[name]`` lists every matching rule index (the SPMD
    auditor checks the list has length exactly 1).
    """
    specs: dict[str, P] = {}
    matches: dict[str, list[int]] = {}
    for name, leaf in leaves.items():
        hit = [
            i for i, (pat, _) in enumerate(rules) if _re.search(pat, name)
        ]
        matches[name] = hit
        if int(getattr(leaf, "ndim", 0)) == 0:
            specs[name] = P()
        elif hit:
            specs[name] = rules[hit[0]][1]
        else:
            raise ValueError(
                f"no partition rule matches leaf {name!r}"
            )
    return specs, matches


def shard_random_effect_dataset(
    ds, mesh: Mesh, *, axis_name: str = DATA_AXIS
):
    """Shard a RandomEffectDataset's entity axis over the mesh (ep).

    Each size bucket's entity axis is padded to a multiple of the device
    count with inert entities (weight 0 / row_count 0, empty subspace,
    entity code == num_entities so their scatter back into the coefficient
    matrix is dropped as out-of-bounds), then every block leaf is placed
    with its leading axis sharded. The per-entity solves are embarrassingly
    parallel (RandomEffectCoordinate.scala:243-292 runs them
    executor-local), so sharding the vmapped solver's batch axis keeps all
    solver FLOPs local to each device — the TPU analog of the reference's
    entity partitioning (RandomEffectDatasetPartitioner.scala:44).

    Lazy ``BlockPlan`` buckets shard their plan arrays on the entity axis;
    the shared raw leaves are replicated over the mesh (each device gathers
    its own entities' rows locally — the replication rides ICI once, and is
    the memory-for-zero-shuffle tradeoff the reference pays per iteration
    in shuffles instead). The materialized scoring table's row axis is
    sharded when evenly divisible. A lazy data set's inverse score map is
    rebased to the padded buckets and placed with its rows sharded,
    padded to the device count (``loop_rows``), from the host where the
    build left it there.
    """
    import dataclasses

    from photon_tpu.data.random_effect import BlockPlan, EntityBlocks

    n_dev = mesh.shape[axis_name]

    def place(leaf):
        return jax.device_put(
            leaf, row_sharding(mesh, np.ndim(leaf), axis_name=axis_name)
        )

    def replicate(leaf):
        return jax.device_put(leaf, NamedSharding(mesh, P()))

    import jax.numpy as jnp

    _rep_cache: dict[int, object] = {}

    def replicate_cached(leaf):
        got = _rep_cache.get(id(leaf))
        if got is None:
            got = jax.tree.map(replicate, leaf)
            _rep_cache[id(leaf)] = got
        return got

    fills = {"entity_codes": ds.num_entities,
             "proj": -1, "intercept_slots": -1}
    plan_fields = (
        "entity_codes", "row_ids", "row_counts", "proj", "intercept_slots"
    )

    def pad_leaf(name, leaf, pad):
        if leaf is None:  # dense-layout EntityBlocks carry x_indices=None
            return None
        widths = [(0, pad)] + [(0, 0)] * (np.ndim(leaf) - 1)
        # A plan leaf still on the host is padded there and goes from
        # the host to its devices, never through one device.
        xp = np if isinstance(leaf, np.ndarray) else jnp
        return xp.pad(leaf, widths, constant_values=fills.get(name, 0))

    codes_np, ints_np = [], []

    def pad_host_mirror(arr, pad, fill):
        a = np.asarray(arr)
        return np.pad(a, (0, pad), constant_values=fill) if pad else a

    def pad_block(i, b):
        pad = (-b.num_entities) % n_dev
        # Host mirrors are padded host-side (never pulled from the device:
        # on a multi-host mesh the placed arrays span non-addressable
        # devices and cannot be fetched back).
        codes_np.append(
            pad_host_mirror(ds.block_codes_np[i], pad, ds.num_entities)
        )
        ints_np.append(pad_host_mirror(ds.block_intercepts_np[i], pad, -1))
        if isinstance(b, BlockPlan):
            vals = {
                name: pad_leaf(name, getattr(b, name), pad) if pad
                else getattr(b, name)
                for name in plan_fields
            }
            # Placement deferred: every block's plan leaves ride ONE
            # batched sharded device_put below (one transfer-path setup
            # per ingest instead of 5 x n_buckets — the sharded analog of
            # the packed single-device plan buffer).
            deferred.append((i, b, vals))
            return b
        if pad:
            b = EntityBlocks(**{
                f.name: pad_leaf(f.name, getattr(b, f.name), pad)
                for f in dataclasses.fields(EntityBlocks)
            })
        return jax.tree.map(place, b)

    deferred: list[tuple] = []
    first = ds.blocks[0] if ds.blocks else None
    on_host = isinstance(first, BlockPlan) and isinstance(
        first.row_ids, np.ndarray)
    out_blocks = [
        pad_block(i, b)
        for i, b in enumerate(ds.blocks if on_host else ds.device_plans())
    ]
    if deferred:
        from photon_tpu.data.pipeline import PIPELINE_STATS

        leaves = [
            vals[name] for _, _, vals in deferred for name in plan_fields
        ]
        shardings = [
            row_sharding(mesh, np.ndim(leaf), axis_name=axis_name)
            for leaf in leaves
        ]
        with PIPELINE_STATS.stage("transfer"):
            placed = jax.device_put(leaves, shardings)
        it = iter(placed)
        for i, b, vals in deferred:
            out_blocks[i] = dataclasses.replace(
                b,
                raw=replicate_cached(b.raw),
                raw_labels=replicate_cached(b.raw_labels),
                raw_offsets=replicate_cached(b.raw_offsets),
                raw_weights=replicate_cached(b.raw_weights),
                **{name: next(it) for name in plan_fields},
            )
    blocks = tuple(out_blocks)
    rep = {
        "blocks": blocks,
        "block_codes_np": tuple(codes_np),
        "block_intercepts_np": tuple(ints_np),
        # The sharded dataset's plan arrays are mesh-placed above; the
        # single-device packed buffer must not shadow them.
        "packed_view": None,
    }
    if ds.is_lazy:
        # Raw leaves must be replicated (BlockPlans gather arbitrary rows),
        # but the residual scorer is per-row: sharding score_codes row-wise
        # (when divisible) makes the fused score dp-parallel — GSPMD slices
        # the replicated raw operand locally for free.
        codes = ds.score_codes
        if codes.shape[0] % n_dev == 0:
            codes = place(codes)
        else:
            codes = replicate(codes)
        inv = (ds.score_inv if isinstance(ds.score_inv, np.ndarray)
               else ds.score_inv_device())
        if inv is not None:
            inv = _padded_score_inv(
                inv, [b.row_ids.shape for b in ds.blocks], n_dev)
            rep["score_inv"] = (
                _rows_from_host(inv, mesh, axis_name)
                if isinstance(inv, np.ndarray)
                else place(jnp.pad(inv, (0, (-inv.shape[0]) % n_dev))))
        rep.update(
            raw=replicate_cached(ds.raw),
            score_codes=codes,
            proj_dev=replicate_cached(ds.proj_device()),
        )
    elif ds.score_codes.shape[0] % n_dev == 0:
        rep.update(
            score_codes=place(ds.score_codes),
            score_indices=place(ds.score_indices),
            score_values=place(ds.score_values),
        )
    return dataclasses.replace(ds, **rep)


def _padded_score_inv(inv, shapes, n_dev: int):
    """The inverse score map (``data/random_effect.py``) of buckets padded
    to the device count: bucket i's ``[B, cap]`` block now starts after
    the ``(B + pad) * cap`` slots of the buckets before it, where ``pad =
    (-B) % n_dev`` is the inert tail ``pad_block`` appends, and the
    passive scores after all of them. ``shapes``: each bucket's ``(B,
    cap)``; a host map stays on the host."""
    starts = np.cumsum([0] + [b * r for b, r in shapes])
    padded = np.cumsum([0] + [(b + (-b) % n_dev) * r for b, r in shapes])
    xp = np if isinstance(inv, np.ndarray) else jnp
    passive = int(xp.count_nonzero(inv >= starts[-1]))
    if padded[-1] + passive >= 2**31:
        raise OverflowError(
            f"the padded flat score layout has {padded[-1] + passive} "
            "elements, which overflows the int32 inverse score map")
    region = xp.searchsorted(starts[1:], inv, side="right")
    return inv + xp.asarray((padded - starts).astype(np.int32))[region]


def loop_rows(n: int, mesh: Mesh | None) -> int:
    """The length of the unfused loop's per-row vectors (residuals in,
    scores out): ``n`` on one device; on a mesh ``n`` padded to the device
    count, as ``shard_batch`` pads the rows, so that they shard by rows (an
    array whose length the device count does not divide cannot)."""
    return n if mesh is None else n + (-n) % mesh.shape[mesh.axis_names[0]]


def make_mesh(
    devices=None, *, axis_name: str = DATA_AXIS
) -> Mesh:
    """One-axis data mesh over the given (default: all) devices.

    GLM/GLMix training is data-parallel + entity-parallel; both shard the
    sample/entity dimension, so a single mesh axis covers every coordinate
    type. Multi-host meshes come straight from jax.devices() spanning hosts.
    """
    devs = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devs, (axis_name,))


def resolve_mesh(setting) -> Mesh | None:
    """Shared mesh-setting resolution for the estimator and the CLIs.

    ``"auto"`` -> all devices (None when single-device), ``"off"``/``None``/
    ``False``/``1`` -> None, an int or digit string -> that many devices, a
    ``Mesh`` -> itself. Unrecognized strings raise — a typo like ``"fof"``
    must not silently mean "auto".
    """
    m = setting
    if isinstance(m, str):
        key = m.lower()
        if key == "auto":
            return make_mesh() if len(jax.devices()) > 1 else None
        if key in ("off", "none", "1"):
            return None
        if key.isdigit():
            m = int(key)
        else:
            raise ValueError(f"unknown mesh setting {setting!r}")
    if isinstance(m, bool):
        return make_mesh() if (m and len(jax.devices()) > 1) else None
    if isinstance(m, int):
        if m < 1:
            raise ValueError(f"mesh setting must be >= 1 device, got {m}")
        if m > len(jax.devices()):
            raise ValueError(
                f"mesh setting requests {m} devices but only "
                f"{len(jax.devices())} are visible")
        return make_mesh(jax.devices()[:m]) if m > 1 else None
    if m is None or isinstance(m, Mesh):
        return m
    raise TypeError(f"unknown mesh setting {setting!r}")


def row_sharding(mesh: Mesh, ndim: int, *, axis_name: str = DATA_AXIS) -> NamedSharding:
    """Shard the leading (row) axis, replicate the rest."""
    return NamedSharding(mesh, P(axis_name, *([None] * (ndim - 1))))


def maybe_row_shard(mesh: Mesh | None, *leaves):
    """Place [n, ...] leaves row-sharded over the mesh's leading axis when n
    divides its extent evenly; otherwise return them unchanged.

    The shared no-padding placement policy for one-pass tables (batch
    scoring, score tables): the per-row work is identical either way, only
    the placement changes, so padding machinery isn't worth it here.
    """
    if mesh is None:
        return leaves
    axis = mesh.axis_names[0]
    if leaves[0].shape[0] % mesh.shape[axis]:
        return leaves
    return tuple(
        jax.device_put(
            leaf, row_sharding(mesh, np.ndim(leaf), axis_name=axis)
        )
        for leaf in leaves
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


MODEL_AXIS = "model"


@jax.tree_util.register_dataclass
@_dataclasses.dataclass(frozen=True)
class FeatureShardedSparse:
    """ELL features sharded over the FEATURE axis (tensor-parallel GLM).

    For d too large to replicate comfortably (SURVEY §7.3 "sparse
    fixed-effect matvec at scale"), each device owns a contiguous feature
    range [j*d_local, (j+1)*d_local) and holds only the ELL entries whose
    feature falls in its range, with LOCAL indices. The coefficient vector is
    sharded over the same axis:

    - ``matvec``: per-device partial margins + one psum over ICI (the
      feature-axis analog of ValueAndGradientAggregator's treeAggregate);
    - ``rmatvec``/``rmatvec_sq``: purely local scatters — each feature is
      owned by exactly one device, no collective at all.

    ``d`` is padded up to a device-count multiple; the padded coefficients
    receive no data gradient (L2 pins them at zero). ``logical_d`` is the
    caller's true feature count.
    """

    local_indices: Array  # [n_dev, n, k_loc] int32, device-local feature ids
    local_values: Array  # [n_dev, n, k_loc]
    d: int = _dataclasses.field(metadata=dict(static=True))  # padded
    logical_d: int = _dataclasses.field(metadata=dict(static=True))
    mesh: Mesh = _dataclasses.field(metadata=dict(static=True))
    axis: str = _dataclasses.field(metadata=dict(static=True))

    @property
    def num_features(self) -> int:
        return self.d

    @property
    def _d_local(self) -> int:
        return self.d // self.mesh.shape[self.axis]

    def matvec(self, w: Array):
        from jax import shard_map

        axis = self.axis
        if w.shape[0] < self.d:
            # Trained models are trimmed to logical_d at the coordinate
            # boundary; re-pad here so scoring accepts them directly.
            w = jnp.pad(w, (0, self.d - w.shape[0]))

        def local(idx, val, w_local):
            z = jnp.sum(val[0] * w_local[idx[0]], axis=-1)
            return jax.lax.psum(z, axis)

        return shard_map(
            local, mesh=self.mesh,
            in_specs=(P(axis), P(axis), P(axis)),
            out_specs=P(),
        )(self.local_indices, self.local_values, w)

    def _scatter(self, g: Array, squared: bool):
        from jax import shard_map

        d_local = self._d_local

        def local(idx, val, g_rep):
            v = val[0] * val[0] if squared else val[0]
            contrib = v * g_rep[:, None]
            return jnp.zeros(d_local, dtype=contrib.dtype).at[idx[0]].add(
                contrib)

        return shard_map(
            local, mesh=self.mesh,
            in_specs=(P(self.axis), P(self.axis), P()),
            out_specs=P(self.axis),
        )(self.local_indices, self.local_values, g)

    def rmatvec(self, g: Array):
        return self._scatter(g, squared=False)

    def rmatvec_sq(self, g: Array):
        return self._scatter(g, squared=True)


def shard_features_by_column(
    indices: np.ndarray,  # [n, k] host-side global feature ids
    values: np.ndarray,  # [n, k]
    num_features: int,
    mesh: Mesh,
    *,
    axis_name: str = MODEL_AXIS,
    dtype=None,
) -> FeatureShardedSparse:
    """Host-side build: split every row's ELL entries by feature range.

    Per-device slab width is the max over devices of the max per-row local
    nnz — rows hash features roughly uniformly, so the width is ~k/n_dev
    plus skew, not k.
    """
    if dtype is None:
        dtype = values.dtype
    n_dev = int(mesh.shape[axis_name])
    d_pad = ((num_features + n_dev - 1) // n_dev) * n_dev
    d_local = d_pad // n_dev
    n, k = indices.shape
    owner = indices // d_local  # [n, k]
    present = values != 0.0

    k_loc = 1
    for j in range(n_dev):
        sel = present & (owner == j)
        k_loc = max(k_loc, int(sel.sum(axis=1).max(initial=0)))

    li = np.zeros((n_dev, n, k_loc), dtype=np.int32)
    lv = np.zeros((n_dev, n, k_loc), dtype=values.dtype)
    for j in range(n_dev):
        sel = present & (owner == j)
        # Compact this device's entries left per row.
        order = np.argsort(~sel, axis=1, kind="stable")
        idx_c = np.take_along_axis(
            np.where(sel, indices - j * d_local, 0), order, axis=1)
        val_c = np.take_along_axis(
            np.where(sel, values, 0.0), order, axis=1)
        li[j] = idx_c[:, :k_loc]
        lv[j] = val_c[:, :k_loc]

    place = NamedSharding(mesh, P(axis_name, None, None))
    return FeatureShardedSparse(
        local_indices=jax.device_put(jnp.asarray(li), place),
        local_values=jax.device_put(jnp.asarray(lv, dtype=dtype), place),
        d=d_pad,
        logical_d=num_features,
        mesh=mesh,
        axis=axis_name,
    )


def shard_batch(
    batch: GLMBatch, mesh: Mesh, *, axis_name: str = DATA_AXIS
) -> GLMBatch:
    """Pad rows to the device count and place every leaf row-sharded.

    The weight-0 padding rows are inert in all aggregations, so sharded and
    unsharded objectives agree bit-for-bit up to reduction order.
    """
    n_dev = mesh.shape[axis_name]
    if all(isinstance(leaf, np.ndarray) for leaf in jax.tree.leaves(batch)):
        # A batch still on the host (make_host_game_dataset): each device
        # is sent its own rows and no device sees the whole table.
        return jax.tree.map(
            lambda leaf: _rows_from_host(leaf, mesh, axis_name), batch)
    batch = pad_batch(batch, n_dev)
    return jax.tree.map(
        lambda leaf: jax.device_put(
            leaf, row_sharding(mesh, np.ndim(leaf), axis_name=axis_name)
        ),
        batch,
    )


def _rows_from_host(leaf: np.ndarray, mesh: Mesh, axis_name: str):
    """A host ``[n, ...]`` array as a row-sharded device array of
    ``pad_batch``'s length (zero rows after the last), each device's rows
    sent from the host to that device alone."""
    n = leaf.shape[0]
    n_dev = mesh.shape[axis_name]
    padded = (n + (-n) % n_dev,) + leaf.shape[1:]

    def rows(index):
        lo, hi, _ = index[0].indices(padded[0])
        part = leaf[lo:min(hi, n)]
        if hi > n:
            part = np.concatenate(
                [part, np.zeros((hi - max(lo, n),) + leaf.shape[1:],
                                leaf.dtype)])
        return part

    return jax.make_array_from_callback(
        padded, row_sharding(mesh, leaf.ndim, axis_name=axis_name), rows)


def placed_bytes(tree, devices) -> list[int]:
    """Bytes of ``tree``'s device arrays that each of ``devices`` holds,
    from shapes and shardings alone (a replicated array counts whole on
    every device it is on, a sharded one by its shard; an array that
    appears twice counts once). Nothing is read from a device."""
    held = {d: 0 for d in devices}
    seen = set()
    for leaf in jax.tree.leaves(tree):
        if not isinstance(leaf, jax.Array) or id(leaf) in seen:
            continue
        seen.add(id(leaf))
        shard = leaf.sharding.shard_shape(leaf.shape)
        size = int(np.prod(shard, dtype=np.int64)) * leaf.dtype.itemsize
        for d in leaf.sharding.device_set:
            if d in held:
                held[d] += size
    return [held[d] for d in devices]
