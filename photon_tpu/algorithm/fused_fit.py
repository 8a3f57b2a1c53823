"""Whole-fit fused coordinate descent: ONE XLA program per GAME fit.

The unfused ``CoordinateDescent`` dispatches one device program per bucket
solve, per scorer, and per residual update — ~24 dispatches per fit on the
bench workload. On a remote-attached TPU every *distinct program* pays a
compile + first-execution round trip (seconds each, noisy under shared
compiler load), and every *dispatch* pays RPC latency. This module traces
the entire block-coordinate-descent fit — fixed-effect L-BFGS solves,
batched per-entity Newton/Cholesky solves, scoring, and the
``summed - old + previous`` residual algebra (CoordinateDescent.scala
:442,583) — into one jitted program with a ``lax.fori_loop`` over CD
iterations, so a fit is ONE compile and ONE dispatch.

Semantics match the unfused loop exactly (pinned by
tests/test_fused_fit.py): the same ``_solve_block`` / ``_run_impl``
primitives are inlined by jit-in-jit tracing, warm starts enter as traced
table operands, and regularization weights stay traced so a config-grid
sweep (GameEstimator.scala:452-468 warm-start ladder) re-enters the SAME
executable with new lambdas.

Row order (``FusedFit._choose_home``): nothing outside the program sees
the order of its rows (``run`` keeps the coefficient tables and drops the
per-row scores), so the rows stand in the entity order of ONE
random-effect coordinate, its "home": that coordinate's rows <-> slab
moves are then contiguous copies (ops/ragged.py) and not element gathers,
which cost 7 ns an index on a TPU v5e whatever they point at. The
permutation is made once per prepared data set, in the materialize
program; DATA.md "The fused fit's row order".

Eligibility (``fuse_eligible``): single device (collectives stay on the
serialized unfused path), no validation-driven best-model tracking, lazy
random-effect datasets, no down-sampling (its per-iteration reseeding is
host-driven). Everything else falls back to ``CoordinateDescent``.
"""

from __future__ import annotations

import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_tpu.algorithm.coordinate import (
    FixedEffectCoordinate,
    ModelCoordinate,
)
from photon_tpu.algorithm.coordinate_descent import (
    CoordinateDescentResult,
    CoordinateUpdateRecord,
)
from photon_tpu.algorithm.problems import (
    VarianceComputationType,
    _run_impl,
)
from photon_tpu.algorithm.random_effect import (
    RandomEffectCoordinate,
    RandomEffectTrainingStats,
    _solve_block,
    fit_stage_coordinate,
    solver_statics as _re_statics,
)
from photon_tpu.data.dataset import feature_layout, feature_major
from photon_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
    _bucket_score_add,
    _passive_score_set_dense,
    _passive_score_set_sparse,
    _score_raw_dense,
    bucket_score_parts,
    passive_raw_scores,
    score_raw_features,
)
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.ops import ragged

Array = jax.Array
logger = logging.getLogger(__name__)

# Program contract (audited by `python -m photon_tpu.analysis --semantic`,
# machinery in analysis/program.py): one fused-fit generation is at most
# THREE distinct compiled programs — the slab materialization, the cold
# whole-fit program, and its warm-start twin (has_init is static). A λ-grid
# config sweep must re-enter those executables; an optimizer swap, an
# iteration-count change, or a precision switch (ops/precision.py mixed
# bf16 vs the default f32) is a declared recompile (new statics/dtypes
# by design).
PROGRAM_AUDIT = dict(
    name="fused-fit",
    entry="algorithm.fused_fit.FusedFit (_mat_fn + _fit_fn)",
    builder="build_fused_fit",
    max_programs=3,
    stable_under=("lambda_grid",),
    recompiles_on=("optimizer_swap", "iteration_count", "precision"),
    hot_loop=True,
)

# Memory contract (audited by `python -m photon_tpu.analysis --memory`,
# machinery in analysis/memory.py): the expected peak-HBM of each fused
# program as a formula over the audit fixture's dims, priced against the
# static live-range walk of the traced jaxpr. Materialize is dominated
# by the packed ingest buffer's fixed 4 MiB transfer granule
# (data/pipeline._TRANSFER_GRANULE_ELEMS) plus a handful of [n] row
# vectors; the fit's live set is ~32 [n]-row working vectors per
# coordinate per sweep (the Newton/CG scan-body residency) on top of the
# design matrices. A new slab-sized buffer that none of these terms
# price fails the audit as memory-undeclared-growth.
MEMORY_AUDIT = dict(
    name="fused-fit-memory",
    entry="algorithm.fused_fit.FusedFit (_mat_fn + _fit_fn)",
    covers=("fused-fit",),
    builder="build_fused_fit_memory",
    budgets={
        "materialize": "4 * 2 ** 20 + 24 * n * wbytes",
        "fit": "iters * coords * 32 * n * wbytes + (d + du) * n * wbytes",
        "fit_warm": (
            "iters * coords * 32 * n * wbytes + (d + du) * n * wbytes"
        ),
    },
    # Declared donations the compiled HLO must actually alias. The CD
    # sweep's carry twin is probed against its lowered module; the
    # random-effect _solve_block's slab donation (positions 9/10) needs
    # a full coordinate build to lower, so it is declared here and
    # enforced at source level by the tier-1 use-after-donate rule.
    donations={
        "algorithm.coordinate_descent._sub_add_donating": (0,),
        "algorithm.random_effect._solve_block": (9, 10),
    },
    tolerance=1.5,
)

# Tier-5 numerics contract (`--numerics`, ANALYSIS.md): the fused
# materialize/fit programs are dtype-flow walked at BOTH precisions —
# the f32 variant is the control (zero bf16 lineage, zero roundings,
# budget 0) and the bf16 variant is the audited policy. The two
# suppressed cast-census rules are the policy itself, not accidents;
# each reason below names the test that pins the behavior.
NUMERICS_AUDIT = dict(
    name="fused-fit-numerics",
    entry="algorithm.fused_fit.FusedFit (_mat_fn + _fit_fn)",
    covers=("fused-fit",),
    builder="build_fused_fit_numerics",
    budgets={
        # the default path traces byte-identical pre-policy programs:
        # no narrowing casts may exist at all
        "*_f32": "0",
        # one slab storage rounding at materialization
        "materialize_bf16": "u16",
        # worst-case compounding over the sweep: each row's score cell
        # passes through at most 4 chained bf16 re-roundings per
        # coordinate per iteration (store_score + quantize + the two
        # storage-dtype casts around the bucket scorer) — the auditor's
        # chain model; measured parity (PERFORMANCE.md) sits ~100x
        # below because the roundings land on independently-stored
        # lanes, not one chained value
        "fit_bf16": "u16 * 4 * n * iters * coords",
    },
    deterministic={
        # convergence diagnostics and per-bucket results scatter with
        # .at[].set into unique destinations (entity codes within a
        # bucket are unique by construction, iteration slots are
        # distinct) — no colliding writes exist to order
        "fit_*:scatter": (
            "set-scatters write unique rows: per-bucket entity codes "
            "are unique and sorted (bucket-slab construction), "
            "diagnostic slots are distinct iteration indices"
        ),
        # the home order's inverse: iota set-scattered through a
        # permutation (a sort's index output), every destination once
        "materialize_*:scatter": (
            "set-scatter through a permutation: _home_order inverts "
            "the sorted row order, each row written exactly once"
        ),
    },
    suppress={
        "numerics-scan-recast": (
            "the bf16 score carries ARE the policy: per-coordinate "
            "score vectors are stored bf16 in the sweep carry and "
            "upcast on read (PERFORMANCE.md policy table); parity is "
            "gated per family by tests/test_precision.py"
        ),
        "numerics-cast-roundtrip": (
            "_quantize_score's f32->bf16->f32 round-trip is "
            "INTENTIONAL and idempotent: convergence checks must see "
            "exactly the value a bf16 carry will store, pinned by "
            "test_score_quantization_is_idempotent_against_storage"
        ),
    },
    tolerance=1.5,
)


# A first cut that spares a compile (``FusedFit._choose_home``): home's order
# is not tried where the fixed effect's batches, of which it keeps a second
# copy, are more than a ninth of the device. On a TPU v5e (16.9 GB) the fit
# program of a 2.14 GB batch (8 M rows of 64 features) reserves 9.87 GB of
# scratch and was refused beside home's copy by 0.95 GB; that of a 1.61 GB
# batch (6 M rows) reserves 6.56 GB and runs (PERF.md section 6, PR 33). The
# cut is a guess between those two readings and decides nothing that can
# fail: what passes it is compiled, and the compiler's own account of the
# two programs has the last word (``FusedFit._home_fits``).
_HOME_MEMORY_FACTOR = 9


def _device_bytes_limit() -> int | None:
    """The device's memory, where the backend says (the CPU does not)."""
    return (jax.local_devices()[0].memory_stats() or {}).get("bytes_limit")


class _PackedDiags:
    """All per-update diagnostic arrays of one fused fit, packed into ONE
    int32 device buffer — one host pull instead of six per-coordinate
    ones (the per-pull cost is not measured on this chip). Pulled
    lazily, once, on first diagnostic access."""

    def __init__(self, flat: Array, shapes: list[tuple]):
        self._flat = flat
        self._shapes = shapes
        self._arrays: list[np.ndarray] | None = None

    def get(self, index: int) -> np.ndarray:
        if self._arrays is None:
            flat = np.asarray(self._flat)
            self._arrays = []
            o = 0
            for shape in self._shapes:
                size = int(np.prod(shape))
                self._arrays.append(flat[o:o + size].reshape(shape))
                o += size
            self._flat = None
        return self._arrays[index]


class FusedFixedEffectStats:
    """Per-update fixed-effect diagnostics from the fused program.

    Mirrors the OptimizationResult attributes the reporting/bench layer
    reads (iterations, convergence_reason); values pull lazily through the
    packed diagnostics buffer."""

    def __init__(self, packed: _PackedDiags, it_index: int, rs_index: int,
                 iteration: int):
        self._packed = packed
        self._it_index = it_index
        self._rs_index = rs_index
        self._iteration = iteration

    @property
    def iterations(self) -> int:
        return int(self._packed.get(self._it_index)[self._iteration])

    @property
    def convergence_reason(self) -> int:
        return int(self._packed.get(self._rs_index)[self._iteration])


def fuse_ineligibility_reasons(
    coords: dict[str, object],
    *,
    mesh=None,
    emitter=None,
) -> list[str]:
    """Every reason this coordinate structure cannot ride the fused fit.

    Empty list == eligible. ``fuse_eligible`` is this predicate on the
    per-coordinate reasons alone; the estimator's program cache
    (``GameEstimator._fused_for``) passes its mesh/listener state in too,
    and the semantic auditor's sharding report uses the same call to
    state *why* the mesh path is unfused today, not just that it is
    (analysis/program.py build_mesh_sharding).
    """
    reasons: list[str] = []
    if mesh is not None:
        reasons.append(
            "mesh execution: fusing would fold every coordinate's "
            "collectives into one program with no host serialization "
            "point between them — the unfused path serializes "
            "collective-bearing dispatches on CPU meshes "
            "(coordinate_descent._serialize_on_cpu_mesh) and keeps "
            "per-bucket programs independently shardable")
    if emitter is not None:
        reasons.append(
            "listeners: per-update events need a host boundary after "
            "each coordinate update; the fused program has none until "
            "the whole fit completes")
    for cid, coord in coords.items():
        if isinstance(coord, ModelCoordinate):
            continue
        inner = getattr(coord, "inner", coord)
        if isinstance(inner, FixedEffectCoordinate):
            rate = inner.config.down_sampling_rate
            if 0.0 < rate < 1.0:
                reasons.append(
                    f"coordinate {cid!r}: down-sampling reseeds per "
                    "iteration on host")
            if inner.config.optimizer.box_constraints is not None:
                reasons.append(
                    f"coordinate {cid!r}: box constraints run the "
                    "untraced solver path (constraint arrays would bake "
                    "in as trace constants)")
            if (inner.logical_rows is not None
                    and inner.batch.num_samples != inner.logical_rows):
                reasons.append(
                    f"coordinate {cid!r}: padded mesh batch "
                    "(num_samples != logical_rows) stays unfused")
            if getattr(inner.batch.features, "logical_d", None) is not None:
                reasons.append(
                    f"coordinate {cid!r}: column-sharded features solve "
                    "on the mesh path")
        elif isinstance(inner, RandomEffectCoordinate):
            if not inner.dataset.is_lazy:
                reasons.append(
                    f"coordinate {cid!r}: materialized score tables ride "
                    "the legacy scoring path")
        else:
            reasons.append(
                f"coordinate {cid!r}: unknown coordinate type "
                f"{type(inner).__name__}")
    return reasons


def fuse_eligible(coords: dict[str, object]) -> bool:
    """True when every coordinate can ride the single-program fit."""
    return not fuse_ineligibility_reasons(coords)


def fused_static_key(coords: dict, seq: list[str], num_iterations: int,
                     locked: set[str],
                     precision: str = "float32") -> tuple:
    """Hashable descriptor of everything baked into the fused trace.

    Initial models are NOT part of the key: warm-start tables are always
    operands (zeros when absent), so their presence never changes the
    traced structure. ``precision`` IS part of the key — the declared
    mixed-precision recompile trigger (slab/score dtypes change)."""
    from photon_tpu.ops import precision as precision_mod

    parts: list = [
        tuple(seq), num_iterations, tuple(sorted(locked)),
        precision_mod.resolve(precision),
    ]
    for cid in seq:
        coord = coords[cid]
        if isinstance(coord, ModelCoordinate):
            parts.append((cid, "locked"))
            continue
        inner = getattr(coord, "inner", coord)
        if isinstance(inner, FixedEffectCoordinate):
            cfg = inner.config
            parts.append((
                cid, "fixed", inner.problem.task, cfg.optimizer,
                cfg.l1_weight != 0.0, cfg.variance_computation,
                inner.problem.intercept_index,
                inner.problem.prior is not None,
                inner.problem.normalization.factors is not None,
                inner.problem.normalization.shifts is not None,
                inner.batch.num_samples, inner.batch.num_features,
            ))
        else:
            ds = inner.dataset
            st = _re_statics(inner)
            parts.append((
                cid, "random", st["task"], st["opt_config"],
                st["use_owlqn"], st["variance_computation"], st["direct"],
                st["newton"], inner.prior is not None,
                inner.normalization.factors is not None,
                inner.normalization.shifts is not None,
                ds.num_entities, ds.max_sub_dim,
                tuple(
                    (b.row_ids.shape, b.proj.shape) for b in ds.blocks
                ),
            ))
    return tuple(parts)


class FusedFit:
    """One estimator-generation's compiled whole-fit program.

    Built from a coords dict (the first config's); ``run`` re-assembles
    traced operands from the CURRENT coords, so later configs in a grid
    (same structure, new lambdas) reuse the compiled executable.
    """

    def __init__(
        self,
        coords: dict[str, object],
        update_sequence: list[str],
        num_iterations: int,
        locked_coordinates: set[str] | None = None,
        mat_share: dict | None = None,
        precision: str = "float32",
    ):
        from photon_tpu.ops import precision as precision_mod

        self.seq = list(update_sequence)
        self.num_iterations = num_iterations
        self.locked = set(locked_coordinates or ())
        # Mixed-precision policy (ops/precision.py): "bfloat16" stores
        # the materialized slabs AND the per-coordinate score carries in
        # bf16 (the two dominant per-sweep HBM reads), with f32
        # accumulators for every row-crossing sum; "float32" (default)
        # traces the historical program. Part of fused_static_key — the
        # declared `precision` recompile family.
        self.precision = precision_mod.resolve(precision)
        self.kinds: dict[str, str] = {}
        self._re_meta: dict[str, dict] = {}
        for cid in self.seq:
            coord = coords[cid]
            if isinstance(coord, ModelCoordinate) or cid in self.locked:
                self.kinds[cid] = "locked"
                continue
            inner = getattr(coord, "inner", coord)
            if isinstance(inner, FixedEffectCoordinate):
                self.kinds[cid] = "fixed"
            else:
                self.kinds[cid] = "random"
                ds = inner.dataset
                keep = np.zeros(ds.num_entities, bool)
                for codes in ds.block_codes_np:
                    real = codes[codes < ds.num_entities]
                    keep[real] = True
                _, passive = ds.covered_row_partition()
                # Packed-plan layout: (element offset, shape) per plan
                # array inside the ingest's single packed device buffer,
                # so the materialization program can slice them IN-TRACE
                # (no split program, no per-shape transfers). The layout
                # contract is the view's static_slices() — None for the
                # non-packed fallback.
                pv = ds.packed_view
                slices = buf = None
                if pv is not None:
                    slices = pv.static_slices()
                    buf = pv.buffer if slices is not None else None
                self._re_meta[cid] = {
                    "keep": keep,
                    "passive": passive if passive.size else None,
                    "slices": slices,
                    "buf": buf,
                    "n_blocks": len(ds.blocks),
                }
        self._home = self._choose_home(coords)
        # FE normalization contexts ride as trace-time constants: the
        # factor/shift arrays are tiny [d] vectors fixed per estimator
        # generation, and embedding them keeps _run_impl's static
        # specialization (None factors -> raw fast path) intact.
        self._norms = []
        for cid in self.seq:
            inner = getattr(coords[cid], "inner", coords[cid])
            self._norms.append(
                inner.problem.normalization
                if isinstance(inner, FixedEffectCoordinate) else None
            )
        self._jit = jax.jit(self._fit_fn, static_argnames=("statics",))
        # Slab materialization runs ONCE per dataset generation as its own
        # single program (every bucket of every RE coordinate together,
        # including the in-trace unpacking of the ingest's packed plan
        # buffer); its outputs feed the fit program as plain operands.
        # Folding it into the fit would re-gather ~0.4s of slabs on every
        # repeated fit; leaving it per-bucket (the unfused device_blocks()
        # path) costs one compile per bucket.
        self._mat_jit = jax.jit(self._mat_fn)
        self._mat_cache: dict | None = None
        # Optional slab share across FusedFit instances (passed by the
        # estimator's program cache): the materialized slabs depend only
        # on the coordinate/dataset structure — identical for every
        # static-key variant of one estimator generation — so cached
        # sibling programs must reference ONE copy, not pin one per
        # optimizer config.
        self._mat_shared = mat_share
        # Zero warm-start tables, created once per generation: an eager
        # jnp.zeros([100k, S]) is a dispatch of its own (cost not
        # measured on this chip), which would otherwise recur on every
        # fit.
        self._zeros_cache: dict[tuple, Array] = {}
        self.static_key = None  # set by the estimator cache
        # Ingest pipeline's overlapped AOT compile: the estimator attaches
        # the background warm-compile future; run() consumes it — the
        # compiled materialize/fit executables are used directly when the
        # static key and operand avals match, else the normal jit path.
        self._aot_future = None
        self._aot: dict | None = None
        # Statics tuples already executed through the jit fallback: the
        # FIRST such call traces (and possibly compiles) INSIDE the
        # telemetry attribution window, so that window is not pure fit
        # execution and must not be attributed to coordinate records.
        self._jit_seen: set[tuple] = set()

    # ------------------------------------------------------------------
    # operand assembly (per run; cheap)
    # ------------------------------------------------------------------

    def _choose_home(self, coords) -> str | None:
        """The random-effect coordinate whose entity order the fit's rows
        take, or None (today's canonical order).

        Home is the coordinate whose two row <-> slab maps hold the most
        indices (slab slots + rows; the rows are every coordinate's
        alike, so: the most slab slots, the first of equals). Only one
        order can be home; the other coordinates keep their gathers,
        renumbered. Shapes and layout alone decide, so the AOT skeleton
        and the built data set agree: every random-effect coordinate must
        carry the packed score map and build dense slabs, home's shard
        must be dense, and every fixed-effect batch must be a set of
        ``[n, ...]`` arrays (a dual-ELL tail is not row-gatherable).

        And the device must have the room: the batches in home's order
        are a second copy of the fixed effect's data for as long as the
        prepared data set lives. Here only the first cut, by the batches'
        size (``_HOME_MEMORY_FACTOR``); ``compile_programs`` asks the
        compiled programs and takes home away again where they say no."""
        from photon_tpu.data.dataset import DenseFeatures
        from photon_tpu.data.random_effect import packed_len_with_score_inv

        slots: dict[str, int] = {}
        copies = 0
        for cid in self.seq:
            if self.kinds[cid] == "locked":
                continue
            inner = getattr(coords[cid], "inner", coords[cid])
            if self.kinds[cid] == "fixed":
                n = inner.batch.num_samples
                leaves = jax.tree.leaves(inner.batch)
                if any(leaf.shape[:1] != (n,) for leaf in leaves):
                    return None
                copies += sum(leaf.nbytes for leaf in leaves)
                continue
            meta = self._re_meta[cid]
            blocks = inner.dataset.blocks
            if (
                not blocks
                or meta["slices"] is None
                or len(meta["slices"])
                != packed_len_with_score_inv(len(blocks))
                or not all(b.dense_slab for b in blocks)
            ):
                return None
            slots[cid] = sum(int(np.prod(b.row_ids.shape)) for b in blocks)
        if not slots:
            return None
        home = max(slots, key=slots.get)
        ds = getattr(coords[home], "inner", coords[home]).dataset
        if not isinstance(ds.raw, DenseFeatures):
            return None
        limit = _device_bytes_limit()
        if limit is not None and copies * _HOME_MEMORY_FACTOR > limit:
            return None
        return home

    @staticmethod
    def _home_of(ebs_all: dict) -> str | None:
        """The coordinate in whose order these materialized slabs, and so
        every fit over them, stand; None: the canonical order."""
        return next(
            (cid for cid, m in ebs_all.items()
             if m.get("home") is not None), None)

    def _mat_fn(self, mat_ops: dict):
        """Unpack plan arrays + materialize every bucket slab, traced.

        Per RE coordinate: slice the packed ingest buffer into the plan
        arrays (static offsets — free in-trace), rebuild the BlockPlans,
        gather the [B, R, S] slabs, and emit (EntityBlocks, scoring plan
        arrays, projector table) — everything later fits consume. Each
        coordinate's operations carry the scope ``coord.<cid>/materialize``
        (metadata only).

        With a home coordinate the fit's row order is made here too, once
        per prepared data set (``_home_order``): home's slabs are built by
        contiguous moves from its shard in that order, the other
        coordinates' maps are renumbered into it, and the fixed-effect
        batches are gathered into it (``out[home]["home"]``)."""
        unpacked = {
            cid: self._unpack(cid, op) for cid, op in mat_ops.items()
            if self.kinds[cid] == "random"
        }
        home = self._home
        order = None
        if home is not None:
            with jax.named_scope(f"coord.{home}/materialize"):
                order = self._home_order(unpacked[home], mat_ops[home])
        out = {}
        for cid, parts in unpacked.items():
            with jax.named_scope(f"coord.{cid}/materialize"):
                out[cid] = self._mat_one(cid, parts, mat_ops[cid], order)
        if order is not None:
            for cid, op in mat_ops.items():
                if self.kinds[cid] != "fixed":
                    continue
                with jax.named_scope(f"coord.{cid}/materialize"):
                    out[home]["home"]["batches"][cid] = jax.tree.map(
                        lambda a: jnp.take(a, order["perm"], axis=0),
                        op["batch"])
        return out

    def _unpack(self, cid: str, op: dict) -> dict:
        from photon_tpu.data.random_effect import (
            PLAN_ARRAYS_PER_BUCKET as _PPB,
            BlockPlan,
            packed_len_with_score_inv,
            packed_proj_index,
            packed_score_inv_index,
        )

        meta = self._re_meta[cid]
        if "buf" not in op:
            return {"plans": list(op["plans"]), "proj_dev": op["proj_dev"],
                    "score_inv": None}
        arrays = []
        for off, shape in meta["slices"]:
            n = int(np.prod(shape)) if shape else 1
            arrays.append(
                jax.lax.slice_in_dim(
                    op["buf"], off, off + n).reshape(shape)
            )
        plans = [
            BlockPlan(
                entity_codes=arrays[_PPB * i],
                row_ids=arrays[_PPB * i + 1],
                row_counts=arrays[_PPB * i + 2],
                proj=arrays[_PPB * i + 3],
                intercept_slots=arrays[_PPB * i + 4],
                raw=op["raw"],
                raw_labels=op["labels"],
                raw_offsets=op["offsets"],
                raw_weights=op["weights"],
            )
            for i in range(meta["n_blocks"])
        ]
        return {
            "plans": plans,
            # Layout contract (build_random_effect_dataset): the
            # projector sits at 5*n_blocks; trailing arrays (the
            # score map) come AFTER it — arrays[-1] would pick those.
            "proj_dev": arrays[packed_proj_index(meta["n_blocks"])],
            # Inverse score map (row -> flat bucket/passive score
            # position): present on packed layouts with the extra
            # trailing array; enables the gather-based scorer.
            "score_inv": (
                arrays[packed_score_inv_index(meta["n_blocks"])]
                if len(meta["slices"])
                == packed_len_with_score_inv(meta["n_blocks"])
                else None
            ),
        }

    @staticmethod
    def _home_order(parts: dict, op: dict) -> dict:
        """The fit's row order from home's plan: ``perm`` (position ->
        canonical row), its inverse, and per bucket ``moves``: where its
        rows start in that order and the receive bits of its two moves
        (ops/ragged.py); and ``stacked``, home's shard beside its label /
        offset / weight vectors, in that order.

        The order: home's buckets in ladder order, inside a bucket its
        entities in slab order, inside an entity its ACTIVE rows as
        ``row_ids`` lists them (that is the order of the score map's flat
        positions); then its passive rows, grouped by entity."""
        score_inv = parts["score_inv"]
        n = score_inv.shape[0]
        slots = sum(int(np.prod(p.row_ids.shape)) for p in parts["plans"])
        iota = jnp.arange(n, dtype=jnp.int32)
        passive = score_inv >= slots
        _, _, perm = lax.sort(
            (
                jnp.where(passive, slots, score_inv),
                jnp.where(passive, op["score_codes"], 0),
                iota,
            ),
            num_keys=2, is_stable=True,
        )
        inv = jnp.zeros(n, jnp.int32).at[perm].set(
            iota, unique_indices=True)
        moves = []
        base = jnp.zeros((), jnp.int32)
        for p in parts["plans"]:
            moves.append(
                (base, *ragged.shift_bits(p.row_counts, p.row_ids.shape[1])))
            base = base + jnp.sum(p.row_counts, dtype=jnp.int32)
        # Home's shard and its three row vectors, side by side: ONE row
        # gather puts them in the order, and one move a bucket builds its
        # slab from them.
        # (In the wider of their dtypes, which holds both exactly; each
        # column goes back to its own.)
        vectors = ("labels", "offsets", "weights")
        x = op["raw"].x
        wide = jnp.result_type(x.dtype, *(op[name].dtype for name in vectors))
        stacked = jnp.take(
            jnp.concatenate(
                [x.astype(wide)]
                + [op[name][:, None].astype(wide) for name in vectors],
                axis=1),
            perm, axis=0)
        return {"perm": perm, "inv": inv, "moves": tuple(moves),
                "stacked": stacked}

    @staticmethod
    def _pad_rows(arr: Array, slab_shapes) -> Array:
        """``arr`` with room behind it for the longest bucket's move."""
        room = max(b * cap for b, cap in slab_shapes)
        return jnp.pad(arr, [(0, room)] + [(0, 0)] * (arr.ndim - 1))

    @staticmethod
    def _slab_rows(padded: Array, move, b: int, cap: int) -> Array:
        """Rows ``[base, ...)`` of a home-ordered (and ``_pad_rows``
        padded) array in ``[B, cap, ...]`` slab layout: a contiguous
        slice, then the ragged -> padded move. Slots past an entity's
        count hold stale values; the caller masks them."""
        base, into_slab, _ = move
        seg = lax.dynamic_slice_in_dim(padded, base, b * cap)
        slab = ragged.ragged_to_padded(
            seg, into_slab, ragged.shift_steps(b, cap))
        return slab.reshape((b, cap) + padded.shape[1:])

    @staticmethod
    def _rows_from_slabs(parts, moves, slab_shapes, n: int) -> Array:
        """The way back: each bucket's flat ``[B * cap]`` slab vector
        leaves by the padded -> ragged move and lands where the bucket's
        rows start in the home order. In ladder order, so a bucket's
        stale tail is overwritten by the next one's rows; the last tail
        falls on the passive rows (the caller writes those) or past
        ``n``."""
        room = max(b * cap for b, cap in slab_shapes)
        z = jnp.zeros(n + room, parts[0].dtype)
        for part, (base, _, out_of_slab), (b, cap) in zip(
            parts, moves, slab_shapes
        ):
            z = lax.dynamic_update_slice_in_dim(
                z,
                ragged.padded_to_ragged(
                    part, out_of_slab, ragged.shift_steps(b, cap)),
                base, axis=0,
            )
        return z[:n]

    def _mat_one(self, cid: str, parts: dict, op: dict, order) -> dict:
        from photon_tpu.ops import precision as precision_mod

        plans = parts["plans"]
        score_inv = parts["score_inv"]
        home = None
        if order is None:
            blocks = [p.materialize(None) for p in plans]
        elif cid != self._home:
            # Slabs from the raw shard by today's ids; the maps a fit
            # reads are renumbered into the fit's order.
            blocks = [
                dataclasses.replace(
                    eb, row_ids=jnp.take(order["inv"], eb.row_ids))
                for eb in (p.materialize(None) for p in plans)
            ]
            score_inv = jnp.take(score_inv, order["perm"])
        else:
            perm = order["perm"]
            p0 = plans[0]
            d = p0.raw.x.shape[1]
            padded = self._pad_rows(
                order["stacked"], [p.row_ids.shape for p in plans])
            blocks = []
            for p, move in zip(plans, order["moves"]):
                slab = self._slab_rows(padded, move, *p.row_ids.shape)
                blocks.append(p.materialize(None, gathered={
                    "x": slab[..., :d].astype(p0.raw.x.dtype),
                    "labels": slab[..., d].astype(p0.raw_labels.dtype),
                    "offsets": slab[..., d + 1].astype(
                        p0.raw_offsets.dtype),
                    "weights": slab[..., d + 2].astype(
                        p0.raw_weights.dtype),
                }))
            # The passive rows are the order's tail: their features and
            # owners are static slices, read (not gathered) by every fit.
            tail = self._re_meta[cid]["passive"]
            n_act = perm.shape[0] - (0 if tail is None else tail.size)
            home = {
                "perm": perm,
                "moves": order["moves"],
                "passive_x": (
                    None if tail is None
                    else order["stacked"][n_act:, :d].astype(
                        p0.raw.x.dtype)),
                "passive_codes": None if tail is None else jnp.take(
                    op["score_codes"], perm[n_act:]),
                "batches": {},
            }
            score_inv = None  # home's scores leave by the moves
        # bf16 slab storage (mixed precision): the gather happens
        # once per dataset generation, so the cast is amortized —
        # every later sweep reads the slab at half HBM width.
        ebs = tuple(
            dataclasses.replace(
                eb,
                x_values=precision_mod.in_storage(
                    eb.x_values, self.precision),
            )
            for eb in blocks
        )
        return {
            "ebs": ebs,
            "score_plans": tuple(
                (p.row_ids, p.row_counts, p.entity_codes)
                for p in plans
            ),
            "proj_dev": parts["proj_dev"],
            "score_inv": score_inv,
            "home": home,
        }

    def _zeros(self, shape, dtype) -> Array:
        key = (shape, jnp.dtype(dtype).name)
        z = self._zeros_cache.get(key)
        if z is None:
            z = jnp.zeros(shape, dtype)
            self._zeros_cache[key] = z
        return z

    def _operands(self, coords, initial_models):
        ops = []
        for cid in self.seq:
            coord = coords[cid]
            kind = self.kinds[cid]
            if kind == "locked":
                # Locked (partial-retrain) coordinates are score-only;
                # their model comes from initial_models exactly as in the
                # unfused CoordinateDescent (locked ids must come with a
                # model). Scoring runs eagerly — once per run, through the
                # coordinate's own jitted scorer.
                if isinstance(coord, ModelCoordinate):
                    z = coord.score()
                else:
                    if not initial_models or cid not in initial_models:
                        raise KeyError(
                            f"locked coordinate {cid!r} requires a model "
                            "in initial_models "
                            "(partialRetrainLockedCoordinates)")
                    z = coord.score(initial_models[cid])
                ops.append({"z": z})
                continue
            inner = getattr(coord, "inner", coord)
            if kind == "fixed":
                dtype = inner.batch.labels.dtype
                d = inner.batch.num_features
                init = None
                if initial_models and cid in initial_models:
                    m = initial_models[cid]
                    glm = m.model if hasattr(m, "model") else m
                    # padded_to covers models loaded with fewer features
                    # than the batch (the unfused FixedEffectCoordinate
                    # .train does the same before solving).
                    init = jnp.asarray(
                        glm.coefficients.padded_to(d).means, dtype=dtype)
                prior = None
                if inner.problem.prior is not None:
                    p = inner.problem.prior.padded_to(d)
                    prior = (jnp.asarray(p.means, dtype=dtype),
                             jnp.asarray(p.variances, dtype=dtype))
                cfg = inner.config
                ops.append({
                    "batch": inner.batch,
                    "w0": (init if init is not None
                           else self._zeros((d,), dtype)),
                    "l1": np.asarray(cfg.l1_weight, dtype=dtype),
                    "l2": np.asarray(cfg.l2_weight, dtype=dtype),
                    "iw": np.asarray(cfg.incremental_weight, dtype=dtype),
                    "prior": prior,
                })
            else:
                ds = inner.dataset
                dtype = jnp.dtype(ds.dtype)
                w0 = None
                if initial_models and cid in initial_models:
                    w0 = initial_models[cid].coefficients
                cfg = inner.config
                prior = None
                if inner.prior is not None:
                    prior = (inner.prior.coefficients,
                             inner.prior.variances)
                meta = self._re_meta[cid]
                ops.append({
                    "w0": (w0 if w0 is not None else self._zeros(
                        (ds.num_entities, ds.max_sub_dim), dtype)),
                    "l1": np.asarray(cfg.l1_weight, dtype=dtype),
                    "l2": np.asarray(cfg.l2_weight, dtype=dtype),
                    "iw": np.asarray(cfg.incremental_weight, dtype=dtype),
                    "prior": prior,
                    "factors": inner.normalization.factors,
                    "shifts": inner.normalization.shifts,
                    "score_codes": ds.score_codes,
                    "raw": ds.raw,
                    "passive": (None if meta["passive"] is None
                                else jnp.asarray(meta["passive"])),
                })
        return tuple(ops)

    def _mat_operands(self, coords) -> dict:
        mat_ops = {}
        for cid in self.seq:
            inner = getattr(coords[cid], "inner", coords[cid])
            if self.kinds[cid] == "fixed" and self._home is not None:
                # The batch to be put in home's order.
                mat_ops[cid] = {"batch": inner.batch}
            if self.kinds[cid] != "random":
                continue
            ds = inner.dataset
            meta = self._re_meta[cid]
            if meta["slices"] is not None and ds.blocks:
                b0 = ds.blocks[0]
                mat_ops[cid] = {
                    "buf": meta["buf"],
                    "raw": ds.raw,
                    "labels": b0.raw_labels,
                    "offsets": b0.raw_offsets,
                    "weights": b0.raw_weights,
                }
                if cid == self._home:
                    mat_ops[cid]["score_codes"] = ds.score_codes
            else:
                mat_ops[cid] = {
                    "plans": ds.device_plans(),
                    "proj_dev": ds.proj_device(),
                }
        return mat_ops

    def _statics(self, coords, initial_models) -> tuple:
        st = []
        for cid in self.seq:
            kind = self.kinds[cid]
            # has_init gates the in-program scoring of the warm-start
            # tables: scoring all-zero tables would waste passes on every
            # cold fit (trailing element, read as st[-1]).
            has_init = bool(initial_models and cid in initial_models)
            if kind == "locked":
                st.append(("locked",))
                continue
            inner = getattr(coords[cid], "inner", coords[cid])
            if kind == "fixed":
                cfg = inner.config
                st.append((
                    "fixed", inner.problem.task, cfg.optimizer,
                    cfg.l1_weight != 0.0, inner.problem.intercept_index,
                    cfg.variance_computation, has_init,
                ))
            else:
                s = _re_statics(inner)
                st.append((
                    "random", s["task"], s["opt_config"], s["use_owlqn"],
                    s["variance_computation"], s["direct"], s["newton"],
                    has_init,
                ))
        return tuple(st)

    # ------------------------------------------------------------------
    # the traced program
    # ------------------------------------------------------------------

    def _re_score(self, w, op, mat):
        """Model contribution per row (active+passive) in the fit's row
        order, traced.

        With a packed score map this is scatter-FREE: per-bucket score
        blocks and the passive-row scores concatenate into one flat
        vector that a single gather distributes to the rows (a TPU
        scatter-add of the same pass measured ~4x slower); the home
        coordinate's need no gather either. Otherwise mirrors
        models/game.py _score_via_buckets."""
        from photon_tpu.data.dataset import DenseFeatures

        n = op["score_codes"].shape[0]
        proj_dev = mat["proj_dev"]
        if any(eb.x_indices is not None for eb in mat["ebs"]):
            # ELL fallback bucket present: score straight off the raw shard.
            return score_raw_features(
                w, op["score_codes"], op["raw"], proj_dev)
        home = mat.get("home")
        if home is not None or mat.get("score_inv") is not None:
            parts = bucket_score_parts(
                w,
                tuple(eb.x_values for eb in mat["ebs"]),
                tuple(eb.entity_codes for eb in mat["ebs"]),
            )
            if home is not None:
                # Home: the buckets' scores leave their slabs by
                # contiguous moves; the passive rows are the order's tail
                # and score from their slice of the ordered shard.
                z = self._rows_from_slabs(
                    parts, home["moves"],
                    [eb.weights.shape for eb in mat["ebs"]], n,
                ).astype(w.dtype)
                if home["passive_x"] is not None:
                    zp = _score_raw_dense(
                        w, home["passive_codes"], home["passive_x"],
                        proj_dev)
                    z = lax.dynamic_update_slice_in_dim(
                        z, zp.astype(w.dtype), n - zp.shape[0], axis=0)
                return z
            if op["passive"] is not None:
                parts.append(passive_raw_scores(
                    w, op["passive"], op["score_codes"], op["raw"],
                    proj_dev))
            if not parts:  # no active entities AND no passive rows
                return jnp.zeros(n, dtype=w.dtype)
            flat = jnp.concatenate(parts)
            return jnp.take(
                flat, mat["score_inv"], mode="clip").astype(w.dtype)
        z = jnp.zeros(n, dtype=w.dtype)
        for (row_ids, row_counts, codes), eb in zip(
            mat["score_plans"], mat["ebs"]
        ):
            z = _bucket_score_add(
                z, eb.x_values, row_ids, row_counts, codes, w,
            )
        if op["passive"] is not None:
            pr = op["passive"]
            if isinstance(op["raw"], DenseFeatures):
                z = _passive_score_set_dense(
                    z, pr, op["score_codes"], op["raw"].x, w, proj_dev)
            else:
                z = _passive_score_set_sparse(
                    z, pr, op["score_codes"], op["raw"].indices,
                    op["raw"].values, w, proj_dev)
        return z

    def _fe_score(self, means, batch):
        """The fixed effect's scores, read through the feature-major view
        as its solve (``_run_impl``) reads them: nothing in the program
        reads the batch row-major, so XLA makes no relaid-out copy."""
        return Coefficients(means=means).compute_score(
            feature_major(batch).features)

    def _store_score(self, z):
        """Score-carry storage cast: bf16 under mixed precision (the
        per-coordinate score vectors are re-read every sweep for the
        residual algebra — half-width storage halves that traffic), the
        identity on the default f32 path."""
        if self.precision == "bfloat16":
            return z.astype(jnp.bfloat16)
        return z

    def _quantize_score(self, z):
        """Round a fresh score through the storage dtype BEFORE it
        enters the residual total: the f32 total must equal the exact
        sum of the STORED carries, or each sweep's ``total - old``
        would leave the carry's quantization residue behind and the
        residual error would grow linearly with iteration count
        instead of staying at one rounding (bf16(f32(bf16(z))) ==
        bf16(z), so the round-trip is idempotent against the stored
        value). Returns ``z`` itself on the default f32 path."""
        if self.precision == "bfloat16":
            return z.astype(jnp.bfloat16).astype(jnp.float32)
        return z

    @staticmethod
    def _read_score(zs, dtype):
        """Upcast a stored score carry back to the f32 accumulator
        dtype (identity on the default path)."""
        return zs if zs.dtype == dtype else zs.astype(dtype)

    def _fit_fn(self, ops, ebs_all, *, statics):
        num_iters = self.num_iterations
        # Convergence telemetry rides the fit program UNCONDITIONALLY as
        # extra outputs (obs/convergence.py METRICS columns): the
        # telemetry enable flag is host-side only, so the traced program
        # — and with it the dispatch census and every recompile key — is
        # byte-identical with telemetry on or off (the audited
        # `telemetry` contract in photon_tpu/obs/__init__.py).
        conv_index = {
            i: j
            for j, i in enumerate(
                i for i, st in enumerate(statics) if st[0] != "locked"
            )
        }

        # --- initial state ------------------------------------------------
        # The running TOTAL stays in f32 (it is the accumulator every
        # residual derives from); the per-coordinate score CARRIES are
        # stored through _store_score — bf16 under mixed precision.
        # The rows stand in home's order (``_mat_fn``), or canonically.
        home = ebs_all.get(self._home_of(ebs_all), {}).get("home")
        if home is not None:
            ops = tuple(
                dict(op, batch=home["batches"][cid]) if "batch" in op
                else op
                for cid, op in zip(self.seq, ops)
            )
        states: list = []
        scores: list = []
        diags: list = []
        total = None
        for i, (op, st) in enumerate(zip(ops, statics)):
            kind = st[0]
            if kind == "locked":
                states.append(())
                z = op["z"]
                if home is not None:
                    z = jnp.take(z, home["perm"])
                diags.append(())
            elif kind == "fixed":
                means = op["w0"]
                has_init = st[-1]
                variances = (
                    None
                    if st[5] == VarianceComputationType.NONE
                    else jnp.zeros_like(means)
                )
                states.append((means, variances))
                with jax.named_scope(f"coord.{self.seq[i]}/score"):
                    z = (
                        self._fe_score(means, op["batch"]) if has_init
                        else jnp.zeros(
                            op["batch"].num_samples, means.dtype)
                    )
                diags.append((
                    jnp.zeros(num_iters, jnp.int32),
                    jnp.zeros(num_iters, jnp.int32),
                ))
            else:
                w_all = op["w0"]
                has_init = st[-1]
                e = w_all.shape[0]
                v_all = (
                    None
                    if st[4] == VarianceComputationType.NONE
                    else jnp.zeros_like(w_all)
                )
                states.append((w_all, v_all))
                with jax.named_scope(f"coord.{self.seq[i]}/score"):
                    z = (
                        self._re_score(w_all, op, ebs_all[self.seq[i]])
                        if has_init
                        else jnp.zeros(
                            op["score_codes"].shape[0], w_all.dtype)
                    )
                diags.append((
                    jnp.zeros((num_iters, e), jnp.int32),
                    jnp.zeros((num_iters, e), jnp.int32),
                ))
            z = self._quantize_score(z)
            total = z if total is None else total + z
            scores.append(self._store_score(z))
        conv0 = jnp.zeros(
            (num_iters, len(conv_index), 5), dtype=total.dtype
        )

        def sweep(it, carry):
            states, scores, total, diags, conv = carry
            states = list(states)
            scores = list(scores)
            diags = list(diags)
            for i, (op, st) in enumerate(zip(ops, statics)):
                kind = st[0]
                if kind == "locked":
                    continue
                # Scopes are metadata on the operations (no operation
                # changes): coord.<cid>, and inside it residual /
                # solve.<route> (_solve_block names the random-effect
                # routes, _solve_newton_batched its own two) / score.
                with jax.named_scope(f"coord.{self.seq[i]}"):
                    z_old = self._read_score(scores[i], total.dtype)
                    with jax.named_scope("residual"):
                        residual = total - z_old
                    if kind == "fixed":
                        _, task, opt_config, use_owlqn, intercept_index, \
                            var_comp = st[:6]
                        batch = op["batch"]
                        with jax.named_scope("residual"):
                            batch = batch.with_offsets(
                                batch.offsets + residual)
                        prev_means = states[i][0]
                        route = (
                            "owlqn" if use_owlqn
                            else opt_config.optimizer_type.value.lower()
                        )
                        with jax.named_scope("solve." + route):
                            means, variances, result = _run_impl(
                                batch,
                                states[i][0],
                                op["l1"], op["l2"],
                                self._fe_norm(i),
                                op["prior"],
                                op["iw"],
                                task=task,
                                opt_config=opt_config,
                                use_owlqn=use_owlqn,
                                intercept_index=intercept_index,
                                variance_computation=var_comp,
                            )
                        states[i] = (means, variances)
                        with jax.named_scope("score"):
                            z = self._fe_score(means, op["batch"])
                        it_arr, rs_arr = diags[i]
                        diags[i] = (
                            it_arr.at[it].set(result.iterations),
                            rs_arr.at[it].set(result.convergence_reason),
                        )
                        # Solver-final objective/gradient come free from the
                        # OptResult — no extra passes over the batch.
                        conv_loss = result.value
                        conv_gnorm = result.gradient_norm
                        conv_wd = jnp.sum((means - prev_means) ** 2)
                        conv_wn = jnp.sum(means ** 2)
                    else:
                        _, task, opt_config, use_owlqn, var_comp, direct, \
                            newton = st[:7]
                        w_prev, v_prev = states[i]
                        w_all = jnp.zeros_like(w_prev)
                        v_all = None if v_prev is None else jnp.zeros_like(
                            v_prev)
                        e = w_prev.shape[0]
                        its_e = jnp.zeros(e, jnp.int32)
                        rs_e = jnp.zeros(e, jnp.int32)
                        mat = ebs_all[self.seq[i]]
                        residuals = [residual] * len(mat["ebs"])
                        if mat.get("home") is not None:
                            # Home's residuals enter each slab by a
                            # contiguous move, not through row_ids.
                            with jax.named_scope("residual"):
                                shapes = [
                                    eb.weights.shape for eb in mat["ebs"]]
                                padded = self._pad_rows(residual, shapes)
                                residuals = [
                                    self._slab_rows(padded, move, *shape)
                                    for move, shape in zip(
                                        mat["home"]["moves"], shapes)
                                ]
                        for (_, _, codes), eb, slab_residual in zip(
                            mat["score_plans"], mat["ebs"], residuals
                        ):
                            w_all, v_all, its, rs = _solve_block(
                                eb,
                                slab_residual,
                                op["factors"],
                                op["shifts"],
                                w_prev,
                                op["l1"], op["l2"], op["iw"],
                                op["prior"],
                                w_all, v_all,
                                sub_dim=eb.sub_dim,
                                task=task,
                                opt_config=opt_config,
                                use_owlqn=use_owlqn,
                                variance_computation=var_comp,
                                direct=direct,
                                newton=newton,
                                precision=self.precision,
                            )
                            its_e = its_e.at[codes].set(its)
                            rs_e = rs_e.at[codes].set(rs)
                        states[i] = (w_all, v_all)
                        with jax.named_scope("score"):
                            z = self._re_score(w_all, op, mat)
                        it_arr, rs_arr = diags[i]
                        diags[i] = (
                            it_arr.at[it].set(its_e),
                            rs_arr.at[it].set(rs_e),
                        )
                        # The batched per-entity solvers return iteration
                        # counts, not objective values: loss/grad_norm are 0
                        # for random effects (obs/convergence.py documents
                        # the column contract); the deltas below are the
                        # convergence signal that exists for every kind.
                        conv_loss = jnp.zeros((), total.dtype)
                        conv_gnorm = jnp.zeros((), total.dtype)
                        conv_wd = jnp.sum((w_all - w_prev) ** 2)
                        conv_wn = jnp.sum(w_all ** 2)
                    z = self._quantize_score(z)
                    # residual_delta_sq: movement of this coordinate's score
                    # contribution this sweep — computed on values the
                    # residual bookkeeping already holds (no extra passes).
                    conv = conv.at[it, conv_index[i]].set(
                        jnp.stack([
                            conv_loss.astype(total.dtype),
                            conv_gnorm.astype(total.dtype),
                            jnp.sum((z - z_old) ** 2).astype(total.dtype),
                            conv_wd.astype(total.dtype),
                            conv_wn.astype(total.dtype),
                        ])
                    )
                    total = total - z_old + z
                    scores[i] = self._store_score(z)
            return tuple(states), tuple(scores), total, tuple(diags), conv

        carry = (tuple(states), tuple(scores), total, tuple(diags), conv0)
        carry = lax.fori_loop(0, num_iters, sweep, carry)
        states, scores, total, diags, conv = carry
        # Pack every diagnostic array into ONE int32 buffer: one host
        # pull instead of 2 x n_coordinates of them (_PackedDiags splits
        # host-side).
        flat_parts = [
            d.reshape(-1) for pair in diags for d in pair
        ]
        packed = (
            jnp.concatenate(flat_parts) if flat_parts
            else jnp.zeros(0, jnp.int32)
        )
        return states, scores, total, packed, conv

    def _fe_norm(self, i):
        """NormalizationContext for coordinate i (host constant — factor
        arrays are tiny [d] vectors; embedding them as program constants
        is deliberate)."""
        return self._norms[i]

    def _attribute_seconds(
        self, total_seconds: float, ops, packed: _PackedDiags, diag_index
    ) -> dict[tuple[int, str], float] | None:
        """Per-(iteration, coordinate) attribution of the fit's measured
        wall — the span tracer's device-time split for fused records.

        The fit is ONE program, so per-coordinate time cannot be measured
        directly; this distributes ``total_seconds`` — the fit program's
        REAL dispatch->completion window, measured by the run span's
        root sync — proportionally to each block's analytic work estimate
        (counts from shapes, as ``benchmark/costs.py`` takes its own), using
        the MEASURED per-iteration solver counts from the packed
        diagnostics: fixed effects at iters x 4nd value/grad passes +
        scoring, random effects at mean-Newton-iters x (margins + Hessian
        contraction) over active rows + per-entity Cholesky + scoring.
        Shares sum to the measurement; they are attribution, not
        independent timings (CoordinateUpdateRecord documents the
        contract). Returns None when no work was attributable.
        """
        weights: dict[tuple[int, str], float] = {}
        for i, cid in enumerate(self.seq):
            kind = self.kinds[cid]
            if kind == "locked":
                continue
            it_idx, _ = diag_index[cid]
            iters = packed.get(it_idx)  # [T] fixed / [T, entities] random
            if kind == "fixed":
                n = ops[i]["batch"].num_samples
                d = ops[i]["batch"].num_features
                for it in range(self.num_iterations):
                    weights[(it, cid)] = (
                        (4.0 * max(float(iters[it]), 1.0) + 2.0) * n * d
                    )
            else:
                n_re = int(ops[i]["score_codes"].shape[0])
                _, s = ops[i]["w0"].shape
                # Only entities the blocks actually solve (the same keep
                # mask the diagnostics apply): phantom padded slots would
                # deflate the measured mean iteration count and inflate
                # the Cholesky term.
                keep = self._re_meta[cid]["keep"]
                kept = int(keep.sum())
                for it in range(self.num_iterations):
                    its_it = iters[it][keep] if kept else iters[it]
                    mean_it = max(
                        float(np.mean(its_it)) if its_it.size else 1.0,
                        1.0,
                    )
                    weights[(it, cid)] = (
                        mean_it * (6.0 * s + 2.0 * s * s) * n_re
                        + max(kept, 1) * s ** 3 / 3.0
                        + 2.0 * n_re * s
                    )
        total_w = sum(weights.values())
        if total_w <= 0.0:
            return None
        scale = float(total_seconds) / total_w
        return {k: v * scale for k, v in weights.items()}

    def _ledger_record(
        self, coords, sp, mat_window, t_fit0, rec_seconds, ebs_all
    ) -> None:
        """Cost-ledger accounting for one measured fit (obs/ledger.py).

        Registers the generation's two programs with LAZY static-cost
        thunks (pricing lowers at report time, never here), records the
        materialize/fit dispatch windows with per-coordinate attribution
        when the fit window was pure, accounts the slab buffers, and
        books the residual (operand assembly, AOT wait) as the explicit
        ``unattributed`` row. Only reached with telemetry on (``sp`` is
        the synced fit span — the one real measurement) and the ledger
        armed.
        """
        from photon_tpu.analysis import costmodel
        from photon_tpu.obs import ledger

        ledger.register_program(
            "materialize", phase="materialize",
            cost_thunk=lambda: costmodel.program_cost(
                self.lower_materialize(coords)),
        )
        ledger.register_program(
            "fused_fit", phase="fit",
            cost_thunk=lambda: costmodel.program_cost(
                self.lower(coords)),
        )
        # Segment-reduce kernel census rows: every instantiation the
        # tracer recorded (ops/segment_reduce._TRACED_SITES) registers
        # with its ANALYTIC cost — the kernel executes inside the fused
        # program, so it has no dispatch row of its own, but the census
        # prices its roofline next to the programs that embed it
        # (cli.profile asserts the row exists when the kernel engaged).
        from photon_tpu.ops import segment_reduce

        for site, info in segment_reduce.traced_sites().items():
            ledger.register_program(
                site, phase="score", cost=info["cost"],
            )
        mat_seconds = 0.0
        if mat_window is not None:
            t0, t1 = mat_window
            mat_seconds = t1 - t0
            ledger.record_dispatch(
                "materialize", mat_seconds, phase="materialize",
                start=t0, end=t1,
            )
            ledger.set_resident(
                "fused_fit/slabs", ledger.tree_nbytes(ebs_all)
            )
        fit_seconds = max(sp.t1 - t_fit0, 0.0)
        parts = None
        if rec_seconds:
            # Fold the per-(iteration, coordinate) attribution down to
            # per-coordinate shares; an impure window (cold fallback,
            # retried attempt) keeps parts=None and the whole fit
            # window lands as ONE measured-only row — degradation, not
            # a fabricated split.
            parts = {}
            for (_, cid), s in rec_seconds.items():
                parts[cid] = parts.get(cid, 0.0) + s
        ledger.record_dispatch(
            "fused_fit", fit_seconds, phase="fit",
            start=t_fit0, end=sp.t1, parts=parts,
        )
        ledger.record_unattributed(
            max(sp.seconds - fit_seconds - mat_seconds, 0.0)
        )

    # ------------------------------------------------------------------
    # abstract lowering (the semantic auditor / cost model entry)
    # ------------------------------------------------------------------

    def trace(self, coords, initial_models=None):
        """Abstractly trace (never execute) the whole-fit program.

        The slab-materialization outputs enter as ``jax.eval_shape``
        avals, so no gather runs. This is the ONE operand-assembly path
        the program auditor (analysis/program.py) and the static cost
        model (analysis/costmodel.py) share with ``run`` — the audited
        jaxpr is the production program by construction. Returns the
        ``jax.stages.Traced`` (``.jaxpr``, ``.lower()``).
        """
        ops = self._operands(coords, initial_models)
        statics = self._statics(coords, initial_models)
        ebs_avals = jax.eval_shape(
            self._mat_fn, self._mat_operands(coords)
        )
        return self._jit.trace(ops, ebs_avals, statics=statics)

    def lower(self, coords, initial_models=None):
        """Lower (never execute) the whole-fit program for these coords."""
        return self.trace(coords, initial_models).lower()

    def lower_materialize(self, coords):
        """Lower (never execute) the slab materialization program."""
        return self._mat_jit.lower(self._mat_operands(coords))

    def compile_programs(self, coords, initial_models=None) -> dict:
        """The materialize and fit programs, compiled ahead of their first
        run: the AOT warm compile's artifact (``key`` is the caller's).

        The SAME operand assembly as ``trace``/``run`` (the audited
        ingest-pipeline contract pins that these jaxprs match the
        production generation's signatures exactly), packaged with the
        statics so the caller can key the compiled executables.

        A home order stands only if the device has room for it
        (``_home_fits``): where the compiled programs say it has not, home
        is given up and both are compiled once more in the canonical
        order. ``home`` says which order the executables are for."""
        from photon_tpu.utils.compile_cache import aot_compile

        while True:
            mat_ops = self._mat_operands(coords)
            mat_traced = self._mat_jit.trace(mat_ops)
            ebs_avals = jax.eval_shape(self._mat_fn, mat_ops)
            ops = self._operands(coords, initial_models)
            statics = self._statics(coords, initial_models)
            fit_traced = self._jit.trace(ops, ebs_avals, statics=statics)
            art = {
                "statics": statics,
                "layout": self.packed_layout(),
                "home": self._home,
                "mat": aot_compile(
                    mat_traced.lower(), ledger_key="fused_fit/materialize"),
                "fit": aot_compile(
                    fit_traced.lower(), ledger_key="fused_fit/fit"),
                "mat_text": str(mat_traced.jaxpr),
                "fit_text": str(fit_traced.jaxpr),
            }
            if self._home is None or self._home_fits(art, mat_ops, ops):
                return art
            self._home = None

    @staticmethod
    def _home_fits(art: dict, mat_ops, ops) -> bool:
        """Whether the device holds both compiled programs beside what
        they keep on it, by the compiler's own account
        (``memory_analysis``, which counts every array in the layout the
        device stores it in): the data set's arrays (the materialize
        program's arguments, and what a fit is handed besides) and the
        materialize program's outputs for as long as the prepared data
        set lives, a fit's outputs, and the larger of the two programs'
        scratch, which the device reserves when a program is loaded and
        keeps. No limit or no account (the CPU states no limit): it fits.
        ``art["memory"]`` keeps the sums."""
        limit = _device_bytes_limit()
        mat = art["mat"].memory_analysis()
        fit = art["fit"].memory_analysis()
        if limit is None or mat is None or fit is None:
            return True
        counted = {id(a) for a in jax.tree.leaves(mat_ops)}
        besides = {
            id(a): int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
            for a in jax.tree.leaves(ops)
            if hasattr(a, "shape") and id(a) not in counted
        }
        art["memory"] = memory = {
            "resident": mat.argument_size_in_bytes
            + mat.output_size_in_bytes + fit.output_size_in_bytes
            + sum(besides.values()),
            "scratch": max(mat.temp_size_in_bytes, fit.temp_size_in_bytes),
            "limit": limit,
        }
        fits = memory["resident"] + memory["scratch"] <= limit
        if not fits:
            logger.info(
                "fused fit: no room for home order %r on the device "
                "(%s); the canonical order is kept", art["home"], memory)
        return fits

    def _consume_aot(self) -> dict | None:
        """Resolve the pending warm-compile future (blocking if the
        compile is still running — that block is the measured
        ``compile_wait`` stage, the non-overlapped remainder) and keep
        the artifacts when they belong to this static structure."""
        fut = self._aot_future
        if fut is not None:
            from photon_tpu.data.pipeline import PIPELINE_STATS

            self._aot_future = None
            with PIPELINE_STATS.stage("compile_wait"):
                art = fut.result()
            if art is not None and art.get("key") == self.static_key:
                self._aot = art
        return self._aot

    def packed_layout(self) -> dict:
        """Per random-effect coordinate, the STATIC (offset, shape)
        slices the materialize program cuts out of the packed plan
        buffer. Not part of any operand's aval: an executable compiled
        for another layout would be accepted by the aval check and read
        the wrong plan arrays, so ``_run_mat`` compares layouts itself."""
        return {cid: m["slices"] for cid, m in self._re_meta.items()}

    def _settle_home(self, coords, initial_models, aot):
        """Before the slabs are built: the prepared data set takes the
        order its compiled programs are for, since ``compile_programs``
        has asked the device. Where no warm compile came (a warm start, a
        validation set) and the device states a limit, the programs are
        compiled here and kept. Returns the artifact to run."""
        if self._home is None:
            return aot
        if aot is None and _device_bytes_limit() is not None:
            aot = self._aot = self.compile_programs(coords, initial_models)
        if aot is not None and aot.get("home") != self._home:
            self._home = None
        return aot

    def _run_mat(self, coords, aot):
        """Materialize slabs via the AOT executable when compatible."""
        mat_ops = self._mat_operands(coords)
        if aot is not None:
            if aot.get("layout") != self.packed_layout():
                logger.info(
                    "ingest pipeline: AOT materialize executable compiled "
                    "for another packed layout; recompiling")
                return self._mat_jit(mat_ops)
            try:
                return aot["mat"](mat_ops)
            except TypeError:
                # A stale shape prediction, and only that: a compiled
                # executable called with other avals or another pytree
                # raises TypeError before anything reaches the device.
                # A device failure (out of HBM, a kernel fault) is not
                # caught here and surfaces with its own message.
                logger.info(
                    "ingest pipeline: AOT materialize executable "
                    "incompatible with the built datasets; recompiling")
        return self._mat_jit(mat_ops)

    # ------------------------------------------------------------------
    # the public entry
    # ------------------------------------------------------------------

    def run(
        self,
        coords: dict[str, object],
        initial_models: dict[str, object] | None = None,
    ) -> CoordinateDescentResult:
        from photon_tpu import obs

        # The whole-fit span is the telemetry layer's device-time ROOT:
        # with telemetry enabled it syncs on the program outputs at exit
        # (the one host sync per fit, at the point the caller's first
        # blocking read would have paid anyway) so the host/device split
        # and the per-record attribution below come from a real
        # measurement. Disabled, the span is a no-op and the dispatch
        # stays fully asynchronous — the pre-telemetry behavior.
        # Inside it the always-recorded stages (obs.stage): `fit` from
        # entry to the return of the dispatch, NOT to the device's end,
        # and its parts `fit.operands`, `compile_wait` (_consume_aot),
        # `fit.materialize`, `fit.dispatch`. None of them syncs.
        with obs.span("fused_fit") as sp, obs.stage("fit") as fit_stage:
            with obs.stage("fit.operands"):
                ops = self._operands(coords, initial_models)
                statics = self._statics(coords, initial_models)
            aot = self._consume_aot()
            # Slabs materialize once per dataset generation (separate
            # cached program that also unpacks the ingest's packed plan
            # buffer); every fit's program receives the results as plain
            # operands. When the estimator provides a share, sibling
            # programs (other static keys of the same generation) reuse
            # the same device slabs.
            # The materialize window (cost-ledger row when armed): only
            # a run that actually gathered slabs records one — a cache
            # hit dispatched nothing.
            mat_window = None
            share = self._mat_shared
            ebs_all = (
                share.get("ebs") if share is not None else self._mat_cache
            )
            if ebs_all is None:
                with obs.stage("fit.materialize") as mat_stage:
                    aot = self._settle_home(coords, initial_models, aot)
                    ebs_all = self._run_mat(coords, aot)
                mat_window = (mat_stage.t0, mat_stage.t1)
                if share is not None:
                    share["ebs"] = ebs_all
                else:
                    self._mat_cache = ebs_all
            # The attribution window opens HERE: operand assembly, the
            # AOT compile wait, and slab materialization above are not
            # fit work and must not be charged to coordinate records.
            t_fit0 = time.perf_counter()
            fit_window_pure = True

            def dispatch_once():
                # The `fit.dispatch` injection point fires BEFORE any
                # executable is entered, so an injected transient fault
                # exercises the retry path without touching device
                # state; the retry wrapper re-runs this whole selection
                # (AOT-or-jit), which is idempotent — operands are
                # unchanged and both paths are pure dispatches.
                from photon_tpu.resilience import faults

                nonlocal fit_window_pure
                faults.check("fit.dispatch")
                res = None
                if aot is not None and statics == aot.get("statics"):
                    try:
                        res = aot["fit"](ops, ebs_all)
                    except TypeError:
                        # Stale shape prediction only (see _run_mat).
                        # Backend faults are not TypeErrors: transient
                        # ones reach the retry wrapper with the
                        # executable kept, and a deterministic device
                        # failure surfaces once instead of being
                        # relabelled and recompiled into the same wall.
                        logger.info(
                            "ingest pipeline: AOT fit executable "
                            "incompatible with the built datasets; "
                            "recompiling")
                        self._aot = None
                if res is None:
                    # A first jit-fallback entry traces + compiles inside
                    # the window: not pure fit execution (see _jit_seen).
                    # AND (not assign): a retried second attempt would
                    # find statics in _jit_seen and flip a window that
                    # already contained attempt 1's trace back to pure.
                    fit_window_pure = (
                        fit_window_pure and statics in self._jit_seen
                    )
                    res = self._jit(ops, ebs_all, statics=statics)
                    self._jit_seen.add(statics)
                return res

            def _mark_impure(attempt, exc):
                # Any retry puts a failed attempt + the backoff sleep
                # inside the t_fit0 window — never attribute it.
                nonlocal fit_window_pure
                fit_window_pure = False

            from photon_tpu.resilience import retry

            # Trace, lower and cache load or compile on a first entry; a
            # bare enqueue on a warm one.
            with obs.stage("fit.dispatch"):
                out = retry.call_with_retry(
                    dispatch_once, site="fused_fit.dispatch",
                    on_retry=_mark_impure,
                )
            states, scores, total, packed_flat, conv = out
            if sp is not None:
                sp.sync = out
            fit_stage.attrs = self._fit_attrs(coords, ebs_all)
        if sp is not None:
            obs.convergence.record(
                tuple(
                    cid for cid in self.seq
                    if self.kinds[cid] != "locked"
                ),
                conv,
            )
            obs.REGISTRY.counter("fused_fits_total").inc()
            obs.REGISTRY.histogram("fused_fit_wall_seconds").observe(
                sp.seconds)
            if sp.device_wait_seconds is not None:
                obs.REGISTRY.histogram(
                    "fused_fit_device_wait_seconds"
                ).observe(sp.device_wait_seconds)
        # Numerics sentinel (obs/health.py): park the SAME convergence
        # block — an output the fit program already computes — for lazy
        # non-finite scanning at gate/report time. Reference
        # bookkeeping only: no sync, no transfer, no program change
        # (the audited `health` contract), and it works with health
        # armed alone (telemetry's span sync is not required).
        if obs.health.enabled():
            obs.health.sentinel_watch(
                tuple(
                    cid for cid in self.seq
                    if self.kinds[cid] != "locked"
                ),
                conv,
            )
        # Diagnostic shapes, in the exact flattening order of _fit_fn's
        # packing; indices into _PackedDiags per coordinate.
        shapes: list[tuple] = []
        diag_index: dict[str, tuple[int, int]] = {}
        t = self.num_iterations
        for i, cid in enumerate(self.seq):
            kind = self.kinds[cid]
            if kind == "locked":
                continue
            if kind == "fixed":
                shape = (t,)
            else:
                e = ops[i]["w0"].shape[0]
                shape = (t, e)
            diag_index[cid] = (len(shapes), len(shapes) + 1)
            shapes.extend([shape, shape])
        packed = _PackedDiags(packed_flat, shapes)

        models: dict[str, object] = {}
        history: list[CoordinateUpdateRecord] = []
        # The whole descent is ONE device program here: per-coordinate
        # time is not independently measurable. With telemetry DISABLED,
        # records carry seconds=None (never a synthetic uniform split
        # consumers would read as measured). With telemetry ENABLED the
        # span above measured the fit program's real dispatch->
        # completion window (materialize/AOT-wait excluded), and each
        # record gets its analytic ATTRIBUTION of that measurement —
        # weighted by the coordinate's measured iteration counts x
        # static shape work (see _attribute_seconds and the
        # CoordinateUpdateRecord contract).
        rec_seconds = None
        if sp is not None and sp.device_wait_seconds is not None:
            # The attributed total is the FIT window only — from the fit
            # program's dispatch (t_fit0, after materialize/AOT wait) to
            # the span's post-sync completion — so compile_wait and slab
            # gathering never masquerade as per-coordinate device work.
            # A cold jit-fallback entry traces/compiles INSIDE that
            # window, so it is attributed only when pure: cold-fallback
            # records keep seconds=None (the pipeline stats report the
            # compile separately) and the span carries fit_window_pure
            # so exporters can say why.
            fit_seconds = max(sp.t1 - t_fit0, 0.0)
            if sp.attrs is None:
                sp.attrs = {}
            sp.attrs["fit_seconds"] = round(fit_seconds, 6)
            sp.attrs["fit_window_pure"] = fit_window_pure
            if fit_window_pure:
                # This forces the packed-diagnostics host pull per fit —
                # a deliberate trade against laziness: records carry a
                # plain float (frozen-dataclass API), the buffer is
                # already synced by the span root (zero-copy on CPU, a
                # small DMA at bench scale on the chip), and the
                # pull shares _PackedDiags' cache, so diagnostics
                # consumers never fetch a second time.
                rec_seconds = self._attribute_seconds(
                    fit_seconds, ops, packed, diag_index)
        from photon_tpu.obs import ledger

        if ledger.enabled() and sp is not None:
            self._ledger_record(
                coords, sp, mat_window, t_fit0, rec_seconds, ebs_all)
        for i, cid in enumerate(self.seq):
            coord = coords[cid]
            kind = self.kinds[cid]
            if kind == "locked":
                models[cid] = (
                    coord.model if isinstance(coord, ModelCoordinate)
                    else initial_models[cid]
                )
                continue
            inner = getattr(coord, "inner", coord)
            if kind == "fixed":
                means, variances = states[i]
                glm = GeneralizedLinearModel(
                    Coefficients(means=means, variances=variances),
                    inner.problem.task,
                )
                models[cid] = FixedEffectModel(
                    glm, coords[cid].feature_shard_id)
            else:
                ds = inner.dataset
                w_all, v_all = states[i]
                models[cid] = RandomEffectModel(
                    coefficients=w_all,
                    random_effect_type=ds.config.random_effect_type,
                    feature_shard_id=ds.config.feature_shard_id,
                    task=inner.task,
                    proj_all=ds.proj_all,
                    variances=v_all,
                    entity_keys=ds.entity_keys,
                )
        for it in range(self.num_iterations):
            for i, cid in enumerate(self.seq):
                kind = self.kinds[cid]
                if kind == "locked":
                    continue
                it_idx, rs_idx = diag_index[cid]
                if kind == "fixed":
                    diag = FusedFixedEffectStats(packed, it_idx, rs_idx, it)
                else:
                    keep = self._re_meta[cid]["keep"]
                    diag = RandomEffectTrainingStats.from_thunk(
                        lambda packed=packed, it_idx=it_idx,
                        rs_idx=rs_idx, it=it, keep=keep: (
                            packed.get(rs_idx)[it][keep],
                            packed.get(it_idx)[it][keep],
                        )
                    )
                history.append(CoordinateUpdateRecord(
                    iteration=it,
                    coordinate_id=cid,
                    seconds=(
                        None if rec_seconds is None
                        else rec_seconds[(it, cid)]
                    ),
                    diagnostics=diag,
                    evaluation=None,
                ))
        final = GameModel(dict(models))
        return CoordinateDescentResult(
            model=final,
            best_model=final,
            best_evaluation=None,
            history=tuple(history),
        )

    _fit_attrs_cache: dict | None = None  # an instance's own once made

    def _fit_attrs(self, coords, ebs_all) -> dict:
        """The ``fit`` stage's attributes: per random-effect coordinate
        what ``fit_stage_coordinate`` gives and per fixed-effect
        coordinate its ``fe_layout`` (the unfused loop's ``fit`` stage
        carries the same), then ``home`` and ``gather_indices``.
        Host ints and strings from shapes alone, made on the first fit of
        this prepared data set and handed to every later one: a warm fit
        pays one attribute read."""
        attrs = self._fit_attrs_cache
        if attrs is None:
            per_coord, rows, fe_layout = {}, {}, {}
            for cid in self.seq:
                inner = getattr(coords[cid], "inner", coords[cid])
                if self.kinds[cid] == "fixed":
                    fe_layout[cid] = feature_layout(inner.batch)
                if self.kinds[cid] != "random":
                    continue
                rows[cid] = inner.dataset.num_rows
                per_coord[cid] = fit_stage_coordinate(
                    inner, ebs_all[cid]["ebs"], precision=self.precision)
            # Indices a CD iteration still gathers element by element:
            # every coordinate but home reads its residuals through
            # row_ids (slab slots), its scores through the score map
            # (rows) and its passive rows' features by row.
            home = self._home_of(ebs_all)
            attrs = self._fit_attrs_cache = {
                "coordinates": per_coord,
                "fe_layout": fe_layout,
                "home": home,
                "gather_indices": sum(
                    c["slab_rows"] + rows[cid] + c.get("passive_rows", 0)
                    for cid, c in per_coord.items() if cid != home
                ),
            }
        return attrs
