"""GameEstimator: the main fit() API orchestrating GAME training.

TPU-native counterpart of photon-api estimators/GameEstimator.scala:55. The
reference's fit (:397-491) converts a DataFrame to a GameDatum RDD, builds
per-coordinate datasets (prepareTrainingDatasets :557-638), prepares the
validation evaluation suite (:649-673), constructs coordinates via
CoordinateFactory (:783) and runs coordinate descent once per optimization
configuration, warm-starting each run from the previous one (:452-468).

Here ingest already produced a columnar GameDataset; fit builds device-side
coordinate datasets once (random-effect block construction is the expensive
step and is cached across the lambda-grid configs, like the reference reuses
its persisted RDD datasets), then runs one CoordinateDescent per
configuration.
"""

from __future__ import annotations

import dataclasses
import logging
from collections import OrderedDict
from typing import Union

import jax

from photon_tpu.algorithm.coordinate import FixedEffectCoordinate
from photon_tpu.algorithm.coordinate_descent import (
    CoordinateDescent,
    CoordinateDescentResult,
    ValidationContext,
)
from photon_tpu.algorithm.problems import (
    GLMOptimizationConfiguration,
    GLMOptimizationProblem,
)
from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
from photon_tpu.data.game_data import GameDataset
from photon_tpu.data.random_effect import (
    PendingRandomEffectDataset,
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_tpu.transformers import (
    fixed_effect_scorer,
    random_effect_scorer,
)
from photon_tpu.evaluation.evaluators import EvaluatorSpec
from photon_tpu.evaluation.suite import EvaluationResults, make_suite
from photon_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
    remap_random_effect_model,
)
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.parallel.mesh import (
    loop_rows,
    resolve_mesh,
    shard_batch,
    shard_random_effect_dataset,
)
from photon_tpu.types import TaskType

Array = jax.Array
logger = logging.getLogger(__name__)

# Distinct fused whole-fit programs retained per estimator. Each entry
# pins one compiled fit executable; the dataset-scale device buffers (the
# materialized bucket slabs) are shared across entries through the
# generation's _fused_mat_share, so the bound limits executables, not
# slab HBM. A handful covers realistic mixed-optimizer config grids.
_FUSED_CACHE_SIZE = 8

# Program contracts (audited by `python -m photon_tpu.analysis
# --semantic`; machinery in analysis/program.py). The first pins the
# _fused_cache static-key discipline: a λ-grid sweep maps to ONE cache
# key (one whole-fit executable re-entered with new traced weights) and
# only a genuinely-static change (optimizer swap) mints a second. The
# second pins the unfused coordinate update (_run_impl under jit): λ and
# warm-start coefficients are traced operands, so one executable serves
# the entire grid.
# Host-concurrency contract (audited by `python -m photon_tpu.analysis
# --concurrency`). The estimator owns no locks: all mutable estimator
# state (_fit_cache, _fused_cache, _aot_future, _primed_datasets) is
# written by the single training thread only. What it DOES own is
# thread entries — per-coordinate planners on the ingest plan pool
# (`build_one`), the background AOT warm compile on the compile pool
# (`_warm_compile`), and the compile-priming thunks (`thunk` inside
# `warmup_thunks`; the ModelCoordinate lambda in `_prime_compilations`
# is the same shape) — and the declared reasons why the JAX entries on
# those threads are safe. Results always come back to the training
# thread through Futures (every one consumed — see consume_futures).
CONCURRENCY_AUDIT = dict(
    name="game-estimator-host",
    locks={},
    thread_entries=(
        "_build_datasets.build_one",
        "_warm_compile",
        "warmup_thunks.thunk",
    ),
    jax_dispatch_ok={
        "_warm_compile": "XLA compiles in C++ with the GIL released — "
        "that release IS the overlap win; the traced skeletons are "
        "thread-private, the persistent compile cache is thread-safe "
        "in JAX, and FusedFit.run serializes consumption through the "
        "future (compile_wait measures any residual block)",
        "warmup_thunks.thunk": "priming executes real warm-up solves "
        "concurrently BY DESIGN (the compiler handles concurrent "
        "requests ~2.5x faster); single-device only — the mesh path "
        "returns before submitting because collective rendezvous must "
        "not interleave (see _prime_compilations docstring)",
    },
)

PROGRAM_AUDIT = [
    dict(
        name="fused-cache-key",
        entry="estimators.game_estimator.GameEstimator._fused_for "
        "(fused_static_key discipline)",
        builder="build_fused_cache_keys",
        max_programs=1,
        stable_under=("lambda_grid",),
        recompiles_on=("optimizer_swap", "precision"),
    ),
    dict(
        name="unfused-coordinate-update",
        entry="algorithm.problems._run_impl "
        "(via GLMOptimizationProblem.run)",
        builder="build_unfused_update",
        max_programs=1,
        stable_under=("lambda_grid", "warm_start"),
        recompiles_on=("optimizer_swap",),
        hot_loop=True,
    ),
]

# Default primary evaluator per task (GameEstimator.scala:673
# prepareValidationEvaluators falls back to the task's default evaluator).
_DEFAULT_EVALUATOR = {
    TaskType.LOGISTIC_REGRESSION: "AUC",
    TaskType.LINEAR_REGRESSION: "RMSE",
    TaskType.POISSON_REGRESSION: "POISSON_LOSS",
    TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM: "AUC",
}


# Feature count above which "auto" feature sharding goes column-wise — the
# reference's own threshold for switching to off-heap PalDB indexes
# (index/FeatureIndexingDriver.scala:40-41 recommends them >200k features).
AUTO_COLUMN_SHARDING_THRESHOLD = 200_000


@dataclasses.dataclass(frozen=True)
class FixedEffectCoordinateConfiguration:
    """Reference: FixedEffectDataConfiguration + its optimization config.

    ``feature_sharding`` picks the coefficient placement on a mesh:

    - ``"replicated"`` (default): coefficients replicated per device, batch
      rows sharded (dp) — right for d that fits every chip's HBM.
    - ``"column"``: the FEATURE axis is sharded (tp): each device owns a
      contiguous coefficient range and the ELL entries whose feature falls
      in it; margins psum over ICI, gradient scatters stay device-local
      (parallel/mesh.py FeatureShardedSparse). This is the product path for
      the reference's "hundreds of billions of coefficients" axis
      (README.md:56, carried there by PalDB off-heap indexes,
      index/PalDBIndexMap.scala:43 + sparse vectors).
    - ``"auto"``: column when a mesh is active and the shard's feature count
      exceeds AUTO_COLUMN_SHARDING_THRESHOLD, else replicated.

    Without a mesh every mode degrades to the single-device replicated path.
    """

    feature_shard_id: str
    optimization: GLMOptimizationConfiguration = dataclasses.field(
        default_factory=GLMOptimizationConfiguration
    )
    feature_sharding: str = "replicated"

    def __post_init__(self):
        if self.feature_sharding not in ("replicated", "column", "auto"):
            raise ValueError(
                f"feature_sharding must be 'replicated', 'column' or "
                f"'auto', got {self.feature_sharding!r}")


@dataclasses.dataclass(frozen=True)
class RandomEffectCoordinateConfiguration:
    """Reference: RandomEffectDataConfiguration + its optimization config."""

    data: RandomEffectDataConfiguration
    optimization: GLMOptimizationConfiguration = dataclasses.field(
        default_factory=GLMOptimizationConfiguration
    )


CoordinateConfiguration = Union[
    FixedEffectCoordinateConfiguration, RandomEffectCoordinateConfiguration
]


@dataclasses.dataclass(frozen=True)
class _FixedEffectModelAdapter:
    """Adapts FixedEffectCoordinate (which speaks bare GLMs) to the GAME
    model vocabulary: train/score exchange shard-tagged FixedEffectModels so
    the composite GameModel knows each sub-model's feature shard."""

    inner: FixedEffectCoordinate
    feature_shard_id: str

    def train(self, residuals=None, initial_model=None, *, seed: int = 0):
        init = initial_model.model if initial_model is not None else None
        glm, diag = self.inner.train(residuals, init, seed=seed)
        return FixedEffectModel(glm, self.feature_shard_id), diag

    def score(self, model: FixedEffectModel):
        return self.inner.score(model.model)

    def programs_per_update(self) -> dict:
        return self.inner.programs_per_update()

    def warmup_thunks(self):
        def thunk():
            model, _ = self.train()
            jax.block_until_ready(self.score(model))

        return [thunk]


@dataclasses.dataclass(frozen=True)
class GameFitResult:
    """One (configuration, trained model) pair from the config sequence.

    Reference: GameEstimator.fit returns Seq[(GameModel, Option[EvaluationResults],
    GameOptimizationConfiguration)].
    """

    model: GameModel  # best-by-validation model of this config's CD run
    config: dict[str, GLMOptimizationConfiguration]
    evaluation: EvaluationResults | None
    # None for a completed-config result rebuilt on resume (the config
    # ran to completion in the interrupted process; its per-update
    # history died with it — model/evaluation are reconstructed from
    # the retained config-final checkpoint).
    descent: CoordinateDescentResult | None


def _log_orphaned_compile(fut) -> None:
    """Done-callback consuming an orphaned warm-compile future (a
    prepare() superseded it mid-compile): the result is discarded by
    design, but an exception must be seen, not dropped."""
    exc = fut.exception()
    if exc is not None:
        logger.warning(
            "orphaned AOT warm compile raised after being superseded: "
            "%r", exc,
        )


class GameEstimator:
    """Reference: estimators/GameEstimator.scala:55.

    ``coordinate_configs`` is ordered; its key order is the default update
    sequence (the reference's coordinateUpdateSequence param).
    """

    def __init__(
        self,
        task: TaskType,
        coordinate_configs: dict[str, CoordinateConfiguration],
        *,
        update_sequence: list[str] | None = None,
        num_iterations: int = 1,
        normalization: dict[str, NormalizationContext] | None = None,
        intercept_indices: dict[str, int] | None = None,
        evaluators: list[str | EvaluatorSpec] | None = None,
        locked_coordinates: set[str] | None = None,
        incremental_training: bool = False,
        mesh="auto",
        listeners=None,
        non_finite_guard: bool = False,
        precision: str = "float32",
    ):
        self.task = task
        self.coordinate_configs = dict(coordinate_configs)
        self.update_sequence = (
            list(update_sequence)
            if update_sequence is not None
            else list(coordinate_configs)
        )
        for cid in self.update_sequence:
            if cid not in self.coordinate_configs:
                raise KeyError(f"update sequence id {cid!r} has no config")
        self.num_iterations = num_iterations
        self.normalization = dict(normalization or {})
        self.intercept_indices = dict(intercept_indices or {})
        self.evaluators = list(evaluators or [])
        self.locked_coordinates = set(locked_coordinates or ())
        # Incremental training: the initial model becomes a per-coefficient
        # Gaussian prior (GameEstimator.scala incrementalTraining param;
        # invariants validated at fit time, :241-382).
        self.incremental_training = incremental_training
        # Multi-device execution. The reference's drivers are distributed by
        # default — GameTrainingDriver.run executes on the cluster session
        # (SparkSessionConfiguration.scala:109) — so "auto" spans all visible
        # devices: fixed-effect batches are row-sharded (dp) and
        # random-effect entity axes are sharded (ep) over a one-axis mesh.
        # Pass "off"/None for single-device, or a jax.sharding.Mesh / device
        # count to control placement explicitly.
        self.mesh = mesh
        # Resilience: per-update NaN/inf guard with rollback in the CD
        # loop (needs a host boundary per update, so it rides the
        # unfused path — see fit()'s fused gating).
        self.non_finite_guard = bool(non_finite_guard)
        # Mixed-precision policy (ops/precision.py; PERFORMANCE.md):
        # "bfloat16" stores random-effect slabs + fused score carries in
        # bf16 with f32 accumulators everywhere a sum crosses a row
        # axis; "float32" (default) is the historical path. Part of the
        # fused static key — the declared `precision` recompile family
        # (the λ grid still adds ZERO programs at either setting).
        from photon_tpu.ops import precision as _precision_mod

        self.precision = _precision_mod.resolve(precision)
        # Training-event fan-out (events.EventEmitter listener registry):
        # CoordinateUpdateEvent per coordinate update, FitEndEvent per
        # optimization config (EventEmitter.scala:24 for the GAME path).
        self.emitter = None
        if listeners:
            from photon_tpu.events import EventEmitter

            self.emitter = EventEmitter(listeners)

    def resolve_mesh(self):
        """mesh param -> Mesh | None (resolved once; devices don't change)."""
        if not hasattr(self, "_resolved_mesh"):
            self._resolved_mesh = resolve_mesh(self.mesh)
        return self._resolved_mesh

    # ------------------------------------------------------------------
    # dataset / coordinate construction (prepareTrainingDatasets + factory)
    # ------------------------------------------------------------------

    def _shard_norm(self, shard: str) -> NormalizationContext:
        return self.normalization.get(shard, NormalizationContext())

    def _build_datasets(
        self, data: GameDataset, initial_model: GameModel | None = None
    ) -> dict[str, object]:
        """The expensive one-time step: per-coordinate device datasets.

        A prior model's per-entity feature support is unioned into the
        subspace projectors (RandomEffectDataset.scala:390-426) so its
        coefficients keep their slots under warm start.

        With a mesh, fixed-effect batches are padded and row-sharded (dp)
        and random-effect entity axes sharded (ep) — the product-surface
        analog of GameTrainingDriver running on the cluster session
        (GameTrainingDriver.scala:363-516).
        """
        from photon_tpu.data.dataset import DualEllFeatures

        mesh = self.resolve_mesh()
        if mesh is None:
            data = data.on_device()

        def build_one(cid: str, cfg):
            from photon_tpu.resilience import faults

            # Chaos boundary: a planner thunk dying on the plan pool
            # must propagate through consume_futures, not hang the fit.
            faults.check("ingest.plan")
            if isinstance(cfg, RandomEffectCoordinateConfiguration):
                extra = None
                if initial_model is not None and cid in initial_model:
                    prior = initial_model[cid]
                    if isinstance(prior, RandomEffectModel):
                        tag = data.id_tags[cfg.data.random_effect_type]
                        extra = {}
                        for eo, key in enumerate(prior.entity_keys):
                            # vocab keys are str-normalized at ingest;
                            # models saved before normalization may carry
                            # numeric keys.
                            code = tag.vocab.get(str(key))
                            if code is not None:
                                p = prior.proj_all[eo]
                                extra[code] = p[p >= 0]
                # Device placement is deferred: every coordinate's plan
                # arrays ride ONE packed transfer below (PendingRandomEffect
                # Dataset), so the host link's per-transfer setup is paid
                # once per fit, not once per coordinate. Materialized
                # layouts (DualEll shards etc.) come back finalized and are
                # sharded here; pendings shard after _resolve_pending.
                ds = build_random_effect_dataset(
                    data,
                    cfg.data,
                    intercept_index=self.intercept_indices.get(
                        cfg.data.feature_shard_id
                    ),
                    extra_features=extra,
                    defer_transfer=True,
                )
                if mesh is not None and not isinstance(
                    ds, PendingRandomEffectDataset
                ):
                    ds = shard_random_effect_dataset(ds, mesh)
                return ds
            if mesh is not None and self._wants_column_sharding(data, cfg):
                return self._build_column_sharded_batch(data, cfg, mesh)
            batch = data.shard_batch(cfg.feature_shard_id)
            if mesh is not None:
                if isinstance(batch.features, DualEllFeatures):
                    logger.info(
                        "coordinate %s: DualEll features are not "
                        "row-shardable; leaving replicated", cid)
                else:
                    batch = shard_batch(batch, mesh)
            return batch

        # Per-coordinate planning runs CONCURRENTLY on the ingest pipeline's
        # plan pool: the planners' hot numpy ops (radix argsort, bincount,
        # fancy gathers, segment-OR) release the GIL, and each coordinate's
        # within-pass chunking rides the separate chunk pool (pipeline.py
        # owns the two-level layout and the deadlock argument). Results are
        # bit-identical to the serial order — builds are independent and the
        # ordered wait below reproduces the dict order exactly; device
        # placement for ALL coordinates is still deferred into one packed
        # transfer. PHOTON_TPU_SERIAL_INGEST=1 restores the in-line path.
        from photon_tpu.data import pipeline

        futs = {
            cid: pipeline.plan_executor.submit(build_one, cid, cfg)
            for cid, cfg in self.coordinate_configs.items()
            if isinstance(cfg, RandomEffectCoordinateConfiguration)
        }
        # consume_futures: every planner's exception is observed even
        # when an earlier coordinate's build already failed (the naive
        # per-future .result() loop abandons — and silences — the rest).
        planned = dict(
            zip(futs, pipeline.consume_futures(futs.values()))
        )
        out = {
            cid: (
                planned[cid] if cid in planned else build_one(cid, cfg)
            )
            for cid, cfg in self.coordinate_configs.items()
        }
        return self._resolve_pending(out, mesh)

    def _resolve_pending(self, out: dict[str, object], mesh):
        """Place all deferred plan arrays with one packed transfer."""
        from photon_tpu.data.random_effect import (
            PendingRandomEffectDataset,
            _plan_arrays_to_device,
        )

        pending = {
            cid: d for cid, d in out.items()
            if isinstance(d, PendingRandomEffectDataset)
        }
        if not pending:
            return out
        if mesh is not None:
            # The plan arrays go from the host to the devices that share
            # them (shard_random_effect_dataset pads them there): the
            # packed buffer would put every one of them on one device
            # first.
            for cid, p in pending.items():
                out[cid] = shard_random_effect_dataset(
                    p.finalize(None), mesh)
            return out
        all_flat: list = []
        spans: dict[str, tuple[int, int]] = {}
        for cid, p in pending.items():
            spans[cid] = (len(all_flat), len(all_flat) + len(p.flat))
            all_flat.extend(p.flat)
        devs = _plan_arrays_to_device(all_flat)
        for cid, p in pending.items():
            lo, hi = spans[cid]
            out[cid] = p.finalize(devs.view(lo, hi))
        return out

    def _wants_column_sharding(
        self, data: GameDataset, cfg: FixedEffectCoordinateConfiguration
    ) -> bool:
        mode = cfg.feature_sharding
        if mode == "column":
            return True
        if mode == "auto":
            feats = data.feature_shards[cfg.feature_shard_id]
            if feats.num_features <= AUTO_COLUMN_SHARDING_THRESHOLD:
                return False
            # The auto heuristic degrades to replicated on shards the
            # column path can't take (explicit "column" hard-fails instead).
            why = self._column_sharding_blocker(data, cfg.feature_shard_id)
            if why is not None:
                logger.info(
                    "shard %s: auto feature sharding staying replicated "
                    "(%s)", cfg.feature_shard_id, why)
                return False
            return True
        return False

    def _column_sharding_blocker(
        self, data: GameDataset, shard: str
    ) -> str | None:
        """Why ``shard`` can't go column-sharded, or None if it can."""
        norm = self.normalization.get(shard)
        if norm is not None and not norm.is_identity:
            return "feature normalization is active"
        if data.host_shard_tail(shard) is not None:
            return "DualEll overflow tail present"
        return None

    def _build_column_sharded_batch(
        self, data: GameDataset, cfg, mesh
    ):
        """Feature-axis-sharded (tp) fixed-effect batch.

        Coefficients and ELL feature entries are split by feature range over
        the mesh; rows stay at canonical length with labels/offsets/weights
        replicated, so residual routing needs no padding bookkeeping.
        """
        from photon_tpu.data.dataset import GLMBatch
        from photon_tpu.parallel.mesh import (
            replicated,
            shard_features_by_column,
        )

        shard = cfg.feature_shard_id
        why = self._column_sharding_blocker(data, shard)
        if why is not None:
            raise ValueError(
                f"coordinate shard {shard!r}: column feature sharding is "
                f"unsupported here ({why}); normalize at ingest / raise the "
                "DualEll slab width cap, or use replicated sharding")
        idx, val, d = data.host_shard_coo(shard)
        feats = shard_features_by_column(
            idx, val, d, mesh,
            axis_name=mesh.axis_names[0],
            dtype=data.labels.dtype,
        )
        rep = replicated(mesh)
        return GLMBatch(
            features=feats,
            labels=jax.device_put(data.labels, rep),
            offsets=jax.device_put(data.offsets, rep),
            weights=jax.device_put(data.weights, rep),
        )

    def _build_coordinates(
        self,
        datasets: dict[str, object],
        opt_configs: dict[str, GLMOptimizationConfiguration],
        priors: dict[str, object] | None = None,
        logical_rows: int | None = None,
    ) -> dict[str, object]:
        """CoordinateFactory.build equivalent (CoordinateFactory.scala:52);
        ``priors`` carries incremental-training prior models per coordinate
        (the factory's priorModelOpt, DistributedGLMLossFunction.scala:184).
        ``logical_rows``: the canonical row count; on a mesh the loop's
        vectors have it padded to the device count (``loop_rows``)."""
        priors = priors or {}
        if logical_rows is not None:
            logical_rows = loop_rows(logical_rows, self.resolve_mesh())
        coords: dict[str, object] = {}
        for cid, cfg in self.coordinate_configs.items():
            opt = opt_configs.get(cid, cfg.optimization)
            if isinstance(cfg, RandomEffectCoordinateConfiguration):
                coords[cid] = RandomEffectCoordinate(
                    datasets[cid],
                    self.task,
                    opt,
                    self._shard_norm(cfg.data.feature_shard_id),
                    prior=priors.get(cid),
                    precision=self.precision,
                    logical_rows=logical_rows,
                )
            else:
                problem = GLMOptimizationProblem(
                    task=self.task,
                    config=opt,
                    normalization=self._shard_norm(cfg.feature_shard_id),
                    intercept_index=self.intercept_indices.get(
                        cfg.feature_shard_id
                    ),
                    prior=priors.get(cid),
                )
                coords[cid] = _FixedEffectModelAdapter(
                    FixedEffectCoordinate(
                        datasets[cid], problem, logical_rows=logical_rows
                    ),
                    cfg.feature_shard_id,
                )
        return coords

    def _prime_compilations(self, coords: dict[str, object], datasets):
        """Compile every coordinate's programs CONCURRENTLY before CD runs.

        The first CD sweep otherwise serializes one XLA compile per bucket
        per coordinate (each 2-4s on the TPU backend); the compiler handles
        concurrent requests ~2.5x faster in wall-clock. Thunks run the real
        jitted entry points with zero inputs, so the jit cache is warm when
        coordinate descent starts; results are discarded. Primed once per
        prepared dataset set (repeat fits hit the cache anyway).

        SINGLE-DEVICE ONLY: on a mesh the thunks' programs carry
        collectives, and two collective-bearing executions in flight from
        different threads can interleave their rendezvous (the same hazard
        coordinate_descent._serialize_on_cpu_mesh guards) — there, the
        first CD sweep compiles serially as before. With fewer than two
        thunks there is no overlap to win and the discarded warm-up solve
        would just double the first fit's work.

        The thunks EXECUTE (one extra discarded solve per program, ~one CD
        iteration of device work) rather than AOT-compiling via
        jit(...).lower().compile(): AOT results don't land in the jit
        dispatch cache, so the real call would re-trace and re-load the
        executable, which executing the thunk pays once and the CD sweep
        then reuses (the per-program load cost is not measured on this
        chip).
        """
        # Identity (not id()): a dead dict's address can be reused, which
        # would silently skip priming for a NEW dataset set. prepare()
        # clears this on every rebuild, so the reference held here never
        # outlives the _fit_cache generation it belongs to (no double
        # retention of device datasets across fits).
        if getattr(self, "_primed_datasets", None) is datasets:
            return
        if self.resolve_mesh() is not None:
            return
        from concurrent.futures import ThreadPoolExecutor

        from photon_tpu.algorithm.coordinate import ModelCoordinate

        thunks = []
        for coord in coords.values():
            if isinstance(coord, ModelCoordinate):
                thunks.append(
                    lambda c=coord: jax.block_until_ready(c.score())
                )
            elif hasattr(coord, "warmup_thunks"):
                thunks.extend(coord.warmup_thunks())
        if len(thunks) < 2:
            return
        from photon_tpu.data.pipeline import consume_futures

        with ThreadPoolExecutor(max_workers=min(8, len(thunks))) as pool:
            # consume_futures: a thunk that fails after another already
            # raised must still be awaited and its exception surfaced —
            # the pool's __exit__ would otherwise swallow it silently.
            consume_futures([pool.submit(t) for t in thunks])
        self._primed_datasets = datasets

    def _fused_for(self, coords, datasets):
        """The whole-fit fused program for this coordinate structure, or
        None when ineligible (mesh execution, listeners, down-sampling,
        materialized datasets — see fused_fit.fuse_eligible).

        Cached per (dataset generation, static structure) in a small LRU
        keyed by the static key: a lambda-grid config sequence re-enters
        the SAME compiled executable with new traced weights (the
        warm-start ladder of GameEstimator.scala:452-468 with zero
        recompiles), and a grid that ALTERNATES static keys (e.g. mixed
        optimizer configs) round-robins among cached programs instead of
        rebuilding the whole-fit trace on every entry."""
        from photon_tpu.algorithm.fused_fit import (
            FusedFit,
            fuse_ineligibility_reasons,
            fused_static_key,
        )

        if fuse_ineligibility_reasons(
            coords, mesh=self.resolve_mesh(), emitter=self.emitter
        ):
            return None
        key = fused_static_key(
            coords, self.update_sequence, self.num_iterations,
            self.locked_coordinates, self.precision,
        )
        cache = getattr(self, "_fused_cache", None)
        share = getattr(self, "_fused_mat_share", None)
        if cache is None or share is None or share["datasets"] is not datasets:
            # New dataset generation (or first use): every cached program
            # and the materialized-slab set are stale together. The share
            # carries its generation's datasets identity so the check is
            # symmetric for hits and misses.
            cache = self._fused_cache = OrderedDict()
            share = self._fused_mat_share = {"datasets": datasets}
        fused = cache.get(key)
        if fused is not None:
            cache.move_to_end(key)
            return self._attach_aot(fused)
        fused = FusedFit(
            coords, self.update_sequence, self.num_iterations,
            self.locked_coordinates,
            mat_share=share,
            precision=self.precision,
        )
        fused.static_key = key
        cache[key] = fused
        while len(cache) > _FUSED_CACHE_SIZE:
            cache.popitem(last=False)
        return self._attach_aot(fused)

    def _unfused_fit_attrs(self, coords, datasets, opt_configs) -> dict:
        """The unfused loop's ``fit`` stage attributes
        (``coordinate_descent.fit_stage_attrs``), made on the first fit of
        a prepared data set under an optimization configuration and
        handed to every later one: a warm fit pays one comparison."""
        key = tuple(self._full_config(opt_configs).items())
        cached = getattr(self, "_unfused_attrs", None)
        if (cached is None or cached[0] is not datasets
                or cached[1] != key):
            from photon_tpu.algorithm.coordinate_descent import (
                fit_stage_attrs,
            )

            cached = self._unfused_attrs = (
                datasets, key, fit_stage_attrs(coords))
        return cached[2]

    def _attach_aot(self, fused):
        """Hand prepare()'s pending AOT warm-compile future to the fused
        program; FusedFit.run consumes it (waiting if still compiling —
        that wait is the measured non-overlapped remainder)."""
        fut = getattr(self, "_aot_future", None)
        if fut is not None and getattr(fused, "_aot_future", None) is None:
            fused._aot_future = fut
            self._aot_future = None
        return fused

    def _warm_compile_eligible(
        self, validation, initial_model
    ) -> bool:
        """Whether prepare() may kick off the background AOT warm compile.

        The overlapped compile targets the fused single-device path with
        the base configs and no warm start — exactly the first fit of a
        validation-free ``fit()`` call. Anything else (mesh collectives,
        listeners, incremental priors, initial models whose per-entity
        support changes the subspace shapes) either can't fuse or can't be
        shape-predicted, so the compile would be wasted by construction."""
        from photon_tpu.data import pipeline

        return (
            validation is None
            and initial_model is None
            and not self.incremental_training
            and self.emitter is None
            and self.resolve_mesh() is None
            and not pipeline.serial_ingest()
        )

    def _warm_compile(self, data: GameDataset):
        """AOT-compile the fused materialize + whole-fit programs from
        PREDICTED block shapes — the ingest pipeline's overlapped-compile
        stage, run on a background thread while the real planner is still
        working (XLA compiles in C++ with the GIL released, so planning
        and compiling genuinely overlap).

        Shape-faithful skeleton datasets (data/random_effect.py
        ``skeleton_random_effect_dataset``) stand in for the coordinates;
        the traced programs are the production ones BY CONSTRUCTION (same
        FusedFit code path — the ingest-pipeline PROGRAM_AUDIT contract
        pins that the signatures match). Returns the compiled artifact
        dict, or None when prediction/fusion DECLINES (explicit returns
        below); a stale prediction only wastes this compile —
        ``FusedFit.run`` falls back to the normal jit path (which may
        still hit the persistent compile cache this compile populated).

        Nothing is caught here: a compiler refusal (Mosaic, out of HBM)
        is the same refusal the jit path would meet, so it travels
        through the future and surfaces once, from ``FusedFit.run``,
        with its own message.
        """
        from photon_tpu.algorithm.fused_fit import (
            FusedFit,
            fuse_ineligibility_reasons,
            fused_static_key,
        )
        from photon_tpu.data.pipeline import PIPELINE_STATS
        from photon_tpu.data.random_effect import (
            share_skeleton_packing,
            skeleton_random_effect_dataset,
        )
        # Eligibility + skeleton construction OUTSIDE the "compile"
        # stage: a declined prediction must leave compile_seconds at
        # 0 (a truthy near-zero value would both fake an overlap
        # fraction and let bench.py under-report compile_seconds
        # past its regression floor).
        skeleton: dict[str, object] = {}
        for cid, cfg in self.coordinate_configs.items():
            if isinstance(cfg, RandomEffectCoordinateConfiguration):
                ds = skeleton_random_effect_dataset(data, cfg.data)
                if ds is None:
                    return None
                skeleton[cid] = ds
            else:
                if self._wants_column_sharding(data, cfg):
                    return None
                skeleton[cid] = data.shard_batch(
                    cfg.feature_shard_id
                )
        # One packed buffer for all coordinates, as _resolve_pending
        # builds it (the materialize program's slice offsets are static).
        skeleton = share_skeleton_packing(skeleton)
        coords = self._build_coordinates(
            skeleton, {}, {}, logical_rows=data.num_samples
        )
        if fuse_ineligibility_reasons(
            coords, mesh=None, emitter=self.emitter
        ):
            return None
        fused = FusedFit(
            coords, self.update_sequence, self.num_iterations,
            self.locked_coordinates,
            precision=self.precision,
        )
        key = fused_static_key(
            coords, self.update_sequence, self.num_iterations,
            self.locked_coordinates, self.precision,
        )
        with PIPELINE_STATS.stage("compile"):
            return {"key": key, **fused.compile_programs(coords)}

    def _build_validation(
        self,
        datasets: dict[str, object],
        validation: GameDataset,
    ) -> ValidationContext:
        """prepareValidationDatasetAndEvaluators equivalent (:649-673).

        Validation scorers ride the same mesh as training: the remapped
        score tables are row-sharded, so per-CD-iteration validation
        scoring scales with the device count too."""
        mesh = self.resolve_mesh()
        specs = list(self.evaluators) or [_DEFAULT_EVALUATOR[self.task]]
        group_ids = {
            name: (tag.codes, tag.num_groups)
            for name, tag in validation.id_tags.items()
        }
        suite = make_suite(
            specs,
            validation.labels,
            offsets=validation.offsets,
            weights=validation.weights,
            group_ids=group_ids,
            dtype=validation.labels.dtype,
        )
        scorers = {}
        for cid, cfg in self.coordinate_configs.items():
            if isinstance(cfg, RandomEffectCoordinateConfiguration):
                ds = datasets[cid]
                scorers[cid] = random_effect_scorer(
                    validation,
                    re_type=cfg.data.random_effect_type,
                    feature_shard_id=cfg.data.feature_shard_id,
                    entity_keys=ds.entity_keys,
                    proj_all=ds.proj_all,
                    width_cap=cfg.data.score_table_width_cap,
                    mesh=mesh,
                )
            else:
                scorers[cid] = fixed_effect_scorer(
                    validation, cfg.feature_shard_id, mesh
                )
        return ValidationContext(suite=suite, scorers=scorers)

    @staticmethod
    def _score_with_validation(val_ctx, model, score_sink=None):
        """Rescore a (re)loaded model against the validation set — same
        model, same scores, so it reproduces a previously recorded
        metric to float-reassociation tolerance.

        Ledger-armed runs book each coordinate's validation scorer and
        the metric suite as ``eval``-phase rows (measured host windows —
        the scorers dispatch asynchronously, so these are enqueue-to-
        enqueue costs; the suite's evaluate is the sync).

        ``score_sink`` (optional) receives the EVALUATED scores as host
        numpy — ``(scores + offsets, labels)``, the exact values the
        suite judged — after the metrics are computed. The health
        layer's calibration sketch rides this (obs/health.py
        ``calibration_sink``); the transfer happens once, post-sync,
        never inside a fit loop."""
        import time as _time

        import numpy as _np

        from photon_tpu.obs import ledger

        armed = ledger.enabled()
        total = None
        for cid, m in model.items():
            t0 = _time.perf_counter() if armed else 0.0
            vs = val_ctx.scorers[cid](m)
            total = vs if total is None else total + vs
            if armed:
                t1 = _time.perf_counter()
                ledger.record_dispatch(
                    "eval/score", t1 - t0, phase="eval",
                    coordinate=cid, start=t0, end=t1,
                )
        t0 = _time.perf_counter() if armed else 0.0
        out = val_ctx.suite.evaluate(total)
        if armed:
            t1 = _time.perf_counter()
            ledger.record_dispatch(
                "eval/suite", t1 - t0, phase="eval",
                start=t0, end=t1,
            )
        if score_sink is not None:
            score_sink(
                _np.asarray(total) + _np.asarray(val_ctx.suite.offsets),
                _np.asarray(val_ctx.suite.labels),
            )
        return out

    def evaluate_model(
        self,
        model: GameModel,
        data: GameDataset,
        validation: GameDataset,
        *,
        initial_model: GameModel | None = None,
        score_sink=None,
    ) -> EvaluationResults:
        """Evaluate an ARBITRARY GameModel (e.g. the currently-serving
        generation) against ``validation`` with this estimator's
        evaluator suite — the same scorers and metric path a
        ``fit(validation=...)`` run records, so the pilot's promotion
        gate compares candidate and incumbent through one ruler.

        ``data`` provides the per-coordinate layouts the scorers remap
        onto (the same dataset the candidate trained on); pass the same
        ``initial_model`` the fit used so ``prepare``'s cache is reused
        instead of rebuilt. Random-effect sub-models whose entity
        vocabulary or projector layout differ from the dataset's are
        remapped by (entity key, feature id) first — entities the
        layout lacks score through the fixed effect, photon-ml's
        left-join semantics. ``score_sink`` receives the evaluated
        host scores + labels (see ``_score_with_validation``) — the
        health layer's calibration feed.
        """
        import numpy as np

        datasets, val_ctx = self.prepare(
            data, validation=validation, initial_model=initial_model
        )
        if val_ctx is None:  # pragma: no cover — prepare always builds
            # a context when validation is given; belt for refactors.
            raise ValueError("evaluate_model needs a validation dataset")
        for cid in self.update_sequence:
            if cid not in model:
                continue
            m = model[cid]
            if not isinstance(m, RandomEffectModel):
                continue
            ds = datasets[cid]
            if (
                tuple(str(k) for k in m.entity_keys)
                != tuple(str(k) for k in ds.entity_keys)
                or not np.array_equal(
                    np.asarray(m.proj_all), np.asarray(ds.proj_all)
                )
            ):
                model = model.updated(
                    cid,
                    remap_random_effect_model(
                        m,
                        entity_keys=ds.entity_keys,
                        proj_all=ds.proj_all,
                    ),
                )
        return self._score_with_validation(
            val_ctx, model, score_sink=score_sink
        )

    def _full_config(self, opt_configs):
        return {
            cid: opt_configs.get(
                cid, self.coordinate_configs[cid].optimization)
            for cid in self.update_sequence
        }

    def _rebuild_completed_config(
        self, checkpointer, resume, i, opt_configs, val_ctx
    ) -> GameFitResult:
        """Rebuild a completed config's result from its retained
        config-final checkpoint (resume path). The model is the best
        model that config committed; the evaluation is recomputed by
        rescoring it against the validation set."""
        from photon_tpu.resilience.checkpoint import load_config_final

        directory = self._checkpoint_directory(checkpointer, resume)
        model = load_config_final(directory, i, resume.static_key)
        return GameFitResult(
            model=model,
            config=self._full_config(opt_configs),
            evaluation=(
                self._score_with_validation(val_ctx, model)
                if val_ctx is not None else None
            ),
            descent=None,
        )

    def _finalize_from_checkpoint(
        self, checkpointer, resume, i, opt_configs, val_ctx
    ) -> GameFitResult:
        """The crash window AFTER a config's last-iteration checkpoint
        committed but BEFORE its config-final artifact was retained:
        the descent finished (the chain holds iteration
        num_iterations-1), so rebuild the result from the chain itself —
        the final model IS the checkpoint's, the best-by-validation
        comes from the retained best artifact — and heal the missing
        config-final so later resumes take the normal path. Without
        this, a valid checkpoint is refused with 'nothing to resume' /
        'retrain from scratch' even though the run produced no results."""
        from photon_tpu.resilience.checkpoint import load_config_best

        directory = self._checkpoint_directory(checkpointer, resume)
        best_model = None
        if val_ctx is not None:
            best_model = load_config_best(
                directory, i, resume.static_key
            )
        if best_model is None:
            best_model = resume.model
        logger.info(
            "GameEstimator: config %d completed its descent before the "
            "interruption but never retained its final artifact; "
            "finalizing it from the checkpoint chain", i)
        result = GameFitResult(
            model=best_model,
            config=self._full_config(opt_configs),
            evaluation=(
                self._score_with_validation(val_ctx, best_model)
                if val_ctx is not None else None
            ),
            descent=None,
        )
        if checkpointer is not None:
            checkpointer.save_config_final(best_model, config_index=i)
        return result

    @staticmethod
    def _checkpoint_directory(checkpointer, resume) -> str:
        import os

        return (
            checkpointer.directory if checkpointer is not None
            else os.path.dirname(resume.path)
        )

    # ------------------------------------------------------------------
    # fit (GameEstimator.scala:397)
    # ------------------------------------------------------------------

    def prepare(
        self,
        data: GameDataset,
        validation: GameDataset | None = None,
        initial_model: GameModel | None = None,
    ):
        """Build (or reuse) the per-coordinate device datasets for ``data``.

        Repeated fits on the same objects (the lambda grid re-entered by the
        hyperparameter tuner, GameEstimatorEvaluationFunction.scala:40) reuse
        the ingested datasets: the build is the expensive host-side step and
        is pure in (data, initial_model, validation). Call explicitly to
        separate ingest from training (the driver's Timed sections around
        prepareTrainingDatasets)."""
        cache_key = (data, initial_model, validation)
        cached = getattr(self, "_fit_cache", None)
        if cached is not None and all(
            a is b for a, b in zip(cached[0], cache_key)
        ):
            return cached[1]
        # Release the previous generation's datasets BEFORE building the
        # new one — _primed_datasets / the fused program's operand cache
        # would otherwise pin the old device arrays through the build
        # (2x peak HBM).
        self._primed_datasets = None
        self._fused_cache = None
        self._fused_mat_share = None
        self._unfused_attrs = None
        self._fit_cache = None
        # Ingest pipeline: fresh stage accounting per dataset generation
        # (raw_transfer survives — it was recorded at make_game_dataset
        # time, before any estimator existed; a still-running previous
        # warm compile is cancelled if unstarted, else its late stage
        # write is discarded by the generation token), and — when the
        # fused path and shape prediction apply — the AOT warm compile
        # starts NOW, before planning, so compile_seconds hides under
        # ingest_seconds instead of adding to it.
        from photon_tpu.data import pipeline

        stale = getattr(self, "_aot_future", None)
        if stale is not None and not stale.cancel():
            # Already running: the compile finishes in the background
            # (its stage write is discarded by the generation token).
            # Consume the orphaned future so its outcome is never
            # dropped — _warm_compile is internally exception-safe, so
            # a late exception here means that safety net broke.
            stale.add_done_callback(_log_orphaned_compile)
        pipeline.PIPELINE_STATS.reset(keep=("raw_transfer",))
        self._aot_future = None
        if self._warm_compile_eligible(validation, initial_model):
            self._aot_future = pipeline.compile_executor.submit(
                self._warm_compile, data
            )
        from photon_tpu import obs

        with obs.stage("prepare"):
            datasets = self._build_datasets(data, initial_model)
            val_ctx = (
                self._build_validation(datasets, validation)
                if validation is not None
                else None
            )
        self._fit_cache = (cache_key, (datasets, val_ctx))
        return datasets, val_ctx

    def fit(
        self,
        data: GameDataset,
        validation: GameDataset | None = None,
        opt_config_sequence: (
            list[dict[str, GLMOptimizationConfiguration]] | None
        ) = None,
        initial_model: GameModel | None = None,
        *,
        init_model=None,
        checkpointer=None,
        resume=None,
    ) -> list[GameFitResult]:
        """Train one GAME model per optimization configuration.

        Configs warm-start from the previous config's trained model
        (GameEstimator.train :452-468); ``initial_model`` seeds the first
        (warm-start / partial-retrain model loading,
        GameTrainingDriver.scala:395-404).

        ``init_model`` is the day-over-day warm-start form of the same
        parameter: a ``GameModel``, or a PATH to yesterday's saved model
        loaded via ``io/model_io.load_initial_model`` (a native
        checkpoint ``.npz`` here — Avro model directories need feature
        index maps, which the CLI layer owns). Exactly one of
        ``initial_model`` / ``init_model`` may be given.

        ``checkpointer`` (a ``resilience.TrainingCheckpointer``) commits
        a crash-safe recovery point after every outer CD iteration;
        ``resume`` (a ``resilience.TrainingCheckpoint``) restarts
        mid-descent from one — the manifest's static key must match this
        estimator + config sequence (``ResumeMismatchError`` otherwise),
        completed configs are skipped, and the in-progress config
        continues at its next iteration with the SAME per-iteration
        seeds, so the resumed run converges to the uninterrupted run's
        model (within float reassociation tolerance; the initial score
        total is re-accumulated in sequence order on resume).
        Best-by-validation selection survives the crash too: the best
        model is retained as its own checkpoint artifact and reseeds
        CD's tracking on resume, and a config whose descent finished
        but whose final artifact was never retained (the crash window
        before ``save_config_final``) is finalized from the checkpoint
        chain instead of being refused.
        Checkpointing needs a host boundary per outer iteration, so an
        active checkpointer (or resume, or the non-finite guard) rides
        the unfused CD loop — crash safety trades away the whole-fit
        fused program by design.
        """
        if init_model is not None:
            if initial_model is not None:
                raise ValueError(
                    "pass exactly one of initial_model / init_model")
            if isinstance(init_model, str):
                from photon_tpu.io.model_io import load_initial_model

                init_model, digest = load_initial_model(init_model)
                logger.info(
                    "warm start from init model (digest %s...)",
                    digest[:12])
            initial_model = init_model
        if self.incremental_training:
            self._validate_incremental(initial_model)
        datasets, val_ctx = self.prepare(
            data, validation=validation, initial_model=initial_model
        )
        if opt_config_sequence is None:
            opt_config_sequence = [{}]

        start_config = 0
        resume_iteration = 0
        if resume is not None:
            from photon_tpu.resilience.checkpoint import (
                training_static_key,
            )
            from photon_tpu.resilience.errors import ResumeMismatchError

            expected = training_static_key(self, opt_config_sequence)
            if resume.static_key != expected:
                raise ResumeMismatchError(
                    "checkpoint was written by a different training "
                    f"configuration (manifest static key "
                    f"{resume.static_key[:12]}..., this run "
                    f"{expected[:12]}...): change the config back, or "
                    "start fresh / warm-start instead of resuming")
            start_config = resume.config_index
            resume_iteration = resume.iteration + 1
            if resume_iteration >= self.num_iterations:
                start_config += 1
                resume_iteration = 0
            if start_config >= len(opt_config_sequence):
                from photon_tpu.resilience.checkpoint import (
                    has_config_final,
                )

                if has_config_final(
                    self._checkpoint_directory(checkpointer, resume),
                    len(opt_config_sequence) - 1,
                ):
                    raise ValueError(
                        "checkpoint records the final configuration's "
                        "last iteration: training already completed; "
                        "nothing to resume")
                # The crash landed between the final config's last-
                # iteration checkpoint and its config-final retention:
                # nothing descends, but every config's result still
                # rebuilds below (the last one finalizing from the
                # checkpoint chain itself) — refusing here would strand
                # a run that produced no results behind 'nothing to
                # resume'.
            # The checkpoint model carries the full mid-descent state —
            # it supersedes any initial_model for the warm-start chain.
            initial_model = resume.model

        # Externally loaded RE models carry their own entity vocab / slot
        # layout; remap each ONCE onto this dataset's layout — the result
        # serves both the config-0 warm start and the incremental prior.
        if initial_model is not None:
            for cid in self.update_sequence:
                if cid not in initial_model:
                    continue
                m = initial_model[cid]
                if isinstance(m, RandomEffectModel):
                    ds = datasets[cid]
                    if (m.entity_keys is not ds.entity_keys
                            or m.proj_all is not ds.proj_all):
                        initial_model = initial_model.updated(
                            cid,
                            remap_random_effect_model(
                                m,
                                entity_keys=ds.entity_keys,
                                proj_all=ds.proj_all,
                            ),
                        )

        # Incremental training: the ORIGINAL initial model (not the previous
        # config's result) becomes the Gaussian prior for every config.
        priors: dict[str, object] = {}
        if self.incremental_training:
            for cid in self.update_sequence:
                if cid in self.locked_coordinates:
                    continue
                m = initial_model[cid]
                if isinstance(m, RandomEffectModel):
                    priors[cid] = m
                else:
                    priors[cid] = m.model.coefficients

        results: list[GameFitResult] = []
        prev_model: GameModel | None = initial_model
        primed = False
        # Crash safety needs a host boundary after every outer CD
        # iteration (the checkpoint write / the non-finite guard's
        # sync); the fused whole-fit program has none until the fit
        # completes, so these features ride the unfused loop.
        needs_host_boundary = (
            checkpointer is not None
            or resume is not None
            or self.non_finite_guard
        )
        for i, opt_configs in enumerate(opt_config_sequence):
            if i < start_config:
                # Completed before the interruption: rebuild its result
                # from the retained config-final artifact so the
                # returned list lines up with the FULL grid — otherwise
                # select_best / tuning observations / per-index artifact
                # writes silently shift and the resumed run can pick a
                # different "best" model than the uninterrupted one.
                # The config the checkpoint chain itself completed may
                # have died before retaining its final — finalize it
                # from the chain instead of refusing the resume.
                from photon_tpu.resilience.checkpoint import (
                    has_config_final,
                )

                if (
                    i == resume.config_index
                    and resume.iteration + 1 >= self.num_iterations
                    and not has_config_final(
                        self._checkpoint_directory(checkpointer, resume),
                        i,
                    )
                ):
                    results.append(self._finalize_from_checkpoint(
                        checkpointer, resume, i, opt_configs, val_ctx
                    ))
                else:
                    results.append(self._rebuild_completed_config(
                        checkpointer, resume, i, opt_configs, val_ctx
                    ))
                continue
            coords = self._build_coordinates(
                datasets, opt_configs, priors,
                logical_rows=data.num_samples,
            )
            fused = (
                self._fused_for(coords, datasets)
                if val_ctx is None and not needs_host_boundary else None
            )
            if fused is None and not primed:
                self._prime_compilations(coords, datasets)
                primed = True
            cd = CoordinateDescent(
                self.update_sequence,
                self.num_iterations,
                locked_coordinates=self.locked_coordinates,
                emitter=self.emitter,
                non_finite_guard=self.non_finite_guard,
            )
            initial_models = {}
            if prev_model is not None:
                for cid in self.update_sequence:
                    if cid not in prev_model:
                        continue
                    m = prev_model[cid]
                    if isinstance(m, RandomEffectModel):
                        ds = datasets[cid]
                        # Externally loaded models carry their own entity
                        # vocab / slot layout; re-route onto this dataset's.
                        # Within-fit warm starts share the dataset's layout
                        # objects, so the identity check skips the remap.
                        if (m.entity_keys is not ds.entity_keys
                                or m.proj_all is not ds.proj_all):
                            m = remap_random_effect_model(
                                m,
                                entity_keys=ds.entity_keys,
                                proj_all=ds.proj_all,
                            )
                    initial_models[cid] = m
            logger.info(
                "GameEstimator: config %d/%d", i + 1, len(opt_config_sequence)
            )
            # Injective seed spacing: CD uses seed+iteration internally, so
            # stride by num_iterations to keep down-sampling draws
            # independent across the lambda-config grid.
            from photon_tpu import obs

            # Resuming mid-config with validation: seed CD's best
            # tracking from the retained best artifact — the iteration
            # chain holds final-iteration state, and restarting best
            # selection from scratch would discard a pre-crash best
            # that never recurs (silently returning a worse model than
            # the uninterrupted run). The evaluation is recovered by
            # rescoring the loaded best.
            initial_best = None
            if (
                resume is not None
                and i == start_config
                and resume_iteration > 0
                and val_ctx is not None
            ):
                from photon_tpu.resilience.checkpoint import (
                    load_config_best,
                )

                best = load_config_best(
                    self._checkpoint_directory(checkpointer, resume),
                    i, resume.static_key,
                )
                if best is not None:
                    initial_best = (
                        best, self._score_with_validation(val_ctx, best)
                    )

            on_iteration = None
            if checkpointer is not None:
                # The best artifact commits BEFORE the iteration's
                # manifest: a crash in between leaves a best at most
                # one replayed iteration ahead of the cursor, which the
                # resumed replay regenerates (same seeds). Identity
                # tracking skips the write when the best didn't change.
                _saved_best = [
                    initial_best[0] if initial_best is not None else None
                ]

                def on_iteration(it, model, best, _ci=i):
                    if best is not None and best is not _saved_best[0]:
                        checkpointer.save_best(best, config_index=_ci)
                        _saved_best[0] = best
                    checkpointer.save(
                        model, config_index=_ci, iteration=it
                    )
            with obs.span(f"fit/config:{i}"):
                if fused is not None:
                    descent = fused.run(coords, initial_models or None)
                else:
                    descent = cd.run(
                        coords, initial_models or None, val_ctx,
                        seed=i * self.num_iterations,
                        start_iteration=(
                            resume_iteration if i == start_config else 0
                        ),
                        on_iteration=on_iteration,
                        initial_best=initial_best,
                        fit_attrs=self._unfused_fit_attrs(
                            coords, datasets, opt_configs),
                    )
            full_config = self._full_config(opt_configs)
            result = GameFitResult(
                model=descent.best_model,
                config=full_config,
                evaluation=descent.best_evaluation,
                descent=descent,
            )
            results.append(result)
            if checkpointer is not None:
                # Retain this config's BEST model so a later resume can
                # rebuild this result (the per-iteration chain holds
                # final-iteration state, not best-by-validation).
                checkpointer.save_config_final(
                    descent.best_model, config_index=i
                )
            if self.emitter is not None:
                from photon_tpu.events import FitEndEvent

                self.emitter.send_event(
                    FitEndEvent(config_index=i, result=result)
                )
            prev_model = descent.model
        return results

    def _validate_incremental(self, initial_model: GameModel | None) -> None:
        """Incremental-training invariants (GameEstimator.validateParams
        :241-382): an initial model must cover every trained coordinate with
        matching shard / random-effect type and carry variances."""
        if initial_model is None:
            raise ValueError(
                "incremental training is enabled but no initial model "
                "provided")
        to_train = [
            cid for cid in self.update_sequence
            if cid not in self.locked_coordinates
        ]
        missing = [cid for cid in to_train if cid not in initial_model]
        if missing:
            raise ValueError(
                "coordinate sets don't match for incremental training; "
                f"missing coordinates: {', '.join(missing)}")
        for cid in to_train:
            cfg = self.coordinate_configs[cid]
            m = initial_model[cid]
            if isinstance(cfg, RandomEffectCoordinateConfiguration):
                if not isinstance(m, RandomEffectModel):
                    raise ValueError(
                        f"incremental training error: coordinate {cid!r} is "
                        "random-effect but the initial model is not")
                if m.feature_shard_id != cfg.data.feature_shard_id:
                    raise ValueError(
                        f"incremental training error: feature shard ID "
                        f"mismatch for coordinate {cid!r} "
                        f"({cfg.data.feature_shard_id!r} vs. "
                        f"{m.feature_shard_id!r})")
                if m.random_effect_type != cfg.data.random_effect_type:
                    raise ValueError(
                        f"incremental training error: random effect type "
                        f"mismatch for coordinate {cid!r} "
                        f"({cfg.data.random_effect_type!r} vs. "
                        f"{m.random_effect_type!r})")
                if m.variances is None:
                    raise ValueError(
                        f"incremental training error: coordinate {cid!r} "
                        "missing variance information")
            else:
                if isinstance(m, RandomEffectModel):
                    raise ValueError(
                        f"incremental training error: coordinate {cid!r} is "
                        "fixed-effect but the initial model is random-effect")
                if m.feature_shard_id != cfg.feature_shard_id:
                    raise ValueError(
                        f"incremental training error: feature shard ID "
                        f"mismatch for coordinate {cid!r} "
                        f"({cfg.feature_shard_id!r} vs. "
                        f"{m.feature_shard_id!r})")
                if m.model.coefficients.variances is None:
                    raise ValueError(
                        f"incremental training error: coordinate {cid!r} "
                        "missing variance information")

    def select_best(self, results: list[GameFitResult]) -> GameFitResult:
        """Best config by validation primary metric (selectBestModel,
        GameTrainingDriver.scala:753-793); first config when no validation."""
        best = results[0]
        for r in results[1:]:
            if r.evaluation is not None and (
                best.evaluation is None
                or best.evaluation.primary_evaluator.better_than(
                    r.evaluation.primary_evaluation,
                    best.evaluation.primary_evaluation,
                )
            ):
                best = r
        return best
