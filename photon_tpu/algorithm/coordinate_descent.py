"""CoordinateDescent: the GAME outer loop with residual-score bookkeeping.

TPU-native counterpart of photon-lib algorithm/CoordinateDescent.scala:43.
The reference's loop (run :132, descend :373, descendWithValidation :493,
descendSingleCoordinate :653) alternates coordinate updates, each training
against the *residual* scores of all other coordinates, with RDD
persist/unpersist choreography around score updates
(``summedScores - oldScores + previousScores``, :442,583). Here every
coordinate's scores are one ``[n]`` device array aligned with the canonical
row order, so the bookkeeping is three vector adds and the choreography
disappears.

Locked coordinates (partial retraining, partialRetrainLockedCoordinates
:47,55) contribute scores but are never retrained. Validation evaluation runs
after every coordinate update (:312-333) and the best full GAME model by the
primary evaluator is tracked across all updates.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from photon_tpu.algorithm.coordinate import Coordinate
from photon_tpu.evaluation.suite import EvaluationResults, EvaluationSuite
from photon_tpu.models.game import GameModel

Array = jax.Array
logger = logging.getLogger(__name__)


def _sub_add_impl(total, old, new):
    """summedScores - oldScores + previousScores as one fused program."""
    return total - old + new


# The residual-total CARRY is donated: after `total = _sub_add(total,
# old, new)` the previous total buffer is dead, so XLA reuses its HBM
# for the result instead of round-tripping a fresh [n] allocation per
# coordinate update (the unfused CD sweep's working-set donation;
# PERFORMANCE.md donation map). The plain twin serves the one aliased
# case — a single-coordinate descent where the carry IS the stored
# score (donating a buffer that is also another operand is an XLA
# runtime error).
_sub_add_donating = jax.jit(_sub_add_impl, donate_argnums=(0,))
_sub_add_plain = jax.jit(_sub_add_impl)


def _sub_add(total, old, new):
    if total is old or total is new:
        return _sub_add_plain(total, old, new)
    return _sub_add_donating(total, old, new)


@functools.partial(jax.jit, static_argnames=("sharding",))
def _placed_residuals(total, old, *, sharding):
    """``total - old`` (``old`` None: ``total``) placed as ``sharding``
    says, in one program: on a mesh the one replication of an update
    whose coordinate gathers its residuals at arbitrary rows."""
    r = total if old is None else total - old
    return jax.lax.with_sharding_constraint(r, sharding)


@jax.jit
def _all_finite(x):
    """One tiny reduce per operand shape (jit caches per aval)."""
    return jnp.all(jnp.isfinite(x))


def _model_weight_arrays(model) -> list:
    """The weight arrays a coordinate model carries (guard operands).

    Knows the three shapes that flow through the CD loop: shard-tagged
    FixedEffectModels (``.model`` is the GLM), RandomEffectModels
    (``.coefficients`` is the padded table), and bare GLMs (direct CD
    use in tests). Unknown types contribute nothing — the score check
    still covers them.
    """
    glm = getattr(model, "model", model)
    coefs = getattr(glm, "coefficients", None)
    if coefs is None:
        return []
    means = getattr(coefs, "means", None)
    if means is not None:
        return [means]
    return [coefs] if hasattr(coefs, "shape") else []


def _update_is_finite(model, scores) -> bool:
    """Host-side non-finite guard for one coordinate update.

    This is a DELIBERATE host sync per update — the guard exists to
    stop a poisoned iterate before it corrupts the residual total, and
    only runs when ``non_finite_guard`` is enabled (the default loop
    stays fully asynchronous).
    """
    for arr in [scores, *_model_weight_arrays(model)]:
        if not bool(_all_finite(arr)):
            return False
    return True


def _serialize_on_cpu_mesh(x) -> None:
    """Block on ``x`` when it lives on a multi-device CPU mesh.

    XLA's CPU in-process communicator can deadlock when two
    collective-bearing executions are in flight at once (their all-reduce
    rendezvous interleave across the shared device threads). TPU streams
    execute programs in dispatch order per device, so the async pipeline is
    safe on hardware — but the forced-host-device mesh (tests, the driver's
    multichip dryrun) must serialize, and one host sync per coordinate
    update is noise next to the solve it waits on.
    """
    devices = getattr(x, "devices", None)
    if devices is None:
        return
    ds = x.devices()
    if len(ds) > 1 and next(iter(ds)).platform == "cpu":
        jax.block_until_ready(x)


def _residual_sharding(coord, total):
    """The placement ``coord`` asks of its residuals
    (``residual_sharding``), where ``total`` lies elsewhere; None where
    it asks none or ``total`` is already there."""
    said = getattr(coord, "residual_sharding", None)
    want = said() if said is not None else None
    if want is None or total.sharding.is_equivalent_to(want, total.ndim):
        return None
    return want


def _programs_per_update(coord) -> dict:
    """``train`` / ``score`` / ``residuals``: the programs one update of
    ``coord`` dispatches to train, to score, and on top of those to take
    residuals in, as the coordinate itself says
    (``programs_per_update``); one each for a coordinate that says
    nothing."""
    said = getattr(coord, "programs_per_update", None)
    return said() if said is not None else {
        "train": 1, "score": 1, "residuals": 0}


def fit_stage_attrs(coordinates: dict[str, Coordinate]) -> dict:
    """What the unfused loop's ``fit`` stage carries beside ``programs``:
    ``coordinates`` (per random-effect coordinate what
    ``fit_stage_coordinate`` gives: the fused fit's ``fit`` stage carries
    the same), ``fe_layout`` (per fixed-effect coordinate, how its solve
    reads the features: ``data.dataset.feature_layout``), ``devices``
    (how many devices hold a leaf of the prepared data sets) and
    ``placed_bytes`` (one entry a device, in the order of the devices'
    ids: the bytes of those leaves it holds, from shapes and shardings).
    Host integers and strings; nothing is read from a device.
    ``GameEstimator`` makes it once per prepared data set."""
    from photon_tpu.algorithm.coordinate import FixedEffectCoordinate
    from photon_tpu.algorithm.random_effect import (
        RandomEffectCoordinate,
        fit_stage_coordinate,
    )
    from photon_tpu.data.dataset import feature_layout
    from photon_tpu.parallel.mesh import placed_bytes

    per_coord, fe_layout, leaves = {}, {}, []
    for cid, coord in coordinates.items():
        inner = getattr(coord, "inner", coord)
        if isinstance(inner, RandomEffectCoordinate):
            ds = inner.dataset
            per_coord[cid] = fit_stage_coordinate(
                inner, ds.device_blocks(), precision=inner.precision)
            leaves.append(ds.device_leaves())
        else:
            if isinstance(inner, FixedEffectCoordinate):
                fe_layout[cid] = feature_layout(inner.batch)
            leaves.append(getattr(inner, "batch", None))
    devices = sorted(
        {d for leaf in jax.tree.leaves(leaves) if isinstance(leaf, jax.Array)
         for d in leaf.sharding.device_set},
        key=lambda d: d.id)
    return {
        "coordinates": per_coord,
        "fe_layout": fe_layout,
        "devices": len(devices),
        "placed_bytes": placed_bytes(leaves, devices),
    }


@dataclasses.dataclass(frozen=True)
class ValidationContext:
    """Validation data + per-coordinate scorers.

    ``scorers[k](model)`` returns coordinate k's score contribution for every
    validation row (the GameEstimator builds these from the validation
    dataset's per-coordinate feature/entity views).
    """

    suite: EvaluationSuite
    scorers: dict[str, Callable[[Any], Array]]


@dataclasses.dataclass(frozen=True)
class CoordinateUpdateRecord:
    """One coordinate update's diagnostics (OptimizationStatesTracker /
    RandomEffectOptimizationTracker equivalents plus timing).

    ``seconds`` is host DISPATCH time: training is fully asynchronous (no
    host sync per update), so device execution overlaps later updates and
    is not attributable per coordinate. End-to-end wall time lives at the
    fit / driver level, where the caller's first blocking read (evaluation,
    model save) absorbs the queued work.

    On the FUSED whole-fit path (algorithm/fused_fit.py) the entire
    descent is one device program, so not even dispatch time exists per
    coordinate. The contract there is two-valued:

    - telemetry OFF (``photon_tpu.obs`` disabled, the default):
      ``seconds`` is ``None`` — never a synthetic split consumers would
      read as measured;
    - telemetry ON: the fused fit's root span measures the fit
      program's real dispatch->completion window (one
      ``block_until_ready`` at the span root; slab materialization and
      the AOT compile wait are excluded), and ``seconds`` is that
      measurement's analytic ATTRIBUTION to this record — weighted by
      the coordinate's measured solver iteration counts x static shape
      work (``FusedFit._attribute_seconds``). Attributed shares sum to
      the measured fit window; treat them as a breakdown of one real
      measurement, not as independent per-coordinate timings. A fit
      whose window was NOT pure execution — the cold jit-fallback entry
      that traces/compiles inside the dispatch call — keeps ``None``
      (the span's ``fit_window_pure`` attr says why); only AOT-served
      and warm re-entries attribute.

    Consumers must treat ``None`` as "unattributable", not zero.
    """

    iteration: int
    coordinate_id: str
    seconds: float | None  # host dispatch time; None on the fused path
    diagnostics: Any
    evaluation: EvaluationResults | None
    # Non-finite guard outcome: True when this update produced NaN/inf
    # loss or weights and the loop kept the PREVIOUS iterate instead
    # (the diagnostics are the poisoned update's, for debugging).
    rolled_back: bool = False


@dataclasses.dataclass(frozen=True)
class CoordinateDescentResult:
    model: GameModel  # final models after the last iteration
    best_model: GameModel  # best by validation primary metric (== model if no validation)
    best_evaluation: EvaluationResults | None
    history: tuple[CoordinateUpdateRecord, ...]


class CoordinateDescent:
    """Reference: algorithm/CoordinateDescent.scala:43.

    ``update_sequence`` lists coordinate ids in update order; ids in
    ``locked_coordinates`` must come with a model in ``initial_models`` and
    are score-only.
    """

    def __init__(
        self,
        update_sequence: list[str],
        num_iterations: int,
        *,
        locked_coordinates: set[str] | None = None,
        emitter=None,
        non_finite_guard: bool = False,
    ):
        # Optional event fan-out (photon_tpu.events.EventEmitter): a
        # CoordinateUpdateEvent after every coordinate update
        # (EventEmitter.scala:24 semantics, wired to the GAME path).
        self.emitter = emitter
        # Resilience: when enabled, every coordinate update is checked
        # for non-finite loss/weights/scores (one host sync per update)
        # and a poisoned update ROLLS BACK to the previous iterate
        # instead of corrupting the model (resilience layer;
        # RESILIENCE.md). Off by default: the asynchronous dispatch
        # pipeline is the performance contract of this loop.
        self.non_finite_guard = bool(non_finite_guard)
        if num_iterations < 1:
            raise ValueError(f"num_iterations must be >= 1: {num_iterations}")
        seen = set()
        for cid in update_sequence:
            if cid in seen:
                raise ValueError(f"duplicate coordinate id {cid!r}")
            seen.add(cid)
        self.update_sequence = list(update_sequence)
        self.num_iterations = num_iterations
        self.locked_coordinates = set(locked_coordinates or ())
        unlocked = [c for c in update_sequence if c not in self.locked_coordinates]
        if not unlocked:
            raise ValueError(
                "update sequence contains no trainable coordinates "
                "(CoordinateDescent.scala:71 checkInvariants)"
            )

    def run(
        self,
        coordinates: dict[str, Coordinate],
        initial_models: dict[str, Any] | None = None,
        validation: ValidationContext | None = None,
        *,
        seed: int = 0,
        start_iteration: int = 0,
        on_iteration=None,
        initial_best=None,
        fit_attrs: dict | None = None,
    ) -> CoordinateDescentResult:
        """Train all coordinates by block coordinate descent.

        The whole descent is one always-recorded ``fit`` stage, from
        entry to the return of the last dispatch (no sync), as the fused
        fit's is. Its attributes are ``fit_attrs`` (``fit_stage_attrs`` of
        these coordinates, which ``GameEstimator`` hands over ready-made;
        made here when left out) and ``programs``: the programs this
        descent dispatched, the loop's own vector programs and what each
        coordinate says an update of it dispatches
        (``programs_per_update``).

        Mirrors CoordinateDescent.descend/descendWithValidation: coordinate k
        trains against offsets + (sum of all other coordinates' scores); its
        new scores replace its old ones in the running total.

        ``start_iteration`` resumes mid-descent from a checkpoint:
        iterations [0, start_iteration) are assumed done and baked into
        ``initial_models`` — the loop runs [start_iteration,
        num_iterations) with the SAME per-iteration seeds the
        uninterrupted run would have used. ``initial_best`` — a
        ``(model, evaluation)`` pair — seeds the best-by-validation
        tracking on resume: without it a resumed run restarts best
        selection from scratch and can silently return a worse model
        than the uninterrupted run when the pre-crash best never
        recurs. ``on_iteration(it, model, best_model)`` fires after
        each completed outer iteration with the full GameModel and the
        best-so-far (None until a full model has been evaluated) — the
        training checkpointer's hook.
        """
        from photon_tpu import obs

        with obs.stage("fit") as fit_stage:
            result, programs = self._descend(
                coordinates, initial_models, validation, seed,
                start_iteration, on_iteration, initial_best)
            if fit_attrs is None:
                fit_attrs = fit_stage_attrs(coordinates)
            fit_stage.attrs = dict(fit_attrs, programs=programs)
        return result

    def _descend(
        self, coordinates, initial_models, validation, seed,
        start_iteration, on_iteration, initial_best,
    ) -> tuple[CoordinateDescentResult, int]:
        if not 0 <= start_iteration <= self.num_iterations:
            raise ValueError(
                f"start_iteration {start_iteration} outside "
                f"[0, {self.num_iterations}]")
        for cid in self.update_sequence:
            if cid not in coordinates:
                raise KeyError(f"no coordinate for id {cid!r}")
        initial_models = dict(initial_models or {})
        for cid in self.locked_coordinates:
            if cid not in initial_models:
                raise ValueError(
                    f"locked coordinate {cid!r} needs an initial model "
                    "(partialRetrainLockedCoordinates invariant)"
                )

        models: dict[str, Any] = {}
        scores: dict[str, Array] = {}
        total: Array | None = None
        # Programs dispatched, for the ``fit`` stage: host integers, what
        # each coordinate says of itself once a descent and the loop's own
        # three vector programs where they run.
        per_update = {
            cid: _programs_per_update(coordinates[cid])
            for cid in self.update_sequence
        }
        programs = 0

        def add(total_, s):
            return s if total_ is None else total_ + s

        # Initial scores from warm-start / locked models
        # (CoordinateDescent.run computes initial model scores up front).
        for cid in self.update_sequence:
            if cid in initial_models:
                models[cid] = initial_models[cid]
                s = coordinates[cid].score(models[cid])
                _serialize_on_cpu_mesh(s)
                scores[cid] = s
                programs += per_update[cid]["score"] + (total is not None)
                total = add(total, s)

        history: list[CoordinateUpdateRecord] = []
        best_model: GameModel | None = None
        best_eval: EvaluationResults | None = None
        if initial_best is not None:
            best_model, best_eval = initial_best
        all_ids = set(self.update_sequence)
        val_scores: dict[str, Array] = {}
        val_total: Array | None = None

        from photon_tpu import obs

        for it in range(start_iteration, self.num_iterations):
            for cid in self.update_sequence:
                if cid in self.locked_coordinates:
                    continue
                coord = coordinates[cid]
                t0 = time.perf_counter()
                rolled_back = False
                # Telemetry span mirrors the measured dispatch window
                # below (host-side only; the obs tree's unfused analog of
                # the fused fit's single whole-fit span — no sync here:
                # per-update syncs are exactly what this loop avoids).
                with obs.span(f"coord:{cid}", attrs={"iteration": it}):
                    residuals = None
                    counts = per_update[cid]
                    programs += counts["train"] + counts["score"]
                    if total is not None:
                        residuals = total
                        programs += counts["residuals"]
                        want = _residual_sharding(coord, total)
                        if want is not None:
                            residuals = _placed_residuals(
                                total, scores.get(cid), sharding=want)
                            programs += 1
                        elif cid in scores:
                            residuals = residuals - scores[cid]
                            programs += 1
                    model, diag = coord.train(
                        residuals=residuals,
                        initial_model=models.get(cid),
                        seed=seed + it,
                    )
                    new_scores = coord.score(model)
                    _serialize_on_cpu_mesh(new_scores)
                    # Non-finite guard (resilience): catch a poisoned
                    # update BEFORE it enters the residual total. The
                    # rollback keeps the previous iterate for this
                    # coordinate; total/scores stay untouched, so every
                    # later update trains against the last good state.
                    if self.non_finite_guard:
                        # The guard's reduce of the scores and of each
                        # weight array.
                        programs += 1 + len(_model_weight_arrays(model))
                    if self.non_finite_guard and not _update_is_finite(
                        model, new_scores
                    ):
                        if cid not in models:
                            from photon_tpu.resilience.errors import (
                                NonFiniteUpdateError,
                            )

                            raise NonFiniteUpdateError(
                                f"coordinate {cid!r} produced non-finite "
                                f"loss/weights on its first update (CD "
                                f"iteration {it}): no previous iterate "
                                "to roll back to")
                        rolled_back = True
                    elif total is None:
                        # summedScores - oldScores + previousScores
                        # (:442,583). One jitted program: each eager
                        # arithmetic op is its own one-off compile
                        # (cost not measured on this chip).
                        total = new_scores
                    elif cid in scores:
                        total = _sub_add(total, scores[cid], new_scores)
                        programs += 1
                    else:
                        programs += 1
                        total = total + new_scores  # photon: ignore[use-after-donate] -- line 354 re-binds `total` to the donating call's result in the same statement, so this branch (a later coordinate's first appearance) reads the NEW buffer; the carry-aliased case routes through the plain twin via _sub_add's identity guard
                if rolled_back:
                    logger.warning(
                        "CD iter %d coordinate %s: non-finite update "
                        "ROLLED BACK to the previous iterate", it, cid)
                    if obs.enabled():
                        obs.REGISTRY.counter(
                            "coordinate_rollbacks_total", coordinate=cid
                        ).inc()
                        from photon_tpu.obs import trace as obs_trace

                        obs_trace.instant(
                            "cd.rollback", cat="resilience",
                            coordinate=cid, iteration=it,
                        )
                    record = CoordinateUpdateRecord(
                        iteration=it,
                        coordinate_id=cid,
                        seconds=time.perf_counter() - t0,
                        diagnostics=diag,
                        evaluation=None,
                        rolled_back=True,
                    )
                    history.append(record)
                    if self.emitter is not None:
                        from photon_tpu.events import (
                            CoordinateRollbackEvent,
                        )

                        self.emitter.send_event(
                            CoordinateRollbackEvent(record)
                        )
                    continue
                models[cid] = model
                scores[cid] = new_scores
                seconds = time.perf_counter() - t0

                evaluation = None
                if validation is not None:
                    # Incremental validation total: only the updated
                    # coordinate is rescored (same - old + new pattern as
                    # the training-side residual bookkeeping). Locked /
                    # warm-start models enter on their first appearance.
                    for vid, m in models.items():
                        if vid == cid or vid not in val_scores:
                            vs = validation.scorers[vid](m)
                            if val_total is None:
                                val_total = vs
                            else:
                                old = val_scores.get(vid)
                                val_total = (
                                    val_total + vs if old is None
                                    else _sub_add(val_total, old, vs)
                                )
                            val_scores[vid] = vs
                    evaluation = validation.suite.evaluate(val_total)  # photon: ignore[use-after-donate] -- the ternary above re-binds `val_total` to the donating call's result before this read, and a carry aliased with an operand dispatches through _sub_add's non-donating plain twin
                    primary = validation.suite.primary
                    # Only a FULL model (every coordinate trained or seeded)
                    # is eligible for best-model selection; partial models
                    # from the first sweep would silently drop coordinates.
                    if set(models) == all_ids and (
                        best_eval is None
                        or primary.better_than(
                            evaluation.primary_evaluation,
                            best_eval.primary_evaluation,
                        )
                    ):
                        best_eval = evaluation
                        best_model = GameModel(dict(models))
                    logger.info(
                        "CD iter %d coordinate %s: %s (%.2fs)",
                        it, cid, evaluation.evaluations, seconds,
                    )
                else:
                    logger.info(
                        "CD iter %d coordinate %s dispatched (%.2fs)",
                        it, cid, seconds,
                    )
                record = CoordinateUpdateRecord(
                    iteration=it,
                    coordinate_id=cid,
                    seconds=seconds,
                    diagnostics=diag,
                    evaluation=evaluation,
                )
                history.append(record)
                if self.emitter is not None:
                    from photon_tpu.events import CoordinateUpdateEvent

                    self.emitter.send_event(CoordinateUpdateEvent(record))
            # End of one OUTER iteration: the crash-safe recovery point.
            # The checkpointer hook runs first (state committed), then
            # the `cd.iteration` injection point — so an injected crash
            # here simulates dying with iteration `it`'s checkpoint
            # already durable, the kill-and-resume chaos window.
            if on_iteration is not None:
                on_iteration(it, GameModel(dict(models)), best_model)
            from photon_tpu.resilience import faults

            faults.check("cd.iteration")

        final = GameModel(dict(models))
        if best_model is None:
            best_model = final
        return CoordinateDescentResult(
            model=final,
            best_model=best_model,
            best_evaluation=best_eval,
            history=tuple(history),
        ), programs
