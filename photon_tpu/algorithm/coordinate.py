"""Coordinates: the trainable/scorable units of a GAME model.

TPU-native counterpart of photon-lib algorithm/Coordinate.scala:28 (train
with optional warm start / residual offsets, score) and photon-api
algorithm/FixedEffectCoordinate.scala:33. The random-effect coordinate lives
in ``random_effect.py``; score-only (locked) coordinates are
``ModelCoordinate`` equivalents.

A coordinate's ``score`` returns the pure model contribution per row — the
CoordinateDataScores used as residual offsets by coordinate descent
(FixedEffectCoordinate.score :144-154 computes coefficient dot features with
no offset added).
"""

from __future__ import annotations

import dataclasses
from typing import Protocol

import jax
import jax.numpy as jnp

from photon_tpu.algorithm.problems import (
    GLMOptimizationConfiguration,
    GLMOptimizationProblem,
)
from photon_tpu.data.dataset import GLMBatch
from photon_tpu.data.sampling import downsample
from photon_tpu.models.glm import GeneralizedLinearModel
from photon_tpu.types import TaskType

Array = jax.Array


class Coordinate(Protocol):
    """Reference: algorithm/Coordinate.scala:28."""

    def train(
        self,
        residuals: Array | None = None,
        initial_model=None,
        *,
        seed: int = 0,
    ):
        """Fit against base offsets + residual scores; returns
        (model, diagnostics)."""

    def score(self, model) -> Array:
        """Model contribution per row of the canonical table."""


def fit_rows(v: Array, rows: int | None) -> Array:
    """``v`` cut or zero-padded to ``rows`` entries (None: as it is): a
    per-row vector moved between a coordinate's own row count and the
    coordinate-descent loop's (``FixedEffectCoordinate.logical_rows``)."""
    if rows is None or v.shape[0] == rows:
        return v
    if v.shape[0] > rows:
        return v[:rows]
    return jnp.pad(v, (0, rows - v.shape[0]))


@dataclasses.dataclass(frozen=True)
class FixedEffectCoordinate:
    """Global GLM coordinate over one feature shard.

    ``batch.offsets`` are the dataset's base offsets; residual scores from
    other coordinates are added per train call (Coordinate.scala:52-53).
    Optional negative down-sampling applies per train call with a fresh
    seeded key (FixedEffectCoordinate.trainModel →
    DistributedOptimizationProblem.runWithSampling :141-167).
    """

    batch: GLMBatch
    problem: GLMOptimizationProblem
    # The length of the vectors the coordinate-descent loop exchanges with
    # this coordinate (residuals in, scores out): the canonical row count,
    # or on a mesh that count padded to the device count
    # (parallel/mesh.py loop_rows), which a row-sharded ``batch`` already
    # has (shard_batch's weight-0 padding rows). A batch of another length
    # has its scores and residuals fitted to it (``fit_rows``).
    logical_rows: int | None = None

    @property
    def config(self) -> GLMOptimizationConfiguration:
        return self.problem.config

    def train(
        self,
        residuals: Array | None = None,
        initial_model: GeneralizedLinearModel | None = None,
        *,
        seed: int = 0,
    ):
        batch = self.batch
        if residuals is not None:
            residuals = fit_rows(residuals, batch.num_samples)
            batch = batch.with_offsets(batch.offsets + residuals)
        rate = self.config.down_sampling_rate
        if 0.0 < rate < 1.0:
            binary = self.problem.task in (
                TaskType.LOGISTIC_REGRESSION,
                TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
            )
            batch = downsample(
                batch, rate, jax.random.key(seed), binary=binary)
        initial = initial_model.coefficients if initial_model is not None else None
        if initial is not None:
            # Column-sharded features solve in a device-count-padded
            # coefficient space; externally visible models stay at the
            # logical feature count (see the trim below).
            initial = initial.padded_to(batch.num_features)
        solution = self.problem.run(batch, initial)
        model = solution.model
        logical_d = getattr(batch.features, "logical_d", None)
        if logical_d is not None and logical_d != batch.num_features:
            coefs = model.coefficients
            model = dataclasses.replace(
                model,
                coefficients=dataclasses.replace(
                    coefs,
                    means=coefs.means[:logical_d],
                    variances=(
                        None if coefs.variances is None
                        else coefs.variances[:logical_d]
                    ),
                ),
            )
        return model, solution.result

    def score(self, model: GeneralizedLinearModel) -> Array:
        return fit_rows(
            model.coefficients.compute_score(self.batch.features),
            self.logical_rows)

    def programs_per_update(self) -> dict:
        """The programs an update dispatches (``fit`` stage, unfused
        loop): the solve; the matvec and, where the batch has another
        length than the loop's vectors, the fit to it; with residuals
        their sum into the offsets and that fit the other way. JAX's
        one-primitive helpers (a cast of a scalar operand, a zeros vector,
        a slice's index) are not counted."""
        padded = (
            self.logical_rows is not None
            and self.batch.num_samples != self.logical_rows)
        return {"train": 1, "score": 1 + padded, "residuals": 1 + padded}


@dataclasses.dataclass(frozen=True)
class ModelCoordinate:
    """Score-only coordinate for locked (partial-retrain) models.

    Reference: algorithm/ModelCoordinate.scala:64,
    FixedEffectModelCoordinate.scala:44.
    """

    inner: Coordinate
    model: GeneralizedLinearModel

    def train(self, residuals=None, initial_model=None, *, seed: int = 0):
        raise RuntimeError(
            "locked coordinate cannot be retrained "
            "(partialRetrainLockedCoordinates)")

    def score(self, model=None) -> Array:
        return self.inner.score(self.model if model is None else model)

    def programs_per_update(self) -> dict:
        return self.inner.programs_per_update()
