"""Optimization problems: config dataclasses + solve/variance orchestration.

TPU-native counterpart of:
- ``GLMOptimizationConfiguration`` + coordinate optimization configs
  (photon-api optimization/game/CoordinateOptimizationConfiguration.scala:113,
  GLMOptimizationConfiguration.scala),
- ``GeneralizedLinearOptimizationProblem`` / ``DistributedOptimizationProblem``
  (optimization/GeneralizedLinearOptimizationProblem.scala:146,
  optimization/DistributedOptimizationProblem.scala:46): zero-model init,
  warm-start lambda updates, SIMPLE (inverse Hessian diagonal) and FULL
  (inverse-Hessian diagonal via Cholesky) coefficient variances (:86-103),
  and the transformed-space-optimize / original-space-report normalization
  round trip (:124-132).

``VarianceComputationType`` mirrors optimization/VarianceComputationType.scala.
"""

from __future__ import annotations

import dataclasses
import enum
import functools

import jax
import jax.numpy as jnp

from photon_tpu import optim
from photon_tpu.data.dataset import GLMBatch, feature_major
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.ops import glm as glm_ops
from photon_tpu.ops import losses as losses_mod
from photon_tpu.ops.normalization import NormalizationContext, no_normalization
from photon_tpu.types import TaskType

Array = jax.Array


class VarianceComputationType(enum.Enum):
    NONE = "NONE"
    SIMPLE = "SIMPLE"
    FULL = "FULL"


@dataclasses.dataclass(frozen=True)
class GLMOptimizationConfiguration:
    """Optimizer + regularization + lambda for one coordinate.

    Reference: GLMOptimizationConfiguration (optimizerConfig,
    regularizationContext, regularizationWeight); FixedEffect adds
    ``down_sampling_rate`` (FixedEffectOptimizationConfiguration).
    """

    optimizer: optim.OptimizerConfig = dataclasses.field(
        default_factory=optim.OptimizerConfig)
    regularization: optim.RegularizationContext = dataclasses.field(
        default_factory=optim.RegularizationContext)
    regularization_weight: float = 0.0
    down_sampling_rate: float = 1.0
    variance_computation: VarianceComputationType = VarianceComputationType.NONE
    # Hyperparameter-tuning search ranges (CoordinateOptimizationConfiguration
    # .scala:40-41 regularizationWeightRange / elasticNetParamRange); None
    # means the tuner's defaults apply.
    regularization_weight_range: tuple[float, float] | None = None
    elastic_net_param_range: tuple[float, float] | None = None
    # Incremental training: importance of the Gaussian prior built from the
    # previous model (GLMOptimizationConfiguration incrementalWeight,
    # DistributedGLMLossFunction.scala:190-192; default 1.0).
    incremental_weight: float = 1.0

    def with_regularization_weight(self, weight: float) -> "GLMOptimizationConfiguration":
        """Warm-start lambda update
        (DistributedOptimizationProblem.updateRegularizationWeight :64)."""
        return dataclasses.replace(self, regularization_weight=weight)

    @property
    def l1_weight(self) -> float:
        return self.regularization.l1_weight(self.regularization_weight)

    @property
    def l2_weight(self) -> float:
        return self.regularization.l2_weight(self.regularization_weight)


@dataclasses.dataclass(frozen=True)
class GLMSolution:
    """run() output: model in ORIGINAL feature space + solver diagnostics."""

    model: GeneralizedLinearModel
    result: optim.OptResult


def variances_in_transformed_space(
    batch: GLMBatch,
    loss: losses_mod.PointwiseLoss,
    coef_transformed: Array,
    norm: NormalizationContext,
    l2_diag: Array,
    variance_computation: VarianceComputationType,
) -> Array:
    """Transformed-space coefficient variances at the optimum.

    Shared core of the fixed-effect and (vmapped) random-effect variance
    paths. Reference semantics (DistributedOptimizationProblem.scala:86-103):
    - SIMPLE: element-wise inverse of the Hessian diagonal;
    - FULL:   diagonal of the inverse Hessian via Cholesky
              (util/Linalg.scala choleskyInverse).
    ``l2_diag`` is the per-coefficient L2 diagonal (0 at the intercept and at
    padded subspace slots). Slots with zero curvature — no data support and
    no L2 — get infinite variance instead of poisoning the Cholesky.
    """
    if variance_computation == VarianceComputationType.SIMPLE:
        diag = glm_ops.hessian_diagonal(batch, loss, coef_transformed, norm)
        diag = diag + l2_diag
        return 1.0 / jnp.where(diag == 0.0, jnp.inf, diag)

    h = glm_ops.hessian_matrix(batch, loss, coef_transformed, norm)
    h = h + jnp.diag(l2_diag)
    # Zero-curvature slots would make H singular; pin their diagonal to 1 and
    # report infinite variance for them.
    dead = jnp.diagonal(h) == 0.0
    h = h + jnp.diag(jnp.where(dead, 1.0, 0.0))
    d = coef_transformed.shape[-1]
    chol = jnp.linalg.cholesky(h)
    inv = jax.scipy.linalg.cho_solve((chol, True), jnp.eye(d, dtype=h.dtype))
    return jnp.where(dead, jnp.inf, jnp.diagonal(inv))


def compute_variances(
    batch: GLMBatch,
    loss: losses_mod.PointwiseLoss,
    coef_transformed: Array,
    norm: NormalizationContext,
    l2_weight: float,
    intercept_index: int | None,
    variance_computation: VarianceComputationType,
) -> Array | None:
    """Coefficient variances at the optimum, reported in original space.

    The L2 term contributes l2 to every non-intercept diagonal entry.
    Variances are computed in the optimization (transformed) space and mapped
    back with Var(w_j) = Var(w'_j) * factor_j^2 (the inverse of
    NormalizationContext.varToTransformedSpace).
    """
    if variance_computation == VarianceComputationType.NONE:
        return None
    d = coef_transformed.shape[-1]
    l2_diag = jnp.full((d,), l2_weight, dtype=coef_transformed.dtype)
    if intercept_index is not None:
        l2_diag = l2_diag.at[intercept_index].set(0.0)

    var_t = variances_in_transformed_space(
        batch, loss, coef_transformed, norm, l2_diag, variance_computation
    )
    if norm.factors is not None:
        var_t = var_t * norm.factors * norm.factors
    return var_t


@dataclasses.dataclass(frozen=True)
class GLMOptimizationProblem:
    """One GLM fit: objective assembly, transformed-space solve, round trip.

    Serves as both the reference's DistributedOptimizationProblem (fixed
    effect: ``batch`` sharded over the mesh) and, under vmap, its
    SingleNodeOptimizationProblem (per-entity: ``batch`` is one entity's padded
    block).
    """

    task: TaskType
    config: GLMOptimizationConfiguration
    normalization: NormalizationContext = dataclasses.field(
        default_factory=no_normalization)
    intercept_index: int | None = None
    # Incremental-training Gaussian prior (previous model's means/variances
    # in original space); replaces the plain L2 penalty when set
    # (DistributedGLMLossFunction.scala:184-193).
    prior: Coefficients | None = None

    @property
    def loss(self) -> losses_mod.PointwiseLoss:
        return losses_mod.get_loss(self.task)

    def initial_coefficients(self, dim: int, dtype=jnp.float32) -> Coefficients:
        """Zero model init (GeneralizedLinearOptimizationProblem
        initializeZeroModel)."""
        return Coefficients.zeros(dim, dtype=dtype)

    def run(
        self,
        batch: GLMBatch,
        initial: Coefficients | None = None,
    ) -> GLMSolution:
        """Fit on ``batch``; returns the model in original feature space.

        Matches Optimizer.optimize + DistributedOptimizationProblem.run: the
        initial (original-space) coefficients are mapped to transformed space,
        the solver runs there against the raw data via effective coefficients,
        and means/variances are mapped back.

        The whole solve runs under ONE cached ``jax.jit`` with the l1/l2
        weights as *traced* scalars, so coordinate-descent iterations, the
        warm-start lambda ladder, and hyperparameter tuning all reuse one
        compiled program per (shapes, optimizer config) — the reference pays
        a broadcast + treeAggregate per iteration instead
        (ValueAndGradientAggregator.scala:299-320).
        """
        d = batch.num_features
        dtype = batch.labels.dtype
        w0_orig = (initial.means if initial is not None
                   else jnp.zeros(d, dtype=dtype))

        cfg = self.config
        use_owlqn = cfg.l1_weight != 0.0
        prior = None
        if self.prior is not None:
            if self.prior.variances is None:
                raise ValueError(
                    "incremental training requires prior variances "
                    "(GameEstimator.scala:241-382 invariants)")
            # padded_to covers column-sharded solves: pad-slot variance 0 is
            # the "absent from prior" marker (inverse_prior_variances).
            p = self.prior.padded_to(d)
            prior = (
                jnp.asarray(p.means, dtype=dtype),
                jnp.asarray(p.variances, dtype=dtype),
            )
        # Box-constraint arrays make the optimizer config unhashable; that
        # rare path runs untraced (the constraints become trace constants).
        run = _run_jit if cfg.optimizer.box_constraints is None else _run_impl
        means, variances, result = run(
            batch,
            jnp.asarray(w0_orig, dtype=dtype),
            jnp.asarray(cfg.l1_weight, dtype=dtype),
            jnp.asarray(cfg.l2_weight, dtype=dtype),
            self.normalization,
            prior,
            jnp.asarray(cfg.incremental_weight, dtype=dtype),
            task=self.task,
            opt_config=cfg.optimizer,
            use_owlqn=use_owlqn,
            intercept_index=self.intercept_index,
            variance_computation=cfg.variance_computation,
        )
        model = GeneralizedLinearModel(
            Coefficients(means=means, variances=variances), self.task)
        return GLMSolution(model=model, result=result)


def _run_impl(
    batch: GLMBatch,
    w0_orig: Array,
    l1_weight: Array,
    l2_weight: Array,
    norm: NormalizationContext,
    prior: tuple[Array, Array] | None,
    incremental_weight: Array,
    *,
    task: TaskType,
    opt_config: optim.OptimizerConfig,
    use_owlqn: bool,
    intercept_index: int | None,
    variance_computation: VarianceComputationType,
):
    """One fused program: transform -> solve -> variances -> round trip.

    Regularization weights are traced operands: a new lambda re-runs the
    cached executable instead of recompiling (the warm-start ladder of
    DistributedOptimizationProblem.updateRegularizationWeight :64 and the
    tuner's retrains hit the same trace). Solver routing is static: OWL-QN
    whenever the config carries an L1 part (OptimizerFactory semantics).
    Every read of dense features goes through the feature-major view
    (``feature_major``): on the TPU no relaid-out copy of them is made.
    """
    batch = feature_major(batch)
    loss = losses_mod.get_loss(task)
    w0 = norm.coef_to_transformed_space(w0_orig)
    fun = glm_ops.make_value_and_grad(batch, loss, norm)

    if prior is not None:
        # Gaussian prior REPLACES the plain L2 term; the L2 weight survives
        # as the inverse-variance fallback for features absent from the
        # prior model (PriorDistribution.scala:31-60, normalizePrior :49).
        prior_means_t = norm.coef_to_transformed_space(prior[0])
        inv_prior_var_t = optim.inverse_prior_variances(
            norm.var_to_transformed_space(prior[1]), l2_weight
        )
        obj = optim.with_gaussian_prior(
            fun, incremental_weight, prior_means_t, inv_prior_var_t
        )
    else:
        obj = optim.with_l2(fun, l2_weight, intercept_index)

    if use_owlqn:
        result = optim.owlqn_solve(obj, w0, l1_weight, opt_config)
    elif opt_config.optimizer_type == optim.OptimizerType.TRON:
        raw_hvp = glm_ops.make_hvp(batch, loss, norm)
        if prior is not None:
            hvp = optim.with_gaussian_prior_hvp(
                raw_hvp, incremental_weight, inv_prior_var_t
            )
        else:
            hvp = optim.with_l2_hvp(raw_hvp, l2_weight, intercept_index)
        result = optim.tron_solve(obj, hvp, w0, opt_config)
    else:
        result = optim.lbfgs_solve(obj, w0, opt_config)

    if variance_computation == VarianceComputationType.NONE:
        variances = None
    else:
        d = w0_orig.shape[-1]
        if prior is not None:
            # The prior contributes iw/var to every diagonal entry
            # (PriorDistributionTwiceDiff.l2RegHessianDiagonal).
            l2_diag = incremental_weight * inv_prior_var_t
        else:
            l2_diag = jnp.full((d,), l2_weight, dtype=w0_orig.dtype)
            if intercept_index is not None:
                l2_diag = l2_diag.at[intercept_index].set(0.0)
        variances = variances_in_transformed_space(
            batch, loss, result.coefficients, norm, l2_diag,
            variance_computation,
        )
        if norm.factors is not None:
            variances = variances * norm.factors * norm.factors
    means = norm.coef_to_original_space(result.coefficients)
    return means, variances, result



_run_jit = functools.partial(
    jax.jit,
    static_argnames=(
        "task", "opt_config", "use_owlqn", "intercept_index",
        "variance_computation",
    ),
)(_run_impl)
