"""The GLM objective: value / gradient / HVP / Hessian as fused matvecs.

TPU-native replacement for the reference's entire aggregator layer:
``ValueAndGradientAggregator`` (photon-lib function/glm/
ValueAndGradientAggregator.scala:33-348), ``HessianVectorAggregator``
(HessianVectorAggregator.scala:33-290), ``HessianMatrixAggregator`` and
``HessianDiagonalAggregator`` (HessianMatrixAggregator.scala,
HessianDiagonalAggregator.scala), and the objective-function plumbing above
them (``DistributedGLMLossFunction``, ``SingleNodeGLMLossFunction``).

Where the reference streams per-row add() calls and merges partial
accumulators via treeAggregate, every quantity here is one or two matvecs
plus an elementwise kernel, fused by XLA:

    z      = X @ ew - es + offset                    (margins)
    value  = sum(weight * l(z, y))
    grad   = f * (X^T c - shift * sum(c)),  c = weight * dl/dz
    Hv     = f * (X^T h - shift * sum(h)),  h = weight * d2l/dz2 * (X @ ev - es_v)

with (ew, es) the normalization effective-coefficient rewrite
(ValueAndGradientAggregator.scala:62-88) so the raw — possibly sparse — data
is never transformed in memory. Under jit with the batch row-sharded over a
mesh data axis and ``w`` replicated, XLA lowers the ``X^T c`` reductions to
psum over ICI: the treeAggregate of the reference with zero host round trips.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from photon_tpu.data.dataset import GLMBatch
from photon_tpu.ops.losses import PointwiseLoss
from photon_tpu.ops.normalization import NormalizationContext, no_normalization
from photon_tpu.optim.base import HessianVectorProduct, ValueAndGrad

Array = jax.Array


def margins(batch: GLMBatch, coef: Array, norm: NormalizationContext) -> Array:
    """z_i = x'_i . w + offset_i in transformed feature space, computed on raw
    features via the effective-coefficient rewrite."""
    ew, es = norm.effective_coefficients(coef)
    return batch.features.matvec(ew) - es + batch.offsets


def make_value_and_grad(
    batch: GLMBatch,
    loss: PointwiseLoss,
    norm: NormalizationContext | None = None,
) -> ValueAndGrad:
    """Build fun(w) -> (value, grad) over the batch in transformed space.

    Replaces ValueAndGradientAggregator.calculateValueAndGradient
    (distributed, :299-320) and its local variant (:331): sharding the batch
    rows over the mesh turns the reductions into collectives automatically.
    """
    norm = norm or no_normalization()

    def fun(w: Array):
        z = margins(batch, w, norm)
        value = jnp.sum(batch.weights * loss.loss(z, batch.labels))
        c = batch.weights * loss.dz(z, batch.labels)
        raw_grad = batch.features.rmatvec(c)
        grad = norm.effective_gradient(raw_grad, jnp.sum(c))
        return value, grad

    return fun


def make_hvp(
    batch: GLMBatch,
    loss: PointwiseLoss,
    norm: NormalizationContext | None = None,
) -> HessianVectorProduct:
    """Build hvp(w, v) -> H(w) @ v (Gauss-Newton Hessian of the GLM loss).

    Replaces HessianVectorAggregator.calcHessianVector (:235): two matvecs
    and one reduction per CG step.
    """
    norm = norm or no_normalization()

    def hvp(w: Array, v: Array):
        z = margins(batch, w, norm)
        ev, es_v = norm.effective_coefficients(v)
        zv = batch.features.matvec(ev) - es_v  # directional margins (no offset)
        h = batch.weights * loss.dzz(z, batch.labels) * zv
        raw = batch.features.rmatvec(h)
        return norm.effective_gradient(raw, jnp.sum(h))

    return hvp


def hessian_diagonal(
    batch: GLMBatch,
    loss: PointwiseLoss,
    coef: Array,
    norm: NormalizationContext | None = None,
) -> Array:
    """diag(H) in transformed space; SIMPLE variance computation.

    Replaces HessianDiagonalAggregator. With x' = (x - s) * f:
      diag_j = f_j^2 * (sum_i c_i x_ij^2 - 2 s_j sum_i c_i x_ij + s_j^2 sum_i c_i),
      c_i = weight_i * dzz_i.
    """
    norm = norm or no_normalization()
    z = margins(batch, coef, norm)
    c = batch.weights * loss.dzz(z, batch.labels)
    d_sq = batch.features.rmatvec_sq(c)
    if norm.shifts is None and norm.factors is None:
        return d_sq
    d1 = batch.features.rmatvec(c)
    total = jnp.sum(c)
    s = norm.shifts if norm.shifts is not None else jnp.zeros_like(d_sq)
    f = norm.factors if norm.factors is not None else jnp.ones_like(d_sq)
    return f * f * (d_sq - 2.0 * s * d1 + s * s * total)


def hessian_matrix(
    batch: GLMBatch,
    loss: PointwiseLoss,
    coef: Array,
    norm: NormalizationContext | None = None,
) -> Array:
    """Full [d, d] Hessian in transformed space; FULL variance computation.

    Replaces HessianMatrixAggregator (X^T diag(c) X einsum). Materializes
    d^2 — only call for small-d coordinates, exactly like the reference's
    FULL variance option. With normalization:
      H = F (H_raw - s a^T - a s^T + (sum c) s s^T) F,  a = X^T c.
    Dense path only; sparse features are densified via their matvec
    structure using an identity sweep (d matvecs) — acceptable for the small
    d this option targets.
    """
    norm = norm or no_normalization()
    z = margins(batch, coef, norm)
    c = batch.weights * loss.dzz(z, batch.labels)

    from photon_tpu.data.dataset import DenseFeatures, FeatureMajorFeatures

    if isinstance(batch.features, (DenseFeatures, FeatureMajorFeatures)):
        h_raw = batch.features.gram(c)
    else:
        d = batch.num_features
        eye = jnp.eye(d, dtype=c.dtype)
        cols = jax.vmap(lambda e: batch.features.rmatvec(c * batch.features.matvec(e)))(eye)
        h_raw = cols.T

    if norm.shifts is None and norm.factors is None:
        return h_raw
    dtype = h_raw.dtype
    dsize = h_raw.shape[0]
    s = norm.shifts if norm.shifts is not None else jnp.zeros(dsize, dtype)
    f = norm.factors if norm.factors is not None else jnp.ones(dsize, dtype)
    a = batch.features.rmatvec(c)
    total = jnp.sum(c)
    h = h_raw - jnp.outer(s, a) - jnp.outer(a, s) + total * jnp.outer(s, s)
    return f[:, None] * h * f[None, :]
