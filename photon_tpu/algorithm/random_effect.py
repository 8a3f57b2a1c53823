"""RandomEffectCoordinate: batched vmapped per-entity GLM solves.

TPU-native counterpart of photon-api algorithm/RandomEffectCoordinate.scala:38
and optimization/game/RandomEffectOptimizationProblem.scala:45. The
reference's design — join activeData with per-entity
SingleNodeOptimizationProblems and run a *local* Breeze optimizer per entity
inside ``mapValues`` (:243-292) — becomes: for each size bucket of entities,
ONE jitted ``vmap`` of the full L-BFGS/OWL-QN/TRON while_loop over the entity
axis. JAX's while_loop batching rule gives masked per-entity convergence for
free (converged entities stop changing), the analog of heterogeneous
convergence across executor-local solves (SURVEY §7.3).

Per-entity projected normalization contexts
(RandomEffectOptimizationProblem.scala:137-198) are gathers of the global
factor/shift vectors through the entity's projector; the per-entity intercept
slot is a traced index, so coefficient space round-trips use one-hot masks
instead of static-index updates.

Scoring covers active AND passive rows uniformly via the dataset's remapped
scoring table (scoreActiveData :314-332 / scorePassiveData :346-366 collapse
into one gather-multiply-reduce).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_tpu import optim
from photon_tpu.algorithm.coordinate import fit_rows
from photon_tpu.algorithm.problems import (
    GLMOptimizationConfiguration,
    VarianceComputationType,
    variances_in_transformed_space,
)
from photon_tpu.data.dataset import (
    DenseFeatures,
    GLMBatch,
    SparseFeatures,
)
from photon_tpu.data.random_effect import (
    BlockPlan,
    EntityBlocks,
    RandomEffectDataset,
    selects_dense,
)
from photon_tpu.models.game import (
    RandomEffectModel,
    score_programs,
    score_route,
    score_rows,
)
from photon_tpu.ops import cg_kernel
from photon_tpu.ops import glm as glm_ops
from photon_tpu.ops import losses as losses_mod
from photon_tpu.ops import placement
from photon_tpu.ops import precision as precision_mod
from photon_tpu.ops import segment_reduce
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.types import TaskType

Array = jax.Array


class RandomEffectTrainingStats:
    """Aggregate per-entity solver diagnostics, fetched LAZILY.

    Reference: RandomEffectOptimizationTracker (optimization/
    RandomEffectOptimizationTracker.scala:89) — counts of convergence reasons
    plus iteration stats over entities.

    The diagnostic arrays live on the device until an attribute is read:
    fetching them eagerly would insert a device->host sync into every
    coordinate update of the CD loop (on a remote-attached chip that sync
    costs more than the solve itself). Training code threads this object
    into the history without touching it; summaries/tests that read it pay
    the one coalesced transfer then. Unread stats pin two [num_entities]
    int32 device buffers per update — bounded well below the [E, S]
    coefficient matrices the same history records already retain, so no
    explicit release hook is needed.
    """

    def __init__(self, reasons=None, iterations=None, *, device=None,
                 thunk=None):
        # device: (reason device arrays, iteration device arrays,
        #          host keep-masks) — one pull on first attribute access.
        # thunk: zero-arg callable -> (reasons np, iterations np); the
        #        fused fit's packed-diagnostics buffer resolves through it.
        self._device = device
        self._thunk = thunk
        self._host = None
        if device is None and thunk is None:
            self._host = (
                np.asarray(reasons) if reasons is not None
                else np.empty(0, np.int32),
                np.asarray(iterations) if iterations is not None
                else np.empty(0, np.int32),
            )

    @staticmethod
    def from_arrays(reasons: np.ndarray, iterations: np.ndarray):
        return RandomEffectTrainingStats(reasons, iterations)

    @staticmethod
    def from_device(reason_arrays, iteration_arrays, keep_masks):
        return RandomEffectTrainingStats(
            device=(reason_arrays, iteration_arrays, keep_masks)
        )

    @staticmethod
    def from_thunk(thunk):
        return RandomEffectTrainingStats(thunk=thunk)

    def _materialize(self):
        if self._host is None and self._thunk is not None:
            reasons, iters = self._thunk()
            self._host = (np.asarray(reasons), np.asarray(iters))
            self._thunk = None
        if self._host is None:
            reasons_d, iters_d, keeps = self._device
            keep = np.concatenate(keeps) if keeps else np.empty(0, bool)
            # One coalesced fetch of all blocks' diagnostics.
            reasons = (
                np.asarray(jnp.concatenate(reasons_d)) if reasons_d
                else np.empty(0, np.int32)
            )
            iters = (
                np.asarray(jnp.concatenate(iters_d)) if iters_d
                else np.empty(0, np.int32)
            )
            self._host = (reasons[keep], iters[keep])
            self._device = None
        return self._host

    @property
    def convergence_reason_counts(self) -> dict[str, int]:
        reasons, _ = self._materialize()
        counts: dict[str, int] = {}
        for code, cnt in zip(*np.unique(reasons, return_counts=True)):
            counts[optim.ConvergenceReason(int(code)).name] = int(cnt)
        return counts

    @property
    def iterations_mean(self) -> float:
        _, iters = self._materialize()
        return float(iters.mean()) if iters.size else 0.0

    @property
    def iterations_max(self) -> int:
        _, iters = self._materialize()
        return int(iters.max()) if iters.size else 0

    @property
    def num_entities(self) -> int:
        _, iters = self._materialize()
        return int(iters.size)


def _onehot(slot: Array, dim: int, dtype) -> Array:
    """One-hot of a traced (possibly -1) slot index; all-zero when slot < 0."""
    iota = jnp.arange(dim)
    return jnp.where(iota == slot, 1.0, 0.0).astype(dtype)


def _coef_to_transformed(w, factors, shifts, int_onehot):
    if shifts is not None:
        w = w + jnp.dot(w, shifts) * int_onehot
    if factors is not None:
        w = w / factors
    return w


def _coef_to_original(w_t, factors, shifts, int_onehot):
    w = w_t if factors is None else w_t * factors
    if shifts is not None:
        w = w - jnp.dot(w, shifts) * int_onehot
    return w


def _features_of(
    x_indices: Array | None, x_values: Array, sub_dim: int
):
    """Per-entity feature view: dense [R, S] matrix or ELL slabs."""
    if x_indices is None:
        return DenseFeatures(x_values)
    return SparseFeatures(x_indices, x_values, sub_dim)


def _densify_ell_slots(
    x_indices: Array, x_values: Array, sub_dim: int
) -> Array:
    """[..., k] slot-ELL -> [..., S] dense via a one-hot selection (NOT
    scatter: batched scatter/gather lowers to a pathologically
    slow-compiling program on TPU; the selection compiles in <1s).
    Duplicate slots sum, matching scatter-add (with an f32 accumulator
    when the values are stored bf16; the densified slab returns to the
    storage dtype).

    A sum of elementwise products, NOT a ``dot_general``, as
    ``BlockPlan.materialize`` selects a dense shard: a slab must hold the
    raw values exactly whatever JAX's matmul precision is, and at
    ``highest`` XLA:TPU has built a one-hot contraction's slab wrong
    (PERF.md section 6)."""
    onehot = (
        x_indices[..., None]
        == jnp.arange(sub_dim, dtype=x_indices.dtype)
    )
    acc = (x_values.astype(jnp.float32)
           if x_values.dtype == jnp.bfloat16 else x_values)
    return jnp.sum(
        jnp.where(onehot, acc[..., None], 0), axis=-2
    ).astype(x_values.dtype)


def _spd_solve_cg(h: Array, b: Array, sub_dim: int,
                  refine: bool = True) -> Array:
    """Solve the SPD system ``h x = b`` by FIXED-count conjugate gradients.

    Batched tiny Cholesky/triangular solves lower to sequential scalar
    loops on TPU — slow to run at B~1e5 under vmap and pathologically slow
    to compile — while CG is ``sub_dim`` iterations of [S, S] matvecs that
    batch cleanly into GEMMs. For SPD H (strict convexity + the unit
    padding diagonal) CG is exact after S steps up to roundoff; sub_dim is
    small by construction (LinearSubspaceProjector compression).

    In float32 S-step CG is NOT backward-stable on ill-conditioned H
    (relative error ~0.5 at cond(H)=1e4 measured), so with ``refine`` one
    round of iterative refinement follows: ``x += cg(H, b - H x)``. Both
    passes are the same batched GEMM shapes; the refined solve tracks a
    direct fp32 Cholesky down to cond(H)~1e6. Newton DIRECTION solves pass
    ``refine=False`` — directions only need descent (enforced by the
    g.d < 0 steepest-descent fallback at the call site), and refinement
    would double the sequential depth of the latency-bound hot loop.
    """

    def run_cg(rhs):
        def cg_step(_, state):
            x, r, p, rs = state
            hp = h @ p
            alpha = rs / jnp.maximum(jnp.dot(p, hp), 1e-30)
            x = x + alpha * p
            r = r - alpha * hp
            rs_new = jnp.dot(r, r)
            p = r + (rs_new / jnp.maximum(rs, 1e-30)) * p
            return x, r, p, rs_new

        init = (jnp.zeros_like(rhs), rhs, rhs, jnp.dot(rhs, rhs))
        x, _, _, _ = lax.fori_loop(0, sub_dim, cg_step, init)
        return x

    x = run_cg(b)
    if not refine:
        return x
    return x + run_cg(b - h @ x)


def _direct_system(
    x_indices: Array | None,  # [R, k] ELL slots, or None (dense layout)
    x_values: Array,  # [R, k] or [R, S]
    labels: Array,  # [R]
    offsets: Array,  # [R]
    weights: Array,  # [R]
    penalty_mask: Array,  # [S]
    valid_mask: Array,  # [S]
    factors: Array | None,  # [S]
    shifts: Array | None,  # [S]
    intercept_slot: Array,
    prior: tuple[Array, Array] | None,
    *,
    sub_dim: int,
    l2_weight: Array,
    incremental_weight: Array,
):
    """First half of the exact per-entity solve for the squared-loss case
    (vmapped over the bucket): the normal equations
      (X'^T diag(wt) X' + diag(pen)) w = X'^T diag(wt) (y - offset) (+ prior)
    as ``(h [S, S], b [S], l2_diag [S])``, with the unit diagonal on
    padding slots that keeps ``h`` SPD. Their minimizer is what the
    reference's LBFGS/TRON iterates toward
    (SingleNodeOptimizationProblem.run); ``_solve_block`` solves the
    bucket's systems by fixed-count CG (``_spd_solve_cg`` vmapped, or the
    lane kernel ``ops/cg_kernel.py``) and ``_direct_finish`` maps the
    solutions back. The subspace design matrix is densified per entity
    (S = sub_dim is small by construction — LinearSubspaceProjector
    compression).
    """
    # Solver STATE (w, H, b, variances) lives in the label dtype (f32);
    # only the design matrix x may be stored bf16 under mixed precision,
    # with every row-axis contraction accumulating f32 (acc_einsum).
    dtype = labels.dtype
    if x_indices is None:
        x = x_values
    else:
        # This branch only runs for wide subspaces (_solve_block densifies
        # small ones up front): scatter-add keeps peak memory at the dense
        # [R, S] result instead of a [R, k, S] one-hot operand.
        r = x_values.shape[0]
        rows = jnp.broadcast_to(jnp.arange(r)[:, None], x_indices.shape)
        x = jnp.zeros((r, sub_dim), x_values.dtype).at[
            rows, x_indices].add(x_values)
    if shifts is not None:
        x = x - precision_mod.like_storage(shifts, x)[None, :]
    if factors is not None:
        x = x * precision_mod.like_storage(factors, x)[None, :]
    y_eff = (labels - offsets) * weights
    h = precision_mod.acc_einsum(
        "rs,rt->st", x * precision_mod.like_storage(weights, x)[:, None], x
    )
    b = precision_mod.acc_einsum(
        "rs,r->s", x, precision_mod.like_storage(y_eff, x)
    )
    if prior is not None:
        int_onehot = (
            None if shifts is None
            else _onehot(intercept_slot, sub_dim, dtype)
        )
        m_t = _coef_to_transformed(prior[0], factors, shifts, int_onehot)
        f_sq = 1.0 if factors is None else factors * factors
        inv_prior_var = optim.inverse_prior_variances(
            prior[1] / f_sq, l2_weight) * valid_mask
        l2_diag = incremental_weight * inv_prior_var
        b = b + l2_diag * m_t
    else:
        l2_diag = l2_weight * penalty_mask
    h = h + jnp.diag(l2_diag + (1.0 - valid_mask))
    return h, b, l2_diag


def _direct_finish(
    w_t: Array,  # [S] solution of _direct_system's h w = b
    x_indices: Array | None,
    x_values: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    valid_mask: Array,
    factors: Array | None,
    shifts: Array | None,
    intercept_slot: Array,
    l2_diag: Array,
    *,
    sub_dim: int,
    variance_computation: VarianceComputationType,
    task: TaskType,
):
    """Second half of the direct solve (vmapped over the bucket): mask
    the padding slots, compute variances, map back to the original
    space; returns the solvers' ``(w, variances, iterations, reason)``."""
    dtype = labels.dtype
    w_t = w_t * valid_mask
    norm = NormalizationContext(
        factors=factors, shifts=shifts,
        intercept_index=None if shifts is None else 0,
    )
    if variance_computation != VarianceComputationType.NONE:
        loss = losses_mod.get_loss(task)
        # Variances run the deep f32 machinery: upcast a bf16-stored
        # design (identity on the default path) — variances are a few
        # tiny solves, not the hot loop.
        batch = GLMBatch(
            _features_of(x_indices, x_values.astype(dtype), sub_dim),
            labels, offsets, weights,
        )
        var_t = variances_in_transformed_space(
            batch, loss, w_t, norm, l2_diag, variance_computation,
        )
        f_sq = 1.0 if factors is None else factors * factors
        variances = jnp.where(valid_mask > 0, var_t * f_sq, 0.0)
    else:
        variances = jnp.zeros_like(w_t)

    int_onehot = (
        None if shifts is None else _onehot(intercept_slot, sub_dim, dtype)
    )
    w_orig = _coef_to_original(w_t, factors, shifts, int_onehot) * valid_mask
    return (
        w_orig,
        variances,
        jnp.asarray(1, jnp.int32),
        jnp.asarray(int(optim.ConvergenceReason.GRADIENT_CONVERGED),
                    jnp.int32),
    )


def _materialize_transformed_design(
    x_indices: Array | None,
    x_values: Array,
    factors: Array | None,
    shifts: Array | None,
    sub_dim: int,
) -> Array:
    """Dense [R, S] transformed design matrix for one entity."""
    dtype = x_values.dtype
    if x_indices is None:
        x = x_values
    else:
        r = x_values.shape[0]
        rows = jnp.broadcast_to(jnp.arange(r)[:, None], x_indices.shape)
        x = jnp.zeros((r, sub_dim), dtype).at[rows, x_indices].add(x_values)
    if shifts is not None:
        x = x - shifts[None, :]
    if factors is not None:
        x = x * factors[None, :]
    return x


_NEWTON_LINE_SEARCH_HALVINGS = 15


def _spd_solve_cg_sb(h_sb: Array, b_sb: Array, sub_dim: int,
                     active: Array) -> Array:
    """Batched SPD solve in BATCH-MINOR layout: ``h_sb`` is [S, S, B] and
    ``b_sb``/result are [S, B].

    Why the layout matters: a vmapped per-entity CG carries H as [B, S, S]
    and state as [B, S]; with S ~ 17 the TPU's (8, 128) tiling pads the
    minor axis 17 -> 128, physically inflating every CG-step re-read of H
    ~7-10x (the dominant HBM traffic of the whole per-entity solve,
    measured by the round-4 Pallas probe, experiments/README.md). With B
    minor, lanes are dense: H is stored compact and each of the S CG steps
    is elementwise-over-B multiply-reduce work at full lane utilization.

    ``active`` [B] masks converged entities: their iterates are frozen so
    a diverging stale system cannot produce NaNs that poison the batch.
    """

    def cg_step(_, state):
        x, r, p, rs = state
        # Broadcast-multiply-reduce, NOT einsum/dot_general: the batched
        # contraction with minor batch dim lowers to per-row slice chains
        # (~3 x 0.7ms per CG step measured), while this form fuses into
        # one elementwise+reduce kernel over the compact [S, S, B] block.
        hp = jnp.sum(h_sb * p[None, :, :], axis=1)
        denom = jnp.sum(p * hp, axis=0)
        alpha = jnp.where(active, rs / jnp.maximum(denom, 1e-30), 0.0)
        x = x + alpha[None, :] * p
        r = r - alpha[None, :] * hp
        rs_new = jnp.sum(r * r, axis=0)
        beta = rs_new / jnp.maximum(rs, 1e-30)
        p = r + jnp.where(active, beta, 0.0)[None, :] * p
        return x, r, p, rs_new

    init = (jnp.zeros_like(b_sb), b_sb, b_sb,
            jnp.sum(b_sb * b_sb, axis=0))
    x, _, _, _ = lax.fori_loop(0, sub_dim, cg_step, init)
    return x


def _solve_direct_gram(
    block,  # EntityBlocks, ELL layout (x_indices is not None)
    offsets: Array,  # [B, R] effective offsets (residuals folded in)
    factors_sub: Array | None,  # [B, S]
    prior: tuple[Array, Array] | None,  # ([B, S], [B, S])
    *,
    sub_dim: int,
    l2_weight: Array,
    incremental_weight: Array,
    gram_mults: tuple,
):
    """Whole-bucket exact squared-loss solve straight from the ELL layout.

    The wide-subspace direct path previously materialized a dense
    [B, R, S] slab (per entity, or bucket-wide via densify_ell_blocks)
    just to form X^T W X — at wide S that slab is the dominant HBM
    object of the whole solve. But the normal equations only need the
    [B, S, S] gram blocks and the [B, S] moment vector, and BOTH are
    segment sums over the ELL entries: pair products w * v_j * v_l land
    in gram segment (entity, slot_j, slot_l), weighted targets in
    (entity, slot). The tiled segment-reduce (ops/segment_reduce)
    aggregates them with the host-computed window bounds sizing
    coverage (``gram_mults`` = data/random_effect.block_gram_mults) —
    the dense slab never exists.

    Engagement is gated by ``_solve_block`` (direct + ELL + no shifts +
    no variances + kernel-served shape). Normalization factors fold in
    AFTER the reduce: X' = X F gives H' = F H F and b' = F b (diagonal
    congruence) — the same algebra the per-entity solver applies
    row-wise before aggregating.
    """
    dtype = block.labels.dtype
    s = sub_dim
    grad_mult, hess_mult = gram_mults
    gram = segment_reduce.ell_gram_blocks(
        block.x_indices, block.x_values, block.weights, s,
        multiplicity=hess_mult,
    )
    y_eff = (block.labels - offsets) * block.weights
    bvec = segment_reduce.ell_segment_slots(
        block.x_indices, block.x_values, y_eff, s,
        multiplicity=grad_mult,
    )
    assert gram is not None and bvec is not None  # ell_gram_supported gate
    h = gram.astype(dtype)
    b_vec = bvec.astype(dtype)
    if factors_sub is not None:
        h = h * factors_sub[:, :, None] * factors_sub[:, None, :]
        b_vec = b_vec * factors_sub
    valid_mask = block.valid_mask
    if prior is not None:
        # Shifts are None on this route, so the transformed prior means
        # are just the factor-rescaled originals (no intercept fold).
        m_t = _coef_to_transformed(prior[0], factors_sub, None, None)
        f_sq = 1.0 if factors_sub is None else factors_sub * factors_sub
        inv_prior_var = optim.inverse_prior_variances(
            prior[1] / f_sq, l2_weight) * valid_mask
        l2_diag = incremental_weight * inv_prior_var
        b_vec = b_vec + l2_diag * m_t
    else:
        l2_diag = l2_weight * block.penalty_mask
    # Padding slots get a unit diagonal so the system stays PD; their
    # gradient is masked (identical to the per-entity solver).
    h = h + jnp.eye(s, dtype=dtype) * (
        l2_diag + (1.0 - valid_mask))[:, None, :]
    # Batch-minor CG (compact lanes, see _spd_solve_cg_sb) plus one
    # refinement pass — matching the refined default the per-entity
    # direct solver gets from _spd_solve_cg.
    h_sb = jnp.transpose(h, (1, 2, 0))
    b_sb = jnp.transpose(b_vec)
    active = jnp.ones(b_vec.shape[0], bool)
    sol = _spd_solve_cg_sb(h_sb, b_sb, s, active)
    res = b_sb - jnp.sum(h_sb * sol[None, :, :], axis=1)
    sol = sol + _spd_solve_cg_sb(h_sb, res, s, active)
    w_t = jnp.transpose(sol).astype(dtype) * valid_mask
    w = _coef_to_original(w_t, factors_sub, None, None) * valid_mask
    bsz = w.shape[0]
    return (
        w,
        jnp.zeros_like(w),
        jnp.ones(bsz, jnp.int32),
        jnp.full(
            bsz,
            int(optim.ConvergenceReason.GRADIENT_CONVERGED),
            jnp.int32,
        ),
    )


def _solve_newton_batched(
    x: Array,  # [B, R, S] dense slab (raw, untransformed)
    labels: Array,  # [B, R]
    offsets: Array,  # [B, R]
    weights: Array,  # [B, R]
    penalty_mask: Array,  # [B, S]
    valid_mask: Array,  # [B, S]
    factors: Array | None,  # [B, S]
    shifts: Array | None,  # [B, S]
    intercept_slots: Array,  # [B]
    w0_orig: Array,  # [B, S]
    prior: tuple[Array, Array] | None,  # ([B, S], [B, S])
    *,
    sub_dim: int,
    task: TaskType,
    opt_config: optim.OptimizerConfig,
    variance_computation: VarianceComputationType,
    l2_weight: Array,
    incremental_weight: Array,
    spmd: bool = False,
):
    """Batch-level damped-Newton/IRLS for a whole dense bucket.

    Numerically the batched transcription of ``_solve_one_entity_newton``
    (same objective, same one-pass Armijo trials, same convergence
    cascade), written WITHOUT vmap so the Hessians and CG state can live
    in batch-minor layout (see ``_spd_solve_cg_sb``): the [B, S, S] MXU
    Hessian batch is transposed ONCE to compact [S, S, B] instead of being
    re-read S times through a 7-10x tiling-padded layout. The Newton
    direction uses a single S-step CG (no refinement pass — directions
    only need descent, which the g.d < 0 guard enforces; the refined
    solver stays on the exact direct path where the solution itself is
    the answer).
    """
    from photon_tpu.ops import newton_kernel as nk

    r = x.shape[1]
    # The fused Newton kernel is f32-only: a bf16-stored slab takes the
    # batch-minor XLA path below (which reads the slab at half width —
    # the storage win survives the fallback). The ONE place that decides
    # the route also names it: `solve.newton_kernel` / `solve.newton_xla`
    # on every operation of the solve (metadata only).
    use_kernel = nk.kernel_supported(task, x.dtype, r, sub_dim, spmd=spmd)
    with jax.named_scope(
        "solve.newton_kernel" if use_kernel else "solve.newton_xla"
    ):
        # Solver state (w, f, g, H, CG iterates) is f32; only the slab x
        # may be stored bf16 under mixed precision — every contraction
        # against it reads bf16 and accumulates f32 (ops/precision.py
        # invariant).
        dtype = labels.dtype
        b = x.shape[0]
        if shifts is not None:
            x = x - precision_mod.like_storage(shifts, x)[:, None, :]
        if factors is not None:
            x = x * precision_mod.like_storage(factors, x)[:, None, :]
        loss = losses_mod.get_loss(task)
        iota = jnp.arange(sub_dim)[None, :]
        int_onehot = (
            None if shifts is None
            else (iota == intercept_slots[:, None]).astype(dtype)
        )

        def to_transformed(w):
            if shifts is not None:
                w = w + jnp.sum(
                    w * shifts, axis=-1, keepdims=True) * int_onehot
            if factors is not None:
                w = w / factors
            return w

        def to_original(w_t):
            w = w_t if factors is None else w_t * factors
            if shifts is not None:
                w = w - jnp.sum(
                    w * shifts, axis=-1, keepdims=True) * int_onehot
            return w

        if prior is not None:
            m_t = to_transformed(prior[0])
            f_sq = 1.0 if factors is None else factors * factors
            inv_prior_var = optim.inverse_prior_variances(
                prior[1] / f_sq, l2_weight) * valid_mask
            l2_diag = incremental_weight * inv_prior_var
        else:
            m_t = jnp.zeros((b, sub_dim), dtype)
            l2_diag = l2_weight * penalty_mask

        def objective(w):  # w [B, S] -> f [B], g [B, S]
            z = precision_mod.acc_einsum(
                "brs,bs->br", x, precision_mod.like_storage(w, x)
            ) + offsets
            f = jnp.sum(
                weights * loss.loss(z, labels), axis=-1
            ) + 0.5 * jnp.sum(l2_diag * (w - m_t) ** 2, axis=-1)
            g = precision_mod.acc_einsum(
                "brs,br->bs", x,
                precision_mod.like_storage(weights * loss.dz(z, labels), x),
            )
            g = g + l2_diag * (w - m_t)
            return f, g * valid_mask

        # Per-entity absolute tolerances from the zero state
        # (Optimizer.scala:167-170 semantics, batched).
        f0z, g0z = objective(jnp.zeros((b, sub_dim), dtype))
        tol = optim.Tolerances(
            loss_abs=jnp.abs(f0z) * opt_config.tolerance,
            gradient_abs=jnp.sqrt(jnp.sum(g0z * g0z, axis=-1))
            * opt_config.tolerance,
        )
        w0 = to_transformed(w0_orig) * valid_mask
        f0, g0 = objective(w0)
        max_iters = opt_config.max_iterations

        if use_kernel:
            # Fused Pallas step: the [S, S] Hessians never leave VMEM (the
            # XLA path's padded [B, S, S] HBM round trip is the traffic it
            # removes; ops/newton_kernel.py).
            bp = nk.pad_lanes(b)

            def pad_b(a):
                return jnp.pad(a, [(0, bp - b)] + [(0, 0)] * (a.ndim - 1))

            x_l = jnp.transpose(pad_b(x), (2, 1, 0))
            y_l = nk.to_lanes(labels, bp)
            wt_l = nk.to_lanes(weights, bp)
            off_l = nk.to_lanes(offsets, bp)
            l2_l = nk.to_lanes(jnp.broadcast_to(l2_diag, (b, sub_dim)), bp)
            mt_l = nk.to_lanes(jnp.broadcast_to(m_t, (b, sub_dim)), bp)
            vm_l = nk.to_lanes(valid_mask, bp)
            w_l = nk.to_lanes(w0, bp)
            g_l = nk.to_lanes(g0, bp)
            f_l = jnp.pad(f0, (0, bp - b))[None, :]
            tol_p = optim.Tolerances(
                loss_abs=jnp.pad(tol.loss_abs, (0, bp - b)),
                gradient_abs=jnp.pad(tol.gradient_abs, (0, bp - b)),
            )

            def cond_k(st):
                return jnp.any(st[4] == 0)

            def body_k(st):
                w_c, f_c, g_c, it_c, code_c = st
                active = code_c == 0
                w_n, f_n, g_n, imp = nk.newton_step_lanes(
                    x_l, w_c, y_l, wt_l, off_l, l2_l, mt_l, vm_l, f_c,
                    r=r, s=sub_dim, task=task,
                    trials=_NEWTON_LINE_SEARCH_HALVINGS + 1,
                    interpret=nk.interpret_required(),
                )
                w_n = jnp.where(active[None, :], w_n, w_c)
                f_n = jnp.where(active[None, :], f_n, f_c)
                g_n = jnp.where(active[None, :], g_n, g_c)
                it_n = jnp.where(active, it_c + 1, it_c)
                code_n = optim.convergence_code(
                    iteration=it_n,
                    max_iterations=max_iters,
                    loss_delta=f_c[0] - f_n[0],
                    gradient_norm=jnp.sqrt(jnp.sum(g_n * g_n, axis=0)),
                    tol=tol_p,
                    not_improving=~(imp[0] > 0),
                )
                code_n = jnp.where(active, code_n, code_c)
                return w_n, f_n, g_n, it_n, code_n

            w_lk, _, _, iters_k, reason_k = lax.while_loop(
                cond_k, body_k,
                (w_l, f_l, g_l, jnp.zeros(bp, jnp.int32),
                 jnp.zeros(bp, jnp.int32)),
            )
            w_t = jnp.transpose(w_lk)[:b] * valid_mask
            iters = iters_k[:b]
            reason = reason_k[:b]
            if variance_computation != VarianceComputationType.NONE:
                variances = _batched_variances(
                    x, labels, offsets, weights, w_t, l2_diag, valid_mask,
                    factors, shifts, loss, variance_computation,
                )
            else:
                variances = jnp.zeros_like(w_t)
            w_orig = to_original(w_t) * valid_mask
            return w_orig, variances, iters, reason

        trial_ts = 0.5 ** jnp.arange(
            _NEWTON_LINE_SEARCH_HALVINGS + 1, dtype=dtype
        )  # [T]

        def cond(s):
            _, _, _, _, code = s
            return jnp.any(code == 0)

        def body(s):
            w, f, g, it, code = s
            active = code == 0
            z = precision_mod.acc_einsum(
                "brs,bs->br", x, precision_mod.like_storage(w, x)
            ) + offsets
            curvature = weights * loss.dzz(z, labels)
            h = precision_mod.acc_einsum(
                "brs,brt->bst",
                x * precision_mod.like_storage(curvature, x)[:, :, None], x,
            )
            h = h + (
                l2_diag[:, :, None] * jnp.eye(sub_dim, dtype=dtype)[None]
                + (1.0 - valid_mask)[:, :, None]
                * jnp.eye(sub_dim, dtype=dtype)[None]
            )
            # ONE compact transpose; CG then re-reads the dense [S, S, B]
            # copy instead of the tiling-padded MXU output.
            h_sb = jnp.transpose(h, (1, 2, 0))
            d = jnp.transpose(
                _spd_solve_cg_sb(h_sb, -jnp.transpose(g), sub_dim, active)
            ) * valid_mask
            gd = jnp.sum(g * d, axis=-1)
            # Descent guard (same as the vmapped path): fp32 CG on a
            # near-singular Hessian can return a non-descent direction.
            bad = gd >= 0.0
            d = jnp.where(bad[:, None], -g, d)
            gd = jnp.where(bad, -jnp.sum(g * g, axis=-1), gd)

            zd = precision_mod.acc_einsum(
                "brs,bs->br", x, precision_mod.like_storage(d, x)
            )
            z_t = z[None] + trial_ts[:, None, None] * zd[None]  # [T, B, R]
            w_t_trials = w[None] + trial_ts[:, None, None] * d[None]  # [T,B,S]
            f_t = jnp.sum(
                weights[None] * loss.loss(z_t, labels[None]), axis=-1
            ) + 0.5 * jnp.sum(
                l2_diag[None] * (w_t_trials - m_t[None]) ** 2, axis=-1
            )  # [T, B]
            armijo = f_t <= f[None] + 1e-4 * trial_ts[:, None] * gd[None]
            first = jnp.argmax(armijo, axis=0)  # [B]
            any_ok = jnp.any(armijo, axis=0)
            t = trial_ts[first]
            f_t_sel = jnp.take_along_axis(f_t, first[None], axis=0)[0]
            improved = any_ok & (f_t_sel < f)
            step_ok = active & improved
            w_new = jnp.where(step_ok[:, None], w + t[:, None] * d, w)
            f_new, g_new = objective(w_new)
            f_new = jnp.where(active, f_new, f)
            g_new = jnp.where(active[:, None], g_new, g)
            it_new = jnp.where(active, it + 1, it)
            code_new = optim.convergence_code(
                iteration=it_new,
                max_iterations=max_iters,
                loss_delta=f - f_new,
                gradient_norm=jnp.sqrt(jnp.sum(g_new * g_new, axis=-1)),
                tol=tol,
                not_improving=~improved,
            )
            code_new = jnp.where(active, code_new, code)
            return w_new, f_new, g_new, it_new, code_new

        w_t, f_fin, g_fin, iters, reason = lax.while_loop(
            cond, body,
            (w0, f0, g0, jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32)),
        )
        w_t = w_t * valid_mask

        if variance_computation != VarianceComputationType.NONE:
            variances = _batched_variances(
                x, labels, offsets, weights, w_t, l2_diag, valid_mask,
                factors, shifts, loss, variance_computation,
            )
        else:
            variances = jnp.zeros_like(w_t)

        w_orig = to_original(w_t) * valid_mask
        return w_orig, variances, iters, reason


def _batched_variances(x_t, labels, offsets, weights, w_t, l2_diag,
                       valid_mask, factors, shifts, loss,
                       variance_computation):
    """Coefficient variances for a dense bucket, batched.

    ``x_t`` is ALREADY the transformed design, so the Hessian diagonal /
    full Hessian come from plain batched contractions (the vmapped
    ``variances_in_transformed_space`` would re-apply normalization).
    SIMPLE inverts the Hessian diagonal; FULL recovers the inverse
    Hessian's diagonal with one refined batch-minor CG per basis vector.
    """
    z = precision_mod.acc_einsum(
        "brs,bs->br", x_t, precision_mod.like_storage(w_t, x_t)
    ) + offsets
    curv = weights * loss.dzz(z, labels)
    f_sq = 1.0 if factors is None else factors * factors
    h_diag = precision_mod.acc_einsum(
        "brs,br->bs", x_t * x_t, precision_mod.like_storage(curv, x_t)
    ) + l2_diag
    dead = h_diag == 0.0  # zero-support, zero-penalty slots: var = inf
    if variance_computation == VarianceComputationType.SIMPLE:
        var_t = 1.0 / jnp.where(dead, jnp.inf, h_diag)
        return jnp.where(valid_mask > 0, var_t * f_sq, 0.0)
    # FULL: diagonal of the inverse Hessian — one refined batch-minor CG
    # per basis vector (refinement keeps fp32 accuracy at the direct
    # path's level; variance columns are s tiny solves, not the hot loop).
    s = w_t.shape[-1]
    h = precision_mod.acc_einsum(
        "brs,brt->bst",
        x_t * precision_mod.like_storage(curv, x_t)[:, :, None], x_t,
    )
    h = h + l2_diag[:, :, None] * jnp.eye(s, dtype=w_t.dtype)[None]
    h = h + dead[:, :, None] * jnp.eye(s, dtype=w_t.dtype)[None]
    h_sb = jnp.transpose(h, (1, 2, 0))
    active = jnp.ones(w_t.shape[0], bool)

    def col(i, acc):
        e = jnp.zeros((s, w_t.shape[0]), w_t.dtype).at[i].set(1.0)
        sol = _spd_solve_cg_sb(h_sb, e, s, active)
        res = e - jnp.sum(h_sb * sol[None, :, :], axis=1)
        sol = sol + _spd_solve_cg_sb(h_sb, res, s, active)
        return acc.at[:, i].set(sol[i])

    var_t = lax.fori_loop(0, s, col, jnp.zeros_like(w_t))
    var_t = jnp.where(dead, jnp.inf, var_t)
    return jnp.where(valid_mask > 0, var_t * f_sq, 0.0)


def _solve_one_entity_newton(
    x_indices: Array | None,  # [R, k] ELL slots, or None (dense layout)
    x_values: Array,  # [R, k] or [R, S]
    labels: Array,  # [R]
    offsets: Array,  # [R]
    weights: Array,  # [R]
    penalty_mask: Array,  # [S]
    valid_mask: Array,  # [S]
    factors: Array | None,  # [S]
    shifts: Array | None,  # [S]
    intercept_slot: Array,
    w0_orig: Array,  # [S] original-space warm start
    prior: tuple[Array, Array] | None,
    *,
    sub_dim: int,
    task: TaskType,
    opt_config: optim.OptimizerConfig,
    variance_computation: VarianceComputationType,
    l2_weight: Array,
    incremental_weight: Array,
):
    """Damped-Newton (IRLS) per-entity solve for smooth convex losses.

    The iterative L-BFGS path runs ~100+ sequential tiny device steps per
    bucket (two-loop recursions and line-search probes on S~17 vectors) —
    latency-bound work that leaves the MXU idle. For logistic/Poisson with
    an L2 term the subproblem is smooth and strictly convex, so exact
    Newton with Armijo backtracking converges in a handful of iterations
    of batched [R,S] GEMMs + one [S,S] Cholesky — the same optimum the
    reference's per-entity LBFGS iterates toward
    (RandomEffectCoordinate.scala:243-292) at a fraction of the sequential
    depth. Convergence reporting matches the Optimizer cascade
    (Optimizer.scala:126-139) via the shared ``convergence_code``.
    """
    dtype = x_values.dtype
    x = _materialize_transformed_design(
        x_indices, x_values, factors, shifts, sub_dim
    )
    loss = losses_mod.get_loss(task)
    int_onehot = (
        None if shifts is None else _onehot(intercept_slot, sub_dim, dtype)
    )
    if prior is not None:
        m_t = _coef_to_transformed(prior[0], factors, shifts, int_onehot)
        f_sq = 1.0 if factors is None else factors * factors
        inv_prior_var = optim.inverse_prior_variances(
            prior[1] / f_sq, l2_weight) * valid_mask
        l2_diag = incremental_weight * inv_prior_var
    else:
        m_t = jnp.zeros(sub_dim, dtype)
        l2_diag = l2_weight * penalty_mask

    def objective(w):
        z = x @ w + offsets
        f = jnp.sum(weights * loss.loss(z, labels)) + 0.5 * jnp.sum(
            l2_diag * (w - m_t) ** 2
        )
        g = x.T @ (weights * loss.dz(z, labels)) + l2_diag * (w - m_t)
        return f, g * valid_mask

    tol = optim.absolute_tolerances(
        objective, w0_orig, opt_config.tolerance
    )
    w0 = _coef_to_transformed(w0_orig, factors, shifts, int_onehot)
    w0 = w0 * valid_mask
    f0, g0 = objective(w0)
    max_iters = opt_config.max_iterations

    def cond(s):
        w, f, g, it, code = s
        return code == 0

    # All Armijo trial steps evaluate in ONE pass: the margin is affine in
    # the step size (z_t = z + t * (x @ d)), so a single extra matvec gives
    # every candidate, replacing up to _NEWTON_LINE_SEARCH_HALVINGS
    # sequential probe loops with elementwise work — sequential depth is
    # what the batched solve is bound by.
    trial_ts = 0.5 ** jnp.arange(
        _NEWTON_LINE_SEARCH_HALVINGS + 1, dtype=dtype
    )  # [T]: 1, 1/2, 1/4, ...

    def body(s):
        w, f, g, it, code = s
        z = x @ w + offsets
        curvature = weights * loss.dzz(z, labels)
        h = x.T @ (curvature[:, None] * x)
        # Padding slots get a unit diagonal so the system stays PD;
        # their gradient is masked, so their step is 0.
        h = h + jnp.diag(l2_diag + (1.0 - valid_mask))
        d = _spd_solve_cg(h, -g, sub_dim, refine=False) * valid_mask
        gd = jnp.dot(g, d)
        # Unrefined fp32 CG can return a non-descent direction on a
        # near-singular Hessian; Armijo would then reject every trial and
        # the loop would exit at a non-optimum. Fall back to steepest
        # descent for such iterations — guaranteed descent, and the next
        # iteration's Hessian is evaluated at the new point.
        bad = gd >= 0.0
        d = jnp.where(bad, -g, d)
        gd = jnp.where(bad, -jnp.sum(g * g), gd)

        zd = x @ d  # [R]; z_t = z + t * zd for every trial t
        z_t = z[None, :] + trial_ts[:, None] * zd[None, :]  # [T, R]
        w_t_trials = w[None, :] + trial_ts[:, None] * d[None, :]  # [T, S]
        f_t = jnp.sum(
            weights[None, :] * loss.loss(z_t, labels[None, :]), axis=1
        ) + 0.5 * jnp.sum(
            l2_diag[None, :] * (w_t_trials - m_t[None, :]) ** 2, axis=1
        )  # [T]
        armijo = f_t <= f + 1e-4 * trial_ts * gd
        # First (largest) t satisfying Armijo — the same step sequential
        # halving would accept.
        first = jnp.argmax(armijo)
        any_ok = jnp.any(armijo)
        t = trial_ts[first]
        f_t_sel = f_t[first]
        improved = any_ok & (f_t_sel < f)
        w_new = jnp.where(improved, w + t * d, w)
        f_new, g_new = objective(w_new)
        code_new = optim.convergence_code(
            iteration=it + 1,
            max_iterations=max_iters,
            loss_delta=f - f_new,
            gradient_norm=jnp.sqrt(jnp.sum(g_new * g_new)),
            tol=tol,
            not_improving=~improved,
        )
        return w_new, f_new, g_new, it + 1, code_new

    w_t, f_fin, g_fin, iters, reason = lax.while_loop(
        cond, body,
        (w0, f0, g0, jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32)),
    )
    w_t = w_t * valid_mask

    if variance_computation != VarianceComputationType.NONE:
        batch = GLMBatch(
            _features_of(x_indices, x_values, sub_dim),
            labels, offsets, weights,
        )
        norm = NormalizationContext(
            factors=factors, shifts=shifts,
            intercept_index=None if shifts is None else 0,
        )
        var_t = variances_in_transformed_space(
            batch, loss, w_t, norm, l2_diag, variance_computation,
        )
        f_sq = 1.0 if factors is None else factors * factors
        variances = jnp.where(valid_mask > 0, var_t * f_sq, 0.0)
    else:
        variances = jnp.zeros_like(w_t)

    w_orig = _coef_to_original(w_t, factors, shifts, int_onehot) * valid_mask
    return w_orig, variances, iters, reason


def _solve_one_entity(
    x_indices: Array | None,  # [R, k] ELL slots, or None (dense layout)
    x_values: Array,  # [R, k] or [R, S]
    labels: Array,  # [R]
    offsets: Array,  # [R]
    weights: Array,  # [R]
    penalty_mask: Array,  # [S]
    valid_mask: Array,  # [S]
    factors: Array,  # [S] (ones where no normalization)
    shifts: Array,  # [S] (zeros where none)
    intercept_slot: Array,  # scalar int32, -1 if absent
    w0_orig: Array,  # [S] original-space warm start
    prior: tuple[Array, Array] | None,  # ([S] means, [S] vars) original space
    *,
    sub_dim: int,
    task: TaskType,
    opt_config: optim.OptimizerConfig,
    use_owlqn: bool,
    variance_computation: VarianceComputationType,
    l1_weight: Array,  # traced scalars, closed over (broadcast under vmap)
    l2_weight: Array,
    incremental_weight: Array,
):
    """One entity's full solve; vmapped over the bucket's entity axis.

    Mirrors SingleNodeOptimizationProblem.run (:90-98): transformed-space
    solve with the effective-coefficient rewrite, reported in original space.
    Regularization weights are traced, so a new lambda (warm-start ladder,
    tuner retrain) reuses the compiled block solve.
    """
    loss = losses_mod.get_loss(task)
    feats = _features_of(x_indices, x_values, sub_dim)
    batch = GLMBatch(feats, labels, offsets, weights)
    # Per-entity projected normalization; factors/shifts are None (static)
    # when the coordinate has no normalization, so the objective specializes
    # to the raw fast path at trace time. intercept_index is only consulted
    # by the static-index round-trip helpers, which we bypass.
    norm = NormalizationContext(
        factors=factors,
        shifts=shifts,
        intercept_index=None if shifts is None else 0,
    )
    int_onehot = (
        None if shifts is None
        else _onehot(intercept_slot, sub_dim, w0_orig.dtype)
    )

    w0 = _coef_to_transformed(w0_orig, factors, shifts, int_onehot)
    fun = glm_ops.make_value_and_grad(batch, loss, norm)
    if prior is not None:
        # Per-entity Gaussian prior (incremental training): replaces the
        # plain L2 term; the L2 weight is the fallback precision for slots
        # absent from the prior model (PriorDistribution.scala:31-60).
        # Padded slots are masked out of the penalty entirely.
        prior_means_t = _coef_to_transformed(
            prior[0], factors, shifts, int_onehot)
        f_sq = 1.0 if factors is None else factors * factors
        inv_prior_var = optim.inverse_prior_variances(
            prior[1] / f_sq, l2_weight) * valid_mask
        obj = optim.with_gaussian_prior(
            fun, incremental_weight, prior_means_t, inv_prior_var)
        l2_diag = incremental_weight * inv_prior_var
    else:
        obj = optim.with_l2_masked(fun, l2_weight, penalty_mask)
        l2_diag = l2_weight * penalty_mask

    if use_owlqn:
        result = optim.owlqn_solve(obj, w0, l1_weight, opt_config)
    elif opt_config.optimizer_type == optim.OptimizerType.TRON:
        hvp = glm_ops.make_hvp(batch, loss, norm)
        if prior is not None:
            obj_hvp = optim.with_gaussian_prior_hvp(
                hvp, incremental_weight, inv_prior_var)
        else:
            obj_hvp = optim.with_l2_hvp_masked(hvp, l2_weight, penalty_mask)
        result = optim.tron_solve(obj, obj_hvp, w0, opt_config)
    else:
        result = optim.lbfgs_solve(obj, w0, opt_config)

    w_t = result.coefficients * valid_mask

    if variance_computation != VarianceComputationType.NONE:
        var_t = variances_in_transformed_space(
            batch, loss, w_t, norm, l2_diag, variance_computation,
        )
        f_sq = 1.0 if factors is None else factors * factors
        # Padded slots (and zero-support slots) carry var inf; report 0 for
        # padding, inf for genuinely unsupported-but-valid slots.
        variances = jnp.where(valid_mask > 0, var_t * f_sq, 0.0)
    else:
        variances = jnp.zeros_like(w_t)

    w_orig = _coef_to_original(w_t, factors, shifts, int_onehot) * valid_mask
    return w_orig, variances, result.iterations, result.convergence_reason


@functools.partial(
    jax.jit,
    static_argnames=(
        "sub_dim", "task", "opt_config", "use_owlqn", "variance_computation",
        "direct", "newton", "precision", "gram_mults", "spmd",
    ),
    # Buffer donation through _scatter_results: the [E, Smax] coefficient
    # and variance tables are CARRIES — each bucket's scatter returns the
    # updated table and the caller rebinds, so the input buffers are dead
    # on return. Donating them lets XLA update the tables in place
    # instead of round-tripping a fresh [E, Smax] allocation per bucket
    # (inline fused calls ignore donation; the fori_loop carries alias
    # there instead). Callers must never alias w_all/v_all with another
    # operand (see warmup_thunks).
    donate_argnums=(9, 10),
)
def _solve_block(
    block,  # EntityBlocks | BlockPlan (pytree structure selects the path)
    residuals: Array | None,  # [n] row residuals, [B, R] slab ones, or None
    factors_full: Array | None,  # [d] global normalization factors
    shifts_full: Array | None,  # [d] global normalization shifts
    w0_full: Array | None,  # [E, Smax] original-space warm starts
    l1_weight: Array,
    l2_weight: Array,
    incremental_weight: Array,
    prior_full: tuple[Array, Array] | None,  # ([E, Smax], [E, Smax]) or None
    w_all: Array,  # [E, Smax] coefficient table to scatter results into
    v_all: Array | None,  # [E, Smax] variance table, or None
    *,
    sub_dim: int,
    task: TaskType,
    opt_config: optim.OptimizerConfig,
    use_owlqn: bool,
    variance_computation: VarianceComputationType,
    direct: bool = False,
    newton: bool = False,
    precision: str = "float32",
    gram_mults: tuple | None = None,
    spmd: bool = False,
):
    """One bucket's batched per-entity solve (everything traced/fused).

    ``spmd``: the block is sharded over a mesh (ops/placement.py), which
    closes every Pallas route in here — GSPMD partitions the XLA solve
    across the entity axis but cannot partition a Mosaic kernel.

    Lazy ``BlockPlan`` buckets materialize their [B, R, k] slabs here, INSIDE
    the compiled program, by gathering the HBM-resident raw arrays — the
    slabs never exist on the host (data/random_effect.py module docstring).
    Warm-start / prior / normalization gathers are also traced, so one fit
    dispatches a single device program per bucket. The result scatter into
    the [E, Smax] tables happens in here too — eager per-block pads and
    scatters each cost a ~0.7s one-time compile on the TPU backend, so the
    whole update rides the bucket's one program. Mesh-padding sentinel codes
    (== num_entities) drop out of bounds in the scatter.
    """
    if isinstance(block, BlockPlan):
        block = block.materialize(residuals)
        offsets = block.offsets
    else:
        offsets = block.offsets
        if residuals is not None:
            with jax.named_scope("residual"):
                # One rule on what is handed over: [n] row residuals are
                # gathered through row_ids; [B, R] ones stand in slab
                # layout already (the fused fit's home coordinate moves
                # them there contiguously). Padding slots alias canonical
                # row 0, or hold stale values: masked either way.
                if residuals.ndim == 1:
                    residuals = jnp.take(
                        residuals, block.row_ids, mode="clip")
                offsets = offsets + jnp.where(
                    block.weights > 0, residuals, 0.0)
    if precision_mod.is_mixed(precision):
        # bf16 SLAB STORAGE (the mixed-precision policy): the design
        # slab — the dominant per-iteration HBM read — is held and read
        # at half width; solver state stays f32 (dtype below) and every
        # row-axis contraction accumulates f32 (ops/precision.py).
        block = dataclasses.replace(
            block,
            x_values=precision_mod.in_storage(block.x_values, precision),
        )
    # Solver state (tables, gradients, Hessians, masks) anchors on the
    # LABEL dtype, not the slab's: a bf16-stored slab must not narrow
    # the iterates.
    dtype = block.labels.dtype
    if block.x_indices is not None and selects_dense(
        block.x_indices.shape, sub_dim
    ):
        # Densify the ELL block so every downstream op is a matmul;
        # batched gather/scatter both execute worse and compile ~40x
        # slower on TPU.
        block = dataclasses.replace(
            block,
            x_indices=None,
            x_values=_densify_ell_slots(
                block.x_indices, block.x_values, sub_dim
            ),
        )
    # Wide-ELL direct solves can skip densification ENTIRELY: the normal
    # equations only need X^T W X and X^T W y, which _solve_direct_gram
    # aggregates straight from the ELL entries through the tiled
    # segment-reduce. Engagement needs the planner's host-computed
    # window bounds (gram_mults), no shift normalization (shifts break
    # ELL sparsity), no variance computation (variances read the dense
    # design), and a kernel-served shape — everything static.
    gram_route = (
        direct
        and gram_mults is not None
        and shifts_full is None
        and variance_computation == VarianceComputationType.NONE
        and block.x_indices is not None
        and segment_reduce.ell_gram_supported(
            *block.x_indices.shape, sub_dim,
            grad_mult=gram_mults[0], hess_mult=gram_mults[1],
            spmd=spmd,
        )
    )
    if (
        block.x_indices is not None
        and (newton or direct)
        and not gram_route
    ):
        # Wide-subspace ELL: one flat tiled segment-reduce densifies the
        # WHOLE bucket (ops/segment_reduce) where the kernel serves this
        # backend — routing it onto the batched dense solvers instead of
        # the per-entity vmapped scatter path. None = keep ELL.
        dense = segment_reduce.densify_ell_blocks(
            block.x_indices, block.x_values, sub_dim, spmd=spmd
        )
        if dense is not None:
            block = dataclasses.replace(
                block, x_indices=None, x_values=dense
            )
    if (
        block.x_values.dtype == jnp.bfloat16
        and not direct
        and not (newton and block.x_indices is None)
    ):
        # The vmapped quasi-Newton/OWL-QN/ELL-Newton paths run f32 end
        # to end: upcast the stored slab once inside the program (the
        # HBM read of the slab is still half-width).
        block = dataclasses.replace(
            block, x_values=block.x_values.astype(dtype)
        )
    # A direct bucket's CG runs in the lane kernel where its gate serves
    # the width (ops/cg_kernel.py; the gram route keeps its own CG).
    direct_kernel = (
        direct and not gram_route
        and cg_kernel.kernel_supported(sub_dim, dtype, spmd=spmd)
    )
    # `solve.<route>`: the solver the statics select (scope names are
    # metadata on the operations; no operation changes). A dense Newton
    # bucket is named where its route is decided, in
    # `_solve_newton_batched`. The scope is entered around each solver
    # call HERE: with the solve moved into a helper function the logistic
    # fit program traced 7 s slower on the chip's host (PERF.md section
    # 6, PR 25; cause not found).
    if direct:
        route = "direct_kernel" if direct_kernel else "direct"
    elif newton:
        route = "newton_xla"  # sparse buckets: the vmapped XLA step
    elif use_owlqn:
        route = "owlqn"
    else:
        route = opt_config.optimizer_type.value.lower()
    solve_scope = jax.named_scope("solve." + route)
    s = sub_dim
    codes = block.entity_codes
    proj = block.proj  # [B, S]; -1 pad
    safe = jnp.maximum(proj, 0)
    factors_sub = shifts_sub = None
    if factors_full is not None:
        f = jnp.take(factors_full.astype(dtype), safe, mode="clip")
        factors_sub = jnp.where(proj >= 0, f, 1.0)
    if shifts_full is not None:
        sh = jnp.take(shifts_full.astype(dtype), safe, mode="clip")
        shifts_sub = jnp.where(proj >= 0, sh, 0.0)
    if w0_full is not None:
        # Sentinel codes (mesh entity padding) clip to the last row; their
        # results are dropped by the out-of-bounds scatter on the way back.
        w0 = jnp.take(w0_full.astype(dtype), codes, axis=0, mode="clip")
        w0 = w0[:, :s]
    else:
        w0 = jnp.zeros((block.num_entities, s), dtype)
    prior = None
    if prior_full is not None:
        prior = (
            jnp.take(
                prior_full[0].astype(dtype), codes, axis=0, mode="clip"
            )[:, :s],
            jnp.take(
                prior_full[1].astype(dtype), codes, axis=0, mode="clip"
            )[:, :s],
        )
    if direct:
        if gram_route:
            with solve_scope:
                w, v, it, reason = _solve_direct_gram(
                    block,
                    offsets,
                    factors_sub,
                    prior,
                    sub_dim=sub_dim,
                    l2_weight=l2_weight,
                    incremental_weight=incremental_weight,
                    gram_mults=gram_mults,
                )
                return _scatter_results(w_all, v_all, codes, w, v, it, reason)

        def system(xi, xv, lb, off, wt, pm, vm, f, sh, islot, prior_e):
            return _direct_system(
                xi, xv, lb, off, wt, pm, vm, f, sh, islot, prior_e,
                sub_dim=sub_dim,
                l2_weight=l2_weight,
                incremental_weight=incremental_weight,
            )

        def finish(w_t, xi, xv, lb, off, wt, vm, f, sh, islot, l2_diag):
            return _direct_finish(
                w_t, xi, xv, lb, off, wt, vm, f, sh, islot, l2_diag,
                sub_dim=sub_dim,
                variance_computation=variance_computation,
                task=task,
            )

        with solve_scope:
            h, b, l2_diag = jax.vmap(system)(
                block.x_indices,
                block.x_values,
                block.labels,
                offsets,
                block.weights,
                block.penalty_mask,
                block.valid_mask,
                factors_sub,
                shifts_sub,
                block.intercept_slots,
                prior,
            )
            if direct_kernel:
                w_t = cg_kernel.spd_solve_lanes(
                    h, b, interpret=cg_kernel.interpret_required())
            else:
                w_t = jax.vmap(
                    functools.partial(_spd_solve_cg, sub_dim=sub_dim))(h, b)
            w, v, it, reason = jax.vmap(finish)(
                w_t,
                block.x_indices,
                block.x_values,
                block.labels,
                offsets,
                block.weights,
                block.valid_mask,
                factors_sub,
                shifts_sub,
                block.intercept_slots,
                l2_diag,
            )
            return _scatter_results(w_all, v_all, codes, w, v, it, reason)

    if newton:
        if block.x_indices is None:
            # Dense buckets take the batch-minor rewrite: compact [S,S,B]
            # Hessians + dense-lane CG instead of the vmapped layout whose
            # tiling-padded H re-reads dominated the solve's HBM traffic.
            w, v, it, reason = _solve_newton_batched(
                block.x_values,
                block.labels,
                offsets,
                block.weights,
                block.penalty_mask,
                block.valid_mask,
                factors_sub,
                shifts_sub,
                block.intercept_slots,
                w0,
                prior,
                sub_dim=sub_dim,
                task=task,
                opt_config=opt_config,
                variance_computation=variance_computation,
                l2_weight=l2_weight,
                incremental_weight=incremental_weight,
                spmd=spmd,
            )
            return _scatter_results(w_all, v_all, codes, w, v, it, reason)

        def newton_solver(xi, xv, lb, off, wt, pm, vm, f, sh, islot, w0_e,
                          prior_e):
            return _solve_one_entity_newton(
                xi, xv, lb, off, wt, pm, vm, f, sh, islot, w0_e, prior_e,
                sub_dim=sub_dim,
                task=task,
                opt_config=opt_config,
                variance_computation=variance_computation,
                l2_weight=l2_weight,
                incremental_weight=incremental_weight,
            )

        with solve_scope:
            w, v, it, reason = jax.vmap(newton_solver)(
                block.x_indices,
                block.x_values,
                block.labels,
                offsets,
                block.weights,
                block.penalty_mask,
                block.valid_mask,
                factors_sub,
                shifts_sub,
                block.intercept_slots,
                w0,
                prior,
            )
            return _scatter_results(w_all, v_all, codes, w, v, it, reason)

    def solver(xi, xv, lb, off, wt, pm, vm, f, sh, islot, w0_e, prior_e):
        return _solve_one_entity(
            xi, xv, lb, off, wt, pm, vm, f, sh, islot, w0_e, prior_e,
            sub_dim=sub_dim,
            task=task,
            opt_config=opt_config,
            use_owlqn=use_owlqn,
            variance_computation=variance_computation,
            l1_weight=l1_weight,
            l2_weight=l2_weight,
            incremental_weight=incremental_weight,
        )

    with solve_scope:
        w, v, it, reason = jax.vmap(solver)(
            block.x_indices,
            block.x_values,
            block.labels,
            offsets,
            block.weights,
            block.penalty_mask,
            block.valid_mask,
            factors_sub,
            shifts_sub,
            block.intercept_slots,
            w0,
            prior,
        )
        return _scatter_results(w_all, v_all, codes, w, v, it, reason)


def _scatter_results(w_all, v_all, codes, w, v, it, reason):
    """Pad to the table width and scatter one bucket's solutions in."""
    pad = w_all.shape[1] - w.shape[1]
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        v = jnp.pad(v, ((0, 0), (0, pad)))
    w_all = w_all.at[codes].set(w)
    if v_all is not None:
        v_all = v_all.at[codes].set(v)
    return w_all, v_all, it, reason


@dataclasses.dataclass(frozen=True)
class RandomEffectCoordinate:
    """Per-entity coordinate over one random-effect type.

    Reference: algorithm/RandomEffectCoordinate.scala:38 (trainModel
    :234-300, scoring :314-366).
    """

    dataset: RandomEffectDataset
    task: TaskType
    config: GLMOptimizationConfiguration
    normalization: NormalizationContext = dataclasses.field(
        default_factory=NormalizationContext
    )
    # Incremental-training prior: a RandomEffectModel (with variances)
    # already remapped onto this dataset's entity/slot layout. Entities or
    # slots absent from it carry variance 0 and fall back to plain L2
    # (RandomEffectOptimizationProblem.scala:137-198 projected priors).
    prior: RandomEffectModel | None = None
    # Mixed-precision policy (ops/precision.py): "bfloat16" stores the
    # design slabs bf16 with f32 accumulators/state; "float32" (default)
    # is the historical path. A declared recompile key (PERFORMANCE.md).
    precision: str = "float32"
    # The length of the coordinate-descent loop's per-row vectors, as
    # FixedEffectCoordinate.logical_rows: scores are fitted to it.
    logical_rows: int | None = None

    def _dispatch_block(self, block, residuals, w0_full, w_all, v_all,
                        block_index=None):
        """Assemble and dispatch one bucket's ``_solve_block`` call.

        Shared by ``train`` (sequential scatter into the tables) and
        ``warmup_thunks`` (concurrent compile priming), so the jit call
        structure cannot drift between them. ``block_index`` keys the
        planner's host-side per-bucket tables (gram window bounds); both
        callers enumerate ``device_blocks()`` so the statics agree.
        """
        dtype = jnp.dtype(self.dataset.dtype)
        # Squared-loss subproblems are convex quadratics: solve them
        # exactly with one batched Cholesky instead of iterating
        # (identical optimum, ~100x fewer sequential device steps).
        # l2 > 0 guarantees X^T W X + diag(pen) is positive definite even
        # for entities with fewer rows than active features — without it
        # the normal equations can be singular and the iterative solver's
        # implicit regularization is the correct behavior.
        well_posed = (
            self.config.l1_weight == 0.0
            and self.config.l2_weight > 0.0
            and self.config.optimizer.box_constraints is None
            # With a prior, absent-feature slots are penalized by
            # incremental_weight * inv_prior_var instead of l2; at
            # incremental_weight == 0 the normal equations can be
            # singular for entities with fewer rows than features.
            and (self.prior is None
                 or self.config.incremental_weight > 0.0)
        )
        direct = well_posed and self.task == TaskType.LINEAR_REGRESSION
        # Smooth strictly-convex losses take the damped-Newton/IRLS
        # path: same optimum as the configured quasi-Newton solver, at
        # ~10x less sequential device depth (MXU-batched GEMM + [S,S]
        # Cholesky per iteration). Smoothed hinge is excluded — its
        # curvature approximation vanishes on flat segments.
        newton = well_posed and self.task in (
            TaskType.LOGISTIC_REGRESSION, TaskType.POISSON_REGRESSION
        )
        # Host-computed gram window bounds for this bucket (None when
        # the planner skipped them — small subspaces densify, lazy
        # datasets have no host slab view): the static coverage key of
        # the direct ELL gram route (_solve_direct_gram).
        gram_mults = None
        if block_index is not None:
            gm = getattr(self.dataset, "block_gram_mults", ())
            if block_index < len(gm):
                gram_mults = gm[block_index]
        # Scalars ride as host float32 jit operands (an eager
        # jnp.asarray would compile its own convert program per call
        # site on the TPU backend).
        return _solve_block(
            block,
            residuals,
            self.normalization.factors,
            self.normalization.shifts,
            w0_full,
            np.asarray(self.config.l1_weight, dtype=dtype),
            np.asarray(self.config.l2_weight, dtype=dtype),
            np.asarray(self.config.incremental_weight, dtype=dtype),
            None if self.prior is None
            else (self.prior.coefficients, self.prior.variances),
            w_all,
            v_all,
            sub_dim=block.sub_dim,
            task=self.task,
            opt_config=self.config.optimizer,
            use_owlqn=self.config.l1_weight != 0.0,
            variance_computation=self.config.variance_computation,
            direct=direct,
            newton=newton,
            precision=precision_mod.resolve(self.precision),
            gram_mults=gram_mults,
            spmd=placement.spans_devices(block),
        )

    def warmup_thunks(self):
        """Zero-argument thunks that compile this coordinate's programs.

        One thunk per bucket solver plus one for the scorer; the estimator
        runs thunks from ALL coordinates on a thread pool so the XLA
        compiles overlap (~2.5x measured) instead of serializing through
        the first CD sweep. Results are discarded — only the jit cache
        entries matter.
        """
        ds = self.dataset
        dtype = jnp.dtype(ds.dtype)
        residuals = jnp.zeros(ds.num_rows, dtype)
        w0_full = jnp.zeros((ds.num_entities, ds.max_sub_dim), dtype)
        v_all = (
            jnp.zeros((ds.num_entities, ds.max_sub_dim), dtype)
            if self.config.variance_computation != VarianceComputationType.NONE
            else None
        )

        def block_thunk(block, idx):
            # w_all/v_all are DONATED by _solve_block: each thunk gets
            # its own fresh tables — reusing w0_full as w_all would
            # alias a donated buffer with a live operand, and a shared
            # v_all would be consumed by the first thunk to run.
            def thunk():
                w_tab = jnp.zeros_like(w0_full)
                v_tab = None if v_all is None else jnp.zeros_like(v_all)
                jax.block_until_ready(self._dispatch_block(
                    block, residuals, w0_full, w_tab, v_tab,
                    block_index=idx,
                )[0])

            return thunk

        def score_thunk():
            model = RandomEffectModel(
                coefficients=w0_full,
                random_effect_type=ds.config.random_effect_type,
                feature_shard_id=ds.config.feature_shard_id,
                task=self.task,
                proj_all=ds.proj_all,
                variances=None,
                entity_keys=ds.entity_keys,
            )
            jax.block_until_ready(self.score(model))

        return [
            block_thunk(b, i) for i, b in enumerate(ds.device_blocks())
        ] + [score_thunk]

    def train(
        self,
        residuals: Array | None = None,
        initial_model: RandomEffectModel | None = None,
        *,
        seed: int = 0,
    ) -> tuple[RandomEffectModel, RandomEffectTrainingStats]:
        ds = self.dataset
        dtype = jnp.dtype(ds.dtype)
        # Normalize the optional inputs to arrays: None vs array changes the
        # jit pytree structure, and CD's first iteration (no residuals, no
        # warm start) would otherwise compile a SECOND program per bucket
        # that is used exactly once. A zeros gather costs nothing; a
        # duplicate XLA compile costs seconds.
        if residuals is None:
            residuals = jnp.zeros(ds.num_rows, dtype)
        w0_full = (
            initial_model.coefficients if initial_model is not None
            else jnp.zeros((ds.num_entities, ds.max_sub_dim), dtype)
        )
        w_all = jnp.zeros((ds.num_entities, ds.max_sub_dim), dtype)
        v_all = (
            jnp.zeros((ds.num_entities, ds.max_sub_dim), dtype)
            if self.config.variance_computation != VarianceComputationType.NONE
            else None
        )
        # (device reason array, host real-entity mask) per block; fetched in
        # two coalesced transfers after all blocks are dispatched.
        reasons: list[tuple[Array, np.ndarray]] = []
        iters: list[Array] = []
        real_masks = [
            ds.real_entity_mask(i) for i in range(len(ds.blocks))
        ]

        if self.normalization.shifts is not None:
            # Shift normalization folds the shift mass into the intercept on
            # the coefficient round trip; every trained entity must have one
            # (the per-entity analog of NormalizationContext.__post_init__).
            for ints, real in zip(ds.block_intercepts_np, real_masks):
                if bool((np.asarray(ints)[real] < 0).any()):
                    raise ValueError(
                        "normalization with shifts requires every entity's "
                        "subspace to contain the intercept; build the "
                        "dataset with intercept_index set"
                    )

        if self.prior is not None and self.prior.variances is None:
            raise ValueError(
                "incremental training requires prior variances for "
                "every entity model (GameEstimator.scala:241-382)")

        # Feature slabs materialize on device once per dataset; per-solve
        # gathers shrink to the [B, R] residual rows (data/random_effect.py
        # device_blocks).
        for i, (block, real) in enumerate(
            zip(ds.device_blocks(), real_masks)
        ):
            w_all, v_all, it, reason = self._dispatch_block(
                block, residuals, w0_full, w_all, v_all, block_index=i
            )
            # Keep diagnostics on device; fetch once after the loop
            # (a per-block np.asarray would sync per block).
            reasons.append((reason, real))
            iters.append(it)

        model = RandomEffectModel(
            coefficients=w_all,
            random_effect_type=ds.config.random_effect_type,
            feature_shard_id=ds.config.feature_shard_id,
            task=self.task,
            proj_all=ds.proj_all,
            variances=v_all,
            entity_keys=ds.entity_keys,
        )
        # Diagnostics stay on device: the CD loop never reads them, and an
        # eager fetch here would sync the host to every block solve.
        stats = RandomEffectTrainingStats.from_device(
            [r for r, _ in reasons], iters, [real for _, real in reasons]
        )
        return model, stats

    def score(self, model: RandomEffectModel) -> Array:
        """Model contribution per row (active + passive), one a row of the
        loop's vectors (``logical_rows``)."""
        return fit_rows(model.score_dataset(self.dataset), self.logical_rows)

    def residual_sharding(self):
        """Where an update wants its residuals: replicated over the mesh
        its buckets are sharded on, since every bucket's solve gathers
        them at arbitrary rows (the loop replicates them once an update,
        and no solve's program reshards them again); None on one device."""
        for plan in self.dataset.device_plans():
            sharding = getattr(plan.row_ids, "sharding", None)
            if (isinstance(sharding, jax.sharding.NamedSharding)
                    and len(sharding.device_set) > 1):
                return jax.sharding.NamedSharding(
                    sharding.mesh, jax.sharding.PartitionSpec())
        return None

    def programs_per_update(self) -> dict:
        """The programs an update dispatches (``fit`` stage, unfused
        loop): one solve a bucket; the scorer's
        (``models.game.score_programs``) and, where its vector has another
        length than the loop's, the fit to it. Residuals enter inside the
        solves; the zeros tables the solves fill are JAX's one-primitive
        helpers and not counted."""
        ds = self.dataset
        refit = (self.logical_rows is not None
                 and score_rows(ds) != self.logical_rows)
        return {
            "train": len(ds.device_blocks()),
            "score": score_programs(ds) + refit,
            "residuals": 0,
        }


def solver_statics(coord: RandomEffectCoordinate) -> dict:
    """Static solver routing for one RE coordinate (mirrors
    RandomEffectCoordinate._dispatch_block's well-posedness analysis)."""
    cfg = coord.config
    well_posed = (
        cfg.l1_weight == 0.0
        and cfg.l2_weight > 0.0
        and cfg.optimizer.box_constraints is None
        and (coord.prior is None or cfg.incremental_weight > 0.0)
    )
    direct = well_posed and coord.task == TaskType.LINEAR_REGRESSION
    newton = well_posed and coord.task in (
        TaskType.LOGISTIC_REGRESSION, TaskType.POISSON_REGRESSION
    )
    return dict(
        task=coord.task,
        opt_config=cfg.optimizer,
        use_owlqn=cfg.l1_weight != 0.0,
        variance_computation=cfg.variance_computation,
        direct=direct,
        newton=newton,
    )


def fit_stage_coordinate(
    coord: RandomEffectCoordinate, slabs, *, precision: str = "float32"
) -> dict:
    """One random-effect coordinate's entry of a ``fit`` stage's
    ``coordinates`` attribute, the same for the fused fit and for the
    unfused loop: what the planner counted
    (``RandomEffectDataset.plan_counts``: ``active_rows``,
    ``passive_rows``, ``capped_entities``, and the projectors' and the
    layout rule's ``layout``, ``shard_form``, ``max_sub_dim``,
    ``subspaces``, ``active_subspace_elements``), its ``slab_rows``, its
    ``rungs`` as ``[entities, row cap, route]`` with the ``solve.<route>``
    scope ``_solve_block`` gives that slab, and its ``score_route``
    (``models.game.score_route``). ``slabs``: the buckets as the
    fit solves them, ``EntityBlocks`` or, for a bucket left lazy, its
    ``BlockPlan``; only shapes, dtypes and placements are read. Host ints
    and strings: a caller makes it once per prepared data set."""
    statics = solver_statics(coord)
    rungs = []
    for slab in slabs:
        spmd = placement.spans_devices(slab)
        if isinstance(slab, BlockPlan):
            slab = jax.eval_shape(lambda b: b.materialize(None), slab)
        rungs.append([
            int(slab.x_values.shape[0]), int(slab.x_values.shape[1]),
            solve_route(statics, slab, precision=precision, spmd=spmd)])
    return dict(
        coord.dataset.plan_counts or {},
        slab_rows=sum(b * r for b, r, _ in rungs),
        rungs=rungs,
        score_route=score_route(coord.dataset, slabs),
    )


def solve_route(
    statics: dict, slab, *, precision: str = "float32", spmd: bool = False
) -> str:
    """The ``solve.<route>`` scope ``_solve_block`` gives a bucket whose
    materialized slab is ``slab`` (an ``EntityBlocks``; only shapes and
    dtypes are read, never a value): the same tests in the same order,
    kept beside it so that a stage attribute can name the route without
    a frame on the traced path. ``statics``: ``solver_statics``. A
    direct bucket is named by the CG kernel's gate alone: the gram route
    (``gram_mults`` from the planner; no cell's bucket reaches it,
    PERF.md 7.24) is not told apart."""
    if statics["direct"]:
        if cg_kernel.kernel_supported(
            slab.proj.shape[-1], slab.labels.dtype, spmd=spmd
        ):
            return "direct_kernel"
        return "direct"
    if statics["newton"]:
        from photon_tpu.ops import newton_kernel as nk

        _, r, s = slab.x_values.shape
        dense = slab.x_indices is None or selects_dense(
            slab.x_indices.shape, s)
        if not dense:
            # A wide ELL slab is dense where the segment-reduce kernel
            # serves it: asked of the function itself, on shapes alone.
            dense = jax.eval_shape(
                lambda xi, xv: segment_reduce.densify_ell_blocks(
                    xi, xv, s, spmd=spmd),
                slab.x_indices, slab.x_values,
            ) is not None
        dtype = (
            jnp.bfloat16 if precision_mod.is_mixed(precision)
            else slab.x_values.dtype
        )
        if dense and nk.kernel_supported(
            statics["task"], dtype, r, s, spmd=spmd
        ):
            return "newton_kernel"
        return "newton_xla"
    if statics["use_owlqn"]:
        return "owlqn"
    return statics["opt_config"].optimizer_type.value.lower()
