"""Fused per-entity Newton-step TPU kernel (Pallas): H never leaves VMEM.

One damped-Newton/IRLS step for a whole bucket of per-entity GLM
subproblems — margins, curvature, the [S, S] Hessian build, an S-step CG
direction solve, the vectorized Armijo line search, and the objective/
gradient refresh at the accepted point — in a single Pallas kernel.

Why: under XLA the batched [B, S, S] Hessian must round-trip through HBM
between its MXU build and the CG re-reads, and TPU (8, 128) tiling
physically inflates that layout ~7-10x at S ~ 17. The round-4 probe
(experiments/README.md) identified fusing the build THROUGH the solve as
the remaining ~3-6x of per-iteration headroom; this kernel implements it:

- ENTITIES LIVE IN LANES: each grid step owns 128 entities. The slab
  arrives pre-transposed as [S, R, B] so every access is a contiguous
  leading-dim block slice; all math is elementwise / single-axis reduces
  over [sublane, 128] tiles at full VPU width (per-entity dot_generals —
  the round-4 probe's layout — serialize and ran 7x SLOWER than XLA).
- H lives in a [S, S, 128] VMEM scratch; the CG matvec is S broadcast
  FMAs over [S, 128] tiles.
- The line search runs its T trials sequentially per 128-lane block,
  tracking the largest passing step per lane (argmax on bools does not
  lower in Mosaic).

Speed against the batch-minor XLA step: not measured on this chip (the
round-5 figure came from a backend that no longer exists).

Scope: float32, dense slabs, logistic/Poisson losses (the two losses the
damped-Newton path serves), and a block whose VMEM estimate fits the
budget (``_vmem_estimate_bytes``). The batch-minor XLA path remains as
the route for every other shape and as parity oracle
(tests/test_newton_kernel.py).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_tpu.types import TaskType

Array = jax.Array

LANES = 128

# Program contract (audited by `python -m photon_tpu.analysis --semantic`;
# machinery in analysis/program.py): one Newton step for a bucket shape is
# ONE program — damping/λ/weights are traced operands; only the bucket
# shape (r, s) and the line-search trial count are static and may mint a
# new executable. No host callbacks, no f64, ever: this kernel sits inside
# the fused fit's per-iteration loop.
PROGRAM_AUDIT = dict(
    name="newton-kernel",
    entry="ops.newton_kernel.newton_step_lanes",
    builder="build_newton_kernel",
    max_programs=1,
    recompiles_on=("bucket_shape", "line_search_trials"),
    hot_loop=True,
)
# VMEM budget of one grid step, out of v5e's 16 MiB default scoped
# limit. The estimate below was calibrated by AOT-compiling the kernel
# for a v5e topology across (r, s): every shape with an estimate up to
# 16.0 MiB compiled and every one from 16.5 MiB was refused ("Scoped
# allocation ... exceeded scoped vmem limit"); the old gate, r * s <=
# 16384, admitted 8 MiB x blocks that are refused once double buffered.
_VMEM_BUDGET_BYTES = 14 * 2**20
_LINE_SEARCH_TRIALS = 16


def _vmem_estimate_bytes(r: int, s: int) -> int:
    """Per-grid-step VMEM of the kernel, f32 x 128 lanes: the
    double-buffered [S, R] x block, ~16 live [R]-row vectors (the three
    double-buffered row operands plus the body's margin / curvature /
    line-search temporaries) and the [S, S] Hessian scratch."""
    return 4 * LANES * (2 * r * s + 16 * r + s * s)


def interpret_required() -> bool:
    """True when pallas_call must run interpreted on this backend.

    Mosaic lowering is TPU-only: a force-flagged run on any other
    backend (CPU, GPU) routes through ``interpret=True`` (slow, but
    correct and traceable) instead of crashing in lowering.
    """
    return jax.default_backend() != "tpu"


def kernel_supported(
    task: TaskType, dtype, r: int, s: int, *, spmd: bool = False
) -> bool:
    """Whether the Pallas step serves this bucket. ``spmd``: the
    operands span several devices (ops/placement.py) — GSPMD does not
    partition a Mosaic kernel, so a mesh fit takes the XLA step."""
    flag = os.environ.get("PHOTON_NEWTON_KERNEL", "auto").lower()
    if flag in ("0", "off", "false"):  # photon: ignore[spmd-host-divergence] -- kernel-select flag is launch config, exported fleet-uniform; divergence trips the --spmd trace proof
        return False
    if spmd:
        return False
    if jnp.dtype(dtype) != jnp.float32:
        return False
    if task not in (TaskType.LOGISTIC_REGRESSION,
                    TaskType.POISSON_REGRESSION):
        return False
    if _vmem_estimate_bytes(r, s) > _VMEM_BUDGET_BYTES:
        return False
    if flag in ("1", "on", "force"):  # photon: ignore[spmd-host-divergence] -- kernel-select flag is launch config, exported fleet-uniform; divergence trips the --spmd trace proof
        # Callers pass interpret=interpret_required() so a forced run on
        # a non-TPU backend executes the interpreter path rather than
        # failing in Mosaic.
        return True
    # Auto: only a real TPU runs the kernel. Other accelerators must take
    # the batch-minor XLA fallback — the interpreter path is orders of
    # magnitude slower and is reserved for the explicit force flag.
    return jax.default_backend() == "tpu"


def _loss_terms(task: TaskType, z, y):
    """(loss, dz, dzz) elementwise — mirrors ops/losses.py for the two
    strictly convex smooth losses the Newton path serves."""
    if task == TaskType.LOGISTIC_REGRESSION:
        # Labels may arrive as {0,1} OR {-1,1}: anything above the
        # positive-response threshold counts as positive, exactly as
        # ops/losses.py (MathConst.POSITIVE_RESPONSE_THRESHOLD = 0.5).
        ind = jnp.where(y > 0.5, 1.0, 0.0)
        p = 1.0 / (1.0 + jnp.exp(-z))
        loss = jnp.log1p(jnp.exp(-jnp.abs(z))) + jnp.maximum(z, 0.0) \
            - z * ind
        return loss, p - ind, p * (1 - p)
    # Poisson: loss = exp(z) - y z (raw counts; PoissonLossFunction.scala)
    ez = jnp.exp(z)
    return ez - y * z, ez - y, ez


def _make_kernel(r: int, s: int, task: TaskType, trials: int):
    def kernel(x_ref, w_ref, y_ref, wt_ref, off_ref, l2_ref, mt_ref,
               vm_ref, f_ref, w_out, f_out, g_out, imp_out, h_ref):
        w = w_ref[...]           # [S, BL]
        l2 = l2_ref[...]
        mt = mt_ref[...]
        vm = vm_ref[...]
        y = y_ref[...]           # [R, BL]
        wt = wt_ref[...]
        off = off_ref[...]
        f_prev = f_ref[...]      # [1, BL]

        z = off
        for i in range(s):
            z = z + x_ref[i] * w[i:i + 1, :]
        loss0, dz0, dzz0 = _loss_terms(task, z, y)
        c = wt * dzz0
        d1 = wt * dz0

        g_rows = []
        for i in range(s):
            xs = x_ref[i]
            xc = xs * c
            for t in range(i + 1):
                row = jnp.sum(xc * x_ref[t], axis=0, keepdims=True)
                if t == i:
                    row = row + l2[i:i + 1, :] + (1.0 - vm[i:i + 1, :])
                h_ref[i, t, :] = row[0]
                if t != i:
                    h_ref[t, i, :] = row[0]
            g_rows.append(jnp.sum(xs * d1, axis=0, keepdims=True))
        g = (jnp.concatenate(g_rows, axis=0) + l2 * (w - mt)) * vm

        def matvec(pp):
            acc = h_ref[:, 0, :] * pp[0:1, :]
            for t in range(1, s):
                acc = acc + h_ref[:, t, :] * pp[t:t + 1, :]
            return acc

        b0 = -g

        def cg_step(_, st):
            xx, rr, pp, rs = st
            hp = matvec(pp)
            denom = jnp.sum(pp * hp, axis=0, keepdims=True)
            alpha = rs / jnp.maximum(denom, 1e-30)
            xx = xx + alpha * pp
            rr = rr - alpha * hp
            rs2 = jnp.sum(rr * rr, axis=0, keepdims=True)
            pp = rr + (rs2 / jnp.maximum(rs, 1e-30)) * pp
            return xx, rr, pp, rs2

        d, _, _, _ = lax.fori_loop(
            0, s, cg_step,
            (jnp.zeros_like(b0), b0, b0,
             jnp.sum(b0 * b0, axis=0, keepdims=True)),
        )
        d = d * vm
        gd = jnp.sum(g * d, axis=0, keepdims=True)
        bad = gd >= 0.0
        d = jnp.where(bad, -g, d)
        gd = jnp.where(bad, -jnp.sum(g * g, axis=0, keepdims=True), gd)

        zd = jnp.zeros_like(z)
        for i in range(s):
            zd = zd + x_ref[i] * d[i:i + 1, :]

        t_sel = jnp.zeros_like(gd)
        f_sel = f_prev
        for k in range(trials):
            tk = 0.5 ** k
            loss_k, _, _ = _loss_terms(task, z + tk * zd, y)
            f_k = jnp.sum(wt * loss_k, axis=0, keepdims=True) + 0.5 * \
                jnp.sum(l2 * (w + tk * d - mt) ** 2, axis=0, keepdims=True)
            ok = (f_k <= f_prev + 1e-4 * tk * gd) & (t_sel == 0.0)
            t_sel = jnp.where(ok, tk, t_sel)
            f_sel = jnp.where(ok, f_k, f_sel)
        improved = (t_sel > 0.0) & (f_sel < f_prev)
        w_new = jnp.where(improved, w + t_sel * d, w)

        z2 = off
        for i in range(s):
            z2 = z2 + x_ref[i] * w_new[i:i + 1, :]
        loss2, dz2, _ = _loss_terms(task, z2, y)
        f_new = jnp.sum(wt * loss2, axis=0, keepdims=True) + 0.5 * \
            jnp.sum(l2 * (w_new - mt) ** 2, axis=0, keepdims=True)
        g2_rows = []
        for i in range(s):
            g2_rows.append(jnp.sum(x_ref[i] * (wt * dz2), axis=0,
                                   keepdims=True))
        g_new = (jnp.concatenate(g2_rows, axis=0) + l2 * (w_new - mt)) * vm

        w_out[...] = w_new
        f_out[...] = f_new
        g_out[...] = g_new
        imp_out[...] = improved.astype(jnp.float32)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("r", "s", "task", "trials", "interpret"),
)
def newton_step_lanes(
    x_t: Array,   # [S, R, Bp] transformed slab, entities in lanes
    w: Array,     # [S, Bp]
    y: Array,     # [R, Bp]
    wt: Array,    # [R, Bp]
    off: Array,   # [R, Bp]
    l2: Array,    # [S, Bp]
    mt: Array,    # [S, Bp]
    vm: Array,    # [S, Bp]
    f: Array,     # [1, Bp]
    *,
    r: int,
    s: int,
    task: TaskType,
    trials: int = _LINE_SEARCH_TRIALS,
    interpret: bool = False,
):
    """One fused Newton step for Bp (lane-padded) entities.

    Returns (w_new [S, Bp], f_new [1, Bp], g_new [S, Bp],
    improved [1, Bp] float)."""
    bp = x_t.shape[-1]
    nb = bp // LANES
    vec = lambda: pl.BlockSpec((s, LANES), lambda i: (0, i))  # noqa: E731
    row = lambda: pl.BlockSpec((r, LANES), lambda i: (0, i))  # noqa: E731
    one = lambda: pl.BlockSpec((1, LANES), lambda i: (0, i))  # noqa: E731
    return pl.pallas_call(
        _make_kernel(r, s, task, trials),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((s, r, LANES), lambda i: (0, 0, i)),
            vec(), row(), row(), row(), vec(), vec(), vec(), one(),
        ],
        out_specs=[vec(), one(), vec(), one()],
        out_shape=[
            jax.ShapeDtypeStruct((s, bp), jnp.float32),
            jax.ShapeDtypeStruct((1, bp), jnp.float32),
            jax.ShapeDtypeStruct((s, bp), jnp.float32),
            jax.ShapeDtypeStruct((1, bp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((s, s, LANES), jnp.float32)],
        interpret=interpret,
        name="newton_step_lanes",
    )(x_t, w, y, wt, off, l2, mt, vm, f)


def pad_lanes(n: int) -> int:
    return -(-n // LANES) * LANES


def to_lanes(a: Array, bp: int) -> Array:
    """[B, ...] -> [..., Bp] with zero padding on the entity axis."""
    pad = bp - a.shape[0]
    if pad:
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
    axes = tuple(range(1, a.ndim)) + (0,)
    return jnp.transpose(a, axes)
