"""Batched SPD solve TPU kernel (Pallas): each entity's H stays in VMEM.

The direct (squared-loss) route solves one small SPD system ``H x = b``
per entity by a FIXED-count conjugate gradient: S steps, the residual
``b - H x``, S more steps, ``x + correction``
(``algorithm/random_effect._spd_solve_cg``). Under XLA the vmapped
``[B, S, S]`` H is read from HBM by every one of the 2S + 1 matvecs, and
at S = 197 its minor axis pads to 256 lanes: the matvecs run at the HBM
roofline and the solve's bytes are the algorithm's 2S + 1 sweeps.

Here a grid step owns 128 entities, ENTITIES IN LANES, and its H block
``[S, S, 128]`` is DMA'd into VMEM once and read from there by all
2S + 1 matvecs. H arrives as ``[j, i, B]`` (``h.transpose(2, 1, 0)``), so
``h_ref[t]`` is column t of every entity's H and the matvec
``sum_t h_ref[t] * p[t]`` forms exactly the products of ``h @ p``, summed
over t in order; every load is a whole contiguous ``[S, 128]`` tile.
The CG state lives in VMEM scratch (``p`` so that the matvec can read
its rows); all arithmetic is float32 on the VPU.

Scope: float32, one device (a mesh closes every Pallas route,
``ops/placement.py``), and a width whose double-buffered H block fits
the scoped-VMEM limit the kernel asks for (``kernel_supported``). Every
other bucket keeps the vmapped XLA CG, which is also the parity oracle
(tests/test_cg_kernel.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

LANES = 128
SUBLANES = 8

# Scoped VMEM the kernel asks Mosaic for (out of a v5e's 128 MiB; the
# default scoped limit is 16 MiB), and the budget the estimate below must
# fit. Calibrated by AOT-compiling the kernel for a v5e topology across S
# with this limit: every S up to 314 (estimate 99.2 MiB) compiled and
# S = 315 (99.5 MiB) was refused ("Ran out of memory in memory space
# vmem"); the budget admits S <= 311.
_VMEM_LIMIT_BYTES = 100 * 2**20
_VMEM_BUDGET_BYTES = 96 * 2**20
# Columns of H a matvec loop iteration takes, a multiple of 8 (one
# aligned load of p's rows a group): on a v5e 16 was at most 3 % slower
# than the best of 8, 16 and 32 wherever a call took over a millisecond.
_MATVEC_GROUP = 16


def _pad8(n: int) -> int:
    return -(-n // SUBLANES) * SUBLANES


def _vmem_estimate_bytes(s: int) -> int:
    """VMEM of one grid step, f32 x 128 lanes: the double-buffered
    ``[S, S8, 128]`` H block, the double-buffered b and x blocks and the
    three ``[S8, 128]`` state scratches (x, r, p)."""
    s8 = _pad8(s)
    return 4 * LANES * (2 * s * s8 + 4 * s8 + 3 * s8)


def interpret_required() -> bool:
    """True where pallas_call must run interpreted (any backend but the
    TPU; Mosaic lowering is TPU-only)."""
    return jax.default_backend() != "tpu"


def kernel_supported(s: int, dtype, *, spmd: bool = False) -> bool:
    """Whether the kernel serves a direct bucket of width ``s``: a TPU,
    one device (``spmd``: the operands span several, ops/placement.py),
    float32 systems, and a block within the VMEM budget. Shapes and
    placement only. No narrowest width: on a v5e the whole direct solve
    ran faster through the kernel at every width the benchmark has, 197
    down to 9, and as fast at 1 (PERF.md section 6)."""
    return (
        not interpret_required()
        and not spmd
        and jnp.dtype(dtype) == jnp.float32
        and _vmem_estimate_bytes(s) <= _VMEM_BUDGET_BYTES
    )


def _make_kernel(s: int):
    group = _MATVEC_GROUP
    full, tail = divmod(s, group)

    def kernel(h_ref, b_ref, x_out, x_ref, r_ref, p_ref):
        def terms(acc, base, count, p_rows):
            for k in range(count):
                acc = acc + h_ref[base + k] * p_rows[k:k + 1, :]
            return acc

        def matvec():
            """``H p`` for the vector held in ``p_ref[:s]``: one
            ``[S, 128]`` column of H a term, t = 0 .. S-1 in order."""
            def chunk(c, acc):
                base = pl.multiple_of(c * group, group)
                return terms(acc, base, group,
                             p_ref[pl.ds(base, group), :])

            acc = jnp.zeros((s, LANES), jnp.float32)
            if full:
                acc = lax.fori_loop(0, full, chunk, acc)
            if tail:
                base = full * group
                acc = terms(acc, base, tail,
                            p_ref[base:base + _pad8(tail), :])
            return acc

        def run_cg(rhs):
            """S CG steps from zero on ``H x = rhs``; returns x."""
            x_ref[...] = jnp.zeros((s, LANES), jnp.float32)
            r_ref[...] = rhs
            p_ref[:s, :] = rhs

            def cg_step(_, rs):
                hp = matvec()
                p = p_ref[:s, :]
                alpha = rs / jnp.maximum(
                    jnp.sum(p * hp, axis=0, keepdims=True), 1e-30)
                x_ref[...] = x_ref[...] + alpha * p
                r = r_ref[...] - alpha * hp
                r_ref[...] = r
                rs_new = jnp.sum(r * r, axis=0, keepdims=True)
                p_ref[:s, :] = r + (rs_new / jnp.maximum(rs, 1e-30)) * p
                return rs_new

            lax.fori_loop(0, s, cg_step,
                          jnp.sum(rhs * rhs, axis=0, keepdims=True))
            return x_ref[...]

        b = b_ref[...]
        x = run_cg(b)
        x_out[...] = x
        p_ref[:s, :] = x
        x_out[...] = x + run_cg(b - matvec())

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def spd_solve_lanes(h: Array, b: Array, *, interpret: bool = False):
    """Solve ``h[e] x[e] = b[e]`` for every entity e with the arithmetic
    of ``random_effect._spd_solve_cg(h[e], b[e], S)`` (S CG steps, one
    refinement pass). ``h`` [B, S, S] float32 SPD, ``b`` [B, S]; returns
    x [B, S]. Entities are padded to a multiple of 128 lanes with H = I
    and b = 0, which stay finite and are dropped."""
    n, s, _ = h.shape
    bp = -(-n // LANES) * LANES
    pad = bp - n
    if pad:
        h = jnp.concatenate(
            [h, jnp.broadcast_to(jnp.eye(s, dtype=h.dtype), (pad, s, s))])
        b = jnp.pad(b, ((0, pad), (0, 0)))
    h_l = jnp.transpose(h, (2, 1, 0))  # [j, i, B]: h_l[t] = column t
    b_l = jnp.transpose(b)
    vec = pl.BlockSpec((s, LANES), lambda i: (0, i))
    x_l = pl.pallas_call(
        _make_kernel(s),
        grid=(bp // LANES,),
        in_specs=[pl.BlockSpec((s, s, LANES), lambda i: (0, 0, i)), vec],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((s, bp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((s, LANES), jnp.float32),
            pltpu.VMEM((s, LANES), jnp.float32),
            pltpu.VMEM((_pad8(s), LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="cg_solve_lanes",
    )(h_l, b_l)
    return jnp.transpose(x_l)[:n]
