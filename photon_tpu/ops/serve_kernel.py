"""Fused serve-score TPU kernel (Pallas): one dispatch per rung.

The AOT score ladder (serve/programs.py) lowers each coordinate's
gather -> contract -> add as its own fusion chain inside the jitted
program: per random coordinate the dense-request route rebuilds an
[E, d + 1] original-space table by scatter on EVERY dispatch
(``models/game._score_raw_dense``), the sparse route materializes the
[B, k, S] one-hot operand, and the per-coordinate adds round-trip the
[B] partial scores. This kernel scores an entire padded rung in ONE
pallas_call:

- the ROW GATHER stays in XLA, in the same jitted program: each random
  coordinate's weight and projector rows for the rung's codes are taken
  once (``[rung, S]``, a few KiB) and cold rows (code -1) are zeroed
  there. A table row cannot be addressed from inside a Mosaic kernel at
  these shapes: a ``(1, S)`` block over an ``[E, S]`` table is refused
  at lowering (a block's last two dims must be (8, 128)-divisible or
  equal the array's), and a manual one-row DMA is refused unless S is a
  multiple of 128 lanes and the table is 32-bit;
- the grid walks the rung in tiles of up to ``_TILE_ROWS`` requests, so
  every block is ``(tile, width)`` with ``tile`` a multiple of 8 or the
  whole rung — legal for every ladder rung, including the latency
  rung 1;
- inside the body every contraction is a static loop over subspace
  slots of masked row reductions in VMEM with float32 accumulators;
  coordinate partials add in registers and the [tile, 1] score block is
  written once. Cold rows carry all-zero weights — fixed-effect-only,
  the same semantics as ``models/game._score_raw_dense`` /
  ``_score_raw_sparse``.

Storage dtypes: f32 or bf16 tables (the serving precision policy);
feature payloads are rounded to the table dtype at the contraction and
every reduction accumulates f32 — the ops/precision.py invariant, and
the parity contract with the jit fallback (tests/test_serve_kernel.py).
Arithmetic inside the body is f32 throughout: a bf16 x bf16 product is
exact in f32, so rounding it back to bf16 reproduces the bf16 multiply
bit for bit without needing a bf16 vector unit (v5e has none).

Scope mirrors ops/segment_reduce.py: Mosaic lowering is TPU-only, so
``interpret_required()`` routes forced runs on other backends through
``interpret=True``; unforced non-TPU backends keep the jitted
per-coordinate chain, which doubles as the parity oracle. The gate
(``kernel_supported``) is decided ONCE at ``ScorePrograms``
construction from the table dtype and the model's static widths —
tables stay traced operands either way, so values-only reloads re-enter
the same executables.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# Program contract (audited by `python -m photon_tpu.analysis
# --semantic`): one ladder rung through the fused kernel is ONE program
# — tables, features and the codes are traced operands; only the rung
# batch and the model structure (shard widths, coordinate count,
# sub_dims) are static and may mint a new executable. No host
# callbacks, no f64: this kernel IS the steady-state request loop.
PROGRAM_AUDIT = dict(
    name="serve-kernel",
    entry="ops.serve_kernel.fused_score",
    builder="build_serve_kernel",
    max_programs=1,
    recompiles_on=("rung", "model_structure"),
    hot_loop=True,
)

# Memory contract (`--memory`, ANALYSIS.md): the fused rung's live set
# is the resident tables (weights at storage width + int32 projector +
# fixed weights) plus the padded request payloads, the gathered
# [rung, s] weight + projector rows and the [rung] f32 output — NO
# rebuilt [e, d] original-space table and no [rung, k, s] one-hot
# operand, which is the kernel's memory story vs the jit chain.
# Scaffold constant mirrors the serving audit.
MEMORY_AUDIT = dict(
    name="serve-kernel-memory",
    entry="ops.serve_kernel.fused_score",
    covers=("serve-kernel",),
    builder="build_serve_kernel_memory",
    budgets={
        # Resident: [e, s] weights at storage width + [e, s] int32
        # projector + [d] fixed weights (+ a fixed scaffold constant);
        # per request row: the padded feature payloads (d dense + du
        # shard columns), the code, the gathered weight + projector
        # row (the XLA-side take the kernel consumes), and the f32
        # score.
        # The last term is the body's [tile, width] lane-iota / mask /
        # select temporaries: VMEM values in the compiled kernel,
        # priced here because the audit walks the interpret-path
        # lowering, where they are ordinary buffers.
        "serve_kernel_b*": (
            "e * s * (wbytes + 4) + d * wbytes + 52 * wbytes"
            " + rung * (d + du + s) * wbytes"
            " + rung * s * (wbytes + 4)"
            " + rung * 3 * (d + du) * 4"
        ),
    },
    tolerance=1.5,
)

# Tier-5 numerics contract (`--numerics`): the kernel traced over bf16
# tables next to the jit fallback on the same fixture. One table
# storage rounding per gathered coefficient + f32 accumulation per
# reduced column; the one-hot contraction is a static single-axis VMEM
# reduce per coordinate — no scatter family, so the determinism census
# has nothing to declare.
NUMERICS_AUDIT = dict(
    name="serve-kernel-numerics",
    entry="ops.serve_kernel.fused_score",
    covers=("serve-kernel",),
    builder="build_serve_kernel_numerics",
    budgets={
        # Two bf16 roundings on the deepest path — the feature cast at
        # the contraction (the table sides are already storage width)
        # and the storage-width PRODUCT, which the jit chain's bf16
        # multiply rounds implicitly and this body rounds explicitly
        # (``_rounded``) — + f32 accumulator rounding over the summed
        # reduce lengths: the [s, d] random gather + the [s] row
        # contraction + the [d] fixed contraction, plus the per-rung
        # output accumulation.
        "serve_kernel_b*": (
            "2 * u16"
            " + u32 * (s * (d + du) + d + du + 2 * s + 4 * rung)"
        ),
    },
    suppress={
        "numerics-cast-roundtrip": (
            "_rounded's f32->bf16->f32 IS the arithmetic: a bf16 x bf16 "
            "product is exact in f32, so rounding it to bf16 and back "
            "reproduces the jit chain's bf16 multiply bit for bit "
            "without a bf16 vector unit (v5e has none)"
        ),
    },
    tolerance=1.5,
)

# Trace-time site registry (host-side), same shape as
# ops/segment_reduce._TRACED_SITES: every kernel instantiation records
# its static shape + analytic cost so cli.profile can register a priced
# census row without the dispatch path touching the ledger. Keyed by
# (site, rung, structure digest); ``traced_sites()`` aggregates per
# site. Cleared between tests by the conftest reset.
_TRACED_SITES: dict[tuple, dict] = {}


def interpret_required() -> bool:
    """True when pallas_call must run interpreted on this backend
    (same contract as ops/segment_reduce.interpret_required)."""
    return jax.default_backend() != "tpu"


# Request rows per grid step; a rung below it is ONE step over the whole
# rung. A multiple of 8 (the f32 sublane count), so every [tile, width]
# block is legal.
_TILE_ROWS = 128
# Shape bounds of the gate (checked in code, never discovered by a
# compiler refusal). The body is a STATIC loop over subspace slots (one
# masked row reduction each), so the unrolled term count is bounded;
# and every operand block is [tile, width] f32 in VMEM, double
# buffered, so the summed lane-padded widths are bounded to keep the
# blocks under half of v5e's 16 MiB scoped VMEM.
_MAX_UNROLLED_TERMS = 512
_MAX_BLOCK_BYTES = 8 * 2**20


def _lanes(width: int) -> int:
    return -(-int(width) // 128) * 128


def shape_supported(fe_dims, re_dims) -> bool:
    """Whether the fused kernel is legal AND sane for this model
    structure: ``fe_dims`` [(kind, d, k)] per fixed coordinate,
    ``re_dims`` [(kind, d, k, s)] per random one (the ``_record_site``
    vocabulary). A wide sparse fixed effect (d in the millions) or a
    wide random subspace stays on the jitted chain."""
    terms = 0
    lanes = 0
    for kind, d, k in fe_dims:
        lanes += _lanes(d)  # the [1, d] weight row
        if kind == "dense":
            lanes += _lanes(d)
        else:
            terms += k
            lanes += 2 * _lanes(k)
    for kind, d, k, s_dim in re_dims:
        terms += s_dim
        lanes += 2 * _lanes(s_dim)  # gathered weight + projector rows
        lanes += _lanes(d) if kind == "dense" else 2 * _lanes(k)
    return (
        terms <= _MAX_UNROLLED_TERMS
        and 2 * 4 * _TILE_ROWS * lanes <= _MAX_BLOCK_BYTES
    )


def kernel_supported(dtype, fe_dims=(), re_dims=()) -> bool:
    """Whether the fused kernel serves score dispatches on this backend.

    ``PHOTON_SERVE_KERNEL``: ``auto`` (default — real TPU only),
    ``force``/``on``/``1`` (every backend; non-TPU runs interpreted —
    slow, for parity tests and the profile probe), ``off``/``0``
    (always the jitted per-coordinate chain). The dtype and
    ``shape_supported`` bounds apply under every flag value.
    """
    flag = os.environ.get("PHOTON_SERVE_KERNEL", "auto").lower()
    if flag in ("0", "off", "false"):  # photon: ignore[spmd-host-divergence] -- kernel-select flag is launch config, exported fleet-uniform; divergence trips the --spmd trace proof
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    if not shape_supported(fe_dims, re_dims):
        return False
    if flag in ("1", "on", "force"):  # photon: ignore[spmd-host-divergence] -- kernel-select flag is launch config, exported fleet-uniform; divergence trips the --spmd trace proof
        return True
    return jax.default_backend() == "tpu"


def _record_site(site: str, rung: int, fe_dims, re_dims, dtype) -> None:
    """Host bookkeeping at trace time (once per rung trace, never per
    dispatch): analytic cost of one fused dispatch in the costmodel's
    counter vocabulary. ``fe_dims`` is [(kind, width, k)] per fixed
    coordinate; ``re_dims`` [(kind, width, k, s)] per random one."""
    esize = jnp.dtype(dtype).itemsize
    flops = 0.0
    hbm = float(rung) * 4.0  # the [rung] f32 output
    for kind, d, k in fe_dims:
        hbm += d * esize  # the resident weight vector, read once
        if kind == "dense":
            flops += 2.0 * rung * d
            hbm += rung * d * 4.0
        else:
            flops += 2.0 * rung * k * d
            hbm += rung * k * 8.0
    for kind, d, k, s in re_dims:
        # One [1, s] weight + projector row gathered per request (the
        # XLA-side take), written once and read once by the kernel.
        hbm += 3.0 * rung * s * (esize + 4.0)
        if kind == "dense":
            flops += 2.0 * rung * (s * d + s)
            hbm += rung * d * 4.0
        else:
            flops += 2.0 * rung * (k * s + s)
            hbm += rung * k * 8.0
    _TRACED_SITES[(site, int(rung), tuple(fe_dims), tuple(re_dims),
                   str(jnp.dtype(dtype)))] = {
        "rung": int(rung),
        "dtype": str(jnp.dtype(dtype)),
        "cost": {
            "flops": flops,
            "hbm_bytes": hbm,
            "transcendentals": 0.0,
        },
    }


def traced_sites() -> dict[str, dict]:
    """Per-SITE aggregate of every fused-score instantiation traced so
    far (host bookkeeping for the cost ledger / cli.profile census): a
    site traced at several rungs prices the SUM of its instances'
    analytic costs."""
    out: dict[str, dict] = {}
    for (site, *_rest), info in _TRACED_SITES.items():
        agg = out.get(site)
        if agg is None:
            agg = out[site] = {
                "instances": 0,
                "rungs": 0,
                "cost": {"flops": 0.0, "hbm_bytes": 0.0,
                         "transcendentals": 0.0},
            }
        agg["instances"] += 1
        agg["rungs"] += info["rung"]
        for key in ("flops", "hbm_bytes", "transcendentals"):
            agg["cost"][key] += info["cost"][key]
    return out


def _rounded(x, wdtype):
    """``x`` (f32) rounded to the table storage dtype, held as f32."""
    if wdtype == jnp.dtype(jnp.float32):
        return x
    return x.astype(wdtype).astype(jnp.float32)


def _make_kernel(fe_ops, re_ops):
    """Kernel body closure over the STATIC coordinate walk.

    ``fe_ops``: [(kind, shard_ref_slots, w_slot, wdtype)] per fixed
    coordinate; ``re_ops``: [(kind, shard_ref_slots, w_slot, wdtype)]
    per random one (the gathered weight rows sit at ``w_slot``, the
    gathered projector rows at ``w_slot + 1``). Slot numbers index the
    positional operand refs. Every value in the body is [tile, width]
    or [tile, 1] float32/int32.
    """

    def kernel(*refs):
        out_ref = refs[-1]
        tile = out_ref.shape[0]
        acc = jnp.zeros((tile, 1), jnp.float32)
        for kind, shard, w_slot, wdtype in fe_ops:
            w = refs[w_slot][...].astype(jnp.float32)  # [1, d]
            d = w.shape[1]
            if kind == "dense":
                x = _rounded(refs[shard[0]][...], wdtype)  # [tile, d]
                acc += jnp.sum(
                    _rounded(x * w, wdtype), axis=1, keepdims=True
                )
            else:
                idx = refs[shard[0]][...]  # [tile, k] int32
                val = _rounded(refs[shard[1]][...], wdtype)
                lane = jax.lax.broadcasted_iota(jnp.int32, (tile, d), 1)
                for j in range(idx.shape[1]):
                    # Masked row reduction = exact gather of w[idx[:, j]]
                    # (one term per row).
                    picked = jnp.sum(
                        jnp.where(idx[:, j:j + 1] == lane, w, 0.0),
                        axis=1, keepdims=True,
                    )
                    acc += _rounded(val[:, j:j + 1] * picked, wdtype)
        for kind, shard, w_slot, wdtype in re_ops:
            # Gathered table rows; cold / padding rows (code -1) were
            # zeroed by the caller — the fixed-effect-only fallback of
            # the jit chain.
            w = refs[w_slot][...].astype(jnp.float32)  # [tile, s]
            proj = refs[w_slot + 1][...]  # [tile, s] int32
            if kind == "dense":
                x = refs[shard[0]][...]  # [tile, d] f32 payload
                lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
                for j in range(w.shape[1]):
                    # proj -1 pads match no feature id: the spill-drop
                    # of _score_raw_dense's scatter. The masked
                    # reduction is an exact gather (distinct projector
                    # slots: one term per row), so rounding AFTER it
                    # equals the jit chain's x.astype(w.dtype) — one
                    # storage rounding.
                    xg = jnp.sum(
                        jnp.where(proj[:, j:j + 1] == lane, x, 0.0),
                        axis=1, keepdims=True,
                    )
                    acc += _rounded(
                        w[:, j:j + 1] * _rounded(xg, wdtype), wdtype
                    )
            else:
                idx = refs[shard[0]][...]  # [tile, k] int32
                val = refs[shard[1]][...].astype(jnp.float32)
                for j in range(w.shape[1]):
                    # Duplicate feature ids in a request sum.
                    contrib = jnp.sum(
                        jnp.where(idx == proj[:, j:j + 1], val, 0.0),
                        axis=1, keepdims=True,
                    )
                    # Storage rounding of the slot total (like_storage);
                    # the product of two storage-width values is exact
                    # in f32, as in the jit chain's f32-accumulated
                    # einsum.
                    acc += _rounded(contrib, wdtype) * w[:, j:j + 1]
        out_ref[...] = acc

    return kernel


def fused_score(
    fe_ws,
    re_ws,
    re_projs,
    feats,
    codes,
    *,
    spec_kinds: tuple[str, ...],
    fe_feat: tuple[int, ...],
    re_feat: tuple[int, ...],
    interpret: bool | None = None,
    site: str = "serve_kernel/score",
) -> Array:
    """Score one padded rung in a single fused kernel dispatch.

    Operand layout is EXACTLY ``ScorePrograms.score_fn``'s: per-shard
    feature leaves in ``shard_order`` position (``spec_kinds``), fixed
    weight vectors + random (weights, projector) tables, and one [rung]
    int32 code vector per random coordinate. Returns [rung] float32.
    Call under an outer jit — the pallas_call is built at trace time
    from the static model structure.
    """
    if not feats:
        raise ValueError("fused_score needs at least one feature shard")
    leaf = feats[0]
    rung = int(
        (leaf if isinstance(leaf, jax.Array) or hasattr(leaf, "shape")
         else leaf[0]).shape[0]
    )
    tile = min(rung, _TILE_ROWS)

    operands: list = []
    in_specs: list = []
    shard_slots: dict[int, tuple[int, ...]] = {}

    def add_rows(arr):
        # [rung, width] request-major operand, walked in row tiles.
        operands.append(arr)
        in_specs.append(
            pl.BlockSpec((tile, arr.shape[1]), lambda i: (i, 0))
        )

    for si, kind in enumerate(spec_kinds):
        if kind == "dense":
            shard_slots[si] = (len(operands),)
            add_rows(feats[si])
        else:
            idx, val = feats[si]
            shard_slots[si] = (len(operands), len(operands) + 1)
            add_rows(idx.astype(jnp.int32))
            add_rows(val)

    fe_ops = []
    fe_dims = []
    for w, fi in zip(fe_ws, fe_feat):
        fe_ops.append(
            (spec_kinds[fi], shard_slots[fi], len(operands),
             jnp.dtype(w.dtype))
        )
        d = int(w.shape[0])
        kk = 0 if spec_kinds[fi] == "dense" else int(
            feats[fi][0].shape[1]
        )
        fe_dims.append((spec_kinds[fi], d, kk))
        operands.append(w.reshape(1, d))
        in_specs.append(pl.BlockSpec((1, d), lambda i: (0, 0)))

    re_ops = []
    re_dims = []
    wdtype = jnp.dtype(fe_ws[0].dtype) if fe_ws else None
    for w, proj, code, fi in zip(re_ws, re_projs, codes, re_feat):
        sdim = int(w.shape[1])
        re_ops.append(
            (spec_kinds[fi], shard_slots[fi], len(operands),
             jnp.dtype(w.dtype))
        )
        wdtype = jnp.dtype(w.dtype)
        if spec_kinds[fi] == "dense":
            re_dims.append(("dense", int(feats[fi].shape[1]), 0, sdim))
        else:
            re_dims.append(
                ("sparse", 0, int(feats[fi][0].shape[1]), sdim)
            )
        # The row gather, in XLA (see the module docstring for why it
        # cannot live in the kernel): jnp.take wraps negative indices
        # numpy-style, so -1 is clamped and masked explicitly.
        code = code.astype(jnp.int32)
        safe = jnp.maximum(code, 0)
        add_rows(jnp.where(
            (code >= 0)[:, None], jnp.take(w, safe, axis=0), 0
        ).astype(w.dtype))
        add_rows(jnp.take(proj.astype(jnp.int32), safe, axis=0))

    _record_site(site, rung, fe_dims, re_dims, wdtype or jnp.float32)
    out = pl.pallas_call(
        _make_kernel(tuple(fe_ops), tuple(re_ops)),
        grid=(pl.cdiv(rung, tile),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rung, 1), jnp.float32),
        interpret=(
            interpret_required() if interpret is None else interpret
        ),
        name="serve_score",
    )(*operands)
    return out[:, 0]
