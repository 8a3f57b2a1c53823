"""Tiled TPU segment-reduce (Pallas): scatter-add as windowed MXU work.

The GLMix hot path scatters per-entity results back to canonical rows in
three places — the bucket scorer's ``z.at[row_ids].add`` (models/game.py),
the width-capped score table's COO overflow tail (``segment_sum``), and
the wide-ELL densify ``.at[rows, slots].add`` (algorithm/random_effect.py).
XLA lowers all three to scatter-add, which serializes on duplicate
indices and reads HBM at gather granularity — the per-entity
gather/scatter is exactly where BENCH_r05's fraction-of-HBM-peak gauge
(~4.6%) says the bandwidth goes unclaimed.

This kernel reformulates scatter-add as a WINDOWED ONE-HOT CONTRACTION:

- the OUTPUT is tiled into ``_OUT_TILE``-segment blocks; the grid is
  ``(out_tiles, k_tiles)`` and each out block accumulates across its k
  steps in VMEM (init at ``k == 0``), so the result is written to HBM
  exactly once;
- for each (out tile j, step k) the kernel streams ONE ``_IN_TILE``
  block of (ids, values), both LANE-major ``[1, IT]`` (blocks
  ``(None, 1, IT)`` over ``[tiles, 1, IT]`` arrays — the layout Mosaic
  accepts), and adds ``values @ onehot(ids - j*_OUT_TILE)`` with the
  one-hot built transposed, ``[OT, IT]``, and contracted over the lane
  dim of both; elements whose id falls outside the window contribute an
  all-zero one-hot column, so visiting extra tiles is always CORRECT,
  only ever wasteful;
- which input tiles each out tile visits comes from a SCALAR-PREFETCHED
  ``starts`` vector (``pltpu.PrefetchScalarGridSpec``): the block index
  maps resolve ``starts[j] + k`` before the body runs. The caller
  guarantees COVERAGE — every element whose id lands in window j sits
  within the K visited tiles — which is a static-shape argument: for
  sorted ids with per-segment multiplicity <= ``multiplicity``, a
  window holds at most ``_OUT_TILE * multiplicity`` elements, so
  ``K = ceil(_OUT_TILE * multiplicity / _IN_TILE) + 1`` always covers.

HBM traffic: each input element is read K times (K == 2 for the
multiplicity-1 scoring scatter) and each output written once — streaming
reads/writes, no per-element gather granularity, which is what lets the
fraction-of-HBM-peak metric actually engage on the scoring pass.

Values may be float32 or bfloat16; accumulation is ALWAYS float32 (the
mixed-precision invariant of ops/precision.py — this module is the
"segment-reduce" the ``bf16-accumulation`` tier-1 rule names).

Scope and fallback mirror ops/newton_kernel.py: Mosaic lowering is
TPU-only, so ``interpret_required()`` routes forced runs on other
backends through ``interpret=True``; unforced non-TPU backends take the
``.at[].add`` / ``segment_sum`` fallback, which doubles as the parity
oracle (tests/test_segment_reduce.py: duplicate slots, empty segments,
phantom-entity masks, out-of-bounds drop codes).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

LANES = 128
_OUT_TILE = 8 * LANES  # segments per output block
_IN_TILE = 8 * LANES  # (id, value) elements per streamed input block
# Over-visit bound: callers whose coverage argument needs more than this
# many input tiles per output tile take the XLA fallback instead of
# compiling a pathological grid.
_MAX_K_TILES = 64

# Program contract (audited by `python -m photon_tpu.analysis
# --semantic`): one segment-reduce shape is ONE program — ids, values
# and the prefetched starts are traced operands; only the static
# (elements, segments, k_tiles) shape mints a new executable. No host
# callbacks, no f64: this kernel runs inside the fused fit's sweep and
# inside score programs.
PROGRAM_AUDIT = dict(
    name="segment-reduce-kernel",
    entry="ops.segment_reduce.sorted_segment_sum",
    builder="build_segment_reduce",
    max_programs=1,
    recompiles_on=("reduce_shape",),
    hot_loop=True,
)

# Tier-5 numerics contract (`--numerics`, ANALYSIS.md): both engage
# modes are dtype-flow walked on bf16 values — the forced Pallas
# kernel (interpreted off-TPU) and the XLA segment_sum fallback. The
# kernel's windowed one-hot contraction replaces the scatter entirely
# (no nondeterministic family at all — the determinism story is
# by-construction); the fallback's scatter-add rides on the sorted-ids
# precondition. Budget: one storage rounding + up to 2 f32
# accumulation steps per element (the kernel re-reduces each streamed
# window tile once).
NUMERICS_AUDIT = dict(
    name="segment-reduce-numerics",
    entry="ops.segment_reduce.sorted_segment_sum",
    covers=("segment-reduce-kernel",),
    builder="build_segment_reduce_numerics",
    budgets={
        "segment_sum_*": "u16 + 2 * u32 * m",
    },
    deterministic={
        "segment_sum_fallback:scatter-add": (
            "ids are sorted by precondition "
            "(indices_are_sorted=True): each segment's colliding adds "
            "form one contiguous run that XLA combines in index order; "
            "the kernel path removes the scatter entirely"
        ),
    },
    tolerance=1.5,
)

# Trace-time site registry (host-side): every kernel instantiation
# records its static shape here so FusedFit._ledger_record /
# cli.profile can register a priced census row for the kernel without
# the dispatch path ever touching the ledger. Keyed by (site, shape) —
# one site (e.g. the bucket scorer) traces once PER BUCKET SHAPE, and
# ``traced_sites()`` aggregates the analytic cost per site so the
# census row prices every instance, not whichever traced last. The
# registry is process-global trace metadata (it lives as long as the
# traces do); tests clear it between cases via the conftest reset.
_TRACED_SITES: dict[tuple, dict] = {}


def interpret_required() -> bool:
    """True when pallas_call must run interpreted on this backend
    (same contract as ops/newton_kernel.interpret_required)."""
    return jax.default_backend() != "tpu"


def kernel_supported(
    num_values: int, num_segments: int, dtype, *, spmd: bool = False
) -> bool:
    """Whether the Pallas path serves this reduce shape on this backend.

    ``PHOTON_SEGMENT_KERNEL``: ``auto`` (default — real TPU only),
    ``force``/``on``/``1`` (every backend; non-TPU runs interpreted —
    slow, for parity tests), ``off``/``0`` (always the XLA fallback).
    ``spmd``: the operands span several devices (ops/placement.py) —
    GSPMD does not partition a Mosaic kernel, so mesh scoring and mesh
    solves keep the XLA scatter.
    """
    flag = os.environ.get("PHOTON_SEGMENT_KERNEL", "auto").lower()
    if flag in ("0", "off", "false"):  # photon: ignore[spmd-host-divergence] -- kernel-select flag is launch config, exported fleet-uniform; divergence trips the --spmd trace proof
        return False
    if spmd:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    if num_values < 1 or num_segments < 1:
        return False
    # int32 position/id arithmetic below: guard the flat sizes.
    if num_values >= 2**31 or num_segments >= 2**31:
        return False
    if flag in ("1", "on", "force"):  # photon: ignore[spmd-host-divergence] -- kernel-select flag is launch config, exported fleet-uniform; divergence trips the --spmd trace proof
        return True
    return jax.default_backend() == "tpu"


def _record_site(site: str, num_values: int, num_segments: int,
                 k_tiles: int, dtype) -> None:
    """Host bookkeeping at the wrapper level (runs per wrapper call on
    the eager path, per TRACE under an outer jit — never per kernel
    dispatch): the analytic cost of one instantiation, in the
    costmodel's counter vocabulary, for the ledger census."""
    esize = jnp.dtype(dtype).itemsize
    dt = str(jnp.dtype(dtype))
    _TRACED_SITES[(site, int(num_values), int(num_segments),
                   int(k_tiles), dt)] = {
        "num_values": int(num_values),
        "num_segments": int(num_segments),
        "k_tiles": int(k_tiles),
        "dtype": dt,
        # K streamed reads of (value + int32 id) per element + one f32
        # write per segment; FLOPs ~ the one-hot FMA per visited pair.
        "cost": {
            "flops": 2.0 * num_values * k_tiles,
            "hbm_bytes": float(
                num_values * k_tiles * (esize + 4) + num_segments * 4
            ),
            "transcendentals": 0.0,
        },
    }


def traced_sites() -> dict[str, dict]:
    """Per-SITE aggregate of every kernel instantiation traced so far
    (host bookkeeping for the cost ledger; see
    FusedFit._ledger_record): a site with several bucket shapes prices
    the SUM of its instances' analytic costs, not whichever traced
    last."""
    out: dict[str, dict] = {}
    for (site, *_rest), info in _TRACED_SITES.items():
        agg = out.get(site)
        if agg is None:
            agg = out[site] = {
                "instances": 0,
                "num_values": 0,
                "num_segments": 0,
                "cost": {"flops": 0.0, "hbm_bytes": 0.0,
                         "transcendentals": 0.0},
            }
        agg["instances"] += 1
        agg["num_values"] += info["num_values"]
        agg["num_segments"] += info["num_segments"]
        for key in ("flops", "hbm_bytes", "transcendentals"):
            agg["cost"][key] += info["cost"][key]
    return out


def _kernel(starts_ref, ids_ref, vals_ref, out_ref):
    del starts_ref  # consumed by the index maps (scalar prefetch)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    base = pl.program_id(0) * _OUT_TILE
    # ids and values both arrive LANE-major, [1, IT]: the one-hot is
    # built transposed, [OT, IT] (segment along sublanes, element along
    # lanes), so the ids broadcast down the sublanes for free and no
    # operand ever has a trailing dim of 1 (which pads to 128 lanes).
    onehot_t = (
        ids_ref[...]
        == base
        + jax.lax.broadcasted_iota(jnp.int32, (_OUT_TILE, _IN_TILE), 0)
    ).astype(jnp.float32)
    vals = vals_ref[...].astype(jnp.float32)  # [1, IT]
    # [1, IT] x [OT, IT]^T -> [1, OT]: contraction over the lane dim of
    # both (the MXU's native transposed-rhs form). HIGHEST: the default
    # f32 contraction may round operands to bf16, and the values are the
    # payload here.
    out_ref[...] += jax.lax.dot_general(
        vals, onehot_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "k_tiles", "interpret"),
)
def _windowed_sum(
    values: Array,  # [m] f32/bf16
    ids: Array,  # [m] int32; id >= num_segments drops
    starts: Array,  # [out_tiles] int32 first input tile per out tile
    *,
    num_segments: int,
    k_tiles: int,
    interpret: bool,
):
    """The pallas_call wrapper: pads to tile multiples, clamps the
    prefetched starts into range, dispatches the windowed grid, and
    slices the flat [num_segments] f32 result back out."""
    m = values.shape[0]
    out_tiles = -(-num_segments // _OUT_TILE)
    n_pad = out_tiles * _OUT_TILE
    m_tiles = max(-(-m // _IN_TILE), k_tiles)
    pad = m_tiles * _IN_TILE - m
    if m >= 2**31 or n_pad >= 2**31:
        # ids/starts are int32 (the kernel's lane dtype): past 2^31 the
        # flat positions would silently wrap — kernel_supported refuses
        # these shapes, and the direct entry must too.
        raise ValueError(
            f"segment_reduce shapes exceed int32 range: m={m}, "
            f"segments={n_pad}")
    # Padding ids sit beyond every window (n_pad > any window base + o);
    # caller-side drop markers (id == num_segments) land either beyond
    # the windows or in the sliced-away [num_segments, n_pad) range.
    ids_p = jnp.pad(ids, (0, pad), constant_values=n_pad)
    vals_p = jnp.pad(values, (0, pad))
    starts = jnp.clip(starts, 0, m_tiles - k_tiles).astype(jnp.int32)
    # Every array is [tiles, 1, TILE] and every block [None, 1, TILE]:
    # Mosaic requires a block's last two dims to be (8, 128)-divisible
    # or EQUAL to the array's, and (1, TILE) equals it by construction.
    # (A [tiles, TILE] array with (1, TILE) blocks is refused at
    # lowering.) The leading tile index is squeezed away in the kernel.
    in_tile = pl.BlockSpec(
        (None, 1, _IN_TILE), lambda j, k, s: (s[j] + k, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(out_tiles, k_tiles),
        in_specs=[in_tile, in_tile],
        out_specs=pl.BlockSpec(
            (None, 1, _OUT_TILE), lambda j, k, s: (j, 0, 0)
        ),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((out_tiles, 1, _OUT_TILE),
                                       jnp.float32),
        interpret=interpret,
        name="segment_reduce",
    )(
        starts,
        ids_p.reshape(m_tiles, 1, _IN_TILE),
        vals_p.reshape(m_tiles, 1, _IN_TILE),
    )
    return out.reshape(-1)[:num_segments]


def _k_for(per_window_elements: int) -> int:
    return -(-int(per_window_elements) // _IN_TILE) + 1


def sorted_segment_sum(
    values: Array,
    ids: Array,
    num_segments: int,
    *,
    multiplicity: int = 1,
    site: str = "segment_reduce",
    interpret: bool | None = None,
) -> Array:
    """Segment sum over SORTED int32 ids (f32 result).

    ``multiplicity`` is a STATIC bound on how many elements share one
    segment id — the coverage argument that sizes the visited-tile
    window (callers derive it from plan structure: 1 for the bucket
    scorer, the host-computed tail bound for score tables). ids equal
    to ``num_segments`` (or beyond) are dropped — the phantom-row /
    padding convention of the ``.at[].add`` paths this replaces.

    Falls back to ``segment_sum`` when the kernel is unsupported here.
    """
    n = int(num_segments)
    m = int(values.shape[0])
    k_tiles = _k_for(_OUT_TILE * max(int(multiplicity), 1))
    if (
        not kernel_supported(m, n, values.dtype)
        or k_tiles > _MAX_K_TILES
    ):
        return jax.ops.segment_sum(
            values.astype(jnp.float32),
            jnp.minimum(ids, n),
            num_segments=n + 1,
            indices_are_sorted=True,
        )[:n]
    bases = jnp.arange(-(-n // _OUT_TILE), dtype=jnp.int32) * _OUT_TILE
    starts = (
        jnp.searchsorted(ids, bases).astype(jnp.int32)
        // _IN_TILE
    )
    # Site bookkeeping lives HERE, not in the jitted wrapper: the site
    # label is census metadata, and making it a static argument would
    # mint one executable per label for identical reduce shapes —
    # contradicting the contract that shape is the only recompile key.
    _record_site(site, m, n, k_tiles, values.dtype)
    return _windowed_sum(
        values, ids.astype(jnp.int32), starts,
        num_segments=n, k_tiles=k_tiles,
        interpret=(
            interpret_required() if interpret is None else interpret
        ),
    )


def scatter_add_rows(
    z: Array,  # [n]
    row_ids: Array,  # [B, R] int32 canonical rows
    zb: Array,  # [B, R] per-slot scores (f32 or bf16)
    valid: Array,  # [B, R] bool — False lanes drop
    *,
    site: str = "segment_reduce/score",
) -> Array:
    """``z.at[row_ids].add(where(valid, zb, 0))`` as sort + tiled
    reduce — the bucket scorer's scatter (models/game.py:_bucket_
    score_add). Valid row ids are DISTINCT within one bucket (each kept
    row belongs to exactly one entity), so multiplicity is 1 and the
    sort is a cheap int32 radix whose cost XLA hoists out of the fused
    sweep loop (the ids are loop-invariant operands).
    """
    n = z.shape[0]
    ids = jnp.where(valid, row_ids, n).reshape(-1).astype(jnp.int32)
    vals = zb.reshape(-1)
    order = jnp.argsort(ids)
    out = sorted_segment_sum(
        jnp.take(vals, order),
        jnp.take(ids, order),
        n,
        multiplicity=1,
        site=site,
    )
    return z + out.astype(z.dtype)


# Pair-product transient cap for the gram route: ``ell_gram_blocks``
# materializes the [B, R, k, k] f32 pair products before the flat
# reduce; past this many elements the transient (plus the argsort over
# it) outweighs what skipping the dense [B, R, S] slab saves, and the
# plan-time host pass that bounds window coverage stops being free.
GRAM_ELEMENT_BUDGET = 1 << 26


def window_counts_np(ids: np.ndarray, num_segments: int) -> np.ndarray:
    """HOST: per-``_OUT_TILE``-window element counts for flat segment
    ids — plan-time numpy bookkeeping for the gram route. The planner
    (data/random_effect) accumulates these over entity chunks and feeds
    the max through ``window_bound_from_counts`` to get the bound
    ``ell_gram_supported`` consumes."""
    return np.bincount(
        ids // _OUT_TILE, minlength=-(-int(num_segments) // _OUT_TILE)
    )


def window_bound_from_counts(max_count) -> int:
    """Convert a max per-window element count to the ``multiplicity``
    currency of ``sorted_segment_sum`` (elements per window divided by
    ``_OUT_TILE``, ceil, floored at 1): the kernel visits
    ``_k_for(_OUT_TILE * bound)`` input tiles per window, which covers
    exactly when no window holds more than ``_OUT_TILE * bound``
    elements."""
    return max(-(-int(max_count) // _OUT_TILE), 1)


def ell_gram_supported(
    b: int, r: int, k: int, sub_dim: int, *,
    grad_mult: int, hess_mult: int, spmd: bool = False,
) -> bool:
    """Whether the gram-route reduces (``ell_gram_blocks`` +
    ``ell_segment_slots``) serve this ELL block shape on this backend.

    ``grad_mult`` / ``hess_mult`` are HOST-computed WINDOW bounds
    (data/random_effect.py ``block_gram_mults``): the max nonzero
    elements landing in one ``_OUT_TILE``-segment output window,
    divided (ceil) by ``_OUT_TILE`` — the same coverage currency
    ``sorted_segment_sum`` sizes its visited-tile window with. A
    uniform per-segment bound would be useless here: the intercept
    slot co-occurs with every row, so per-SEGMENT multiplicity is the
    row count, while whole windows stay cheap.
    """
    s = int(sub_dim)
    m_pair = b * r * k * k
    if m_pair > GRAM_ELEMENT_BUDGET:
        return False
    if _k_for(_OUT_TILE * max(int(grad_mult), 1)) > _MAX_K_TILES:
        return False
    if _k_for(_OUT_TILE * max(int(hess_mult), 1)) > _MAX_K_TILES:
        return False
    # Products are formed f32 regardless of the storage dtype.
    return (
        kernel_supported(m_pair, b * s * s, jnp.float32, spmd=spmd)
        and kernel_supported(b * r * k, b * s, jnp.float32, spmd=spmd)
    )


def ell_segment_slots(
    x_indices: Array,  # [B, R, k] int32 subspace slots
    x_values: Array,  # [B, R, k] (f32 or bf16 storage)
    row_weights: Array,  # [B, R] per-row scale (e.g. weighted targets)
    sub_dim: int,
    *,
    multiplicity: int,
    site: str = "segment_reduce/gram",
) -> Array | None:
    """Per-entity weighted slot totals straight from the ELL layout:
    ``out[b, s] = sum_{r, j: idx[b,r,j] == s} row_weights[b,r] * v[b,r,j]``
    as ONE flat sorted tiled reduce — the ``X^T (w*y)`` half of the
    normal equations with no [B, R, S] densified slab in between.

    Products are formed in f32 (the ELL payload is read once at storage
    width, then upcast), and ZERO products are remapped to the drop
    segment: the host-computed window bound counts only nonzero entries,
    so padding lanes must not land in real segments. ``multiplicity`` is
    the window bound described at ``ell_gram_supported``. Returns None
    when the kernel does not serve this shape.
    """
    b, r, k = x_indices.shape
    s = int(sub_dim)
    n = b * s
    m = b * r * k
    if (
        not kernel_supported(m, n, jnp.float32)
        or _k_for(_OUT_TILE * max(int(multiplicity), 1)) > _MAX_K_TILES
    ):
        return None
    vals = (
        x_values.astype(jnp.float32)
        * row_weights.astype(jnp.float32)[:, :, None]
    ).reshape(-1)
    ent = jnp.arange(b, dtype=jnp.int32)[:, None, None] * s
    ids = (x_indices.astype(jnp.int32) + ent).reshape(-1)
    ids = jnp.where(vals != 0.0, ids, n)
    order = jnp.argsort(ids)
    flat = sorted_segment_sum(
        jnp.take(vals, order), jnp.take(ids, order), n,
        multiplicity=multiplicity, site=site,
    )
    return flat.reshape(b, s)


def ell_gram_blocks(
    x_indices: Array,  # [B, R, k] int32 subspace slots
    x_values: Array,  # [B, R, k] (f32 or bf16 storage)
    weights: Array,  # [B, R] row weights (curvature)
    sub_dim: int,
    *,
    multiplicity: int,
    site: str = "segment_reduce/gram",
) -> Array | None:
    """Per-entity weighted gram matrices ``X^T diag(w) X`` straight from
    the ELL layout, [B, S, S] f32: every pair product
    ``w[b,r] * v[b,r,j] * v[b,r,l]`` lands in flat segment
    ``b*S^2 + idx[b,r,j]*S + idx[b,r,l]`` and ONE sorted tiled reduce
    aggregates the whole bucket's Hessians — the dense [B, R, S] slab
    the direct solver previously needed never exists.

    Same f32-product / zero-drop / window-bound conventions as
    ``ell_segment_slots`` (the bound here is ``hess_mult``). Returns
    None when the kernel does not serve this shape.
    """
    b, r, k = x_indices.shape
    s = int(sub_dim)
    n = b * s * s
    m = b * r * k * k
    if (
        m > GRAM_ELEMENT_BUDGET
        or not kernel_supported(m, n, jnp.float32)
        or _k_for(_OUT_TILE * max(int(multiplicity), 1)) > _MAX_K_TILES
    ):
        return None
    xf = x_values.astype(jnp.float32)
    vals = (
        weights.astype(jnp.float32)[:, :, None, None]
        * xf[:, :, :, None]
        * xf[:, :, None, :]
    ).reshape(-1)
    idx = x_indices.astype(jnp.int32)
    ent = (
        jnp.arange(b, dtype=jnp.int32)[:, None, None, None] * (s * s)
    )
    ids = (ent + idx[:, :, :, None] * s + idx[:, :, None, :]).reshape(-1)
    ids = jnp.where(vals != 0.0, ids, n)
    order = jnp.argsort(ids)
    flat = sorted_segment_sum(
        jnp.take(vals, order), jnp.take(ids, order), n,
        multiplicity=multiplicity, site=site,
    )
    return flat.reshape(b, s, s)


def densify_ell_blocks(
    x_indices: Array,  # [B, R, k] int32 subspace slots (dups sum)
    x_values: Array,  # [B, R, k]
    sub_dim: int,
    *,
    site: str = "segment_reduce/densify",
    spmd: bool = False,
) -> Array | None:
    """[B, R, k] slot-ELL -> [B, R, S] dense via ONE flat tiled reduce
    (the wide-subspace ``.at[rows, slots].add`` scatter of
    algorithm/random_effect.py, batched over the whole bucket instead
    of per entity under vmap). Returns None when the kernel does not
    serve this shape — the caller keeps the ELL layout.

    Coverage here uses blockedness, not sortedness: flat ids are
    ``row * S + slot`` with rows ascending in flatten order, so the
    elements touching output window j span at most ``_OUT_TILE/S + 2``
    rows — a static position range the ``starts`` vector encodes.
    """
    b, r, k = x_indices.shape
    s = int(sub_dim)
    rows = b * r
    n = rows * s
    m = rows * k
    rows_per_window = _OUT_TILE // s + 3
    k_tiles = _k_for(rows_per_window * k)
    if (
        s > _OUT_TILE
        or k_tiles > _MAX_K_TILES
        or not kernel_supported(m, n, x_values.dtype, spmd=spmd)
    ):
        return None
    row_base = (
        jnp.arange(rows, dtype=jnp.int32)[:, None] * s
    )  # [BR, 1]
    ids = (
        x_indices.reshape(rows, k).astype(jnp.int32) + row_base
    ).reshape(-1)
    out_tiles = -(-n // _OUT_TILE)
    # Exact row containing each window's base id (j*_OUT_TILE)//s — an
    # approximation here would drift by j*(_OUT_TILE % s)/s rows and
    # outrun the k_tiles coverage window at large j.
    first_row = (
        jnp.arange(out_tiles, dtype=jnp.int32) * _OUT_TILE
    ) // s
    starts = (first_row * k) // _IN_TILE
    _record_site(site, m, n, k_tiles, x_values.dtype)
    flat = _windowed_sum(
        x_values.reshape(-1), ids, starts,
        num_segments=n,
        k_tiles=k_tiles,
        interpret=interpret_required(),
    )
    return flat.reshape(b, r, s).astype(x_values.dtype)
