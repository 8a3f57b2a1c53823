"""Ragged <-> padded moves of contiguous per-entity segments, gather-free.

A bucket slab is ``[B, cap]``; entity ``b`` owns ``count[b] <= cap`` rows.
When the rows stand in the slab's own entity order (the fused fit's
"home" order, algorithm/fused_fit.py), entity ``b``'s rows are the
contiguous segment ``seg[start[b] : start[b] + count[b]]`` with
``start = cumsum(count) - count``, and the two moves a coordinate-descent
iteration needs

- ragged -> padded: ``slab[b, r] = seg[start[b] + r]`` for ``r < count[b]``
- padded -> ragged: ``seg[start[b] + r] = slab[b, r]`` for ``r < count[b]``

are pure data movement with a shift ``d[b] = b * cap - start[b]`` that is
non-negative and never decreases along the bucket (``cap - count[b] >= 0``
is its increment). An element gather of the same move costs about 7 ns an
index on a TPU v5e whatever the index points at (PERF.md section 6, PR 33);
this module moves the elements with a LOG-STEP SHIFT NETWORK instead:

For bit ``t`` from the highest down, every element whose ``d`` has bit
``t`` set moves right by ``2**t`` slots. After the bits ``>= t`` an element
that started at ragged position ``i`` stands at ``i + (d >> t << t)``, which
is strictly increasing in ``i`` because ``d`` is non-decreasing: no two
elements ever meet, at any step. Each step is one ``where(bit, rolled,
kept)``, so the whole move is ``steps`` streaming passes and no gather. The
padded -> ragged move is the same steps backwards.

Which slot receives at which step does not depend on the payload: it is
one int32 array per bucket and direction (``shift_bits``; bit ``t`` of slot
``j``: "at step ``t`` slot ``j`` takes the value of slot ``j - 2**t``", or
of slot ``j + 2**t`` on the way back), made once by running the network
backwards on ``d`` itself, and every later move of any payload reads it.
Slots that hold no element carry stale values through the network and
never overwrite an element; callers mask them (``r < count[b]``) exactly
as they mask a gather's padding slots.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Array = jax.Array


def shift_steps(entities: int, cap: int) -> int:
    """Static number of network steps for a ``[entities, cap]`` slab: the
    bit length of the largest shift any counts of that shape can give
    (``d[b] <= b * cap``, reached when no entity before ``b`` owns a row)."""
    return ((entities - 1) * cap).bit_length()


# Jitted, so that a caller's trace inlines ONE cached jaxpr per shape, and
# rolled (a ``fori_loop`` over the steps, each a dynamic ``roll`` and a
# ``where``), so that the program text does not grow with the steps: a
# retrain job traces, lowers and loads the fused programs anew every job,
# and 24 unrolled steps a move cost its host 0.5 s. On the chip the rolled
# form is also the faster one at the benchmark's widest slab (2.4 ms against
# 4.5-5.8 ms unrolled over static slices, 20 000 x 512; 1.4 against 1.0 ms at
# 72 380 x 64; PERF.md section 6, PR 33). A roll wraps where a shift would
# pad; no receiving slot ever reads the wrapped part.
@functools.partial(jax.jit, static_argnames=("cap",))
def shift_bits(counts: Array, cap: int) -> tuple[Array, Array]:
    """The ``[B * cap]`` int32 receive bits of the bucket whose entity
    ``b`` owns ``counts[b]`` rows, for ``ragged_to_padded`` and for
    ``padded_to_ragged`` (traced; runs the network once, backwards, on the
    shifts themselves)."""
    b = counts.shape[0]
    slots = b * cap
    counts = counts.astype(jnp.int32)
    start = jnp.cumsum(counts) - counts
    d = jnp.arange(b, dtype=jnp.int32) * cap - start  # [B], non-decreasing
    r = jnp.arange(cap, dtype=jnp.int32)
    valid = (r[None, :] < counts[:, None]).reshape(-1)
    d = jnp.broadcast_to(d[:, None], (b, cap)).reshape(-1)
    slot = jnp.arange(slots, dtype=jnp.int32)
    none = jnp.zeros(slots, jnp.int32)

    # Padded -> ragged, lowest bit first: the configuration before the
    # backward step t is the one after the forward step t, so the elements
    # that move now sit on the forward step's receiving slots, and where
    # they arrive are the backward step's.
    def step(t, carry):
        valid, d, into_slab, out_of_slab = carry
        sh = jnp.left_shift(1, t)
        moving = valid & (d & sh != 0)
        arrives = jnp.roll(moving, -sh) & (slot < slots - sh)
        into_slab = into_slab | jnp.where(moving, sh, 0)
        out_of_slab = out_of_slab | jnp.where(arrives, sh, 0)
        d = jnp.where(arrives, jnp.roll(d, -sh), d)
        return arrives | (valid & ~moving), d, into_slab, out_of_slab

    _, _, into_slab, out_of_slab = jax.lax.fori_loop(
        0, shift_steps(b, cap), step, (valid, d, none, none))
    return into_slab, out_of_slab


def _move(x: Array, bits: Array, steps: int, into: bool) -> Array:
    """The network's steps over ``x``: highest bit first and rightwards
    into the slab, lowest first and leftwards out of it."""
    def step(i, x):
        sh = jnp.left_shift(1, steps - 1 - i if into else i)
        on = bits & sh != 0
        on = on.reshape(on.shape + (1,) * (x.ndim - 1))
        return jnp.where(on, jnp.roll(x, sh if into else -sh, axis=0), x)

    return jax.lax.fori_loop(0, steps, step, x)


@functools.partial(jax.jit, static_argnames=("steps",))
def ragged_to_padded(seg: Array, into_slab: Array, steps: int) -> Array:
    """Move a ragged segment into slab layout.

    ``seg``: ``[B * cap, ...]`` whose leading slots hold the bucket's rows
    in entity order (what stands behind them is ignored). Returns the
    ``[B * cap, ...]`` flat slab: slot ``b * cap + r`` holds the entity's
    row ``r`` for ``r < count[b]``; other slots hold stale values."""
    return _move(seg, into_slab, steps, into=True)


@functools.partial(jax.jit, static_argnames=("steps",))
def padded_to_ragged(flat: Array, out_of_slab: Array, steps: int) -> Array:
    """The inverse move: ``[B * cap, ...]`` flat slab -> the bucket's rows
    in entity order in the leading slots (stale values behind them)."""
    return _move(flat, out_of_slab, steps, into=False)
