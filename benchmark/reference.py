"""The plain reference: GLMix by block coordinate descent, written out.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``;
no kernels, no bucket ladder, no fused program. It imports nothing of the
program and takes nothing the program has made: its inputs are the
generated host arrays and the configuration's numbers.

The model is ``z = x_g . w_g + x_u . w_u[user] + x_m . w_m[movie]``. One
sweep refits each coordinate in turn against the others' scores as an
offset; each refit minimises ``sum_rows loss(z, y) + 0.5 * l2 * |w|^2``
(intercept, the last column, unpenalised) exactly: by damped Newton to the
float32 floor, for the global coordinate over all rows and for a random
effect over each entity's own rows. Each such block has one minimiser, so
the program's solvers (L-BFGS, batched Newton/IRLS, closed form), which stop
at a tolerance, are held to it. A step is halved until the gradient's norm
falls (``_newton_step``), so where the steps end does not depend on how
the rows are packed.

One kind of block has no minimiser: a logistic entity whose training rows
all carry one label. Its unpenalised intercept runs off to that label's
side and its penalised coefficients to 0. ``one_label_side`` finds these
entities by that rule on the data; they are in no batch, their rows are
scored at a margin of +-SATURATED while the other coordinates are fitted,
and ``fit`` states the point their infimum is approached along: a table row
of zeros with an intercept of +-inf (``predict`` gives their rows a margin
of +-inf). ``check.compare`` holds the program's intercepts there to a
number of their own.

``storage`` narrows what the program's mixed-precision policy narrows
(features and the per-coordinate score vectors are rounded to that dtype
where they are stored; sums stay float32): it is the low-precision control
of the comparison, never the reference.

Per-entity problems are packed by size, a packing of the reference's
own: the entities of a random effect are sorted by the number of rows they
train on and cut into groups whose widest member has at most twice the
rows of its narrowest. A group is one batch ``[entities, width, d]`` of
the Newton step, so all groups together hold under twice the rows that
train, whatever the law of rows per entity (one dense slab of a
heavy-tailed coordinate is entities x the LARGEST entity).

The active-data cap: where a coordinate states ``active_data_upper_bound``
and an entity has more rows, that entity is trained on ``upper`` of them
and every row is scored. The rows kept are the ``upper`` with the smallest
keys, a row's key being the splitmix64 finaliser of (the row's uid, which
is its row number in generated data, xor the seed of the coordinate's id
tag: CRC-32 of the tag's name under the word 0x9E3779B9): ``_cap_keys``,
written out here from the rule's description and not imported from the
program. Where no entity exceeds the cap nothing is hashed and every row
trains.
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

NEWTON_MAX_STEPS = 25
NEWTON_HALVINGS = 8
# Stop when no coefficient of any problem moved by more than this: ten
# float32 roundings of a coefficient of order 1, a hundredth of the gap
# the program's own stopping rule leaves.
NEWTON_STEP_FLOOR = 1e-6
# The margin at which the rows of an entity without a minimiser are scored
# while the other coordinates are fitted. In float32 sigmoid(30 - 5) is 1:
# such a row drops out of their gradients, as it does in the limit.
SATURATED = 30.0


def _loss(task: str):
    """(d1, d2): the loss's first and second derivative in the margin."""
    if task == "LOGISTIC_REGRESSION":
        def d1(z, y):
            return jax.nn.sigmoid(z) - y

        def d2(z, y):
            s = jax.nn.sigmoid(z)
            return s * (1.0 - s)
    elif task == "LINEAR_REGRESSION":
        def d1(z, y):
            return z - y

        def d2(z, y):
            return jnp.ones_like(z)
    else:
        raise ValueError(f"the reference has no loss for task {task!r}")
    return d1, d2


def _stored(x, storage):
    """Round to the storage dtype and come back to float32."""
    if storage is None:
        return x
    return x.astype(storage).astype(jnp.float32)


def _solve_spd(h, g):
    """x with h x = g for a batch of small symmetric positive definite
    systems, h [d, d, B] and g [d, B] with the batch last: Gauss-Jordan
    elimination, no pivoting (the Hessians are positive definite)."""
    d = g.shape[0]
    a = jnp.concatenate([h, g[:, None, :]], axis=1)  # [d, d + 1, B]

    def eliminate(k, a):
        pivot = a[k] / a[k, k][None, :]  # [d + 1, B]
        a = a - a[:, k][:, None, :] * pivot[None, :, :]
        return a.at[k].set(pivot)

    return jax.lax.fori_loop(0, d, eliminate, a)[:, d]


@functools.partial(jax.jit, static_argnames=("task",))
def _newton_step(x, mask, y, off, w, l2_diag, *, task):
    """One damped Newton step of every problem in a batch.

    x [B, R, d] rows of each problem (zero where ``mask`` is 0), y/off/mask
    [B, R], w [B, d], l2_diag [d]. Returns the new w and the largest move.

    The step is halved until the GRADIENT's norm falls, which along a
    Newton direction of a convex block it does for a short enough step.
    (The objective is no test of a step: a float32 sum over a block's rows
    stops showing a fall some 5e-4 from the minimiser, and where that is
    depends on how the rows are packed.) A problem whose whole step is
    under the floor has arrived and is left where it is.
    """
    d1, d2 = _loss(task)

    def gradient(wv):
        z = jnp.einsum("brd,bd->br", x, wv) + off
        return z, jnp.einsum("brd,br->bd", x, mask * d1(z, y)) + l2_diag * wv

    z, g = gradient(w)
    h = jnp.einsum("brd,br,bre->deb", x, mask * d2(z, y), x)
    h = h + jnp.diag(l2_diag)[:, :, None]
    step = -_solve_spd(h, g.T).T
    g0 = jnp.sum(g * g, axis=1)
    arrived = jnp.max(jnp.abs(step), axis=1) < NEWTON_STEP_FLOOR * (
        jnp.maximum(1.0, jnp.max(jnp.abs(w), axis=1)))

    def halve(state):
        k, best_w, taken = state
        trial = w + (0.5 ** k) * step
        g_trial = gradient(trial)[1]
        # First trial that lowers the gradient's norm wins, per problem.
        take = (jnp.sum(g_trial * g_trial, axis=1) < g0) & ~taken
        return k + 1.0, jnp.where(take[:, None], trial, best_w), taken | take

    _, best_w, _ = jax.lax.while_loop(
        lambda state: (state[0] < NEWTON_HALVINGS) & ~jnp.all(state[2]),
        halve, (jnp.float32(0.0), w, arrived))
    return best_w, jnp.max(jnp.abs(best_w - w))


def _solve(x, mask, y, off, w, l2_diag, task, steps_taken=None):
    for step in range(NEWTON_MAX_STEPS):
        w, moved = _newton_step(x, mask, y, off, w, l2_diag, task=task)
        if float(moved) < NEWTON_STEP_FLOOR * max(
                1.0, float(jnp.max(jnp.abs(w)))):
            break
    if steps_taken is not None:
        steps_taken.append(step + 1)
    return w


def _tag_seed(id_tag: str) -> int:
    """64-bit seed of an id tag: CRC-32 of its name in the low half, the
    golden-ratio word 0x9E3779B9 in the high half."""
    return (0x9E3779B9 << 32) | zlib.crc32(id_tag.encode())


def _cap_keys(uids: np.ndarray, id_tag: str) -> np.ndarray:
    """[n] uint64 reservoir keys: splitmix64's finaliser of uid ^ seed."""
    z = uids.astype(np.uint64) ^ np.uint64(_tag_seed(id_tag))
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def kept_rows(ids: np.ndarray, entities: int, upper, id_tag: str):
    """(order, starts, kept): ``order`` lists the rows entity by entity,
    ``starts[e]`` is where entity e begins in it and its first ``kept[e]``
    rows there are the ones it trains on. Without a cap that binds, all of
    its rows in row order; with one, the ``upper`` of smallest key."""
    counts = np.bincount(ids, minlength=entities)
    if upper is not None and counts.max(initial=0) > upper:
        uids = np.arange(ids.shape[0], dtype=np.uint64)
        order = np.lexsort((_cap_keys(uids, id_tag), ids))
        kept = np.minimum(counts, int(upper))
    else:
        order = np.argsort(ids, kind="stable")
        kept = counts
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    return order, starts, kept


def one_label_side(labels, ids, order, starts, kept) -> np.ndarray:
    """[entities] +1 for an entity whose kept rows all carry the label 1,
    -1 where all carry 0, else 0 (also for an entity that keeps no row)."""
    owner = ids[order]
    trains = np.arange(order.shape[0]) - starts[owner] < kept[owner]
    ones = np.bincount(owner[trains], weights=labels[order][trains],
                       minlength=kept.shape[0])
    side = (ones == kept).astype(np.int8) - (ones == 0).astype(np.int8)
    return np.where(kept > 0, side, 0)


def size_groups(kept: np.ndarray) -> list[np.ndarray]:
    """Entity numbers in groups of like size: sorted by ``kept``, a group
    closed before the first entity with over twice its narrowest member's
    rows. An entity that trains on no row is in no group."""
    by_size = np.argsort(kept, kind="stable")
    sizes = kept[by_size]
    lo = int(np.searchsorted(sizes, 1))
    groups = []
    while lo < sizes.shape[0]:
        hi = int(np.searchsorted(sizes, 2 * int(sizes[lo]), side="right"))
        groups.append(by_size[lo:hi])
        lo = hi
    return groups


def _group_index(members, order, starts, kept, n: int) -> np.ndarray:
    """[members, widest] row numbers of each member's kept rows, padded
    with ``n`` (one past the last row)."""
    slot = np.arange(int(kept[members].max()))[None, :]
    at = np.minimum(starts[members][:, None] + slot, n - 1)
    return np.where(slot < kept[members][:, None], order[at], n).astype(
        np.int32)


@jax.jit
def _row_scores(x, ids, w):
    return jnp.einsum("nd,nd->n", x, w[ids])


class Coordinate:
    """One coordinate's rows on the device, and how they fall into
    batches of problems: one batch of one problem over all rows for the
    fixed effect, one batch per size group for a random effect."""

    def __init__(self, spec: dict, x: np.ndarray, ids, storage,
                 labels: np.ndarray, task: str):
        self.n, d = x.shape
        self.name = spec["name"]
        self.random = spec["kind"] != "fixed"
        penalised = np.ones(d, np.float32)
        penalised[-1] = 0.0
        self.l2_diag = jnp.asarray(np.float32(spec["l2"]) * penalised)
        self.x = _stored(jnp.asarray(x), storage)
        if not self.random:
            self.w = jnp.zeros((1, d), jnp.float32)
            return
        entities = int(spec["entities"])
        order, starts, kept = kept_rows(
            ids, entities, spec.get("active_data_upper_bound"), spec["id"])
        self.side = np.zeros(entities, np.int8)
        if task == "LOGISTIC_REGRESSION":
            self.side = one_label_side(labels, ids, order, starts, kept)
        self.groups = []
        for members in size_groups(np.where(self.side == 0, kept, 0)):
            index = jnp.asarray(
                _group_index(members, order, starts, kept, self.n))
            self.groups.append((jnp.asarray(members.astype(np.int32)),
                                index, self.gather(self.x, index)))
        self.ids = jnp.asarray(ids.astype(np.int32))
        w = np.zeros((entities, d), np.float32)
        w[:, -1] = SATURATED * self.side
        self.w = jnp.asarray(w)

    def gather(self, rows_vector, index):
        """[n, ...] -> [B, R, ...] in a group's packing, 0 where it pads."""
        return jnp.take(rows_vector, index, axis=0, mode="fill",
                        fill_value=0)

    def solve(self, y, off, task, steps_taken=None) -> None:
        """Refit every problem of the coordinate against ``off``, batch
        by batch."""
        if not self.random:
            self.w = _solve(self.x[None], jnp.ones((1, self.n), jnp.float32),
                            y[None], off[None], self.w, self.l2_diag, task,
                            steps_taken)
            return
        for members, index, x in self.groups:
            solved = _solve(
                x, (index < self.n).astype(jnp.float32),
                self.gather(y, index), self.gather(off, index),
                self.w[members], self.l2_diag, task, steps_taken)
            self.w = self.w.at[members].set(solved)

    def scores(self):
        """[n] this coordinate's part of z, of EVERY row: the rows an
        entity did not train on are scored by its coefficients too."""
        if not self.random:
            return self.x @ self.w[0]
        return _row_scores(self.x, self.ids, self.w)

    def table(self) -> np.ndarray:
        """The coefficients; an entity without a minimiser gets the point
        its infimum is approached along: zeros and an intercept of +-inf."""
        w = np.array(self.w, np.float32)
        if not self.random:
            return w[0]
        w[self.side != 0, -1] = np.inf * self.side[self.side != 0]
        return w


def fit(config: dict, data, storage=None, matmul_precision="highest",
        steps_taken=None) -> dict:
    """coordinate name -> coefficient table of the reference fit.

    ``storage`` and ``matmul_precision`` are for the controls only: the
    reference is float32 at ``highest``. ``steps_taken`` collects the
    Newton steps of each batch solved."""
    task = config["task"]
    with jax.default_matmul_precision(matmul_precision):
        labels = np.asarray(data.labels, np.float32)
        coords = [
            Coordinate(
                c, data.features[c["shard"]],
                None if c["kind"] == "fixed" else data.ids[c["id"]],
                storage, labels, task)
            for c in config["coordinates"]
        ]
        y = jnp.asarray(labels)
        part = {c.name: jnp.zeros(y.shape[0], jnp.float32) for c in coords}
        for _ in range(int(config["num_iterations"])):
            for c in coords:
                others = sum(v for k, v in part.items() if k != c.name)
                c.solve(y, others, task, steps_taken)
                part[c.name] = _stored(c.scores(), storage)
        return {c.name: c.table() for c in coords}


def predict(config: dict, data, tables: dict, block: int = 1_000_000):
    """[n] float32 margins z of a model given as tables, in row blocks.
    A row of an entity whose intercept is +-inf has that margin."""
    n = data.labels.shape[0]
    out = np.empty(n, np.float32)
    random = [c for c in config["coordinates"] if c["kind"] != "fixed"]
    sides = {c["name"]: np.where(
        np.isinf(tables[c["name"]][:, -1]),
        np.sign(tables[c["name"]][:, -1]), 0.0) for c in random}
    finite = {name: jnp.asarray(np.where(np.isinf(w), 0.0, w), jnp.float32)
              for name, w in tables.items()}
    with jax.default_matmul_precision("highest"):
        for lo in range(0, n, block):
            hi = min(n, lo + block)
            z = jnp.zeros(hi - lo, jnp.float32)
            for c in config["coordinates"]:
                x = jnp.asarray(data.features[c["shard"]][lo:hi])
                w = finite[c["name"]]
                if c["kind"] != "fixed":
                    w = w[jnp.asarray(data.ids[c["id"]][lo:hi])]
                    z = z + jnp.einsum("nd,nd->n", x, w)
                else:
                    z = z + x @ w
            out[lo:hi] = np.asarray(z)
            for c in random:
                side = sides[c["name"]][data.ids[c["id"]][lo:hi]]
                out[lo:hi][side != 0] = np.inf * side[side != 0]
    return out
