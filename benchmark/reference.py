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
at a tolerance, are held to it.

``storage`` narrows what the program's mixed-precision policy narrows
(features and the per-coordinate score vectors are rounded to that dtype
where they are stored; sums stay float32): it is the low-precision control
of the comparison, never the reference.

Per-entity problems are packed into one ``[entities, max rows, d]`` slab
by a stable sort on the entity id: a packing of the reference's own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEWTON_MAX_STEPS = 25
NEWTON_HALVINGS = 8
# Stop when no coefficient of any problem moved by more than this: ten
# float32 roundings of a coefficient of order 1, a hundredth of the gap
# the program's own stopping rule leaves.
NEWTON_STEP_FLOOR = 1e-6


def _loss(task: str):
    if task == "LOGISTIC_REGRESSION":
        def value(z, y):
            return jax.nn.softplus(z) - y * z

        def d1(z, y):
            return jax.nn.sigmoid(z) - y

        def d2(z, y):
            s = jax.nn.sigmoid(z)
            return s * (1.0 - s)
    elif task == "LINEAR_REGRESSION":
        def value(z, y):
            return 0.5 * (z - y) ** 2

        def d1(z, y):
            return z - y

        def d2(z, y):
            return jnp.ones_like(z)
    else:
        raise ValueError(f"the reference has no loss for task {task!r}")
    return value, d1, d2


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
    """
    value, d1, d2 = _loss(task)

    def objective(wv):
        z = jnp.einsum("brd,bd->br", x, wv) + off
        return (jnp.sum(mask * value(z, y), axis=1)
                + 0.5 * jnp.sum(l2_diag * wv * wv, axis=1))

    z = jnp.einsum("brd,bd->br", x, w) + off
    g = jnp.einsum("brd,br->bd", x, mask * d1(z, y)) + l2_diag * w
    h = jnp.einsum("brd,br,bre->deb", x, mask * d2(z, y), x)
    h = h + jnp.diag(l2_diag)[:, :, None]
    step = -_solve_spd(h, g.T).T
    f0 = objective(w)
    best_w, best_f = w, f0
    for k in range(NEWTON_HALVINGS):
        trial = w + (0.5 ** k) * step
        f = objective(trial)
        # First trial that lowers the objective wins, per problem.
        take = (f < f0) & (best_f >= f0)
        best_w = jnp.where(take[:, None], trial, best_w)
        best_f = jnp.where(take, f, best_f)
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


def _pack_by_entity(ids: np.ndarray, entities: int):
    """[entities, max rows] row numbers of each entity's rows, padded with
    ``n`` (one past the last row), from a stable sort on the id."""
    n = ids.shape[0]
    counts = np.bincount(ids, minlength=entities)
    order = np.argsort(ids, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    width = int(counts.max())
    slot = np.arange(n) - np.repeat(starts, counts)
    index = np.full((entities, width), n, np.int32)
    index[ids[order], slot] = order
    return index


class Coordinate:
    """One coordinate's rows as a batch of problems on the device."""

    def __init__(self, spec: dict, x: np.ndarray, ids, storage):
        n, d = x.shape
        self.name = spec["name"]
        self.random = spec["kind"] != "fixed"
        penalised = np.ones(d, np.float32)
        penalised[-1] = 0.0
        self.l2_diag = jnp.asarray(np.float32(spec["l2"]) * penalised)
        xs = _stored(jnp.asarray(x), storage)
        if self.random:
            entities = int(spec["entities"])
            self.index = jnp.asarray(_pack_by_entity(ids, entities))
            padded = jnp.concatenate([xs, jnp.zeros((1, d), xs.dtype)])
            self.x = jnp.take(padded, self.index, axis=0)
            self.mask = (self.index < n).astype(jnp.float32)
            self.w = jnp.zeros((entities, d), jnp.float32)
        else:
            self.index = None
            self.x = xs[None]
            self.mask = jnp.ones((1, n), jnp.float32)
            self.w = jnp.zeros((1, d), jnp.float32)
        self.n = n

    def gather(self, rows_vector):
        """[n] -> [B, R] in this coordinate's packing."""
        if not self.random:
            return rows_vector[None]
        padded = jnp.concatenate([rows_vector, jnp.zeros(1, jnp.float32)])
        return jnp.take(padded, self.index, axis=0)

    def scores(self):
        """[n] this coordinate's part of z."""
        z = jnp.einsum("brd,bd->br", self.x, self.w)
        if not self.random:
            return z[0]
        out = jnp.zeros(self.n + 1, jnp.float32)
        return out.at[self.index.reshape(-1)].set(z.reshape(-1))[: self.n]

    def table(self) -> np.ndarray:
        w = np.asarray(self.w, np.float32)
        return w if self.random else w[0]


def fit(config: dict, data, storage=None, matmul_precision="highest",
        steps_taken=None) -> dict:
    """coordinate name -> coefficient table of the reference fit.

    ``storage`` and ``matmul_precision`` are for the controls only: the
    reference is float32 at ``highest``. ``steps_taken`` collects the
    Newton steps of each solve."""
    task = config["task"]
    with jax.default_matmul_precision(matmul_precision):
        coords = [
            Coordinate(
                c, data.features[c["shard"]],
                None if c["kind"] == "fixed" else data.ids[c["id"]],
                storage)
            for c in config["coordinates"]
        ]
        y = jnp.asarray(np.asarray(data.labels, np.float32))
        part = {c.name: jnp.zeros(y.shape[0], jnp.float32) for c in coords}
        for _ in range(int(config["num_iterations"])):
            for c in coords:
                others = sum(v for k, v in part.items() if k != c.name)
                c.w = _solve(c.x, c.mask, c.gather(y), c.gather(others),
                             c.w, c.l2_diag, task, steps_taken)
                part[c.name] = _stored(c.scores(), storage)
        return {c.name: c.table() for c in coords}


def predict(config: dict, data, tables: dict, block: int = 1_000_000):
    """[n] float32 margins z of a model given as tables, in row blocks."""
    n = data.labels.shape[0]
    out = np.empty(n, np.float32)
    with jax.default_matmul_precision("highest"):
        for lo in range(0, n, block):
            hi = min(n, lo + block)
            z = jnp.zeros(hi - lo, jnp.float32)
            for c in config["coordinates"]:
                x = jnp.asarray(data.features[c["shard"]][lo:hi])
                w = jnp.asarray(tables[c["name"]], jnp.float32)
                if c["kind"] != "fixed":
                    w = w[jnp.asarray(data.ids[c["id"]][lo:hi])]
                    z = z + jnp.einsum("nd,nd->n", x, w)
                else:
                    z = z + x @ w
            out[lo:hi] = np.asarray(z)
    return out

