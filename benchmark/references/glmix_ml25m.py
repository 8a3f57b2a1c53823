"""The plain reference of ``glmix_ml25m``: ``benchmark/reference.py``'s GLMix
by block coordinate descent, with the rows held over ALL of the cell's
devices so that 25 M rows fit.

The semantics are ``benchmark/reference.py``'s, function for function:
the same model, the same sweep, the same damped Newton step to the float32
floor (its ``_solve`` / ``_newton_step`` are called, not copied), the same
reservoir rule (``kept_rows`` / ``_cap_keys``), the same one-label rule
(``one_label_side``), the same packing by size (``size_groups`` /
``_group_index``), float32 at ``highest``. It imports nothing of the
program. ``run_cell`` calls it after the program's state is released, so
every device of the cell is free.

Departures from that file, each because one device cannot hold 25 M rows
(there ``Coordinate.__init__`` puts all of a coordinate's ``x`` on one
device and ``_row_scores`` makes two ``f32[n, 17]`` temporaries whose 17
columns pad to 128 lanes: it stops near 10 M rows on one v5e):

1. Every ``[n, ...]`` array (features, ids, labels, the coordinates' score
   vectors) is padded with zero rows to a multiple of the device count and
   laid over the devices by rows (a one-axis ``jax.sharding`` mesh). The
   fixed effect's Newton step then sums its gradient and Hessian over the
   devices' row blocks (the same ``_newton_step``, partitioned by the
   compiler); a padded row has mask 0. The sums run in another order than
   on one device: the tables agree to float32 rounding, not bit for bit.
2. A random effect's size groups are gathered on the HOST from the host
   arrays (``x[index]``) and each group is sent whole to one device, the
   groups dealt out by size so that the devices hold alike; the plain
   file gathers them on the device from a whole ``x``. A group's problems
   are solved on its device; the coefficient table stays on the host
   between groups.
3. ``predict`` spreads its row blocks over the devices; the arithmetic of
   a block is the plain file's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark import reference as plain

ROWS = "rows"


def _mesh() -> Mesh:
    return Mesh(np.asarray(jax.devices()), (ROWS,))


def _over_rows(mesh: Mesh, host: np.ndarray):
    """A host ``[n, ...]`` array over the mesh by rows, zero rows after
    the last up to a multiple of the device count; each device is sent
    its own rows."""
    n, n_dev = host.shape[0], mesh.devices.size
    padded = (n + (-n) % n_dev,) + host.shape[1:]

    def rows(index):
        lo, hi, _ = index[0].indices(padded[0])
        part = host[lo:min(hi, n)]
        if hi > n:
            part = np.concatenate([part, np.zeros(
                (hi - max(lo, n),) + host.shape[1:], host.dtype)])
        return part

    spec = P(ROWS, *([None] * (host.ndim - 1)))
    return jax.make_array_from_callback(
        padded, NamedSharding(mesh, spec), rows)


class Coordinate:
    """``reference.Coordinate`` with its rows over the mesh and its size
    groups dealt out to single devices."""

    def __init__(self, spec: dict, x: np.ndarray, ids, storage,
                 labels: np.ndarray, task: str, mesh: Mesh):
        self.n, d = x.shape
        self.name = spec["name"]
        self.random = spec["kind"] != "fixed"
        self.mesh, self.storage = mesh, storage
        penalised = np.ones(d, np.float32)
        penalised[-1] = 0.0
        self.l2_diag = np.float32(spec["l2"]) * penalised
        self.x = plain._stored(_over_rows(mesh, x), storage)
        if not self.random:
            self.w = jnp.zeros((1, d), jnp.float32)
            self.mask = _over_rows(mesh, np.ones(self.n, np.float32))
            return
        entities = int(spec["entities"])
        order, starts, kept = plain.kept_rows(
            ids, entities, spec.get("active_data_upper_bound"), spec["id"])
        self.side = np.zeros(entities, np.int8)
        if task == "LOGISTIC_REGRESSION":
            self.side = plain.one_label_side(
                labels, ids, order, starts, kept)
        devices = list(mesh.devices.flat)
        held = [0] * len(devices)
        self.groups = []
        groups = plain.size_groups(np.where(self.side == 0, kept, 0))
        for members in sorted(groups, key=lambda m: -m.size * int(
                kept[m].max())):
            index = plain._group_index(members, order, starts, kept, self.n)
            at = held.index(min(held))
            held[at] += index.size
            dev = devices[at]
            pad = index >= self.n
            rows = np.where(pad, 0, index)
            gx = x[rows]
            gx[pad] = 0.0
            self.groups.append((
                members, dev,
                jax.device_put(index, dev),
                plain._stored(jax.device_put(gx, dev), storage),
                jax.device_put(np.where(pad, 0.0, labels[rows]).astype(
                    np.float32), dev),
            ))
        self.ids = _over_rows(mesh, ids.astype(np.int32))
        self.w = np.zeros((entities, d), np.float32)
        self.w[:, -1] = plain.SATURATED * self.side

    def solve(self, y, off, task, steps_taken=None) -> None:
        """Refit every problem of the coordinate against ``off`` (over the
        mesh by rows), batch by batch."""
        if not self.random:
            self.w = plain._solve(
                self.x[None], self.mask[None], y[None], off[None], self.w,
                jnp.asarray(self.l2_diag), task, steps_taken)
            return
        off_on = {}
        for members, dev, index, gx, gy in self.groups:
            if dev not in off_on:
                off_on[dev] = jax.device_put(off, dev)
            goff = jnp.take(off_on[dev], index, axis=0, mode="fill",
                            fill_value=0)
            solved = plain._solve(
                gx, (index < self.n).astype(jnp.float32), gy, goff,
                jax.device_put(self.w[members], dev),
                jax.device_put(self.l2_diag, dev), task, steps_taken)
            self.w[members] = np.asarray(solved)

    def scores(self):
        """[n padded] this coordinate's part of z, of EVERY row."""
        if not self.random:
            return self.x @ self.w[0]
        w = jax.device_put(self.w, NamedSharding(self.mesh, P()))
        return plain._row_scores(self.x, self.ids, w)

    def table(self) -> np.ndarray:
        w = np.array(self.w, np.float32)
        if not self.random:
            return w[0]
        w[self.side != 0, -1] = np.inf * self.side[self.side != 0]
        return w


def fit(config: dict, data, storage=None, matmul_precision="highest",
        steps_taken=None) -> dict:
    """coordinate name -> coefficient table of the reference fit; the
    arguments are ``benchmark/reference.py``'s ``fit``'s."""
    task = config["task"]
    mesh = _mesh()
    with jax.default_matmul_precision(matmul_precision):
        labels = np.asarray(data.labels, np.float32)
        coords = [
            Coordinate(
                c, data.features[c["shard"]],
                None if c["kind"] == "fixed" else data.ids[c["id"]],
                storage, labels, task, mesh)
            for c in config["coordinates"]
        ]
        y = _over_rows(mesh, labels)
        part = {c.name: jnp.zeros_like(y) for c in coords}
        for _ in range(int(config["num_iterations"])):
            for c in coords:
                others = sum(v for k, v in part.items() if k != c.name)
                c.solve(y, others, task, steps_taken)
                part[c.name] = plain._stored(c.scores(), storage)
        return {c.name: c.table() for c in coords}


@jax.jit
def _block_margins(xs, ids, ws):
    """z of one row block: ``reference.predict``'s arithmetic. ``ids[k]``
    is None for the fixed effect."""
    z = jnp.zeros(xs[0].shape[0], jnp.float32)
    for x, i, w in zip(xs, ids, ws):
        z = z + (x @ w if i is None else jnp.einsum("nd,nd->n", x, w[i]))
    return z


def predict(config: dict, data, tables: dict, block: int = 1_000_000):
    """[n] float32 margins z of a model given as tables, in row blocks
    dealt round to the devices. A row of an entity whose intercept is
    +-inf has that margin."""
    n = data.labels.shape[0]
    out = np.empty(n, np.float32)
    coords = config["coordinates"]
    random = [c for c in coords if c["kind"] != "fixed"]
    sides = {c["name"]: np.where(
        np.isinf(tables[c["name"]][:, -1]),
        np.sign(tables[c["name"]][:, -1]), 0.0) for c in random}
    devices = jax.devices()
    finite = [
        [jax.device_put(np.where(np.isinf(tables[c["name"]]), 0.0,
                                 tables[c["name"]]).astype(np.float32), dev)
         for c in coords]
        for dev in devices]
    pending = []
    with jax.default_matmul_precision("highest"):
        for k, lo in enumerate(range(0, n, block)):
            hi = min(n, lo + block)
            at = k % len(devices)
            dev = devices[at]
            xs = [jax.device_put(data.features[c["shard"]][lo:hi], dev)
                  for c in coords]
            ids = [None if c["kind"] == "fixed" else jax.device_put(
                data.ids[c["id"]][lo:hi].astype(np.int32), dev)
                for c in coords]
            pending.append((lo, hi, _block_margins(xs, ids, finite[at])))
            if len(pending) >= 2 * len(devices):
                _collect(out, pending.pop(0), data, random, sides)
        while pending:
            _collect(out, pending.pop(0), data, random, sides)
    return out


def _collect(out, done, data, random, sides) -> None:
    lo, hi, z = done
    out[lo:hi] = np.asarray(z)
    for c in random:
        side = sides[c["name"]][data.ids[c["id"]][lo:hi]]
        out[lo:hi][side != 0] = np.inf * side[side != 0]
