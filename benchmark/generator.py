"""GLMix data from a configuration file and ``--seed``.

How many rows each entity has comes from the configuration alone: each
random coordinate's ``rows_per_entity`` law, drawn from ``shape_seed``. So
every seed plans the same bucket shapes and every run after a cell's first
finds its programs in the compile cache. The features, true coefficients,
labels AND which entity id owns which row set come from the
configuration's ``data_seed`` where it states one: the data set is then the
configuration's, every ``--seed`` trains on the same arrays, and every run
of a cell does the same work. Both halves were measured (PERF.md): values
drawn from ``--seed`` moved ``train_rows_per_s`` by 3 % from seed to seed
(the solvers' data-dependent iteration counts; PR 24), and a renaming of
the entities by ``--seed``, which stood until PR 35, moved it by 4 % and
one naming in five by a fifth (the fused fit sums the fixed effect's loss
in one random coordinate's entity order, in float32, and its L-BFGS stops
an iteration apart). A configuration whose ``data_seed`` is null draws
values and names from ``--seed``.

Marginals follow ``bench.py`` ``_synth_arrays``: standard-normal features
with the last column the intercept, true coefficients N(0, scale^2),
logistic labels from sigmoid(0.5 z) or linear labels z + 0.2 N(0, 1).
"""

from __future__ import annotations

import concurrent.futures
import dataclasses

import numpy as np

_SHAPE_STREAM = 0x5EED
_DATA_STREAM = 0xDA7A
_NAME_STREAM = 0x1D5
# Features are drawn in row blocks, each from a generator of its own keyed
# by (seed, coordinate, block), on a few threads: the same arrays whatever
# the number of threads, in a third of the time of one stream.
_BLOCK_ROWS = 1 << 19
_THREADS = 4


@dataclasses.dataclass
class GlmixData:
    """Host arrays of one generated data set (float32 / int64)."""

    labels: np.ndarray  # [n]
    features: dict  # shard name -> [n, d], last column 1.0
    ids: dict  # id tag -> [n] entity index


def _entity_shares(law: dict, entities: int) -> np.ndarray:
    """[entities] probabilities that a row falls to each entity."""
    if law["law"] == "uniform":
        return np.full(entities, 1.0 / entities)
    if law["law"] == "power":
        # Entity of rank k gets a share proportional to k^-exponent.
        weights = np.arange(1, entities + 1, dtype=np.float64) ** -float(
            law["exponent"])
        return weights / weights.sum()
    raise ValueError(f"no rows_per_entity law {law['law']!r}")


def rows_per_entity(config: dict, coordinate: dict) -> np.ndarray:
    """[entities] row counts, a function of the configuration only: every
    entity gets the law's ``min_rows`` (0 if not stated) and the other
    rows fall by the law's shares, drawn from ``shape_seed``."""
    position = [c["name"] for c in config["coordinates"]].index(
        coordinate["name"])
    rng = np.random.default_rng(
        [int(config["shape_seed"]), _SHAPE_STREAM, position])
    law = coordinate["rows_per_entity"]
    entities = int(coordinate["entities"])
    floor = int(law.get("min_rows", 0))
    free = int(config["rows"]) - floor * entities
    if free < 0:
        raise ValueError(
            f"{coordinate['name']}: min_rows {floor} x {entities} entities "
            f"is over rows {config['rows']}")
    return floor + rng.multinomial(free, _entity_shares(law, entities))


def _features(seed: int, position: int, rows: int, d: int, pool):
    """[rows, d] standard normals, the last column 1."""
    x = np.empty((rows, d), np.float32)

    def fill(block: int) -> None:
        lo = block * _BLOCK_ROWS
        hi = min(rows, lo + _BLOCK_ROWS)
        rng = np.random.default_rng(
            [int(seed), _DATA_STREAM, position + 1, block])
        rng.standard_normal(out=x[lo:hi], dtype=np.float32)

    blocks = range((rows + _BLOCK_ROWS - 1) // _BLOCK_ROWS)
    for done in [pool.submit(fill, b) for b in blocks]:
        done.result()
    x[:, -1] = 1.0
    return x


def generate(config: dict, seed: int) -> GlmixData:
    rows = int(config["rows"])
    data_seed = int(
        seed if config.get("data_seed") is None else config["data_seed"])
    rng = np.random.default_rng([data_seed, _DATA_STREAM])
    names = np.random.default_rng([data_seed, _NAME_STREAM])
    features, ids = {}, {}
    z = np.zeros(rows, np.float32)
    with concurrent.futures.ThreadPoolExecutor(_THREADS) as pool:
        for position, coord in enumerate(config["coordinates"]):
            features[coord["shard"]] = _features(
                data_seed, position, rows, int(coord["features"]), pool)
    for coord in config["coordinates"]:
        d = int(coord["features"])
        x = features[coord["shard"]]
        scale = np.float32(coord["true_scale"])
        if coord["kind"] == "fixed":
            z += x @ (rng.standard_normal(d, dtype=np.float32) * scale)
            continue
        counts = rows_per_entity(config, coord)
        owner = rng.permutation(
            np.repeat(np.arange(counts.shape[0]), counts))
        w = rng.standard_normal(
            size=(counts.shape[0], d), dtype=np.float32) * scale
        z += np.einsum("nd,nd->n", x, w[owner])
        ids[coord["id"]] = names.permutation(counts.shape[0])[owner]
    if config["task"] == "LOGISTIC_REGRESSION":
        p = 1.0 / (1.0 + np.exp(-0.5 * z))
        labels = (rng.random(rows, dtype=np.float32) < p).astype(np.float32)
    elif config["task"] == "LINEAR_REGRESSION":
        labels = z + np.float32(0.2) * rng.standard_normal(
            rows, dtype=np.float32)
    else:
        raise ValueError(f"no generator for task {config['task']!r}")
    return GlmixData(labels=labels, features=features, ids=ids)
