"""Operations and bytes the algorithm needs, and the peaks.

These are the yardstick's own counts: REAL rows, never padded ones, and
one evaluation per coordinate and CD iteration, never a solver's
iterations. A coordinate is counted from what its shard holds
(``shard_counts``): a dense ``[n, d]`` shard by its shape, an ELL shard by
its stored nonzeros, never by its width. Whatever implements the fit has
to do at least this much, so a share built on them cannot pass 100 %.
"""

from __future__ import annotations

import json
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_STORAGE_BYTES = {"float32": 4, "bfloat16": 2}


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip by JAX's ``device_kind``. A device without a row
    is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: the benchmark "
            f"reports no share of a peak on it (benchmark/peaks.json has "
            f"{sorted(table)})")
    return table[device_kind]


def shard_counts(config: dict, data) -> dict:
    """What each coordinate's shard holds, by coordinate name: ``rows``;
    for an ELL shard (``indices`` and ``values``, as
    ``generators/glmix_yahoo_music.py`` ``Bag`` hands it over)
    ``nonzeros``, the stored values that are not zero, and
    ``square_nonzeros``, the sum over rows of each row's nonzeros squared
    (padding slots hold 0, do no work and are not counted); for a dense
    ``[n, d]`` array its ``shape``."""
    counts = {}
    for c in config["coordinates"]:
        x = data.features[c["shard"]]
        if hasattr(x, "indices") and hasattr(x, "values"):
            k = np.count_nonzero(np.asarray(x.values), axis=1)
            counts[c["name"]] = {"rows": int(k.shape[0]),
                                 "nonzeros": int(k.sum()),
                                 "square_nonzeros": int(k @ k)}
        else:
            n, d = x.shape
            counts[c["name"]] = {"rows": int(n), "shape": (int(n), int(d))}
    return counts


def _count(counts: dict, coordinate: dict) -> dict:
    """The coordinate's entry of ``shard_counts``, which has to say what
    its shard holds: an ELL shard is never counted by its width."""
    count = (counts or {}).get(coordinate["name"])
    if count is None or not ({"shape", "nonzeros"} & set(count)):
        raise ValueError(
            f"no count of what coordinate {coordinate['name']!r}'s shard "
            f"holds (costs.shard_counts gives it): {count!r}")
    return count


def coordinate_flops(count: dict) -> float:
    """Model FLOPs of one coordinate in one CD iteration over its real rows:
    the forward product, the gradient product and the Hessian product
    X^T D X. Over an ELL shard with nnz stored nonzeros, k of them in a
    row: 2 nnz + 2 nnz + 2 sum(k^2). Over a dense [n, d] shard, the case
    k = d of every row: 2nd(2 + d)."""
    if "nonzeros" in count:
        return (4.0 * float(count["nonzeros"])
                + 2.0 * float(count["square_nonzeros"]))
    n, d = (float(v) for v in count["shape"])
    return 2.0 * n * d * (2.0 + d)


def coordinate_hbm_bytes(count: dict, width: int) -> float:
    """Bytes of one coordinate in one CD iteration that no implementation
    avoids: one read of its features at ``width`` bytes a value (an ELL
    shard's stored nonzeros, each with its int32 index; a dense shard's
    n x d values), plus three float32 row vectors (labels, offsets in,
    scores out)."""
    n = float(count["rows"])
    if "nonzeros" in count:
        return float(count["nonzeros"]) * (width + 4.0) + 3.0 * 4.0 * n
    d = float(count["shape"][1])
    return n * d * width + 3.0 * 4.0 * n


def fit_flops(config: dict, counts: dict) -> float:
    """Model FLOPs of one fit: ``coordinate_flops`` of every coordinate
    (``counts`` from ``shard_counts``), times the CD iterations."""
    per_sweep = sum(coordinate_flops(_count(counts, c))
                    for c in config["coordinates"])
    return per_sweep * float(config["num_iterations"])


def fit_hbm_bytes(config: dict, counts: dict) -> float:
    """Bytes of one fit: ``coordinate_hbm_bytes`` of every coordinate at
    the storage dtype the configuration states, times the CD
    iterations."""
    width = _STORAGE_BYTES[config["precision"]]
    per_sweep = sum(coordinate_hbm_bytes(_count(counts, c), width)
                    for c in config["coordinates"])
    return per_sweep * float(config["num_iterations"])


def newton_step_cost(rows: int, dim: int, lanes: int) -> tuple[float, float]:
    """(FLOPs, bytes) one call of the fused Newton step needs for a block
    of ``lanes`` entities of ``rows`` x ``dim`` float32 features, by the
    call's own shapes: margins, gradient and Hessian products once
    (2 r s (2 + s) per entity) and the s^3 / 3 of the factorisation; one
    read of the slab and of the three row vectors, one read and one write
    of the iterate."""
    flops = lanes * (2.0 * rows * dim * (2.0 + dim) + dim ** 3 / 3.0)
    bytes_ = lanes * 4.0 * (rows * dim + 3.0 * rows + 2.0 * dim)
    return flops, bytes_


def least_seconds(flops: float, bytes_: float, peaks: dict):
    """(seconds, which bound binds) of the roofline."""
    by_flops = flops / peaks["flops_per_s"]
    by_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "hbm")
