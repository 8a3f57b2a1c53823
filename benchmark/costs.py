"""Operations and bytes the algorithm needs, from shapes alone; the peaks.

These are the yardstick's own counts: REAL rows, never padded ones, and
one evaluation per coordinate and CD iteration, never a solver's
iterations. Whatever implements the fit has to do at least this much, so a
share built on them cannot pass 100 %.
"""

from __future__ import annotations

import json
import os

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


def fit_flops(config: dict) -> float:
    """Model FLOPs of one fit: per CD iteration and coordinate, over the
    real rows n with d features, the forward product (2nd), the gradient
    product (2nd) and the Hessian product X^T D X (2nd^2)."""
    n = float(config["rows"])
    per_sweep = sum(
        2.0 * n * d * (2.0 + d)
        for d in (float(c["features"]) for c in config["coordinates"]))
    return per_sweep * float(config["num_iterations"])


def fit_hbm_bytes(config: dict) -> float:
    """Bytes of one fit that no implementation avoids: per CD iteration
    and coordinate, one read of that coordinate's features over the real
    rows at the storage dtype the configuration states, plus three float32
    row vectors (labels, offsets in, scores out)."""
    n = float(config["rows"])
    width = _STORAGE_BYTES[config["precision"]]
    per_sweep = sum(
        n * float(c["features"]) * width + 3.0 * 4.0 * n
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
