"""Static per-program cost model: FLOPs / HBM bytes from lowered HLO.

``jax.stages.Lowered.cost_analysis()`` runs XLA's HLO cost analysis over
the *unoptimized* module — no compilation, no device — and returns FLOP
and bytes-accessed counts per program. Dividing by the target chip's
peaks gives a roofline lower bound on runtime per dispatch, which is the
number ``obs/ledger.py`` sets a program's measured seconds against
(``photon profile``'s measured-vs-predicted rows).

These are COMPILER counts, not the analytic model-FLOP counts in
``benchmark/costs.py`` (which count real rows only): the two
deliberately bracket the truth — cost_analysis counts every padded lane
the program will really execute, the analytic count only the useful
model work.
"""

from __future__ import annotations

import json
import re
from typing import Any, Iterable, Mapping

# Per-chip peaks, keyed by the ``device_kind`` string JAX reports for
# the part (``jax.devices()[0].device_kind``). Source of the v5e row:
# Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s
# HBM per chip. ``ici_bytes_per_sec`` is the per-chip aggregate
# inter-chip-interconnect bandwidth this repo has priced collectives
# with since PR 20 (4 links x ~400 Gb/s, less framing; the same page
# publishes 1,600 Gbit/s); it prices collective transfers — the --spmd
# auditor's implicit-reshard findings — as a per-dispatch lower bound
# the same way hbm_bytes_per_sec prices local traffic.
CHIP_PEAKS = {
    "TPU v5 lite": {
        "flops_per_sec": 197e12,
        "hbm_bytes_per_sec": 819e9,
        "ici_bytes_per_sec": 186e9,
    },
}
# The part the ABSTRACT tiers price for (static cost reports, the
# --spmd reshard pricing): they analyse programs without running them,
# so they name their target explicitly. A path that MEASURES asks
# ``device_chip()`` instead and never falls back to this.
TARGET_CHIP = "TPU v5 lite"


def device_chip() -> str:
    """The peaks-table key of the device this process runs on.

    For measuring paths (the cost ledger's priced report, the bench): a
    measured second is only comparable with the peaks of the part that
    spent it, so a device that is not in the table is an error, not a
    default.
    """
    import jax

    device = jax.devices()[0]
    kind = device.device_kind
    if kind not in CHIP_PEAKS:
        raise LookupError(
            f"no peaks for device kind {kind!r} "
            f"(platform {device.platform!r}): known "
            f"{sorted(CHIP_PEAKS)}. Measured seconds are priced against "
            "the part that ran them; name a target with chip= only for "
            "an abstract report."
        )
    return kind


def program_cost(lowered: Any) -> dict[str, float]:
    """Normalized cost counters for one lowered program.

    Returns ``{"flops", "hbm_bytes", "transcendentals"}`` (floats, 0.0 for
    counters the backend does not report). ``cost_analysis`` may return a
    dict or a one-element list of dicts depending on the jax version, and
    some backends return None — all normalized here. Backends that omit
    ``bytes accessed`` entirely fall back to the program's operand +
    result aval bytes (a one-pass lower bound — every operand is read
    and every result written at least once) so the roofline row keeps an
    HBM estimate instead of silently degrading to measured-only.
    """
    ca = lowered.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, Mapping):
        ca = {}
    hbm = ca.get("bytes accessed")
    if hbm is None:
        hbm = _boundary_aval_bytes(lowered)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "hbm_bytes": float(hbm),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }


def _boundary_aval_bytes(lowered: Any) -> float:
    """Sum of input + output aval bytes of a lowered program — the
    fallback HBM-traffic floor when the backend's ``cost_analysis``
    reports no ``bytes accessed`` counter."""
    import numpy as np

    def leaf_bytes(info) -> float:
        shape = getattr(info, "shape", None)
        dtype = getattr(info, "dtype", None)
        if shape is None or dtype is None:
            return 0.0
        size = 1
        for dim in shape:
            size *= int(dim)
        return float(size * np.dtype(dtype).itemsize)

    total = 0.0
    for attr in ("args_info", "out_info"):
        tree = getattr(lowered, attr, None)
        if tree is None:
            continue
        import jax

        leaves = jax.tree_util.tree_leaves(
            tree, is_leaf=lambda n: hasattr(n, "shape")
        )
        total += sum(leaf_bytes(leaf) for leaf in leaves)
    return total


def roofline(
    cost: Mapping[str, float], chip: str = TARGET_CHIP
) -> dict[str, Any]:
    """Roofline classification of one program's cost counters.

    ``min_seconds`` is the per-dispatch lower bound at the chip's peaks;
    ``bound`` names the resource that sets it. Arithmetic intensity below
    the chip's ridge point (peak_flops / peak_hbm) means HBM-bound — the
    expected regime for GLM training.
    """
    peaks = CHIP_PEAKS[chip]
    flops = float(cost.get("flops", 0.0))
    bytes_ = float(cost.get("hbm_bytes", 0.0))
    t_flops = flops / peaks["flops_per_sec"]
    t_hbm = bytes_ / peaks["hbm_bytes_per_sec"]
    return {
        "chip": chip,
        "arithmetic_intensity": (flops / bytes_) if bytes_ else None,
        "min_seconds_flops": t_flops,
        "min_seconds_hbm": t_hbm,
        "min_seconds": max(t_flops, t_hbm),
        "bound": "flops" if t_flops >= t_hbm else "hbm",
    }


def program_report(
    lowered: Any, chip: str = TARGET_CHIP
) -> dict[str, Any]:
    """cost + roofline for one lowered program."""
    cost = program_cost(lowered)
    out = dict(cost)
    out["roofline"] = roofline(cost, chip)
    return out


# --------------------------------------------------------------------------
# collective-transfer pricing (the --spmd implicit-reshard detector)
# --------------------------------------------------------------------------

# One HLO shape token: dtype[dims] — "f32[128,64]", "bf16[8]", "pred[]".
# Tuple shapes of async collective pairs contain several tokens; summing
# them prices the whole transfer.
_HLO_SHAPE_RE = re.compile(
    r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|f8e\w+|bf16|f16|f32|f64"
    r"|c64|c128)\[([0-9,]*)\]"
)

_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def hlo_shape_bytes(shape_text: str) -> float:
    """Total bytes of every dtype[dims] token in an HLO shape string.

    Accepts the raw shape region of an instruction line — scalar
    (``f32[]``), array (``f32[128,64]{1,0}``), or tuple
    (``(f32[8]{0}, f32[8]{0})``) — and sums them all; layout annotations
    are ignored. Unknown dtypes (future f8 variants) price at 1 byte —
    an undercount, never a silent zero.
    """
    total = 0.0
    for dtype, dims in _HLO_SHAPE_RE.findall(shape_text):
        size = 1
        for d in dims.split(","):
            if d:
                size *= int(d)
        total += size * _HLO_DTYPE_BYTES.get(dtype, 1)
    return total


def collective_transfer(
    sequence: Iterable[Mapping[str, str]], chip: str = TARGET_CHIP
) -> dict[str, Any]:
    """Price an ordered collective sequence as bytes over the interconnect.

    ``sequence`` is ``spmd.collective_sequence`` output
    (``[{"op", "shape"}, ...]``). Returns per-op bytes, the total, and
    the ICI-bandwidth lower bound per dispatch — the cost an implicit
    compiler-inserted reshard silently adds to every step.
    """
    ops: list[dict[str, Any]] = []
    total = 0.0
    for step in sequence:
        b = hlo_shape_bytes(step.get("shape", ""))
        total += b
        ops.append({"op": step.get("op", "?"), "bytes": b})
    peak = CHIP_PEAKS[chip].get("ici_bytes_per_sec")
    return {
        "chip": chip,
        "ops": ops,
        "total_bytes": total,
        "min_seconds_ici": (total / peak) if peak else None,
    }


def write_report(path: str, report: Mapping[str, Any]) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=2, sort_keys=True)
