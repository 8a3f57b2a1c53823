"""Async device-side convergence traces.

The fused whole-fit program (algorithm/fused_fit.py) computes a small
per-(CD-iteration, coordinate) convergence block INSIDE the already-traced
fit — extra outputs of the existing program, so the tier-2 dispatch
census is unchanged and the recompile keys are identical with telemetry
on or off (the audited ``telemetry`` contract). ``FusedFit.run`` hands
the device array here WITHOUT any host sync: the trace is "fetched
asynchronously" — the jax array reference is parked and only converted
to numpy when a consumer (``obs.snapshot()``, the JSONL exporter, a
test) actually reads it, by which point the fit has long completed.

Metric columns, in order (``METRICS``):

- ``loss``: the coordinate's final objective value from its solver
  (fixed-effect coordinates only — the batched per-entity solvers return
  iteration counts, not objective values; 0.0 for random effects);
- ``grad_norm``: final gradient norm at the solution (fixed-effect only,
  same reason);
- ``residual_delta_sq``: sum of squared change of the coordinate's score
  vector this sweep — the residual-bookkeeping convergence signal, and
  the one that exists for EVERY coordinate kind;
- ``weight_delta_sq``: sum of squared coefficient movement this sweep;
- ``weight_norm_sq``: squared norm of the new coefficient table.
"""

from __future__ import annotations

import threading
from collections import deque

import numpy as np

METRICS = (
    "loss",
    "grad_norm",
    "residual_delta_sq",
    "weight_delta_sq",
    "weight_norm_sq",
)

# Bounded: a bench steady-state loop runs dozens of fits; keeping every
# device buffer would pin HBM for telemetry nobody reads.
_MAX_TRACES = 8

# Host-concurrency contract (audited by `python -m photon_tpu.analysis
# --concurrency`). `record()` runs on the training thread while
# exporters materialize traces from any thread; the parked-trace deque
# and the fit counter share the module lock. The device->host fetch in
# `_series` runs OUTSIDE the lock on purpose (a transfer under the lock
# would block `record()` for its duration — the `blocking-under-lock`
# rule's canonical case) with a double-checked swap installing the
# cached numpy array under the lock.
CONCURRENCY_AUDIT = dict(
    name="obs-convergence",
    locks={
        "_lock": ("_traces", "_fits_recorded"),
    },
    thread_entries=(),
    jax_dispatch_ok={},
)

_lock = threading.Lock()
_traces: deque = deque(maxlen=_MAX_TRACES)
_fits_recorded = 0


def reset() -> None:
    global _fits_recorded
    with _lock:
        _traces.clear()
        _fits_recorded = 0


def record(coordinates: tuple[str, ...], array) -> None:
    """Park one fit's [num_iters, len(coordinates), len(METRICS)] device
    array. No sync, no host transfer — pure reference bookkeeping."""
    global _fits_recorded
    with _lock:
        _traces.append({"coordinates": tuple(coordinates), "array": array})
        _fits_recorded += 1


def _series(t: dict) -> dict:
    """Materialize one parked trace (device->host fetch cached per
    entry: repeated consumers — snapshot then write_jsonl — pay the
    transfer once).

    Double-checked swap: the transfer itself runs OUTSIDE the module
    lock — a concurrent exporter must never block the training thread's
    ``record()`` for the duration of a device->host pull — and the
    cache installs atomically under the lock (a lost race wastes one
    duplicate transfer, never corrupts the entry)."""
    with _lock:
        arr = t.get("np")
        dev = t.get("array")
    if arr is None:
        fetched = np.asarray(dev)
        with _lock:
            arr = t.get("np")
            if arr is None:
                arr = t["np"] = fetched
                t["array"] = None  # drop the device ref once fetched
    return {
        cid: {
            m: [float(v) for v in arr[:, j, k]]
            for k, m in enumerate(METRICS)
        }
        for j, cid in enumerate(t["coordinates"])
    }


def traces() -> list[dict]:
    """Materialized traces, oldest first: per fit a dict
    ``{coordinate: {metric: [per-iteration floats]}}``.

    The fetch inside ``_series`` is the deferred one — by consumption
    time the fit finished, so this is a plain device->host copy, not a
    sync inside any hot loop.
    """
    with _lock:
        parked = list(_traces)
    return [_series(t) for t in parked]


def snapshot() -> dict:
    """JSON-ready summary: fit count, metric names, and the LAST fit's
    full per-coordinate series (the one consumers chart). Only the
    newest trace is materialized here — older parked fits stay on
    device until something (the JSONL exporter) actually reads them."""
    with _lock:
        n = _fits_recorded
        last = _traces[-1] if _traces else None
    return {
        "fits_recorded": n,
        "metrics": list(METRICS),
        "last": None if last is None else _series(last),
    }
