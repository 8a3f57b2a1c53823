"""photon_tpu.serve — AOT-compiled online scoring.

The reference ships scoring as a first-class product surface (photon-client
GameScoringDriver); this package is its low-latency twin for the TPU build:

- **Coefficient tables** (``serve/tables.py``): a trained ``GameModel``
  loaded into device-resident state — dense fixed-effect weight vectors
  plus per-coordinate random-effect tables ``[E, S]`` with their device
  projector matrices and a host entity-id -> row-index map. Unknown /
  cold entities fall back to fixed-effect-only scores (the reference's
  left-join-with-no-match semantics). ``reload`` swaps a new model in
  without a recompile — a dispatch-safe reference swap by default, or a
  donated in-place buffer update (``donate=True``) for quiesced,
  memory-constrained reloads.
- **AOT score programs** (``serve/programs.py``): ONE jitted scoring
  function per model structure, ahead-of-time compiled at server start
  for a small ladder of fixed batch shapes through
  ``utils.compile_cache.aot_compile``. Requests pad up to the nearest
  rung, so the steady-state serving loop adds ZERO programs — an audited
  contract (PROGRAM_AUDIT below), not a promise.
- **Micro-batching queue** (``serve/queue.py``): a bounded request queue
  with a latency/throughput-tunable flush policy (max batch size, max
  linger), one worker thread that pads/dispatches/scatters results back
  to per-request futures, and graceful draining shutdown — audited by
  the tier-3 concurrency gate via its declared CONCURRENCY_AUDIT.
- **Synchronous driver** (``serve/driver.py``): feeds requests from a
  dataset or a synthetic generator (no network dependency) and reports
  p50/p99 latency, QPS, batch-fill fraction, and cold-entity rate —
  the fields ``python -m photon_tpu.cli.serve`` emits.

Architecture, tuning knobs, and the zero-recompile contract: SERVING.md.
"""

from __future__ import annotations

from photon_tpu.serve.driver import drive, synthetic_requests
from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
from photon_tpu.serve.queue import MicroBatchQueue
from photon_tpu.serve.tables import (
    CoefficientTables,
    build_index_maps_from_model,
)

# Program contract (audited by `python -m photon_tpu.analysis --semantic`;
# machinery in analysis/program.py build_serving): the serving score
# ladder must be CLOSED — every request batch size pads to one of the
# AOT-compiled rung programs (census bound = the ladder's rung count;
# a broken pad rule mints a new program and fails the census), a model
# reload with unchanged shapes re-enters the SAME executables
# (stable_under=model_reload: coefficients are traced operands, never
# baked constants), and the scoring jaxpr carries no host callback
# (hot_loop) — the request hot path never round-trips to Python.
PROGRAM_AUDIT = dict(
    name="serving",
    entry="serve.programs.ScorePrograms "
    "(AOT score ladder over serve.tables)",
    builder="build_serving",
    max_programs=3,  # == len(rungs) the builder's ladder declares
    stable_under=("request_batch", "model_reload"),
    hot_loop=True,
)

__all__ = [
    "CoefficientTables",
    "MicroBatchQueue",
    "PROGRAM_AUDIT",
    "ScorePrograms",
    "ShapeLadder",
    "build_index_maps_from_model",
    "drive",
    "synthetic_requests",
]
