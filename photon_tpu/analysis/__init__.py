"""photon_tpu.analysis — six static-analysis tiers that gate the package.

Tier 1 is a pure-``ast`` lint pass (nothing analyzed is imported, no JAX
needed at analysis time), so it runs in milliseconds on any machine. The
rule set encodes the failure modes that silently destroy TPU performance
or correctness and that this repo has actually hit: hidden host syncs
inside jitted code, numpy-on-tracer calls, recompile-triggering jit
misuse, float64 leaking into float32 pipelines, int32 index arithmetic
near 2^31, and leftover debugging debris.

Tier 2 (``--semantic``; analysis/program.py) audits the PROGRAMS the
package builds rather than the source text: the public jitted entry
points are traced under abstract shapes (no device execution — CPU CI is
enough) and the jaxprs/lowered HLO are checked against contracts each
audited module declares (dispatch census, recompile-key stability,
host-boundary and f64 audits, mesh sharding, and a static FLOP/HBM cost
model for the roofline numbers ``obs/ledger.py`` sets measured seconds
against).

Tier 3 (``--concurrency``; analysis/concurrency.py) audits the THREADED
HOST RUNTIME: a pure-``ast`` lockset lint (Eraser-style) checked against
the ``CONCURRENCY_AUDIT`` contracts the concurrent modules declare —
unlocked writes to guarded state, blocking calls under a lock, AB/BA
lock-order hazards, dropped futures, executor/thread hygiene, off-thread
JAX dispatch without a declared reason, and stale contracts.

Tier 4 (``--memory``; analysis/memory.py) audits the MEMORY of those
same programs before any device sees them: a static live-range walk of
each tier-2-traced entry point yields its peak-HBM high-water mark
(donation-aware), every declared buffer donation is verified to actually
alias in the compiled HLO (XLA drops unusable donations silently), and
each audited module's ``MEMORY_AUDIT`` contract prices the peak as a
formula in model-dimension terms — so HBM growth and rotten budgets both
fail CI, and ``predict_resident_bytes`` answers the admission question
("will this model fit") statically.

Tier 5 (``--numerics``; analysis/numerics.py) audits the DTYPE FLOW of
those same programs: a dtype-provenance lattice walk proves every
reduction over bf16-stored values accumulates in f32 (into
scan/while/cond bodies and across the Pallas boundary), censuses
narrowing casts, prices a static worst-case rounding-error bound per
program against declared ``NUMERICS_AUDIT`` budgets, and requires
order-nondeterministic reductions to be declared
deterministic-by-construction with a reason.

Tier 6 (``--spmd``; analysis/spmd.py) audits the MULTI-HOST behavior of
the mesh path on one CPU machine: each ``SPMD_AUDIT`` contract's entry
points are traced under N simulated ``jax.process_index()`` values (jit
caches cleared per host, so the proof cannot be satisfied by cache
replay) and the jaxprs must be byte-identical; the ordered collective
sequence of each host's compiled HLO must match position-by-position (a
mismatch is the deadlock, named statically); a host-divergence AST lint
flags time/env/pid/``process_index``/unseeded-RNG values flowing into
shapes or trace-affecting branches; and the declared ``PARTITION_RULES``
tree must cover every placed pytree leaf exactly once, implicit reshards
priced as bytes over the interconnect.

Usage::

    python -m photon_tpu.analysis photon_tpu/            # tier-1 gate
    python -m photon_tpu.analysis --semantic             # tier-2 gate
    python -m photon_tpu.analysis --concurrency          # tier-3 gate
    python -m photon_tpu.analysis --memory               # tier-4 gate
    python -m photon_tpu.analysis --numerics             # tier-5 gate
    python -m photon_tpu.analysis --spmd                 # tier-6 gate
    python -m photon_tpu.analysis --list-rules
    python -m photon_tpu.analysis --format json photon_tpu/data/

Per-line suppression (reason after ``--`` is part of the contract)::

    y = labels.astype(np.float64)  # photon: ignore[float64-literal] -- host-side stats

See ANALYSIS.md for every rule's rationale with its in-repo example.
"""

from photon_tpu.analysis.core import (
    Finding,
    ModuleContext,
    Rule,
    analyze_file,
    analyze_paths,
    analyze_source,
    registered_rules,
    rule,
)
from photon_tpu.analysis.report import (
    render_json,
    render_rule_list,
    render_text,
    summarize,
)

__all__ = [
    "Finding",
    "ModuleContext",
    "Rule",
    "analyze_file",
    "analyze_paths",
    "analyze_source",
    "registered_rules",
    "rule",
    "render_json",
    "render_rule_list",
    "render_text",
    "summarize",
]
