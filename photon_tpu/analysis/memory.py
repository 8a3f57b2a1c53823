"""Tier 4: the memory auditor — static peak-HBM accounting and
donation-safety audits against declared ``MEMORY_AUDIT`` budget contracts.

The ledger (obs/ledger.py) knows the serving/fit HBM footprint at
RUNTIME, after the allocation already happened; ROADMAP items 3 and 5
(beyond-HBM tiering, multi-tenant admission) need the answer BEFORE a
device allocation. This tier computes it statically, with the same
contract machinery as tier 2 (analysis/program.py) and no device
execution — CPU CI is enough:

- **Static peak accounting**: every public jitted entry point already
  traced by tier 2 (fused materialize/fit, the serve score ladder,
  eval/score) is walked under abstract shapes for a live-buffer
  high-water mark (:func:`static_peak_bytes` — aval bytes over equation
  live ranges, donation-aware: a donated operand's bytes retire at its
  last use). Where the backend supports it the walk is cross-checked
  against ``lowered.compile().memory_analysis()`` (argument / output /
  temp / generated sizes) in the report.
- **Donation safety**: each declared donation must actually alias in
  the compiled HLO (``tf.aliasing_output`` / ``jax.buffer_donor`` arg
  attributes). XLA drops an unaliasable donation SILENTLY — the operand
  is simply DCE'd from the entry signature with no warning — so a
  dropped donation is a finding naming the operand
  (``memory-dropped-donation``). The source-level half is the tier-1
  ``use-after-donate`` rule (analysis/rules.py).
- **Budget contracts**: the declaring modules (``MEMORY_DECLARING_
  MODULES``) export ``MEMORY_AUDIT`` — each entry point's expected
  peak-HBM formula in model-dimension terms (E/S/d/rung/precision
  byte-widths) plus its donation map. The auditor prices every formula
  against the static walk and flags drift in BOTH directions: real
  growth the formula missed (``memory-undeclared-growth``) and a
  formula that rotted above reality (``memory-stale-formula``).
  ``rebuild_from``'s double-residency window is an explicit declared
  transient allowance, not an accident.
- **The admission oracle**: :func:`predict_resident_bytes` — the
  static "will this model + ladder + precision fit" half that ROADMAP
  items 3/5 call, keyed to match the ledger's ``table/<coordinate>``
  owners byte-for-byte (pinned by ``tests/test_analysis_memory.py``
  against the ledger's measured resident rows).

Run it: ``python -m photon_tpu.analysis --memory``. Exit codes follow
the other tiers: 0 clean, 1 unsuppressed findings, 2 usage error.
Contract schema and the four-tier table: ANALYSIS.md.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import importlib
from typing import Any, Callable, Iterable, Iterator

import numpy as np

from photon_tpu.analysis.core import Finding

MEMORY_RULES: dict[str, str] = {
    "memory-undeclared-growth": (
        "a program's static peak-HBM walk exceeds its declared budget "
        "formula beyond the contract tolerance"
    ),
    "memory-stale-formula": (
        "a declared budget formula prices far above the static walk "
        "(or no longer evaluates) — the contract rotted"
    ),
    "memory-dropped-donation": (
        "a declared donation did not alias in the compiled HLO — XLA "
        "dropped it silently and both buffers stay resident"
    ),
    "memory-contract": (
        "memory-contract declaration, coverage, or builder integrity "
        "error (uncovered tier-2 entry point, stale waiver, oracle "
        "drift, builder crash)"
    ),
}

# Modules that declare memory contracts (each exports MEMORY_AUDIT —
# one declaration dict or a list of them). Plain data, like the tier-2
# PROGRAM_AUDIT hooks: importing the audited modules never imports the
# analysis machinery.
MEMORY_DECLARING_MODULES = (
    "photon_tpu.algorithm.fused_fit",
    "photon_tpu.ops.serve_kernel",
    "photon_tpu.serve.programs",
    "photon_tpu.serve.tables",
    "photon_tpu.pilot.serving",
)

# Tier-2 contracts with NO memory contract, each with its reason. The
# coverage check (every tier-2 entry point carries a MEMORY_AUDIT or a
# reasoned waiver) is what keeps this list honest: a new tier-2
# contract fails the audit until someone either budgets it or writes
# its waiver down here.
TIER2_WAIVERS: dict[str, str] = {
    "ingest-pipeline": (
        "host-side ETL: device residency is the packed ingest buffer, "
        "accounted by the pipeline's own ledger booking, and its "
        "programs are one-shot transforms, not resident state"
    ),
    "streaming-ingest": (
        "bounded by the declared chunk size by construction; no "
        "long-lived device buffers beyond the in-flight chunk"
    ),
    "fused-cache-key": (
        "key-only contract — it traces no programs and allocates "
        "nothing; the fused-fit memory contract covers the programs "
        "the keys select"
    ),
    "unfused-coordinate-update": (
        "the unfused CD path is the debugging fallback; its per-block "
        "working set is strictly dominated by the fused fit's budget"
    ),
    "telemetry": "host-side spans/counters; no device allocations",
    "trace": "host-side chrome-trace writer; no device allocations",
    "monitor": "host-side HTTP surface; no device allocations",
    "ledger": (
        "the ledger MEASURES residency; it allocates only host dicts"
    ),
    "health": (
        "sketches and calibration bins are tiny host-side state; the "
        "device-side sentinel reduces are O(1) scalars"
    ),
    "newton-kernel": (
        "executes only inline inside the fused-fit program; its slabs "
        "are priced by the fused-fit budget it is embedded in"
    ),
    "segment-reduce-kernel": (
        "same: an inlined kernel of the fused program, no buffers of "
        "its own beyond the fused-fit budget"
    ),
    "mesh-sharding": (
        "per-device residency under a mesh is the global budget over "
        "the axis size; a per-shard budget needs the mesh geometry, "
        "which is a runtime deployment choice (ROADMAP item 2)"
    ),
    "resilience-retry": (
        "host-side retry/fault machinery; zero device programs is "
        "already its tier-2 contract"
    ),
    "fleet-obs": (
        "host-side bundle shipping and trace merge; its tier-2 "
        "contract proves byte-identical device programs with the "
        "fleet armed, and the bundles live on disk, not HBM"
    ),
    "evaluation-scoring": (
        "one [n] score vector per evaluator invocation, freed on "
        "return; dominated by the fit/serve budgets that feed it"
    ),
}


# --------------------------------------------------------------------------
# data model
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ProgramMemory:
    """One traced entry point under the memory walk: its closed jaxpr,
    optional Lowered (donation flags + XLA cross-check), and the
    per-program dims (e.g. this rung's batch) merged over the trace
    dims when pricing formulas."""

    name: str
    jaxpr: Any
    lowered: Any | None = None
    dims: dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class DonationProbe:
    """One lowered donating program to verify against the compiled HLO:
    ``declared`` is the donate_argnums the source declares for it."""

    name: str
    lowered: Any
    declared: tuple[int, ...]


@dataclasses.dataclass
class ResidentProbe:
    """Built device tables at one precision: measured bytes per ledger
    owner next to the admission oracle's prediction for the same
    model/precision."""

    precision: str
    dims: dict[str, float]
    measured: dict[str, float]
    predicted: dict[str, float]


@dataclasses.dataclass
class MemoryTrace:
    """Everything a memory contract's builder hands the checks."""

    programs: dict[str, ProgramMemory] = dataclasses.field(
        default_factory=dict
    )
    dims: dict[str, float] = dataclasses.field(default_factory=dict)
    donation_probes: list[DonationProbe] = dataclasses.field(
        default_factory=list
    )
    residents: list[ResidentProbe] = dataclasses.field(
        default_factory=list
    )
    transient_values: dict[str, float] = dataclasses.field(
        default_factory=dict
    )
    notes: list[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass(frozen=True)
class MemoryContract:
    name: str
    entry: str  # human-readable entry-point path (report/docs)
    build: Callable[[], MemoryTrace]
    covers: tuple[str, ...] = ()  # tier-2 contract names this budgets
    budgets: dict[str, str] = dataclasses.field(default_factory=dict)
    resident: dict[str, str] = dataclasses.field(default_factory=dict)
    transients: dict[str, str] = dataclasses.field(default_factory=dict)
    donations: dict[str, tuple[int, ...]] = dataclasses.field(
        default_factory=dict
    )
    tolerance: float = 1.5
    suppress: dict[str, str] = dataclasses.field(default_factory=dict)


def _finding(contract: MemoryContract, rule: str, message: str) -> Finding:
    return Finding(
        rule=rule, path=f"<{contract.name}>", line=0, col=0, message=message
    )


# --------------------------------------------------------------------------
# the static walk
# --------------------------------------------------------------------------


def aval_nbytes(aval: Any) -> int:
    """Bytes of one abstract value (0 for non-array avals)."""
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    size = 1
    for dim in getattr(aval, "shape", ()):
        size *= int(dim)
    return int(size) * np.dtype(dtype).itemsize


def _is_literal(v: Any) -> bool:
    return hasattr(v, "val")  # jax.core.Literal duck type


def _sub_jaxprs(params: dict) -> Iterator[Any]:
    for v in params.values():
        for cand in v if isinstance(v, (list, tuple)) else (v,):
            if hasattr(cand, "eqns") or hasattr(cand, "jaxpr"):
                if hasattr(getattr(cand, "jaxpr", cand), "eqns"):
                    yield cand


def _jaxpr_boundary_bytes(jaxpr: Any) -> int:
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0
    for v in list(inner.invars) + list(inner.outvars):
        if not _is_literal(v):
            total += aval_nbytes(v.aval)
    return total


def static_peak_bytes(
    jaxpr: Any, donated: Iterable[bool] | None = None
) -> int:
    """Live-buffer high-water mark of a (Closed)Jaxpr, in bytes.

    An event sweep over the top-level equations: non-donated inputs and
    constants stay live for the whole program (the caller owns them), a
    DONATED input's bytes retire after its last use (that is the whole
    point of donation), an intermediate lives from its defining
    equation to its last use, and outputs live to the end. A sub-jaxpr
    (scan/while/cond body, inner pjit) contributes its own recursive
    internal peak minus its boundary bytes as a transient spike at its
    equation — its boundary operands are already priced as this level's
    live values.

    This is a STATIC model, deliberately scheduler-naive: XLA may do
    better (rematerialization, buffer sharing between disjoint live
    ranges it proves) and the declared contract tolerance absorbs that;
    what the model cannot do is silently miss a new slab-sized buffer,
    which is the failure the budget contracts exist to catch.
    """
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    eqns = list(inner.eqns)
    n = len(eqns)
    donated = list(donated) if donated is not None else []
    if len(donated) != len(inner.invars):
        donated = [False] * len(inner.invars)

    last_use: dict[Any, int] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last_use[v] = i
    outvars = {v for v in inner.outvars if not _is_literal(v)}

    # live interval per var: [start, end] inclusive over eqn indices;
    # index n is the program epilogue (outputs + caller-owned inputs).
    starts: dict[int, int] = {}
    ends: dict[int, int] = {}

    def add(start: int, end: int, nbytes: int) -> None:
        if nbytes <= 0:
            return
        starts[start] = starts.get(start, 0) + nbytes
        ends[end] = ends.get(end, 0) + nbytes

    for v in getattr(inner, "constvars", ()):
        add(0, n, aval_nbytes(v.aval))
    for v, dn in zip(inner.invars, donated):
        if v in outvars:
            end = n
        elif dn:
            end = last_use.get(v, 0)
        else:
            end = n
        add(0, end, aval_nbytes(v.aval))
    seen_inv = set(inner.invars) | set(getattr(inner, "constvars", ()))
    for i, eqn in enumerate(eqns):
        for v in eqn.outvars:
            if v in seen_inv:
                continue
            end = n if v in outvars else last_use.get(v, i)
            add(i, end, aval_nbytes(v.aval))

    # transient spikes from sub-jaxprs, attributed to their equation
    extra: dict[int, int] = {}
    for i, eqn in enumerate(eqns):
        for sub in _sub_jaxprs(eqn.params):
            spike = static_peak_bytes(sub) - _jaxpr_boundary_bytes(sub)
            if spike > 0:
                extra[i] = extra.get(i, 0) + spike

    live = 0
    peak = 0
    for t in range(n + 1):
        live += starts.get(t, 0)
        peak = max(peak, live + extra.get(t, 0))
        live -= ends.get(t, 0)
    return peak


def donated_mask(lowered: Any) -> list[bool] | None:
    """Per-flat-invar donation flags from a Lowered's args_info (leaf
    order matches the flattened jaxpr invars), or None when the tree is
    unavailable."""
    info = getattr(lowered, "args_info", None)
    if info is None:
        return None
    import jax

    leaves = jax.tree_util.tree_leaves(
        info, is_leaf=lambda x: hasattr(x, "donated")
    )
    if not leaves:
        return None
    return [bool(getattr(x, "donated", False)) for x in leaves]


def program_peak(prog: ProgramMemory) -> int:
    """Static peak of one traced program, donation-aware when its
    Lowered carries arg info."""
    mask = donated_mask(prog.lowered) if prog.lowered is not None else None
    return static_peak_bytes(prog.jaxpr, mask)


# --------------------------------------------------------------------------
# donation-safety audit
# --------------------------------------------------------------------------

_ALIAS_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


def donation_report(lowered: Any) -> dict[str, Any]:
    """Declared-vs-compiled donation facts for one lowered program.

    ``declared`` counts args_info leaves marked donated; ``aliased``
    counts input/output alias attributes in the lowered module text. A
    donation XLA could not use leaves NO trace — the argument is DCE'd
    from the entry signature without a warning — so ``aliased <
    declared`` is the silent-drop signal.
    """
    mask = donated_mask(lowered) or []
    txt = lowered.as_text()
    aliased = sum(txt.count(marker) for marker in _ALIAS_MARKERS)
    return {
        "declared": sum(mask),
        "aliased": aliased,
        "positions": [i for i, d in enumerate(mask) if d],
    }


# --------------------------------------------------------------------------
# formula pricing
# --------------------------------------------------------------------------


def _price(formula: str, dims: dict[str, float]) -> float:
    """Evaluate a declared budget formula over the builder's dims.

    The formula language is deliberately just Python arithmetic over
    named dims (plus min/max) — expressive enough for E*S*wbytes-style
    budgets, reviewable in a diff, and with no access to anything else.
    """
    scope = dict(dims)
    scope["min"] = min
    scope["max"] = max
    return float(eval(formula, {"__builtins__": {}}, scope))  # noqa: S307


def _budget_for(contract: MemoryContract, program: str) -> str | None:
    """The budget formula covering ``program`` (exact key first, then
    fnmatch patterns — the serve ladder declares one formula for every
    ``score_b*`` rung)."""
    if program in contract.budgets:
        return contract.budgets[program]
    for pat, formula in contract.budgets.items():
        if fnmatch.fnmatchcase(program, pat):
            return formula
    return None


# --------------------------------------------------------------------------
# the admission oracle
# --------------------------------------------------------------------------


def predict_resident_bytes(
    model: Any, ladder: Any = None, precision: str = "float32"
) -> dict[str, Any]:
    """Predicted device-resident bytes for serving ``model`` — the
    static half of the HBM admission question, from model SHAPES alone
    (no arrays are built, no device is touched).

    Keys under ``"tables"`` are exactly the ledger's resident owners
    (``table/<coordinate>``; serve/tables.account_resident), so the
    prediction can be joined byte-for-byte against the measured
    watermark.

    ``rebuild_peak_bytes`` is the transient high-water mark of a
    structure-changing ``rebuild_from``: the new generation is built
    OFF-PATH while the old one keeps serving, so both are resident
    until the swap.
    """
    from photon_tpu.models.game import (
        FixedEffectModel,
        RandomEffectModel,
    )
    from photon_tpu.ops import precision as precision_mod

    resolved = precision_mod.resolve(precision)
    wbytes = 2 if resolved == "bfloat16" else 4
    tables: dict[str, float] = {}
    shard_width: dict[str, int] = {}
    n_random = 0
    for name, sub in model.items():
        if isinstance(sub, FixedEffectModel):
            d = int(sub.model.coefficients.means.shape[0])
            tables[f"table/{name}"] = float(d * wbytes)
            shard_width[sub.feature_shard_id] = max(
                shard_width.get(sub.feature_shard_id, 1), d
            )
        elif isinstance(sub, RandomEffectModel):
            e, s = (int(x) for x in sub.coefficients.shape)
            # weights [E,S] at storage width + projector [E,S] int32
            # (the projector never narrows; serve/tables.from_game_model)
            tables[f"table/{name}"] = float(e * s * (wbytes + 4))
            proj = np.asarray(sub.proj_all)
            width = int(proj.max(initial=-1)) + 1 if proj.size else 1
            shard_width[sub.feature_shard_id] = max(
                shard_width.get(sub.feature_shard_id, 1), width
            )
            n_random += 1
        else:
            raise TypeError(f"unknown sub-model type for {name!r}")
    total = float(sum(tables.values()))
    out: dict[str, Any] = {
        "precision": resolved,
        "tables": tables,
        "tables_total_bytes": total,
        "rebuild_peak_bytes": 2.0 * total,
    }
    if ladder is not None:
        # Request payloads stay a numpy-native float even over bf16
        # tables (serve/programs.ScorePrograms.dtype): 4 bytes/lane.
        payload = 4
        per_rung = {
            int(r): float(
                r * sum(shard_width.values()) * payload  # features
                + r * 4 * n_random  # int32 row codes
                + r * 4  # the score output
            )
            for r in ladder.rungs
        }
        out["per_rung_request_bytes"] = per_rung
        out["peak_bytes"] = total + max(per_rung.values())
    else:
        out["peak_bytes"] = total
    return out


# --------------------------------------------------------------------------
# checks
# --------------------------------------------------------------------------


def check_budgets(
    contract: MemoryContract, trace: MemoryTrace
) -> Iterator[Finding]:
    """Price every budget formula against the static walk, both ways."""
    tol = contract.tolerance
    for name, prog in trace.programs.items():
        formula = _budget_for(contract, name)
        if formula is None:
            yield _finding(
                contract,
                "memory-contract",
                f"traced program {name!r} has no declared budget: every "
                "audited entry point must carry a peak-HBM formula",
            )
            continue
        peak = program_peak(prog)
        dims = {**trace.dims, **prog.dims}
        try:
            declared = _price(formula, dims)
        except Exception as exc:  # noqa: BLE001 — a rotten formula is the finding
            yield _finding(
                contract,
                "memory-stale-formula",
                f"program {name!r}: budget formula {formula!r} no longer "
                f"evaluates over dims {sorted(dims)}: {exc!r}",
            )
            continue
        if peak > declared * tol:
            yield _finding(
                contract,
                "memory-undeclared-growth",
                f"program {name!r}: static peak {peak} B exceeds the "
                f"declared budget {formula!r} = {declared:.0f} B beyond "
                f"the {tol}x tolerance — a buffer grew that the "
                "contract does not price",
            )
        elif declared > peak * tol and declared - peak > 1024:
            yield _finding(
                contract,
                "memory-stale-formula",
                f"program {name!r}: declared budget {formula!r} = "
                f"{declared:.0f} B prices beyond {tol}x the static peak "
                f"{peak} B — the formula rotted above reality and would "
                "mask real growth",
            )
    for pat in contract.budgets:
        if not any(
            pat == name or fnmatch.fnmatchcase(name, pat)
            for name in trace.programs
        ):
            yield _finding(
                contract,
                "memory-contract",
                f"budget key {pat!r} matches no traced program — stale "
                "declaration",
            )


def check_donations(
    contract: MemoryContract, trace: MemoryTrace
) -> Iterator[Finding]:
    """Every probed donation must alias in the compiled HLO."""
    probed = set()
    for probe in trace.donation_probes:
        probed.add(probe.name)
        rep = donation_report(probe.lowered)
        if rep["declared"] != len(probe.declared):
            yield _finding(
                contract,
                "memory-dropped-donation",
                f"{probe.name}: {len(probe.declared)} donation(s) "
                f"declared at positions {tuple(probe.declared)} but the "
                f"traced program marks {rep['declared']} operand(s) "
                "donated — the donate_argnums drifted from the "
                "declaration",
            )
            continue
        if rep["aliased"] < rep["declared"]:
            dropped = rep["declared"] - rep["aliased"]
            yield _finding(
                contract,
                "memory-dropped-donation",
                f"{probe.name}: {dropped} of {rep['declared']} declared "
                f"donation(s) (operand position(s) "
                f"{tuple(rep['positions'])}) did not alias in the "
                "lowered module — XLA dropped the donation silently, "
                "both generations stay resident",
            )
    for name in contract.donations:
        if name not in probed:
            # Declared-but-unprobed donations (e.g. _solve_block, whose
            # operand assembly needs a full coordinate build) are noted,
            # not failed: the tier-1 use-after-donate rule covers their
            # call sites.
            trace.notes.append(
                f"donation map entry {name!r} declared at positions "
                f"{tuple(contract.donations[name])} is not probed "
                "against lowered HLO (covered by the tier-1 "
                "use-after-donate rule at its call sites)"
            )


def check_residents(
    contract: MemoryContract, trace: MemoryTrace
) -> Iterator[Finding]:
    """Resident-byte formulas vs built tables vs the admission oracle."""
    tol = contract.tolerance
    for probe in trace.residents:
        dims = {**trace.dims, **probe.dims}
        for owner, formula in contract.resident.items():
            measured = probe.measured.get(owner)
            if measured is None:
                yield _finding(
                    contract,
                    "memory-contract",
                    f"resident formula for {owner!r} matches no built "
                    f"table at precision {probe.precision} — stale "
                    "declaration",
                )
                continue
            try:
                declared = _price(formula, dims)
            except Exception as exc:  # noqa: BLE001
                yield _finding(
                    contract,
                    "memory-stale-formula",
                    f"resident {owner!r}: formula {formula!r} no longer "
                    f"evaluates: {exc!r}",
                )
                continue
            if measured > declared * tol:
                yield _finding(
                    contract,
                    "memory-undeclared-growth",
                    f"resident {owner!r} at {probe.precision}: built "
                    f"tables hold {measured:.0f} B, beyond {tol}x the "
                    f"declared {formula!r} = {declared:.0f} B",
                )
            elif declared > measured * tol:
                yield _finding(
                    contract,
                    "memory-stale-formula",
                    f"resident {owner!r} at {probe.precision}: declared "
                    f"{formula!r} = {declared:.0f} B prices beyond "
                    f"{tol}x the built {measured:.0f} B",
                )
        for owner, measured in probe.measured.items():
            predicted = probe.predicted.get(owner)
            if predicted is None or int(predicted) != int(measured):
                yield _finding(
                    contract,
                    "memory-contract",
                    f"admission-oracle drift at {probe.precision}: "
                    f"predict_resident_bytes says {predicted} B for "
                    f"{owner!r} but the built tables hold "
                    f"{measured:.0f} B — the static half of the "
                    "admission answer no longer matches reality",
                )


def check_transients(
    contract: MemoryContract, trace: MemoryTrace
) -> Iterator[Finding]:
    """Declared transient allowances (rebuild double-residency) vs the
    builder's computed transient peaks."""
    tol = contract.tolerance
    for name, formula in contract.transients.items():
        observed = trace.transient_values.get(name)
        if observed is None:
            yield _finding(
                contract,
                "memory-contract",
                f"transient allowance {name!r} has no computed value "
                "from the builder — stale declaration",
            )
            continue
        try:
            declared = _price(formula, trace.dims)
        except Exception as exc:  # noqa: BLE001
            yield _finding(
                contract,
                "memory-stale-formula",
                f"transient {name!r}: formula {formula!r} no longer "
                f"evaluates: {exc!r}",
            )
            continue
        if observed > declared * tol:
            yield _finding(
                contract,
                "memory-undeclared-growth",
                f"transient {name!r}: computed double-residency peak "
                f"{observed:.0f} B exceeds the declared allowance "
                f"{formula!r} = {declared:.0f} B beyond {tol}x",
            )
        elif declared > observed * tol:
            yield _finding(
                contract,
                "memory-stale-formula",
                f"transient {name!r}: declared allowance {formula!r} = "
                f"{declared:.0f} B prices beyond {tol}x the computed "
                f"{observed:.0f} B",
            )


CHECKS = (
    check_budgets,
    check_donations,
    check_residents,
    check_transients,
)


def run_checks(
    contract: MemoryContract, trace: MemoryTrace
) -> list[Finding]:
    """All memory checks over one contract's trace, suppressions
    applied (the tier-2 run_checks discipline: suppressed findings are
    kept, with their reasons, for the report)."""
    findings: list[Finding] = []
    for check in CHECKS:
        for f in check(contract, trace):
            reason = contract.suppress.get(f.rule)
            if reason is not None:
                f = dataclasses.replace(
                    f, suppressed=True, suppress_reason=reason
                )
            findings.append(f)
    return findings


# --------------------------------------------------------------------------
# shared tiny serving fixtures (abstract-trace scale; CPU-cheap)
# --------------------------------------------------------------------------


def _tiny_game_model(
    d: int, e: int, s: int, du: int, *, proj_seed: int, rng_seed: int,
    scale: float = 1.0,
):
    """The tier-2 serving/pilot fixture model, parameterized: one dense
    fixed effect + one random effect with a non-trivial projector."""
    import jax.numpy as jnp

    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(rng_seed)
    prng = np.random.default_rng(proj_seed)
    proj = np.sort(
        np.stack([prng.permutation(du)[:s] for _ in range(e)]), axis=1
    ).astype(np.int64)
    return GameModel({
        "global": FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(means=jnp.asarray(
                    scale * rng.normal(size=d).astype(np.float32)
                )),
                TaskType.LOGISTIC_REGRESSION,
            ),
            "features",
        ),
        "per-user": RandomEffectModel(
            coefficients=jnp.asarray(
                scale * rng.normal(size=(e, s)).astype(np.float32)
            ),
            random_effect_type="userId",
            feature_shard_id="userShard",
            task=TaskType.LOGISTIC_REGRESSION,
            proj_all=proj,
            entity_keys=tuple(str(i) for i in range(e)),
        ),
    })


def _measured_table_bytes(tables: Any) -> dict[str, float]:
    """tree_nbytes of the BUILT device arrays, keyed like the ledger's
    resident owners (serve/tables.account_resident)."""
    from photon_tpu.obs import ledger

    out: dict[str, float] = {}
    for n, t in tables.fixed.items():
        out[f"table/{n}"] = float(ledger.tree_nbytes(t.weights))
    for n, t in tables.random.items():
        out[f"table/{n}"] = float(
            ledger.tree_nbytes((t.weights, t.proj))
        )
    return out


def _score_rung_programs(
    programs: Any, rungs: Iterable[int]
) -> dict[str, ProgramMemory]:
    out: dict[str, ProgramMemory] = {}
    for r in rungs:
        traced = programs.trace(r)
        out[f"score_b{r}"] = ProgramMemory(
            name=f"score_b{r}",
            jaxpr=traced.jaxpr,
            lowered=traced.lower(),
            dims={"rung": float(r)},
        )
    return out


def _donating_swap_probe(shape, dtype) -> DonationProbe:
    """The serve reload's donating value swap — the PRODUCTION body
    (serve/tables._swap_values), lowered with donation ON. The runtime
    wrapper gates donation off on CPU backends to avoid per-call
    warnings; the audit must check the donating form regardless of the
    host backend, so it jits the body with the donation forced."""
    import jax

    from photon_tpu.serve.tables import _swap_values

    fn = jax.jit(_swap_values, donate_argnums=(0,))
    sds = jax.ShapeDtypeStruct(tuple(shape), dtype)
    return DonationProbe(
        name="serve.tables._swap_values",
        lowered=fn.trace(sds, sds).lower(),
        declared=(0,),
    )


# --------------------------------------------------------------------------
# contract builders (named by the MEMORY_AUDIT declarations)
# --------------------------------------------------------------------------


def build_fused_fit_memory() -> MemoryTrace:
    """Trace one fused-fit generation's three programs for the walk and
    probe the CD sweep's donating carry."""
    from photon_tpu.algorithm.coordinate_descent import _sub_add_donating
    from photon_tpu.algorithm.fused_fit import FusedFit
    from photon_tpu.analysis import program as tier2

    import jax

    est, data = tier2._tiny_glmix()
    datasets, _ = est.prepare(data)
    n = data.num_samples
    coords = est._build_coordinates(datasets, {}, {}, logical_rows=n)
    fused = FusedFit(
        coords, est.update_sequence, 2, set(), precision="float32"
    )
    mat = fused._mat_jit.trace(fused._mat_operands(coords))
    fit = fused.trace(coords)
    fit_warm = fused.trace(coords, tier2._zero_initial_models(coords))
    coord = coords["per-user"]
    ds = getattr(coord, "inner", coord).dataset
    programs = {
        "materialize": ProgramMemory(
            "materialize", mat.jaxpr, mat.lower()
        ),
        "fit": ProgramMemory("fit", fit.jaxpr, fit.lower()),
        "fit_warm": ProgramMemory(
            "fit_warm", fit_warm.jaxpr, fit_warm.lower()
        ),
    }
    sds = jax.ShapeDtypeStruct((n,), np.float32)
    probe = DonationProbe(
        name="algorithm.coordinate_descent._sub_add_donating",
        lowered=_sub_add_donating.trace(sds, sds, sds).lower(),
        declared=(0,),
    )
    return MemoryTrace(
        programs=programs,
        dims={
            "n": float(n),
            "d": 5.0,
            "du": 4.0,
            "e": float(ds.num_entities),
            "s": float(ds.max_sub_dim),
            "iters": 2.0,
            "coords": 2.0,
            "wbytes": 4.0,
        },
        donation_probes=[probe],
        notes=[
            "dims from the tier-2 tiny GLMix fixture (one dense fixed "
            "effect [n,d] + one random effect [e,s] over du features); "
            "f32 storage",
        ],
    )


def build_serving_memory() -> MemoryTrace:
    """The serve score ladder's per-rung peaks + the reload donation."""
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables

    d, e, s, du = 5, 7, 3, 6
    model = _tiny_game_model(d, e, s, du, proj_seed=1234, rng_seed=20260803)
    ladder = ShapeLadder((1, 8, 64))
    tables = CoefficientTables.from_game_model(model)
    programs = ScorePrograms(tables, ladder=ladder, compile_now=False)
    return MemoryTrace(
        programs=_score_rung_programs(programs, ladder.rungs),
        dims={
            "d": float(d),
            "e": float(e),
            "s": float(s),
            "du": float(du),
            "wbytes": 4.0,
        },
        donation_probes=[
            _donating_swap_probe((e, s), np.float32),
        ],
        notes=[
            f"score ladder {ladder.rungs} over the tier-2 serving "
            "fixture model; tables f32",
        ],
    )


def build_serve_kernel_memory() -> MemoryTrace:
    """The fused serve kernel's per-rung peaks (PHOTON_SERVE_KERNEL
    forced so the pallas path is what gets walked; env restored after).

    The kernel's memory story vs the jit chain is the ABSENCE of the
    gathered intermediates: the live set is the resident tables plus
    the padded request payloads and the [rung] output — no [rung, s]
    gathered coefficient rows, no [rung, k, s] one-hot operand. The
    budget formula in ops/serve_kernel.MEMORY_AUDIT prices exactly
    that, so a lowering regression that rematerializes a gather
    surfaces as memory-undeclared-growth here."""
    import os

    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables

    d, e, s, du = 5, 7, 3, 6
    model = _tiny_game_model(
        d, e, s, du, proj_seed=1234, rng_seed=20260803
    )
    ladder = ShapeLadder((1, 8, 64))
    prev = os.environ.get("PHOTON_SERVE_KERNEL")
    os.environ["PHOTON_SERVE_KERNEL"] = "force"
    try:
        tables = CoefficientTables.from_game_model(model)
        programs = ScorePrograms(
            tables, ladder=ladder, compile_now=False
        )
        if not programs.use_kernel:
            raise RuntimeError(
                "PHOTON_SERVE_KERNEL=force did not engage the fused "
                "kernel — the serve-kernel memory contract audits "
                "nothing"
            )
        traced = {
            f"serve_kernel_b{r}": ProgramMemory(
                name=f"serve_kernel_b{r}",
                jaxpr=(t := programs.trace(r)).jaxpr,
                lowered=t.lower(),
                dims={"rung": float(r)},
            )
            for r in ladder.rungs
        }
    finally:
        if prev is None:  # photon: ignore[spmd-host-divergence] -- env save/restore of the audit fixture's kernel flag; host-local tooling, not fleet code
            os.environ.pop("PHOTON_SERVE_KERNEL", None)
        else:
            os.environ["PHOTON_SERVE_KERNEL"] = prev
    return MemoryTrace(
        programs=traced,
        dims={
            "d": float(d),
            "e": float(e),
            "s": float(s),
            "du": float(du),
            "wbytes": 4.0,
        },
        notes=[
            f"fused kernel over ladder {ladder.rungs}, tier-2 serving "
            "fixture model, f32 tables, interpret-path lowering",
        ],
    )


def build_tables_memory() -> MemoryTrace:
    """Resident tables at BOTH precisions vs the admission oracle, and
    the rebuild_from double-residency transient."""
    from photon_tpu.serve.tables import CoefficientTables

    d, e, s, du = 5, 7, 3, 6
    model = _tiny_game_model(d, e, s, du, proj_seed=1234, rng_seed=20260803)
    residents: list[ResidentProbe] = []
    rebuild_peak = 0.0
    for precision, wbytes in (("float32", 4.0), ("bfloat16", 2.0)):
        tables = CoefficientTables.from_game_model(model, precision)
        predicted = predict_resident_bytes(model, precision=precision)
        residents.append(
            ResidentProbe(
                precision=precision,
                dims={"wbytes": wbytes},
                measured=_measured_table_bytes(tables),
                predicted=dict(predicted["tables"]),
            )
        )
        if precision == "float32":
            rebuild_peak = predicted["rebuild_peak_bytes"]
    return MemoryTrace(
        dims={
            "d": float(d),
            "e": float(e),
            "s": float(s),
            "du": float(du),
            "wbytes": 4.0,  # transient priced at the f32 build
        },
        donation_probes=[_donating_swap_probe((e, s), np.float32)],
        residents=residents,
        transient_values={"rebuild_from": rebuild_peak},
        notes=[
            "tables built at f32 AND bf16: the resident formulas price "
            "the precision width on both sides of the admission oracle",
        ],
    )


def build_pilot_serving_memory() -> MemoryTrace:
    """The pilot's serving bundle: its ladder rungs' peaks plus the
    promotion rebuild allowance."""
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables

    d, e, s, du = 5, 6, 3, 5
    model = _tiny_game_model(d, e, s, du, proj_seed=99, rng_seed=20260804)
    ladder = ShapeLadder((1, 8))
    tables = CoefficientTables.from_game_model(model)
    programs = ScorePrograms(tables, ladder=ladder, compile_now=False)
    predicted = predict_resident_bytes(model, ladder=ladder)
    return MemoryTrace(
        programs=_score_rung_programs(programs, ladder.rungs),
        dims={
            "d": float(d),
            "e": float(e),
            "s": float(s),
            "du": float(du),
            "wbytes": 4.0,
        },
        transient_values={
            "promotion_rebuild": predicted["rebuild_peak_bytes"]
        },
        notes=[
            f"pilot ladder {ladder.rungs} over the tier-2 pilot fixture "
            "model (PilotServer defaults, f32 tables)",
        ],
    )


_BUILDERS: dict[str, Callable[[], MemoryTrace]] = {
    "build_fused_fit_memory": build_fused_fit_memory,
    "build_serve_kernel_memory": build_serve_kernel_memory,
    "build_serving_memory": build_serving_memory,
    "build_tables_memory": build_tables_memory,
    "build_pilot_serving_memory": build_pilot_serving_memory,
}


def contract_from_declaration(spec: dict) -> MemoryContract:
    builder = spec.get("builder")
    if builder not in _BUILDERS:
        raise ValueError(
            f"MEMORY_AUDIT declaration {spec.get('name')!r} names unknown "
            f"builder {builder!r}"
        )
    return MemoryContract(
        name=spec["name"],
        entry=spec["entry"],
        build=_BUILDERS[builder],
        covers=tuple(spec.get("covers", ())),
        budgets=dict(spec.get("budgets", {})),
        resident=dict(spec.get("resident", {})),
        transients=dict(spec.get("transients", {})),
        donations={
            k: tuple(v) for k, v in dict(spec.get("donations", {})).items()
        },
        tolerance=float(spec.get("tolerance", 1.5)),
        suppress=dict(spec.get("suppress", {})),
    )


def collect_contracts() -> list[MemoryContract]:
    """The repo's declared memory-contract registry."""
    specs: list[dict] = []
    for modname in MEMORY_DECLARING_MODULES:
        mod = importlib.import_module(modname)
        decl = getattr(mod, "MEMORY_AUDIT", None)
        if decl is None:
            raise ValueError(
                f"{modname} is a memory-declaring module but exports no "
                "MEMORY_AUDIT"
            )
        specs.extend(decl if isinstance(decl, (list, tuple)) else [decl])
    return [contract_from_declaration(s) for s in specs]


def check_coverage(
    contracts: Iterable[MemoryContract],
) -> list[Finding]:
    """Every tier-2 entry point carries a memory contract or a reasoned
    waiver — and no waiver outlives its reason."""
    from photon_tpu.analysis import program as tier2

    tier2_names = {c.name for c in tier2.collect_contracts()}
    covered: dict[str, str] = {}
    findings: list[Finding] = []
    anchor = MemoryContract(
        name="memory-coverage", entry="analysis.memory", build=MemoryTrace
    )
    for c in contracts:
        for name in c.covers:
            if name not in tier2_names:
                findings.append(
                    _finding(
                        anchor,
                        "memory-contract",
                        f"memory contract {c.name!r} covers unknown "
                        f"tier-2 contract {name!r}",
                    )
                )
            covered[name] = c.name
    for name, reason in TIER2_WAIVERS.items():
        if name not in tier2_names:
            findings.append(
                _finding(
                    anchor,
                    "memory-contract",
                    f"stale waiver: {name!r} is not a tier-2 contract",
                )
            )
        elif name in covered:
            findings.append(
                _finding(
                    anchor,
                    "memory-contract",
                    f"stale waiver: {name!r} is covered by memory "
                    f"contract {covered[name]!r} — drop the waiver",
                )
            )
        if not reason or not reason.strip():
            findings.append(
                _finding(
                    anchor,
                    "memory-contract",
                    f"waiver for {name!r} has no reason — a waiver "
                    "without a reason is a gap, not a decision",
                )
            )
    for name in sorted(tier2_names):
        if name not in covered and name not in TIER2_WAIVERS:
            findings.append(
                _finding(
                    anchor,
                    "memory-contract",
                    f"tier-2 contract {name!r} has no MEMORY_AUDIT "
                    "coverage and no waiver: declare its peak-HBM "
                    "budget or add a reasoned TIER2_WAIVERS entry",
                )
            )
    return findings


# --------------------------------------------------------------------------
# the audit driver
# --------------------------------------------------------------------------


def _xla_memory_analysis(prog: ProgramMemory) -> dict[str, float] | None:
    """XLA's own compiled memory accounting, where the backend exposes
    it — the cross-check column next to the static walk (works on CPU
    in current jax; absent backends degrade to walk-only)."""
    if prog.lowered is None:
        return None
    try:
        stats = prog.lowered.compile().memory_analysis()
    except Exception:  # noqa: BLE001 — optional cross-check only
        return None
    if stats is None:
        return None
    out: dict[str, float] = {}
    for field in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(stats, field, None)
        if v is not None:
            out[field] = float(v)
    return out or None


def audit(
    contracts: Iterable[MemoryContract] | None = None,
    *,
    with_xla: bool = True,
) -> tuple[list[Finding], dict]:
    """Run every memory contract; returns (findings, report).

    Builds run under ``jax.enable_x64(False)`` (the tier-2 discipline:
    audited traces match the production f32 configuration even when the
    host process enabled x64).
    """
    import jax

    findings: list[Finding] = []
    report: dict[str, Any] = {"contracts": {}, "waivers": dict(TIER2_WAIVERS)}
    with jax.enable_x64(False):
        resolved = (
            collect_contracts() if contracts is None else list(contracts)
        )
        findings.extend(check_coverage(resolved))
        for contract in resolved:
            entry: dict[str, Any] = {
                "entry": contract.entry,
                "covers": list(contract.covers),
                "programs": {},
                "donations": {},
                "notes": [],
            }
            report["contracts"][contract.name] = entry
            try:
                trace = contract.build()
            except Exception as exc:  # noqa: BLE001 — any builder crash is a finding
                findings.append(
                    _finding(
                        contract,
                        "memory-contract",
                        f"contract builder failed: {exc!r}",
                    )
                )
                continue
            findings.extend(run_checks(contract, trace))
            for name, prog in trace.programs.items():
                dims = {**trace.dims, **prog.dims}
                formula = _budget_for(contract, name)
                pentry: dict[str, Any] = {
                    "static_peak_bytes": program_peak(prog),
                    "budget": formula,
                }
                if formula is not None:
                    try:
                        pentry["budget_bytes"] = _price(formula, dims)
                    except Exception:  # noqa: BLE001 — already a finding
                        pass
                if with_xla:
                    xla = _xla_memory_analysis(prog)
                    if xla is not None:
                        pentry["xla_memory_analysis"] = xla
                entry["programs"][name] = pentry
            for probe in trace.donation_probes:
                entry["donations"][probe.name] = donation_report(
                    probe.lowered
                )
            if trace.residents:
                entry["residents"] = [
                    {
                        "precision": p.precision,
                        "measured": dict(p.measured),
                        "predicted": dict(p.predicted),
                    }
                    for p in trace.residents
                ]
            if trace.transient_values:
                entry["transients"] = dict(trace.transient_values)
            entry["notes"] = list(trace.notes)
    findings.sort(key=lambda f: (f.path, f.rule, f.message))
    return findings, report
