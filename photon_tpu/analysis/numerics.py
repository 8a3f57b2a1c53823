"""Tier 5: the numerics auditor — dtype-flow verification of the
mixed-precision policy on the traced jaxprs.

``python -m photon_tpu.analysis --numerics``

The roofline push made bf16 storage with f32 accumulators the default
bench path (PERFORMANCE.md), but until this tier the only guard was the
tier-1 ``bf16-accumulation`` AST rule — a textual pattern-match that
cannot see through helper indirection, ``preferred_element_type``
plumbing, or scan carries. This tier re-traces the audited programs
abstractly (no device, same harness as tiers 2 and 4) and walks a
**dtype-provenance lattice** over each jaxpr, recursing into
scan/while/cond/pjit/custom-call bodies and the Pallas kernel boundary,
to verify the policy *semantically*:

1. **Accumulation-dtype audit** — every reduction-class eqn
   (``reduce_sum``, ``dot_general``, scatter/segment reductions, the
   Pallas segment-reduce kernel, scan carries that accumulate) whose
   operand lineage carries bf16 must accumulate in f32
   (``numerics-bf16-accumulation``).
2. **Cast census** — pointless f32→bf16→f32 round-trips
   (``numerics-cast-roundtrip``), downcasts of accumulator outputs that
   are then RE-reduced (``numerics-acc-downcast``), and per-iteration
   re-roundings of loop-carried state inside scan/while bodies
   (``numerics-scan-recast``). Deliberate instances (the fused fit's
   idempotent score quantization, its bf16 score carries) are
   suppressed per contract with a written reason.
3. **Static error budgets** — each contract declares a worst-case
   relative-error budget per program as a formula over the builder's
   dims (the MEMORY_AUDIT formula language plus the rounding constants
   ``u16`` = 2^-9 and ``u32`` = 2^-24). The auditor derives a bound
   from the cast graph and the static reduction lengths::

       derived = u16 * max_rounds + u32 * reduce_len

   where ``max_rounds`` is the deepest chain of bf16 roundings along
   any dataflow path (scan bodies multiply their per-iteration deltas
   by the static trip count) and ``reduce_len`` is the summed static
   length of every f32 accumulation over bf16-lineage operands (the
   f32 accumulator's own rounding grows with the reduction length).
   Gated BOTH directions at the contract tolerance, like tier 4:
   undeclared error growth (``numerics-undeclared-error``) and rotten
   budgets (``numerics-stale-budget``) both fail. This ties the
   PERFORMANCE.md per-family parity tolerances to a derivation.
4. **Reduction-determinism census** — every order-nondeterministic
   primitive family present in a program (``scatter-add`` and friends)
   must be declared deterministic-by-construction in the contract
   (e.g. "sorted bucket-slab segment ids") or carry a reasoned waiver
   (``numerics-nondeterministic-reduce``); stale declarations are
   contract findings.
5. **Coverage gate** — every tier-2 PROGRAM_AUDIT name must be claimed
   by a ``NUMERICS_AUDIT`` contract or a reasoned ``TIER2_WAIVERS``
   entry; stale waivers are findings (the tier-4 discipline).

Plus the **unstable-exp check** (``numerics-unstable-exp``): an ``exp``
whose operand carries no dominating upper bound (no ``min``/``clamp``
on the path, no ``-|x|`` shape) feeding a reduction — the failure mode
the Poisson linkage had before its margin clamp (ops/losses.py).

Contracts are plain-data ``NUMERICS_AUDIT`` dicts declared beside the
code they audit (ops/precision.py, algorithm/fused_fit.py,
ops/segment_reduce.py, serve/programs.py), naming a builder in this
module — importing the audited modules never imports the analysis
machinery. See ANALYSIS.md (tier 5) for the contract schema.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import importlib
from collections import Counter
from typing import Any, Callable, Iterable, Iterator

import numpy as np

from photon_tpu.analysis.core import Finding

NUMERICS_RULES: dict[str, str] = {
    "numerics-bf16-accumulation": (
        "a reduction-class eqn with bf16 operand lineage accumulates "
        "below f32 (bf16 dot_general/reduce/scatter output, or a bf16 "
        "scan carry that accumulates) — the semantic form of the "
        "tier-1 bf16-accumulation rule"
    ),
    "numerics-cast-roundtrip": (
        "a single-use f32->bf16->f32 cast round-trip: the value is "
        "rounded twice and never stored — either a wasted double "
        "rounding or an intentional quantization that needs a reason"
    ),
    "numerics-acc-downcast": (
        "an f32 accumulator output is downcast to bf16 and then "
        "RE-reduced — the accumulated precision is thrown away "
        "between reduction stages"
    ),
    "numerics-scan-recast": (
        "a loop-carried value is re-rounded to bf16 every iteration "
        "inside a scan/while body — one rounding per trip compounds "
        "across the loop"
    ),
    "numerics-unstable-exp": (
        "an exp() whose operand carries no dominating upper bound "
        "feeds a reduction — a large margin overflows to inf and "
        "poisons the whole accumulation (the raw-exp Poisson bug)"
    ),
    "numerics-undeclared-error": (
        "a program's derived worst-case relative-error bound exceeds "
        "its declared budget formula beyond the contract tolerance — "
        "error grew that the contract does not price"
    ),
    "numerics-stale-budget": (
        "a declared error budget prices far above the derived bound "
        "(or no longer evaluates) — the contract rotted and would "
        "mask real error growth"
    ),
    "numerics-nondeterministic-reduce": (
        "an order-nondeterministic reduction family (scatter-add, "
        "unsorted segment ops) appears in a program without a "
        "deterministic-by-construction declaration"
    ),
    "numerics-contract": (
        "numerics-contract declaration, coverage, or builder "
        "integrity error (uncovered tier-2 entry point, stale "
        "waiver or declaration, builder crash)"
    ),
}

# Modules that declare numerics contracts (each exports NUMERICS_AUDIT —
# one declaration dict or a list of them). Plain data, like the tier-2
# PROGRAM_AUDIT / tier-4 MEMORY_AUDIT hooks.
NUMERICS_DECLARING_MODULES = (
    "photon_tpu.ops.precision",
    "photon_tpu.algorithm.fused_fit",
    "photon_tpu.ops.segment_reduce",
    "photon_tpu.ops.serve_kernel",
    "photon_tpu.serve.programs",
)

# Tier-2 contracts with NO numerics contract, each with its reason. The
# coverage check keeps this list honest: a new tier-2 contract fails
# the audit until someone either audits its dtype flow or writes its
# waiver down here.
TIER2_WAIVERS: dict[str, str] = {
    "fused-cache-key": (
        "key-only contract — traces no programs; precision is one of "
        "its declared key fields and the fused-fit numerics contract "
        "audits the programs the keys select"
    ),
    "unfused-coordinate-update": (
        "the unfused CD path is the f32 debugging fallback; it never "
        "receives bf16 operands (precision is plumbed only through "
        "FusedFit) and its reductions are covered by the fused-fit "
        "contract's f32 control program"
    ),
    "newton-kernel": (
        "executes only inline inside the fused-fit program — its eqns "
        "are walked by the fused-fit contract's recursion; the f32-only "
        "Pallas variant gates itself off bf16 slabs (PERFORMANCE.md)"
    ),
    "mesh-sharding": (
        "sharding annotations do not change dtype flow; the replicated "
        "fused programs this tier walks are the same jaxprs the mesh "
        "partitions, and cross-device psum determinism needs the mesh "
        "geometry (ROADMAP item 1's verification harness)"
    ),
    "ingest-pipeline": (
        "host-side ETL at f64/f32 numpy; the device programs it feeds "
        "are audited by the fused-fit contract"
    ),
    "streaming-ingest": (
        "host-side shard streaming; same story as ingest-pipeline"
    ),
    "telemetry": "host-side spans/counters; no float device programs",
    "trace": "host-side chrome-trace writer; no device programs",
    "monitor": "host-side HTTP surface; no device programs",
    "ledger": (
        "the ledger measures seconds and bytes in f64 host floats; it "
        "traces no device reductions"
    ),
    "health": (
        "sketches/calibration accumulate in f64 host floats; the "
        "device-side sentinel reduces are f32-only O(1) scalars"
    ),
    "pilot": (
        "the pilot serves the same ScorePrograms ladder the serving "
        "numerics contract audits and trains through the fused-fit "
        "contract's programs; it adds no reductions of its own"
    ),
    "resilience-retry": (
        "host-side retry machinery; zero device programs is already "
        "its tier-2 contract"
    ),
    "fleet-obs": (
        "host-side bundle shipping and trace merge in f64 host "
        "floats; its tier-2 contract proves byte-identical device "
        "programs with the fleet armed — it traces no reductions"
    ),
    "evaluation-scoring": (
        "evaluators reduce f32 scores at f64 numpy precision on host; "
        "no bf16 operand can reach them (scores are upcast at the "
        "serve/fit boundary)"
    ),
}

# Rounding constants of the budget-formula language: one bf16 storage
# rounding is 2^-9 relative (8 mantissa bits incl. the implicit one),
# one f32 accumulation step is 2^-24.
U16 = 2.0 ** -9
U32 = 2.0 ** -24

# Order-nondeterministic primitive families for the determinism census:
# XLA does not pin the combination order of colliding scatter indices,
# so any of these in a program needs a deterministic-by-construction
# declaration (sorted ids, unique ids) or a reasoned waiver.
NONDETERMINISTIC_FAMILIES = frozenset({
    "scatter-add",
    "scatter-mul",
    "scatter",
})


# --------------------------------------------------------------------------
# data model
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ProgramNumerics:
    """One traced entry point under the dtype-flow walk: its closed
    jaxpr and per-program dims merged over the trace dims when pricing
    error-budget formulas."""

    name: str
    jaxpr: Any
    dims: dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class NumericsTrace:
    """Everything a contract builder hands the checks."""

    programs: dict[str, ProgramNumerics] = dataclasses.field(
        default_factory=dict
    )
    dims: dict[str, float] = dataclasses.field(default_factory=dict)
    notes: list[str] = dataclasses.field(default_factory=list)
    # memoized flow states, keyed by program name (filled lazily)
    _flows: dict[str, "FlowState"] = dataclasses.field(
        default_factory=dict, repr=False
    )


@dataclasses.dataclass(frozen=True)
class NumericsContract:
    """One NUMERICS_AUDIT declaration, resolved."""

    name: str
    entry: str
    build: Callable[[], NumericsTrace]
    covers: tuple[str, ...] = ()
    # program name (or fnmatch pattern) -> error-budget formula over
    # dims (+ u16/u32/min/max)
    budgets: dict[str, str] = dataclasses.field(default_factory=dict)
    # "program:family" fnmatch pattern -> deterministic-by-construction
    # reason for the determinism census
    deterministic: dict[str, str] = dataclasses.field(default_factory=dict)
    tolerance: float = 1.5
    suppress: dict[str, str] = dataclasses.field(default_factory=dict)


def _finding(
    contract: NumericsContract, rule: str, message: str
) -> Finding:
    return Finding(
        rule=rule, path=f"<{contract.name}>", line=0, col=0, message=message
    )


# --------------------------------------------------------------------------
# the dtype-provenance lattice
# --------------------------------------------------------------------------


@dataclasses.dataclass
class VarInfo:
    """Per-value lattice state, joined across operands at each eqn."""

    bf16: bool = False          # lineage passed through bf16 storage
    rounds: int = 0             # deepest chain of narrowing roundings
    lo_bounded: bool = False    # value has a static lower bound
    hi_bounded: bool = False    # value has a static upper bound
    unstable_exp: bool = False  # derives from exp() of an unbounded arg
    acc_out: bool = False       # is (a cast/reshape of) an f32
    #                             accumulator output over bf16 lineage
    carries: frozenset = frozenset()  # loop-carry tokens in the lineage

    def join(self, other: "VarInfo") -> "VarInfo":
        return VarInfo(
            bf16=self.bf16 or other.bf16,
            rounds=max(self.rounds, other.rounds),
            lo_bounded=False,
            hi_bounded=False,
            unstable_exp=self.unstable_exp or other.unstable_exp,
            acc_out=False,
            carries=self.carries | other.carries,
        )


@dataclasses.dataclass
class FlowEvent:
    kind: str    # a NUMERICS_RULES key minus the "numerics-" prefix
    detail: str


@dataclasses.dataclass
class FlowState:
    """Accumulated result of walking one program's jaxpr."""

    events: list[FlowEvent] = dataclasses.field(default_factory=list)
    families: set[str] = dataclasses.field(default_factory=set)
    max_rounds: int = 0
    reduce_len: float = 0.0  # summed static length of f32 accumulations
    #                          over bf16-lineage operands
    notes: list[str] = dataclasses.field(default_factory=list)

    @property
    def derived_bound(self) -> float:
        return U16 * self.max_rounds + U32 * self.reduce_len


def _aval(v: Any) -> Any:
    a = getattr(v, "aval", None)
    # pallas kernels take Refs; unwrap to the carried array aval
    return getattr(a, "inner_aval", a)


def _dtype(v: Any):
    a = _aval(v)
    return getattr(a, "dtype", None)


def _shape(v: Any) -> tuple:
    a = _aval(v)
    return tuple(getattr(a, "shape", ()) or ())


def _is_bf16(dt) -> bool:
    return dt is not None and str(dt) == "bfloat16"


def _is_f32(dt) -> bool:
    return dt is not None and str(dt) == "float32"


def _is_narrow_float(dt) -> bool:
    return dt is not None and str(dt) in (
        "bfloat16", "float16", "float8_e4m3fn", "float8_e5m2"
    )


def _is_float(dt) -> bool:
    return dt is not None and (
        str(dt).startswith("float") or str(dt).startswith("bfloat")
    )


def _is_literal(v: Any) -> bool:
    return not hasattr(v, "count") and hasattr(v, "val")


def _count(shape: Iterable[int]) -> float:
    out = 1.0
    for s in shape:
        out *= float(s)
    return out


# ops that move values without arithmetic: acc_out survives them (a
# reshape of an accumulator output is still an accumulator output),
# everything else is joined generically
_SHAPE_OPS = frozenset({
    "convert_element_type", "broadcast_in_dim", "reshape", "transpose",
    "squeeze", "slice", "concatenate", "rev", "copy", "stop_gradient",
    "expand_dims",
})

# reduction-class primitives: (name -> True) means the output dtype IS
# the accumulator dtype
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_prod", "reduce_window_sum", "cumsum",
    "cumlogsumexp", "dot_general",
})

_PASSTHROUGH_TRACE = frozenset({
    "convert_element_type", "broadcast_in_dim", "reshape", "transpose",
    "squeeze", "copy", "stop_gradient",
})


def _closed(j: Any) -> Any:
    """Normalize ClosedJaxpr-or-Jaxpr to the open Jaxpr."""
    return getattr(j, "jaxpr", j)


def _literal_info(v: Any) -> VarInfo:
    return VarInfo(
        bf16=_is_bf16(_dtype(v)),
        rounds=1 if _is_bf16(_dtype(v)) else 0,
        lo_bounded=True,
        hi_bounded=True,
    )


def _seed_info(v: Any) -> VarInfo:
    dt = _dtype(v)
    if _is_bf16(dt):
        # an entry operand already stored in bf16 carries one rounding
        # relative to the real-valued quantity it represents
        return VarInfo(bf16=True, rounds=1)
    return VarInfo()


def _operand_infos(
    eqn: Any, env: dict, default: Callable[[Any], VarInfo] = _seed_info
) -> list[VarInfo]:
    out = []
    for v in eqn.invars:
        if _is_literal(v):
            out.append(_literal_info(v))
        else:
            out.append(env.get(v) or default(v))
    return out


def _defining(jaxpr: Any) -> dict:
    return {ov: eqn for eqn in jaxpr.eqns for ov in eqn.outvars}


def _traces_to(
    var: Any, target: Any, defs: dict, depth: int = 0
) -> bool:
    """Does ``var``'s def chain reach ``target`` through arithmetic
    accumulation ops and shape/cast passthroughs only? (Used to decide
    whether a scan carry ACCUMULATES — new = old + delta — versus being
    rebuilt from scratch each iteration.)"""
    if depth > 64:
        return False
    if var is target:
        return True
    eqn = defs.get(var)
    if eqn is None:
        return False
    if eqn.primitive.name in _PASSTHROUGH_TRACE or eqn.primitive.name in (
        "add", "sub", "add_any"
    ):
        return any(
            _traces_to(v, target, defs, depth + 1)
            for v in eqn.invars
            if not _is_literal(v)
        )
    return False


def analyze_jaxpr(
    jaxpr: Any,
    in_infos: list[VarInfo],
    state: FlowState,
    *,
    in_loop: bool = False,
) -> list[VarInfo]:
    """Walk one (open) jaxpr with the given entry infos; returns the
    outvar infos and accumulates events/lengths into ``state``."""
    jaxpr = _closed(jaxpr)
    env: dict[Any, VarInfo] = {}
    for v, info in zip(jaxpr.invars, in_infos):
        env[v] = info
    for v in jaxpr.constvars:
        env[v] = _seed_info(v)

    uses: Counter = Counter()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not _is_literal(v):
                uses[v] += 1
    for v in jaxpr.outvars:
        if not _is_literal(v):
            uses[v] += 1
    defs = _defining(jaxpr)

    for eqn in jaxpr.eqns:
        _apply_eqn(eqn, env, state, uses, defs, in_loop=in_loop)
        for ov in eqn.outvars:
            info = env.get(ov)
            if info is not None and info.rounds > state.max_rounds:
                state.max_rounds = info.rounds

    out: list[VarInfo] = []
    for v in jaxpr.outvars:
        if _is_literal(v):
            out.append(_literal_info(v))
        else:
            out.append(env.get(v) or _seed_info(v))
    return out


def _join_all(infos: list[VarInfo]) -> VarInfo:
    out = VarInfo()
    for i in infos:
        out = out.join(i)
    return out


def _apply_eqn(
    eqn: Any,
    env: dict,
    state: FlowState,
    uses: Counter,
    defs: dict,
    *,
    in_loop: bool,
) -> None:
    name = eqn.primitive.name
    infos = _operand_infos(eqn, env)
    joined = _join_all(infos)

    if name == "convert_element_type":
        _apply_convert(eqn, env, state, uses, defs, infos[0],
                       in_loop=in_loop)
        return

    if name == "scan":
        _apply_scan(eqn, env, state, infos)
        return
    if name == "while":
        _apply_while(eqn, env, state, infos)
        return
    if name == "cond":
        _apply_cond(eqn, env, state, infos)
        return
    if name == "pallas_call":
        _apply_pallas(eqn, env, state, infos)
        return
    sub = _mapped_sub_jaxpr(eqn)
    if sub is not None:
        outs = analyze_jaxpr(sub, infos, state, in_loop=in_loop)
        for ov, info in zip(eqn.outvars, outs):
            env[ov] = info
        return

    if name in _REDUCE_PRIMS:
        _apply_reduction(eqn, env, state, infos, joined)
        return
    if name in NONDETERMINISTIC_FAMILIES:
        state.families.add(name)
        _apply_scatter(eqn, env, state, infos, joined)
        return
    if name == "exp":
        op = infos[0]
        out = joined
        out = dataclasses.replace(
            out,
            lo_bounded=True,
            hi_bounded=op.hi_bounded,
            unstable_exp=op.unstable_exp or not op.hi_bounded,
        )
        env[eqn.outvars[0]] = out
        return

    # bounds-aware elementwise transfer
    out = joined
    if name in ("min", "max"):
        a, b = infos[0], infos[1]
        if name == "min":
            out = dataclasses.replace(
                out,
                hi_bounded=a.hi_bounded or b.hi_bounded,
                lo_bounded=a.lo_bounded and b.lo_bounded,
            )
        else:
            out = dataclasses.replace(
                out,
                lo_bounded=a.lo_bounded or b.lo_bounded,
                hi_bounded=a.hi_bounded and b.hi_bounded,
            )
    elif name == "clamp":
        lo, _x, hi = infos[0], infos[1], infos[2]
        out = dataclasses.replace(
            out, lo_bounded=lo.lo_bounded, hi_bounded=hi.hi_bounded
        )
    elif name == "abs":
        out = dataclasses.replace(out, lo_bounded=True,
                                  hi_bounded=infos[0].hi_bounded
                                  and infos[0].lo_bounded)
    elif name == "neg":
        out = dataclasses.replace(
            out,
            lo_bounded=infos[0].hi_bounded,
            hi_bounded=infos[0].lo_bounded,
        )
    elif name in ("logistic", "tanh", "erf", "sin", "cos", "sign"):
        out = dataclasses.replace(out, lo_bounded=True, hi_bounded=True)
    elif name in ("add", "sub"):
        a, b = infos[0], infos[1]
        if name == "add":
            out = dataclasses.replace(
                out,
                lo_bounded=a.lo_bounded and b.lo_bounded,
                hi_bounded=a.hi_bounded and b.hi_bounded,
            )
        else:
            out = dataclasses.replace(
                out,
                lo_bounded=a.lo_bounded and b.hi_bounded,
                hi_bounded=a.hi_bounded and b.lo_bounded,
            )
    elif name in _SHAPE_OPS:
        # pure data movement: bounds AND accumulator-output status ride
        out = dataclasses.replace(
            out,
            lo_bounded=infos[0].lo_bounded,
            hi_bounded=infos[0].hi_bounded,
            acc_out=infos[0].acc_out,
        )
    elif name == "select_n":
        cases = infos[1:]
        out = dataclasses.replace(
            out,
            lo_bounded=all(c.lo_bounded for c in cases),
            hi_bounded=all(c.hi_bounded for c in cases),
        )
    for ov in eqn.outvars:
        env[ov] = out


def _apply_convert(
    eqn: Any,
    env: dict,
    state: FlowState,
    uses: Counter,
    defs: dict,
    op: VarInfo,
    *,
    in_loop: bool,
) -> None:
    src = eqn.invars[0]
    dst = eqn.outvars[0]
    src_dt, dst_dt = _dtype(src), _dtype(dst)
    out = dataclasses.replace(
        op, lo_bounded=op.lo_bounded, hi_bounded=op.hi_bounded
    )
    narrowing = (
        _is_float(src_dt)
        and _is_narrow_float(dst_dt)
        and not _is_narrow_float(src_dt)
    )
    if narrowing:
        out = dataclasses.replace(
            out, bf16=True, rounds=op.rounds + 1, acc_out=op.acc_out
        )
        # downcast of a fresh accumulator output: remembered; flagged
        # only if the bf16 value is re-reduced (_apply_reduction)
        if in_loop and op.carries:
            state.events.append(FlowEvent(
                "scan-recast",
                f"{_src(eqn)}: loop-carried value re-rounded to "
                f"{dst_dt} every iteration",
            ))
        # pointless round-trip: this bf16 value's ONLY use is an
        # immediate upcast — the value is rounded twice, stored never
        if uses.get(dst, 0) == 1:
            for e2 in _consumers_of(dst, defs, uses):
                if (
                    e2.primitive.name == "convert_element_type"
                    and not _is_narrow_float(_dtype(e2.outvars[0]))
                ):
                    state.events.append(FlowEvent(
                        "cast-roundtrip",
                        f"{_src(eqn)}: f32->bf16->f32 round-trip "
                        "(single-use downcast immediately upcast)",
                    ))
    else:
        out = dataclasses.replace(out, acc_out=op.acc_out)
    env[dst] = out


def _consumers_of(var: Any, defs: dict, uses: Counter) -> list:
    # defs maps outvar -> eqn; consumers need the eqn list — walk the
    # defining jaxpr's eqns lazily via the defs values' containers
    seen = []
    for eqn in {id(e): e for e in defs.values()}.values():
        if any(v is var for v in eqn.invars):
            seen.append(eqn)
    return seen


def _src(eqn: Any) -> str:
    """A short human-readable source anchor for an eqn."""
    try:
        from jax._src import source_info_util

        name = source_info_util.summarize(eqn.source_info)
        if name:
            return f"{eqn.primitive.name} @ {name.rsplit('/', 1)[-1]}"
    except Exception:  # noqa: BLE001 — source info is best-effort
        pass
    return eqn.primitive.name


def _reduction_length(eqn: Any) -> float:
    name = eqn.primitive.name
    if name == "dot_general":
        (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
        shape = _shape(eqn.invars[0])
        return _count(shape[d] for d in lhs_c) or 1.0
    in_n = _count(_shape(eqn.invars[0]))
    out_n = _count(_shape(eqn.outvars[0])) or 1.0
    return max(in_n / out_n, 1.0)


def _apply_reduction(
    eqn: Any, env: dict, state: FlowState, infos: list[VarInfo],
    joined: VarInfo,
) -> None:
    out_dt = _dtype(eqn.outvars[0])
    float_ops = [
        i for i, v in zip(infos, eqn.invars)
        if _dtype(v) is not None
        and (str(_dtype(v)).startswith("float") or _is_bf16(_dtype(v)))
    ]
    bf16_lineage = any(i.bf16 for i in float_ops)
    if bf16_lineage and _is_narrow_float(out_dt):
        state.events.append(FlowEvent(
            "bf16-accumulation",
            f"{_src(eqn)}: {eqn.primitive.name} over bf16 lineage "
            f"accumulates in {out_dt} — use an f32 accumulator "
            "(ops.precision.acc_sum/acc_einsum or "
            "preferred_element_type=float32)",
        ))
    if any(i.acc_out for i in infos):
        state.events.append(FlowEvent(
            "acc-downcast",
            f"{_src(eqn)}: {eqn.primitive.name} re-reduces a value "
            "that was downcast from an f32 accumulator output — the "
            "accumulated precision was thrown away between stages",
        ))
    if any(i.unstable_exp for i in float_ops):
        state.events.append(FlowEvent(
            "unstable-exp",
            f"{_src(eqn)}: {eqn.primitive.name} reduces an exp() of an "
            "unbounded operand — clamp the argument at a documented "
            "threshold first (the ops/losses.py Poisson pattern)",
        ))
    acc_is_f32 = _is_f32(out_dt)
    if bf16_lineage and acc_is_f32:
        state.reduce_len += _reduction_length(eqn)
    out = dataclasses.replace(
        joined, acc_out=bf16_lineage and acc_is_f32
    )
    for ov in eqn.outvars:
        env[ov] = out


_ACCUMULATING_SCATTERS = frozenset({"scatter-add", "scatter-mul"})


def _apply_scatter(
    eqn: Any, env: dict, state: FlowState, infos: list[VarInfo],
    joined: VarInfo,
) -> None:
    # plain `scatter` (an .at[].set overwrite) moves storage without
    # combining — an accumulation hazard only for the -add/-mul forms;
    # ALL forms join the determinism census (colliding indices combine
    # or overwrite in an unpinned order)
    accumulates = eqn.primitive.name in _ACCUMULATING_SCATTERS
    out_dt = _dtype(eqn.outvars[0])
    if accumulates and joined.bf16 and _is_narrow_float(out_dt):
        state.events.append(FlowEvent(
            "bf16-accumulation",
            f"{_src(eqn)}: {eqn.primitive.name} over bf16 lineage "
            f"accumulates in {out_dt} — upcast the operand to f32 "
            "before scattering (the segment_reduce fallback pattern)",
        ))
    if any(i.unstable_exp for i in infos):
        state.events.append(FlowEvent(
            "unstable-exp",
            f"{_src(eqn)}: {eqn.primitive.name} scatters an exp() of "
            "an unbounded operand",
        ))
    if accumulates and joined.bf16 and _is_f32(out_dt):
        # count one accumulation step per scattered element
        state.reduce_len += _count(_shape(eqn.invars[-1]))
    for ov in eqn.outvars:
        env[ov] = dataclasses.replace(joined, acc_out=False)


def _mapped_sub_jaxpr(eqn: Any) -> Any | None:
    """A sub-jaxpr whose invars map 1:1 onto the eqn's operands
    (pjit, closed_call, custom_jvp/vjp, remat)."""
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key) if isinstance(eqn.params, dict) else None
        if sub is None:
            continue
        inner = _closed(sub)
        if hasattr(inner, "eqns") and len(inner.invars) == len(eqn.invars):
            return inner
    return None


def _apply_scan(
    eqn: Any, env: dict, state: FlowState, infos: list[VarInfo]
) -> None:
    body = _closed(eqn.params["jaxpr"])
    nc = eqn.params.get("num_consts", 0)
    k = eqn.params.get("num_carry", 0)
    length = float(eqn.params.get("length", 1) or 1)

    def seed() -> list[VarInfo]:
        inner: list[VarInfo] = []
        for i, info in enumerate(infos):
            if nc <= i < nc + k:
                info = dataclasses.replace(
                    info, carries=info.carries | {(id(eqn), i - nc)}
                )
            inner.append(info)
        return inner

    # pass 1 (throwaway state): let carry-out info reach carry-in so
    # booleans stabilize; pass 2 records the real events
    probe = FlowState()
    first = analyze_jaxpr(body, seed(), probe, in_loop=True)
    carried = seed()
    for i in range(k):
        carried[nc + i] = carried[nc + i].join(first[i])
        carried[nc + i] = dataclasses.replace(
            carried[nc + i],
            rounds=infos[nc + i].rounds,  # rounds re-derived below
            carries=carried[nc + i].carries | {(id(eqn), i)},
        )
    sub = FlowState()
    outs = analyze_jaxpr(body, carried, sub, in_loop=True)

    # per-iteration rounding deltas compound across the static trip
    # count; body reduction lengths likewise run once per iteration
    state.events.extend(sub.events)
    state.families |= sub.families
    state.reduce_len += sub.reduce_len * length
    state.max_rounds = max(state.max_rounds, sub.max_rounds)
    defs = _defining(body)
    for i in range(k):
        in_info = carried[nc + i]
        out_info = outs[i]
        delta = max(0, out_info.rounds - in_info.rounds)
        total_rounds = in_info.rounds + int(delta * length)
        out_info = dataclasses.replace(out_info, rounds=total_rounds)
        state.max_rounds = max(state.max_rounds, total_rounds)
        # a bf16 carry that ACCUMULATES (new = old + delta) rounds its
        # running value every iteration — bf16 accumulation, whatever
        # dtype the increments had
        ov = body.outvars[i]
        carry_dt = _dtype(eqn.outvars[i]) if i < len(eqn.outvars) else None
        def_eqn = defs.get(ov)
        if (
            _is_narrow_float(carry_dt)
            and def_eqn is not None
            and def_eqn.primitive.name in ("add", "sub", "add_any")
            and _traces_to(ov, body.invars[nc + i], defs)
        ):
            state.events.append(FlowEvent(
                "bf16-accumulation",
                f"{_src(def_eqn)}: scan carry {i} accumulates in "
                f"{carry_dt} across {int(length)} iterations — carry "
                "the running value in f32 and cast on store",
            ))
        outs[i] = out_info
    for ov, info in zip(eqn.outvars, outs):
        env[ov] = info


def _apply_while(
    eqn: Any, env: dict, state: FlowState, infos: list[VarInfo]
) -> None:
    body = _closed(eqn.params["body_jaxpr"])
    cn = eqn.params.get("cond_nconsts", 0)
    bn = eqn.params.get("body_nconsts", 0)
    # eqn operands: cond consts, body consts, carry
    carry_infos = infos[cn + bn:]
    body_in = list(infos[cn:cn + bn]) + [
        dataclasses.replace(ci, carries=ci.carries | {(id(eqn), i)})
        for i, ci in enumerate(carry_infos)
    ]
    probe = FlowState()
    first = analyze_jaxpr(body, body_in, probe, in_loop=True)
    for i in range(len(carry_infos)):
        body_in[bn + i] = body_in[bn + i].join(first[i])
        body_in[bn + i] = dataclasses.replace(
            body_in[bn + i],
            rounds=carry_infos[i].rounds,
            carries=body_in[bn + i].carries | {(id(eqn), i)},
        )
    sub = FlowState()
    outs = analyze_jaxpr(body, body_in, sub, in_loop=True)
    state.events.extend(sub.events)
    state.families |= sub.families
    # trip count is dynamic: charge the body once and note it
    state.reduce_len += sub.reduce_len
    state.max_rounds = max(state.max_rounds, sub.max_rounds)
    if any(
        max(0, outs[i].rounds - body_in[bn + i].rounds) > 0
        for i in range(len(carry_infos))
    ):
        state.notes.append(
            "while-loop carry gains a rounding per iteration with a "
            "dynamic trip count — bound not statically priceable"
        )
    for ov, info in zip(eqn.outvars, outs):
        env[ov] = info


def _apply_cond(
    eqn: Any, env: dict, state: FlowState, infos: list[VarInfo]
) -> None:
    branches = eqn.params["branches"]
    operand_infos = infos[1:]
    branch_outs: list[list[VarInfo]] = []
    for br in branches:
        branch_outs.append(
            analyze_jaxpr(_closed(br), list(operand_infos), state)
        )
    for i, ov in enumerate(eqn.outvars):
        joined = branch_outs[0][i]
        for bo in branch_outs[1:]:
            joined = joined.join(bo[i])
        env[ov] = joined


def _apply_pallas(
    eqn: Any, env: dict, state: FlowState, infos: list[VarInfo]
) -> None:
    """The kernel boundary: recurse into the kernel jaxpr when its ref
    arity maps, and regardless check the boundary dtype contract —
    bf16 operands must come out through f32 outputs."""
    joined = _join_all(infos)
    out_dts = [_dtype(ov) for ov in eqn.outvars]
    if joined.bf16 and any(_is_narrow_float(dt) for dt in out_dts):
        state.events.append(FlowEvent(
            "bf16-accumulation",
            f"{_src(eqn)}: pallas_call with bf16 operands writes a "
            "narrow-float output — the kernel accumulator must be f32 "
            "(out_shape float32, preferred_element_type=float32)",
        ))
    sub = eqn.params.get("jaxpr") if isinstance(eqn.params, dict) else None
    if sub is not None:
        inner = _closed(sub)
        try:
            seeds = [_seed_info(v) for v in inner.invars]
            analyze_jaxpr(inner, seeds, state)
        except Exception:  # noqa: BLE001 — kernel walk is best-effort
            state.notes.append(
                "pallas kernel jaxpr not walkable on this jax version; "
                "boundary dtype contract checked only"
            )
    if joined.bf16:
        # charge the kernel's streamed elements to the f32 accumulator
        state.reduce_len += max(
            (_count(_shape(v)) for v in eqn.invars), default=0.0
        )
    for ov in eqn.outvars:
        env[ov] = dataclasses.replace(
            joined, acc_out=joined.bf16
        )


def flow_program(prog: ProgramNumerics) -> FlowState:
    """Walk one traced program's jaxpr end to end."""
    jaxpr = _closed(prog.jaxpr)
    state = FlowState()
    seeds = [_seed_info(v) for v in jaxpr.invars]
    analyze_jaxpr(jaxpr, seeds, state)
    return state


def _flows(trace: NumericsTrace) -> dict[str, FlowState]:
    for name, prog in trace.programs.items():
        if name not in trace._flows:
            trace._flows[name] = flow_program(prog)
    return trace._flows


# --------------------------------------------------------------------------
# budget pricing (the MEMORY_AUDIT formula language + u16/u32)
# --------------------------------------------------------------------------


def _price(formula: str, dims: dict[str, float]) -> float:
    scope = dict(dims)
    scope["min"] = min
    scope["max"] = max
    scope["u16"] = U16
    scope["u32"] = U32
    return float(eval(formula, {"__builtins__": {}}, scope))  # noqa: S307


def _budget_for(
    contract: NumericsContract, program: str
) -> str | None:
    if program in contract.budgets:
        return contract.budgets[program]
    for pat, formula in contract.budgets.items():
        if fnmatch.fnmatchcase(program, pat):
            return formula
    return None


# --------------------------------------------------------------------------
# the checks
# --------------------------------------------------------------------------

_EVENT_RULES = {
    "bf16-accumulation": "numerics-bf16-accumulation",
    "cast-roundtrip": "numerics-cast-roundtrip",
    "acc-downcast": "numerics-acc-downcast",
    "scan-recast": "numerics-scan-recast",
    "unstable-exp": "numerics-unstable-exp",
}


def check_flow(
    contract: NumericsContract, trace: NumericsTrace
) -> Iterator[Finding]:
    """Accumulation-dtype audit + cast census + unstable-exp, from the
    walked flow events."""
    for name, flow in _flows(trace).items():
        seen: set[tuple[str, str]] = set()
        for ev in flow.events:
            key = (ev.kind, ev.detail)
            if key in seen:
                continue
            seen.add(key)
            yield _finding(
                contract,
                _EVENT_RULES[ev.kind],
                f"program {name!r}: {ev.detail}",
            )


def check_error_budgets(
    contract: NumericsContract, trace: NumericsTrace
) -> Iterator[Finding]:
    """Price every declared error budget against the derived bound,
    both directions (the tier-4 dual gate)."""
    tol = contract.tolerance
    flows = _flows(trace)
    for name, prog in trace.programs.items():
        flow = flows[name]
        derived = flow.derived_bound
        formula = _budget_for(contract, name)
        if formula is None:
            yield _finding(
                contract,
                "numerics-contract",
                f"traced program {name!r} has no declared error "
                "budget: every audited entry point must carry a "
                "worst-case relative-error formula",
            )
            continue
        dims = {**trace.dims, **prog.dims}
        try:
            declared = _price(formula, dims)
        except Exception as exc:  # noqa: BLE001 — rotten formula IS the finding
            yield _finding(
                contract,
                "numerics-stale-budget",
                f"program {name!r}: error budget {formula!r} no longer "
                f"evaluates over dims {sorted(dims)}: {exc!r}",
            )
            continue
        if derived > declared * tol:
            yield _finding(
                contract,
                "numerics-undeclared-error",
                f"program {name!r}: derived error bound {derived:.3e} "
                f"(rounds={flow.max_rounds}, "
                f"reduce_len={flow.reduce_len:.0f}) exceeds the "
                f"declared budget {formula!r} = {declared:.3e} beyond "
                f"the {tol}x tolerance — error grew that the contract "
                "does not price",
            )
        elif declared > derived * tol and declared - derived > 1e-6:
            yield _finding(
                contract,
                "numerics-stale-budget",
                f"program {name!r}: declared budget {formula!r} = "
                f"{declared:.3e} prices beyond {tol}x the derived "
                f"bound {derived:.3e} — the formula rotted above "
                "reality and would mask real error growth",
            )
    for pat in contract.budgets:
        if not any(
            pat == name or fnmatch.fnmatchcase(name, pat)
            for name in trace.programs
        ):
            yield _finding(
                contract,
                "numerics-contract",
                f"error-budget key {pat!r} matches no traced program — "
                "stale declaration",
            )


def _determinism_reason(
    contract: NumericsContract, program: str, family: str
) -> str | None:
    key = f"{program}:{family}"
    if key in contract.deterministic:
        return contract.deterministic[key]
    for pat, reason in contract.deterministic.items():
        if fnmatch.fnmatchcase(key, pat):
            return reason
    return None


def check_determinism(
    contract: NumericsContract, trace: NumericsTrace
) -> Iterator[Finding]:
    """Every order-nondeterministic primitive family per program must
    be declared deterministic-by-construction, with a reason."""
    flows = _flows(trace)
    present: set[str] = set()
    for name, flow in flows.items():
        for family in sorted(flow.families):
            present.add(f"{name}:{family}")
            reason = _determinism_reason(contract, name, family)
            if reason is None:
                yield _finding(
                    contract,
                    "numerics-nondeterministic-reduce",
                    f"program {name!r} contains {family!r} with no "
                    "deterministic-by-construction declaration — "
                    "declare WHY the combination order cannot matter "
                    "(sorted ids, unique ids) or restructure the "
                    "reduction",
                )
            elif not reason.strip():
                yield _finding(
                    contract,
                    "numerics-contract",
                    f"determinism declaration for {name}:{family} has "
                    "no reason — a declaration without a reason is a "
                    "gap, not a decision",
                )
    for pat, reason in contract.deterministic.items():
        if not reason or not reason.strip():
            yield _finding(
                contract,
                "numerics-contract",
                f"determinism declaration {pat!r} has no reason",
            )
        if not any(
            pat == key or fnmatch.fnmatchcase(key, pat)
            for key in present
        ):
            yield _finding(
                contract,
                "numerics-contract",
                f"determinism declaration {pat!r} matches no "
                "nondeterministic site in any traced program — stale "
                "declaration",
            )


CHECKS = (
    check_flow,
    check_error_budgets,
    check_determinism,
)


def run_checks(
    contract: NumericsContract, trace: NumericsTrace
) -> list[Finding]:
    """All numerics checks over one contract's trace, suppressions
    applied (suppressed findings are kept, with reasons, for the
    report — the tier-2/4 discipline)."""
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
# contract builders (named by the NUMERICS_AUDIT declarations)
# --------------------------------------------------------------------------


def build_precision_numerics() -> NumericsTrace:
    """Probe programs for the policy helpers themselves and the GLM
    loss families over bf16-stored margins — acc_sum/acc_einsum must
    accumulate f32, and every family's exp() must be dominated by a
    clamp (the Poisson stability fix)."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.ops import losses
    from photon_tpu.ops import precision as px

    m, b, k = 4096, 128, 64
    bf = jnp.bfloat16
    f32 = np.float32
    S = jax.ShapeDtypeStruct

    def acc_sum_probe(x):
        return px.acc_sum(x)

    def acc_einsum_probe(a, v):
        return px.acc_einsum("bk,k->b", a, v)

    programs = {
        "acc_sum": ProgramNumerics(
            "acc_sum",
            jax.jit(acc_sum_probe).trace(S((m,), bf)).jaxpr,
            dims={},
        ),
        "acc_einsum": ProgramNumerics(
            "acc_einsum",
            jax.jit(acc_einsum_probe).trace(
                S((b, k), bf), S((k,), bf)
            ).jaxpr,
            dims={},
        ),
    }
    for loss in (losses.LOGISTIC, losses.SQUARED, losses.POISSON,
                 losses.SMOOTHED_HINGE):
        def family_probe(z, y, _l=loss):
            # margins arrive bf16-STORED (the fused sweep's score-carry
            # shape) and are upcast on read; loss, curvature, and link
            # each reduce with the sanctioned f32 accumulator
            zz = z.astype(jnp.float32)
            return (
                px.acc_sum(_l.loss(zz, y))
                + px.acc_sum(_l.dzz(zz, y))
                + px.acc_sum(_l.mean(zz))
            )

        programs[f"loss_{loss.name}"] = ProgramNumerics(
            f"loss_{loss.name}",
            jax.jit(family_probe).trace(  # photon: ignore[recompile-hazard] -- trace-only audit builder, one trace per family per audit run; nothing executes
                S((m,), bf), S((m,), f32)
            ).jaxpr,
            dims={},
        )
    return NumericsTrace(
        programs=programs,
        dims={"m": float(m), "b": float(b), "k": float(k)},
        notes=[
            "policy helpers + all four GLM families over bf16-stored "
            "margins (the score-carry shape); one storage rounding "
            "each, f32 accumulation"
        ],
    )


def build_fused_fit_numerics() -> NumericsTrace:
    """The fused whole-fit programs at BOTH precisions: the bf16
    variant is the policy under audit, the f32 variant is the control
    (zero bf16 lineage — a leak there is a policy bug too)."""
    from photon_tpu.algorithm.fused_fit import FusedFit
    from photon_tpu.analysis import program as tier2

    est, data = tier2._tiny_glmix()
    datasets, _ = est.prepare(data)
    n = data.num_samples
    coords = est._build_coordinates(datasets, {}, {}, logical_rows=n)
    coord = coords["per-user"]
    ds = getattr(coord, "inner", coord).dataset
    programs: dict[str, ProgramNumerics] = {}
    for precision, tag in (("float32", "f32"), ("bfloat16", "bf16")):
        fused = FusedFit(
            coords, est.update_sequence, 2, set(), precision=precision
        )
        mat = fused._mat_jit.trace(fused._mat_operands(coords))
        fit = fused.trace(coords)
        programs[f"materialize_{tag}"] = ProgramNumerics(
            f"materialize_{tag}", mat.jaxpr
        )
        programs[f"fit_{tag}"] = ProgramNumerics(f"fit_{tag}", fit.jaxpr)
    return NumericsTrace(
        programs=programs,
        dims={
            "n": float(n),
            "d": 5.0,
            "du": 4.0,
            "e": float(ds.num_entities),
            "s": float(ds.max_sub_dim),
            "iters": 2.0,
            "coords": 2.0,
        },
        notes=[
            "tier-2 tiny GLMix fixture traced through FusedFit at f32 "
            "(control: no bf16 lineage) and bf16 (the audited policy)"
        ],
    )


def build_segment_reduce_numerics() -> NumericsTrace:
    """The segment-reduce at the kernel boundary AND the fallback, on
    bf16 values — both must accumulate f32."""
    import functools
    import os

    import jax

    from photon_tpu.ops import segment_reduce as sr

    m, nseg = 4096, 2048
    S = jax.ShapeDtypeStruct
    programs: dict[str, ProgramNumerics] = {}
    prev = os.environ.get("PHOTON_SEGMENT_KERNEL")
    for mode, tag in (("force", "kernel"), ("off", "fallback")):
        os.environ["PHOTON_SEGMENT_KERNEL"] = mode
        try:
            fn = functools.partial(
                sr.sorted_segment_sum,
                num_segments=nseg,
                multiplicity=2,
                interpret=sr.interpret_required(),
            )
            traced = jax.jit(fn).trace(  # photon: ignore[recompile-hazard] -- trace-only audit builder, one trace per engage mode per audit run; nothing executes
                S((m,), jax.numpy.bfloat16), S((m,), np.int32)
            )
            programs[f"segment_sum_{tag}"] = ProgramNumerics(
                f"segment_sum_{tag}", traced.jaxpr
            )
        finally:
            if prev is None:  # photon: ignore[spmd-host-divergence] -- env save/restore of the audit fixture's kernel flag; host-local tooling, not fleet code
                os.environ.pop("PHOTON_SEGMENT_KERNEL", None)
            else:
                os.environ["PHOTON_SEGMENT_KERNEL"] = prev
    return NumericsTrace(
        programs=programs,
        dims={"m": float(m), "nseg": float(nseg)},
        notes=[
            "sorted_segment_sum on bf16 values through the forced "
            "Pallas kernel (interpret off-TPU) and the XLA fallback"
        ],
    )


def build_serve_kernel_numerics() -> NumericsTrace:
    """The fused serve kernel over bf16 tables (PHOTON_SERVE_KERNEL
    forced; env restored after) — the production serving precision
    through the pallas path, next to ``build_serving_numerics``'s jit
    fallback on the same fixture."""
    import os

    from photon_tpu.analysis.memory import _tiny_game_model
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables

    d, e, s, du = 5, 7, 3, 6
    model = _tiny_game_model(
        d, e, s, du, proj_seed=1234, rng_seed=20260803
    )
    ladder = ShapeLadder((1, 8))
    prev = os.environ.get("PHOTON_SERVE_KERNEL")
    os.environ["PHOTON_SERVE_KERNEL"] = "force"
    try:
        tables = CoefficientTables.from_game_model(model, "bfloat16")
        programs = ScorePrograms(
            tables, ladder=ladder, compile_now=False
        )
        if not programs.use_kernel:
            raise RuntimeError(
                "PHOTON_SERVE_KERNEL=force did not engage the fused "
                "kernel — the serve-kernel numerics contract audits "
                "nothing"
            )
        out = {
            f"serve_kernel_b{r}": ProgramNumerics(
                f"serve_kernel_b{r}",
                programs.trace(r).jaxpr,
                dims={"rung": float(r)},
            )
            for r in ladder.rungs
        }
    finally:
        if prev is None:  # photon: ignore[spmd-host-divergence] -- env save/restore of the audit fixture's kernel flag; host-local tooling, not fleet code
            os.environ.pop("PHOTON_SERVE_KERNEL", None)
        else:
            os.environ["PHOTON_SERVE_KERNEL"] = prev
    return NumericsTrace(
        programs=out,
        dims={
            "d": float(d), "e": float(e), "s": float(s), "du": float(du),
        },
        notes=[
            f"fused kernel ladder {ladder.rungs} over BF16 tables, "
            "interpret-path lowering; request payloads f32"
        ],
    )


def build_serving_numerics() -> NumericsTrace:
    """The serve score ladder over bf16 coefficient tables — the
    production mixed-precision serving path."""
    from photon_tpu.analysis.memory import _tiny_game_model
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables

    d, e, s, du = 5, 7, 3, 6
    model = _tiny_game_model(
        d, e, s, du, proj_seed=1234, rng_seed=20260803
    )
    ladder = ShapeLadder((1, 8))
    tables = CoefficientTables.from_game_model(model, "bfloat16")
    programs = ScorePrograms(tables, ladder=ladder, compile_now=False)
    out: dict[str, ProgramNumerics] = {}
    for r in ladder.rungs:
        traced = programs.trace(r)
        out[f"score_b{r}"] = ProgramNumerics(
            f"score_b{r}", traced.jaxpr, dims={"rung": float(r)}
        )
    return NumericsTrace(
        programs=out,
        dims={
            "d": float(d), "e": float(e), "s": float(s), "du": float(du),
        },
        notes=[
            f"score ladder {ladder.rungs} over BF16 tables (the "
            "production serving precision); request payloads f32"
        ],
    )


_BUILDERS: dict[str, Callable[[], NumericsTrace]] = {
    "build_precision_numerics": build_precision_numerics,
    "build_fused_fit_numerics": build_fused_fit_numerics,
    "build_segment_reduce_numerics": build_segment_reduce_numerics,
    "build_serve_kernel_numerics": build_serve_kernel_numerics,
    "build_serving_numerics": build_serving_numerics,
}


def contract_from_declaration(spec: dict) -> NumericsContract:
    builder = spec.get("builder")
    if builder not in _BUILDERS:
        raise ValueError(
            f"NUMERICS_AUDIT declaration {spec.get('name')!r} names "
            f"unknown builder {builder!r}"
        )
    return NumericsContract(
        name=spec["name"],
        entry=spec["entry"],
        build=_BUILDERS[builder],
        covers=tuple(spec.get("covers", ())),
        budgets=dict(spec.get("budgets", {})),
        deterministic=dict(spec.get("deterministic", {})),
        tolerance=float(spec.get("tolerance", 1.5)),
        suppress=dict(spec.get("suppress", {})),
    )


def collect_contracts() -> list[NumericsContract]:
    """The repo's declared numerics-contract registry."""
    specs: list[dict] = []
    for modname in NUMERICS_DECLARING_MODULES:
        mod = importlib.import_module(modname)
        decl = getattr(mod, "NUMERICS_AUDIT", None)
        if decl is None:
            raise ValueError(
                f"{modname} is a numerics-declaring module but exports "
                "no NUMERICS_AUDIT"
            )
        specs.extend(decl if isinstance(decl, (list, tuple)) else [decl])
    return [contract_from_declaration(s) for s in specs]


def check_coverage(
    contracts: Iterable[NumericsContract],
) -> list[Finding]:
    """Every tier-2 entry point carries a numerics contract or a
    reasoned waiver — and no waiver outlives its reason."""
    from photon_tpu.analysis import program as tier2

    tier2_names = {c.name for c in tier2.collect_contracts()}
    covered: dict[str, str] = {}
    findings: list[Finding] = []
    anchor = NumericsContract(
        name="numerics-coverage", entry="analysis.numerics",
        build=NumericsTrace,
    )
    for c in contracts:
        for name in c.covers:
            if name not in tier2_names:
                findings.append(
                    _finding(
                        anchor,
                        "numerics-contract",
                        f"numerics contract {c.name!r} covers unknown "
                        f"tier-2 contract {name!r}",
                    )
                )
            covered[name] = c.name
    for name, reason in TIER2_WAIVERS.items():
        if name not in tier2_names:
            findings.append(
                _finding(
                    anchor,
                    "numerics-contract",
                    f"stale waiver: {name!r} is not a tier-2 contract",
                )
            )
        elif name in covered:
            findings.append(
                _finding(
                    anchor,
                    "numerics-contract",
                    f"stale waiver: {name!r} is covered by numerics "
                    f"contract {covered[name]!r} — drop the waiver",
                )
            )
        if not reason or not reason.strip():
            findings.append(
                _finding(
                    anchor,
                    "numerics-contract",
                    f"waiver for {name!r} has no reason — a waiver "
                    "without a reason is a gap, not a decision",
                )
            )
    for name in sorted(tier2_names):
        if name not in covered and name not in TIER2_WAIVERS:
            findings.append(
                _finding(
                    anchor,
                    "numerics-contract",
                    f"tier-2 contract {name!r} has no NUMERICS_AUDIT "
                    "coverage and no waiver: audit its dtype flow or "
                    "add a reasoned TIER2_WAIVERS entry",
                )
            )
    return findings


# --------------------------------------------------------------------------
# the audit driver
# --------------------------------------------------------------------------


def audit(
    contracts: Iterable[NumericsContract] | None = None,
) -> tuple[list[Finding], dict]:
    """Run every numerics contract; returns (findings, report).

    Builds run under ``jax.enable_x64(False)`` (the tier-2 discipline:
    audited traces match the production f32 configuration even when the
    host process enabled x64).
    """
    import jax

    findings: list[Finding] = []
    report: dict[str, Any] = {
        "contracts": {},
        "waivers": dict(TIER2_WAIVERS),
    }
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
                "notes": [],
            }
            report["contracts"][contract.name] = entry
            try:
                trace = contract.build()
            except Exception as exc:  # noqa: BLE001 — any builder crash is a finding
                findings.append(
                    _finding(
                        contract,
                        "numerics-contract",
                        f"contract builder failed: {exc!r}",
                    )
                )
                continue
            findings.extend(run_checks(contract, trace))
            for name, prog in trace.programs.items():
                flow = _flows(trace)[name]
                dims = {**trace.dims, **prog.dims}
                formula = _budget_for(contract, name)
                pentry: dict[str, Any] = {
                    "rounds": flow.max_rounds,
                    "reduce_len": flow.reduce_len,
                    "derived_bound": flow.derived_bound,
                    "budget": formula,
                    "families": sorted(flow.families),
                }
                if formula is not None:
                    try:
                        pentry["budget_value"] = _price(formula, dims)
                    except Exception:  # noqa: BLE001 — already a finding
                        pass
                entry["programs"][name] = pentry
            entry["notes"] = list(trace.notes) + [
                n for f in _flows(trace).values() for n in f.notes
            ]
    findings.sort(key=lambda f: (f.path, f.rule, f.message))
    return findings, report
