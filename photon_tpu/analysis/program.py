"""Tier-2 semantic auditor: jaxpr/HLO program contracts for photon_tpu.

Where the tier-1 rules (``rules.py``) read SOURCE TEXT, this tier audits
the PROGRAMS the package actually builds: the public jitted entry points
are traced under abstract shapes (``jax.jit(...).trace`` / ``.lower()`` —
no device execution, so the whole pass runs on CPU CI) and the resulting
jaxprs / lowered HLO are checked against contracts DECLARED NEXT TO THE
CODE they constrain (each audited module carries a ``PROGRAM_AUDIT``
declaration; this module owns the tracing machinery).

Checks (rule ids):

- ``program-dispatch-census``: the number of distinct traced programs
  across a contract's declared config grid must stay within the declared
  bound — a config family that should re-enter one executable (the λ-grid
  warm-start ladder) must not mint new programs.
- ``program-recompile-key``: per config family, the trace signature either
  MUST be stable (``stable_under``) or MUST change (``recompiles_on`` —
  a declared static specialization that stops specializing means the
  declaration went stale). The report names which argument perturbs the
  key.
- ``program-host-boundary``: no callback primitives inside hot-loop
  jaxprs — a ``pure_callback``/``io_callback``/``debug_callback`` in a
  fit program is a host round trip per dispatch, the jaxpr-level twin of
  tier-1's ``host-sync-in-jit``.
- ``program-f64-cast``: no ``convert_element_type`` TO float64 anywhere
  in an audited jaxpr (tier-1's ``float64-literal``, after tracing).
- ``program-sharding``: mesh entry points must carry the declared
  ``NamedSharding`` axis on every hot-loop operand, replicate exactly the
  operands declared replicated, and lower to HLO whose collectives are a
  subset of the declared set (an unplanned all-gather is a silent
  cross-device transfer per dispatch).
- ``program-contract``: registry integrity — a contract whose builder
  raises is a finding, never a silent skip.

Findings reuse :class:`photon_tpu.analysis.core.Finding` (path is the
contract name) so the text/JSON reporters and the suppression audit work
unchanged. Suppressions are PER CONTRACT, declared in the contract's
``suppress`` mapping with a written reason.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import importlib
import os
import re
import sys
from typing import Any, Callable, Iterable, Iterator

from photon_tpu.analysis.core import Finding

SEMANTIC_RULES: dict[str, str] = {
    "program-dispatch-census": (
        "distinct compiled programs across a config grid exceed the "
        "contract's bound"
    ),
    "program-recompile-key": (
        "a config family perturbs (or stops perturbing) a compile-cache "
        "key against its declaration"
    ),
    "program-host-boundary": (
        "callback primitive inside a hot-loop jaxpr (host round trip "
        "per dispatch)"
    ),
    "program-f64-cast": (
        "convert_element_type to float64 inside an audited jaxpr"
    ),
    "program-sharding": (
        "mesh operand lost its NamedSharding axis, or lowered HLO "
        "carries undeclared collectives"
    ),
    "program-contract": "contract declaration or builder integrity error",
}

# Modules that declare program contracts (each exports PROGRAM_AUDIT —
# one declaration dict or a list of them). The declarations are plain
# data so importing the audited modules stays free of analysis imports.
DECLARING_MODULES = (
    "photon_tpu.algorithm.fused_fit",
    "photon_tpu.data.pipeline",
    "photon_tpu.data.stream",
    "photon_tpu.estimators.game_estimator",
    "photon_tpu.obs",
    "photon_tpu.ops.newton_kernel",
    "photon_tpu.ops.segment_reduce",
    "photon_tpu.ops.serve_kernel",
    "photon_tpu.parallel.mesh",
    "photon_tpu.pilot",
    "photon_tpu.resilience",
    "photon_tpu.serve",
)

_CALLBACK_PRIMITIVES = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "callback",
        "outside_call",
        "host_callback_call",
    }
)

# Cross-device transfer ops: owned since PR 20 by the tier-6 SPMD
# census (analysis/spmd.py) — one list, one census, so the tier-2
# sharding audit and the --spmd collective-order audit cannot drift.
from photon_tpu.analysis.spmd import COLLECTIVE_OPS as _COLLECTIVE_OPS


# --------------------------------------------------------------------------
# data model
# --------------------------------------------------------------------------


# Function reprs inside higher-order primitive params (custom_jvp's
# jvp_jaxpr_thunk and friends) embed id() addresses in the jaxpr text.
# They vary per trace — across simulated hosts and across re-traces of
# one config — without any semantic divergence, so both the tier-2
# recompile-key proxy and the tier-6 cross-host proof scrub them.
_JAXPR_ADDR_RE = re.compile(r" at 0x[0-9a-fA-F]+")


@dataclasses.dataclass
class TracedProgram:
    """One traced entry point: its jaxpr (for the boundary walk), the
    jaxpr text hash (the recompile-key proxy: two configs tracing to
    different jaxprs get different compiled programs), and optionally the
    Lowered for HLO/cost checks."""

    name: str
    text: str
    jaxpr: Any | None = None  # ClosedJaxpr; None for key-only programs
    lowered: Any | None = None

    def __post_init__(self) -> None:
        self.text = _JAXPR_ADDR_RE.sub(" at 0x", self.text)

    @property
    def signature(self) -> str:
        return hashlib.sha1(self.text.encode("utf-8")).hexdigest()[:16]


@dataclasses.dataclass
class ContractTrace:
    """Everything a contract's builder hands the checks.

    ``variants`` maps a config-family name to one signature-dict per
    generated config (program name -> signature); ``opshardings`` /
    ``replicated`` / ``collectives`` feed the sharding audit (None when
    the builder ran single-device); ``notes`` surface in the report.
    """

    programs: dict[str, TracedProgram]
    variants: dict[str, list[dict[str, str]]] = dataclasses.field(
        default_factory=dict
    )
    opshardings: dict[str, str] | None = None
    collectives: list[str] | None = None
    notes: list[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass(frozen=True)
class ProgramContract:
    name: str
    entry: str  # human-readable entry-point path (report/docs)
    build: Callable[[], ContractTrace]
    max_programs: int | None = None
    stable_under: tuple[str, ...] = ()
    recompiles_on: tuple[str, ...] = ()
    hot_loop: bool = False
    sharded_operands: tuple[str, ...] = ()
    replicated_operands: tuple[str, ...] = ()
    axis: str | None = None
    allowed_collectives: tuple[str, ...] = ()
    suppress: dict[str, str] = dataclasses.field(default_factory=dict)


def _finding(contract: ProgramContract, rule: str, message: str) -> Finding:
    return Finding(
        rule=rule, path=f"<{contract.name}>", line=0, col=0, message=message
    )


# --------------------------------------------------------------------------
# jaxpr utilities
# --------------------------------------------------------------------------


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """Every equation of a (Closed)Jaxpr, recursing into sub-jaxprs held
    in eqn params (scan/while/cond bodies, pjit calls, custom calls)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        for sub in _param_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _param_jaxprs(params: dict) -> Iterator[Any]:
    for v in params.values():
        for cand in v if isinstance(v, (list, tuple)) else (v,):
            if hasattr(cand, "eqns") or hasattr(cand, "jaxpr"):
                if hasattr(getattr(cand, "jaxpr", cand), "eqns"):
                    yield cand


def trace_program(name: str, fn: Any, *args: Any, **kwargs: Any) -> TracedProgram:
    """Trace ``jax.jit(fn)`` (or an already-jitted fn) abstractly.

    ``args`` may mix concrete arrays and ``jax.ShapeDtypeStruct`` leaves;
    nothing executes. The Lowered is captured for cost/HLO analysis.
    """
    import jax

    jitted = fn if hasattr(fn, "trace") else jax.jit(fn)
    traced = jitted.trace(*args, **kwargs)
    return TracedProgram(
        name=name,
        text=str(traced.jaxpr),
        jaxpr=traced.jaxpr,
        lowered=traced.lower(),
    )


# --------------------------------------------------------------------------
# checks
# --------------------------------------------------------------------------


def check_dispatch_census(
    contract: ProgramContract, trace: ContractTrace
) -> Iterator[Finding]:
    if contract.max_programs is None:
        return
    sigs: dict[str, str] = {
        p.signature: f"{name} (base)" for name, p in trace.programs.items()
    }
    for fam in contract.stable_under:
        for i, cfg in enumerate(trace.variants.get(fam, [])):
            for name, sig in cfg.items():
                sigs.setdefault(sig, f"{name} ({fam}[{i}])")
    if len(sigs) > contract.max_programs:
        yield _finding(
            contract,
            "program-dispatch-census",
            f"{len(sigs)} distinct compiled programs across the declared "
            f"config grid, contract allows {contract.max_programs}: "
            + ", ".join(sorted(sigs.values())),
        )


def check_recompile_key(
    contract: ProgramContract, trace: ContractTrace
) -> Iterator[Finding]:
    base = {name: p.signature for name, p in trace.programs.items()}
    for fam in contract.stable_under:
        if not trace.variants.get(fam):
            # Same integrity rule as recompiles_on below: a declared
            # family with no generated variants is an UNCHECKED
            # stability guarantee, not a passing one.
            yield _finding(
                contract,
                "program-contract",
                f"declared stable family '{fam}' generated no "
                "variants — the stability guarantee is unchecked",
            )
            continue
        for i, cfg in enumerate(trace.variants.get(fam, [])):
            moved = sorted(
                name
                for name, sig in cfg.items()
                if name in base and sig != base[name]
            )
            if moved:
                yield _finding(
                    contract,
                    "program-recompile-key",
                    f"config family '{fam}' (variant {i}) perturbs the "
                    f"compile key of {', '.join(moved)} — these configs "
                    "must re-enter the same executable",
                )
    for fam in contract.recompiles_on:
        variants = trace.variants.get(fam, [])
        if not variants:
            yield _finding(
                contract,
                "program-contract",
                f"declared recompile family '{fam}' generated no "
                "variants — the declaration is unchecked",
            )
            continue
        if all(
            all(sig == base.get(name) for name, sig in cfg.items())
            for cfg in variants
        ):
            yield _finding(
                contract,
                "program-recompile-key",
                f"declared recompile trigger '{fam}' no longer perturbs "
                "any program key — the static specialization it documents "
                "is gone; tighten the contract declaration",
            )


def check_host_boundary(
    contract: ProgramContract, trace: ContractTrace
) -> Iterator[Finding]:
    import numpy as np

    f64 = np.dtype("float64")
    for name, prog in trace.programs.items():
        if prog.jaxpr is None:
            continue
        seen_cb: set[str] = set()
        seen_f64 = False
        for eqn in iter_eqns(prog.jaxpr):
            pname = eqn.primitive.name
            if contract.hot_loop and pname in _CALLBACK_PRIMITIVES:
                if pname not in seen_cb:
                    seen_cb.add(pname)
                    yield _finding(
                        contract,
                        "program-host-boundary",
                        f"program '{name}' carries host-callback "
                        f"primitive '{pname}' in its hot-loop jaxpr — "
                        "one host round trip per dispatch",
                    )
            if not seen_f64 and pname == "convert_element_type":
                new = eqn.params.get("new_dtype")
                if new is not None and np.dtype(new) == f64:
                    seen_f64 = True
                    yield _finding(
                        contract,
                        "program-f64-cast",
                        f"program '{name}' converts to float64 in its "
                        "traced jaxpr (2x HBM + off the TPU fast path)",
                    )


def check_sharding(
    contract: ProgramContract, trace: ContractTrace
) -> Iterator[Finding]:
    if not (contract.sharded_operands or contract.replicated_operands):
        return
    if trace.opshardings is None:
        # Builder ran single-device; the note in the report says so.
        return
    for op in contract.sharded_operands:
        spec = trace.opshardings.get(op)
        if spec is None:
            yield _finding(
                contract,
                "program-sharding",
                f"operand '{op}' missing from the sharding trace",
            )
        elif contract.axis and f"'{contract.axis}'" not in spec:
            yield _finding(
                contract,
                "program-sharding",
                f"operand '{op}' lost the '{contract.axis}' mesh axis "
                f"(sharding is {spec}) — unplanned replication",
            )
    for op in contract.replicated_operands:
        spec = trace.opshardings.get(op)
        if spec is None:
            yield _finding(
                contract,
                "program-sharding",
                f"operand '{op}' missing from the sharding trace",
            )
        elif contract.axis and f"'{contract.axis}'" in spec:
            yield _finding(
                contract,
                "program-sharding",
                f"operand '{op}' is declared replicated but carries the "
                f"'{contract.axis}' axis ({spec})",
            )
    undeclared = sorted(
        set(trace.collectives or ()) - set(contract.allowed_collectives)
    )
    if undeclared:
        yield _finding(
            contract,
            "program-sharding",
            "lowered HLO carries undeclared cross-device transfer op(s): "
            + ", ".join(undeclared)
            + f" (declared: {', '.join(contract.allowed_collectives) or 'none'})",
        )


CHECKS = (
    check_dispatch_census,
    check_recompile_key,
    check_host_boundary,
    check_sharding,
)


def run_checks(
    contract: ProgramContract, trace: ContractTrace
) -> list[Finding]:
    """All checks over one contract's trace, suppressions applied."""
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
# collective HLO census (shared by the mesh builder and tests)
# --------------------------------------------------------------------------


def hlo_collectives(compiled: Any) -> list[str]:
    """Collective op names present in a compiled executable's HLO text.

    Delegates to the tier-6 census (``spmd.collective_census``) — the
    single source of truth the ``--spmd`` collective-order audit also
    gates on, so the two tiers see the same ops by construction.
    """
    from photon_tpu.analysis import spmd

    return spmd.collective_census(compiled)


# --------------------------------------------------------------------------
# shared tiny workload (abstract-trace fixtures; CPU-cheap)
# --------------------------------------------------------------------------


def _l2_config(weight: float, optimizer=None, variance=None):
    from photon_tpu import optim
    from photon_tpu.algorithm.problems import GLMOptimizationConfiguration

    kw: dict[str, Any] = dict(
        regularization=optim.RegularizationContext(
            optim.RegularizationType.L2
        ),
        regularization_weight=weight,
    )
    if optimizer is not None:
        kw["optimizer"] = optimizer
    if variance is not None:
        kw["variance_computation"] = variance
    return GLMOptimizationConfiguration(**kw)


def _tiny_glmix(num_iterations: int = 2, n: int = 96, e: int = 7):
    """A miniature single-device GLMix estimator + dataset: one dense
    fixed effect and one random effect, logistic task — the smallest
    structure that exercises every fused-fit program family."""
    import numpy as np

    from photon_tpu.data.dataset import DenseFeatures
    from photon_tpu.data.game_data import make_game_dataset
    from photon_tpu.data.random_effect import RandomEffectDataConfiguration
    from photon_tpu.estimators.game_estimator import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu.types import TaskType

    d, du = 5, 4
    rng = np.random.default_rng(20260803)
    x = rng.normal(size=(n, d)).astype(np.float32)
    x[:, -1] = 1.0
    xu = rng.normal(size=(n, du)).astype(np.float32)
    xu[:, -1] = 1.0
    users = rng.integers(0, e, size=n)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    data = make_game_dataset(
        y,
        {"global": DenseFeatures(x), "userShard": DenseFeatures(xu)},
        id_tags={"userId": users},
    )
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {
            "global": FixedEffectCoordinateConfiguration(
                "global", _l2_config(0.01)
            ),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "userShard"),
                _l2_config(0.5),
            ),
        },
        intercept_indices={"global": d - 1, "userShard": du - 1},
        num_iterations=num_iterations,
        mesh="off",
    )
    return est, data


def _zero_initial_models(coords: dict) -> dict:
    """Warm-start models with the right structure (values never matter —
    tracing sees only avals — but has_init flips the statics)."""
    import jax.numpy as jnp

    from photon_tpu.algorithm.coordinate import FixedEffectCoordinate
    from photon_tpu.models.game import FixedEffectModel, RandomEffectModel
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel

    out = {}
    for cid, coord in coords.items():
        inner = getattr(coord, "inner", coord)
        if isinstance(inner, FixedEffectCoordinate):
            glm = GeneralizedLinearModel(
                Coefficients(
                    means=jnp.zeros(
                        inner.batch.num_features, inner.batch.labels.dtype
                    )
                ),
                inner.problem.task,
            )
            out[cid] = FixedEffectModel(glm, coord.feature_shard_id)
        else:
            ds = inner.dataset
            out[cid] = RandomEffectModel(
                coefficients=jnp.zeros(
                    (ds.num_entities, ds.max_sub_dim), ds.dtype
                ),
                random_effect_type=ds.config.random_effect_type,
                feature_shard_id=ds.config.feature_shard_id,
                task=inner.task,
                proj_all=ds.proj_all,
                entity_keys=ds.entity_keys,
            )
    return out


# --------------------------------------------------------------------------
# contract builders (named by the PROGRAM_AUDIT declarations)
# --------------------------------------------------------------------------


def build_fused_fit() -> ContractTrace:
    """Trace the three programs of one fused-fit generation and the config
    families of the λ-grid / optimizer-swap discipline."""
    from photon_tpu import optim
    from photon_tpu.algorithm.fused_fit import FusedFit

    est, data = _tiny_glmix()
    datasets, _ = est.prepare(data)
    n = data.num_samples

    def fused_for(opt_configs: dict, iters: int = 2,
                  precision: str = "float32"):
        coords = est._build_coordinates(
            datasets, opt_configs, {}, logical_rows=n
        )
        return FusedFit(
            coords, est.update_sequence, iters, set(),
            precision=precision,
        ), coords

    def fit_trace(
        fused: FusedFit, coords: dict, initial_models=None, lower=True
    ):
        # FusedFit.trace is the SAME operand assembly run() uses — the
        # audited jaxpr is the production program by construction.
        traced = fused.trace(coords, initial_models)
        return TracedProgram(
            name="fit",
            text=str(traced.jaxpr),
            jaxpr=traced.jaxpr,
            lowered=traced.lower() if lower else None,
        )

    fused, coords = fused_for({})
    mat = trace_program(
        "materialize", fused._mat_jit, fused._mat_operands(coords)
    )
    fit_cold = fit_trace(fused, coords)
    warm = _zero_initial_models(coords)
    fit_warm = dataclasses.replace(
        fit_trace(fused, coords, warm), name="fit_warm"
    )

    variants: dict[str, list[dict[str, str]]] = {
        "lambda_grid": [],
        "optimizer_swap": [],
        "iteration_count": [],
    }
    for w in (0.003, 3.0):
        f2, c2 = fused_for(
            {"global": _l2_config(w), "per-user": _l2_config(w)}
        )
        variants["lambda_grid"].append(
            {
                "fit": fit_trace(f2, c2, lower=False).signature,
                "fit_warm": fit_trace(
                    f2, c2, _zero_initial_models(c2), lower=False
                ).signature,
            }
        )
    f3, c3 = fused_for(
        {
            "global": _l2_config(
                0.01, optimizer=optim.OptimizerConfig.tron()
            )
        }
    )
    variants["optimizer_swap"].append(
        {"fit": fit_trace(f3, c3, lower=False).signature}
    )
    f4, c4 = fused_for({}, iters=3)
    variants["iteration_count"].append(
        {"fit": fit_trace(f4, c4, lower=False).signature}
    )
    # Mixed precision is a DECLARED recompile: bf16 slab/score storage
    # changes the traced dtypes (ops/precision.py), so the bfloat16
    # program must differ from the f32 base — and a silent no-op here
    # (the mixed path quietly tracing f32) fails the contract.
    f5, c5 = fused_for({}, precision="bfloat16")
    variants["precision"] = [
        {"fit": fit_trace(f5, c5, lower=False).signature}
    ]

    return ContractTrace(
        programs={
            "materialize": mat,
            "fit": fit_cold,
            "fit_warm": fit_warm,
        },
        variants=variants,
        notes=[
            "a fused fit is 2 dispatches (materialize once per dataset "
            "generation + the whole-fit program); the warm-start entry is "
            "a third distinct executable of the same generation",
        ],
    )


def build_fused_cache_keys() -> ContractTrace:
    """The estimator's static-key discipline, checked on keys alone: a
    λ grid maps to ONE fused-cache entry, an optimizer swap to a second,
    and a realistic mixed grid stays within the LRU bound."""
    from photon_tpu import optim
    from photon_tpu.algorithm.fused_fit import fused_static_key
    from photon_tpu.estimators.game_estimator import _FUSED_CACHE_SIZE

    est, data = _tiny_glmix()
    datasets, _ = est.prepare(data)
    n = data.num_samples

    def key_for(opt_configs: dict, precision: str = "float32") -> str:
        coords = est._build_coordinates(
            datasets, opt_configs, {}, logical_rows=n
        )
        return str(
            fused_static_key(
                coords,
                est.update_sequence,
                est.num_iterations,
                est.locked_coordinates,
                precision,
            )
        )

    base = TracedProgram(name="fused_static_key", text=key_for({}))
    lam = [
        {"fused_static_key": TracedProgram("k", key_for(
            {"global": _l2_config(w), "per-user": _l2_config(w)}
        )).signature}
        for w in (1e-4, 0.01, 1.0, 100.0)
    ]
    swap = [
        {"fused_static_key": TracedProgram("k", key_for(
            {"global": _l2_config(
                0.01, optimizer=optim.OptimizerConfig.tron()
            )}
        )).signature}
    ]
    prec = [
        {"fused_static_key": TracedProgram(
            "k", key_for({}, precision="bfloat16")).signature}
    ]
    mixed = {sig["fused_static_key"] for sig in lam + swap + prec} | {
        base.signature
    }
    notes = [
        f"mixed λ×optimizer grid occupies {len(mixed)} of "
        f"{_FUSED_CACHE_SIZE} fused-cache slots",
    ]
    trace = ContractTrace(
        programs={"fused_static_key": base},
        variants={
            "lambda_grid": lam, "optimizer_swap": swap,
            "precision": prec,
        },
        notes=notes,
    )
    if len(mixed) > _FUSED_CACHE_SIZE:
        trace.notes.append(
            "mixed grid exceeds the fused-cache LRU capacity — "
            "alternating configs will rebuild whole-fit traces"
        )
    return trace


def build_unfused_update() -> ContractTrace:
    """The unfused coordinate update (_run_impl under jit): λ and warm
    starts are traced operands — ONE executable per static config."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_tpu import optim
    from photon_tpu.algorithm.problems import (
        VarianceComputationType,
        _run_jit,
    )
    from photon_tpu.data.dataset import make_dense_batch
    from photon_tpu.ops.normalization import NormalizationContext
    from photon_tpu.types import TaskType

    n, d = 64, 5
    rng = np.random.default_rng(0)
    batch = make_dense_batch(
        rng.normal(size=(n, d)).astype(np.float32),
        (rng.uniform(size=n) < 0.5).astype(np.float32),
    )
    norm = NormalizationContext()

    def tr(l2: float, opt_config=None, w0=None) -> TracedProgram:
        dtype = batch.labels.dtype
        return trace_program(
            "coordinate_update",
            _run_jit,
            batch,
            (jnp.zeros(d, dtype) if w0 is None else w0),
            jnp.asarray(0.0, dtype),
            jnp.asarray(l2, dtype),
            norm,
            None,
            jnp.asarray(1.0, dtype),
            task=TaskType.LOGISTIC_REGRESSION,
            opt_config=opt_config or optim.OptimizerConfig(),
            use_owlqn=False,
            intercept_index=d - 1,
            variance_computation=VarianceComputationType.NONE,
        )

    base = tr(0.01)
    warm = jax.numpy.ones(d, batch.labels.dtype)
    return ContractTrace(
        programs={"coordinate_update": base},
        variants={
            "lambda_grid": [
                {"coordinate_update": tr(w).signature} for w in (1e-3, 10.0)
            ],
            "warm_start": [
                {"coordinate_update": tr(0.01, w0=warm).signature}
            ],
            "optimizer_swap": [
                {
                    "coordinate_update": tr(
                        0.01, opt_config=optim.OptimizerConfig.tron()
                    ).signature
                }
            ],
        },
    )


def build_newton_kernel() -> ContractTrace:
    """The Pallas Newton-step wrapper, traced through the interpreter
    path on non-TPU backends (Mosaic lowering is TPU-only)."""
    import jax

    from photon_tpu.ops.newton_kernel import (
        LANES,
        interpret_required,
        newton_step_lanes,
    )
    from photon_tpu.types import TaskType

    s, r, bp = 4, 6, LANES
    f32 = "float32"

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, f32)

    def tr(name: str, *, s=s, r=r, trials=16) -> TracedProgram:
        return trace_program(
            name,
            newton_step_lanes,
            sds(s, r, bp), sds(s, bp), sds(r, bp), sds(r, bp), sds(r, bp),
            sds(s, bp), sds(s, bp), sds(s, bp), sds(1, bp),
            r=r, s=s,
            task=TaskType.LOGISTIC_REGRESSION,
            trials=trials,
            interpret=interpret_required(),
        )

    base = tr("newton_step")
    return ContractTrace(
        programs={"newton_step": base},
        variants={
            "bucket_shape": [{"newton_step": tr("n", r=r + 2).signature}],
            "line_search_trials": [
                {"newton_step": tr("n", trials=8).signature}
            ],
        },
    )


def build_segment_reduce() -> ContractTrace:
    """The Pallas segment-reduce wrapper, traced through the interpreter
    path on non-TPU backends (Mosaic lowering is TPU-only). Values, ids
    and the prefetched starts are traced operands; only the static
    reduce shape (elements, segments, k_tiles) keys a new executable."""
    import functools
    import os

    import jax
    import numpy as np

    from photon_tpu.ops import segment_reduce as sr

    def tr(name: str, *, m: int, n: int, mult: int = 1) -> TracedProgram:
        fn = functools.partial(
            sr.sorted_segment_sum,
            num_segments=n,
            multiplicity=mult,
            interpret=sr.interpret_required(),
        )
        return trace_program(
            name,
            fn,
            jax.ShapeDtypeStruct((m,), np.float32),
            jax.ShapeDtypeStruct((m,), np.int32),
        )

    # The kernel path must be what gets traced here regardless of the
    # host's backend: force it for the audit (env restored after).
    prev = os.environ.get("PHOTON_SEGMENT_KERNEL")
    os.environ["PHOTON_SEGMENT_KERNEL"] = "force"
    try:
        base = tr("segment_sum", m=4096, n=2048)
        variants = {
            "reduce_shape": [
                {"segment_sum": tr("v", m=8192, n=2048).signature},
                {"segment_sum": tr("v", m=4096, n=2048,
                                   mult=4).signature},
            ],
        }
    finally:
        if prev is None:
            os.environ.pop("PHOTON_SEGMENT_KERNEL", None)
        else:
            os.environ["PHOTON_SEGMENT_KERNEL"] = prev
    return ContractTrace(
        programs={"segment_sum": base},
        variants=variants,
    )


def build_serve_kernel() -> ContractTrace:
    """The fused serve-score kernel's one-program contract.

    The same tiny GLMix fixture as ``build_serving`` is loaded into
    serving tables with ``PHOTON_SERVE_KERNEL=force`` (env restored
    after), so ``ScorePrograms.trace`` lowers the fused pallas_call
    instead of the per-coordinate jit chain — through the interpreter
    path on non-TPU hosts (Mosaic lowering is TPU-only). One rung is
    ONE program: tables, features and the scalar-prefetched codes are
    traced operands. The declared recompile families prove the two
    static specializations still specialize: a different ``rung``
    (grid size) and a different ``model_structure`` (feature width)
    must each perturb the compile key.
    """
    import os

    import numpy as np

    import jax.numpy as jnp

    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(20260806)

    def model_for(d: int, e: int = 7, s: int = 3, du: int = 6):
        prng = np.random.default_rng(1234)
        proj = np.sort(
            np.stack([
                prng.permutation(du)[:s] for _ in range(e)
            ]), axis=1,
        ).astype(np.int64)
        return GameModel({
            "global": FixedEffectModel(
                GeneralizedLinearModel(
                    Coefficients(means=jnp.asarray(
                        rng.normal(size=d).astype(np.float32)
                    )),
                    TaskType.LOGISTIC_REGRESSION,
                ),
                "features",
            ),
            "per-user": RandomEffectModel(
                coefficients=jnp.asarray(
                    rng.normal(size=(e, s)).astype(np.float32)
                ),
                random_effect_type="userId",
                feature_shard_id="userShard",
                task=TaskType.LOGISTIC_REGRESSION,
                proj_all=proj,
                entity_keys=tuple(str(i) for i in range(e)),
            ),
        })

    def rung_program(d: int, rung: int, *, name: str) -> TracedProgram:
        ladder = ShapeLadder((rung,))
        tables = CoefficientTables.from_game_model(model_for(d))
        programs = ScorePrograms(
            tables, ladder=ladder, compile_now=False
        )
        if not programs.use_kernel:
            raise RuntimeError(
                "PHOTON_SERVE_KERNEL=force did not engage the fused "
                "kernel — the serve-kernel contract audits nothing"
            )
        traced = programs.trace(rung)
        return TracedProgram(
            name=name,
            text=str(traced.jaxpr),
            jaxpr=traced.jaxpr,
            lowered=traced.lower(),
        )

    # The kernel path must be what gets traced here regardless of the
    # host's backend: force it for the audit (env restored after).
    prev = os.environ.get("PHOTON_SERVE_KERNEL")
    os.environ["PHOTON_SERVE_KERNEL"] = "force"
    try:
        base = rung_program(5, 8, name="serve_kernel_b8")
        variants = {
            "rung": [
                {"serve_kernel_b8": rung_program(
                    5, r, name="v").signature}
                for r in (1, 64)
            ],
            "model_structure": [
                {"serve_kernel_b8": rung_program(
                    9, 8, name="v").signature},
            ],
        }
    finally:
        if prev is None:  # photon: ignore[spmd-host-divergence] -- env save/restore of the audit fixture's kernel flag; host-local tooling, not fleet code
            os.environ.pop("PHOTON_SERVE_KERNEL", None)
        else:
            os.environ["PHOTON_SERVE_KERNEL"] = prev
    return ContractTrace(
        programs={"serve_kernel_b8": base},
        variants=variants,
        notes=[
            "fused pallas_call traced through the interpret path; "
            "tables/features/codes are traced operands — a values-only "
            "reload re-enters the same executable (build_serving's "
            "model_reload family covers the jit fallback)",
        ],
    )


def build_mesh_sharding() -> ContractTrace:
    """Mesh entry points: the data-parallel GLM objective over a sharded
    batch, plus the random-effect dataset placement rules — checked from
    the placed arrays' NamedShardings and the compiled HLO's collectives.
    Includes the reasoned report of why the fused path rejects meshes."""
    import jax
    import numpy as np

    from photon_tpu.algorithm.fused_fit import fuse_ineligibility_reasons
    from photon_tpu.data.dataset import make_dense_batch
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )
    from photon_tpu.ops import losses as losses_mod
    from photon_tpu.ops import glm as glm_ops
    from photon_tpu.ops.normalization import NormalizationContext
    from photon_tpu.parallel.mesh import (
        make_mesh,
        replicated,
        shard_batch,
        shard_random_effect_dataset,
    )
    from photon_tpu.types import TaskType

    if len(jax.devices()) < 2:
        return ContractTrace(
            programs={},
            notes=[
                "sharding audit SKIPPED: single visible device (run under "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8, as "
                "CI does, to exercise it)",
            ],
        )

    mesh = make_mesh()
    n_dev = len(mesh.devices.reshape(-1))
    n, d = 8 * n_dev, 5
    rng = np.random.default_rng(1)
    batch = shard_batch(
        make_dense_batch(
            rng.normal(size=(n, d)).astype(np.float32),
            (rng.uniform(size=n) < 0.5).astype(np.float32),
        ),
        mesh,
    )
    loss = losses_mod.get_loss(TaskType.LOGISTIC_REGRESSION)

    def objective(b, w):
        return glm_ops.make_value_and_grad(b, loss, NormalizationContext())(w)

    w = jax.device_put(
        jax.numpy.zeros(d, batch.labels.dtype), replicated(mesh)
    )
    prog = trace_program("sharded_objective", objective, batch, w)
    collectives = hlo_collectives(prog.lowered.compile())

    opshardings = {
        "features": str(batch.features.x.sharding.spec),
        "labels": str(batch.labels.sharding.spec),
        "offsets": str(batch.offsets.sharding.spec),
        "weights": str(batch.weights.sharding.spec),
    }

    # Random-effect placement rules: plan arrays entity-sharded, shared
    # raw leaves replicated (mesh.shard_random_effect_dataset contract).
    est, data = _tiny_glmix(n=16 * n_dev, e=2 * n_dev)
    re_ds = build_random_effect_dataset(
        data,
        RandomEffectDataConfiguration("userId", "userShard"),
        intercept_index=3,
    )
    re_ds = shard_random_effect_dataset(re_ds, mesh)
    b0 = re_ds.blocks[0]
    opshardings["re_entity_codes"] = str(b0.entity_codes.sharding.spec)
    opshardings["re_row_ids"] = str(b0.row_ids.sharding.spec)
    raw = re_ds.raw
    raw_leaf = getattr(raw, "x", None)
    if raw_leaf is None:
        raw_leaf = raw.values
    opshardings["re_raw"] = str(raw_leaf.sharding.spec)

    # Why the fused whole-fit path refuses this mesh today — the reasoned
    # report the ROADMAP's multi-device fusion work starts from.
    datasets, _ = est.prepare(data)
    coords = est._build_coordinates(datasets, {}, {}, data.num_samples)
    reasons = fuse_ineligibility_reasons(coords, mesh=mesh)
    notes = [f"mesh fusion blocked: {r}" for r in reasons] or [
        "fuse_ineligibility_reasons reports no blockers — revisit the "
        "estimator's mesh gate"
    ]
    return ContractTrace(
        programs={"sharded_objective": prog},
        opshardings=opshardings,
        collectives=collectives,
        notes=notes,
    )


def build_ingest_pipeline() -> ContractTrace:
    """The ingest pipeline's overlapped AOT warm-compile entry.

    Two properties, both checked against the PRODUCTION fused generation:

    - **census unchanged**: the programs the background warm compile
      traces from shape-PREDICTED skeleton datasets
      (``GameEstimator._warm_compile`` over
      ``skeleton_random_effect_dataset``) must have EXACTLY the
      signatures of the production materialize/fit programs — the warm
      compile mints zero new executables, it pre-pays existing ones. A
      drifted skeleton (wrong predicted bucket shapes, wrong statics)
      shows up as extra programs in the census and as an
      ``aot_warm_compile`` stability violation.
    - **no host sync in the overlap window**: the traced fit jaxpr (which
      signature-equality proves is also the warm-compiled one) carries no
      callback primitive (``hot_loop`` host-boundary check).

    Runs with ``PHOTON_TPU_SERIAL_INGEST=1`` so the build itself is
    deterministic and the warm compile is invoked synchronously.
    """
    with _serial_ingest_env():
        est, data = _tiny_glmix()
        datasets, _ = est.prepare(data)
        coords = est._build_coordinates(
            datasets, {}, {}, data.num_samples
        )
        fused = est._fused_for(coords, datasets)
        mat = trace_program(
            "materialize", fused._mat_jit, fused._mat_operands(coords)
        )
        traced = fused.trace(coords)
        fit = TracedProgram(
            name="fit",
            text=str(traced.jaxpr),
            jaxpr=traced.jaxpr,
            lowered=traced.lower(),
        )
        art = est._warm_compile(data)
    variants: dict[str, list[dict[str, str]]] = {"aot_warm_compile": []}
    notes = []
    if art is not None:
        variants["aot_warm_compile"].append({
            "materialize": TracedProgram(
                "materialize", art["mat_text"]).signature,
            "fit": TracedProgram("fit", art["fit_text"]).signature,
        })
        notes.append(
            "warm compile traced from predicted shapes; signature "
            "equality with the production programs proves the compiled "
            "executables are the ones the first fit dispatches"
        )
    # else: the empty declared-stable family trips the program-contract
    # integrity finding — prediction silently declining on the canonical
    # fixture is a contract violation, not a skip.
    return ContractTrace(
        programs={"materialize": mat, "fit": fit},
        variants=variants,
        notes=notes,
    )


def build_telemetry() -> ContractTrace:
    """The telemetry layer's audited zero-overhead guarantee.

    The instrumented entry points — the fused materialize + whole-fit
    programs that every obs span wraps and every convergence trace rides
    — are traced twice, with telemetry DISABLED (base) and ENABLED
    (the ``telemetry_toggle`` variant family). The checks then prove:

    - **zero dispatches added**: the census across both states stays at
      the fused generation's own 2 programs — enabling telemetry mints
      no executable (convergence metrics are unconditional outputs of
      the existing fit program, never a side program or a split);
    - **zero host callbacks**: the hot-loop host-boundary walk over the
      (shared) jaxpr finds no callback primitive — spans and the async
      convergence fetch live entirely OUTSIDE the trace;
    - **identical recompile keys**: ``stable_under=telemetry_toggle`` —
      the enabled-state signatures must be byte-identical to the
      disabled-state ones, so flipping telemetry can never trigger a
      recompile in production.
    """
    from photon_tpu import obs

    with _serial_ingest_env():
        est, data = _tiny_glmix()
        datasets, _ = est.prepare(data)
        coords = est._build_coordinates(
            datasets, {}, {}, data.num_samples
        )
        fused = est._fused_for(coords, datasets)
        was_enabled = obs.enabled()
        obs.disable()
        try:
            mat_off = trace_program(
                "materialize", fused._mat_jit, fused._mat_operands(coords)
            )
            traced_off = fused.trace(coords)
            fit_off = TracedProgram(
                name="fit",
                text=str(traced_off.jaxpr),
                jaxpr=traced_off.jaxpr,
                lowered=traced_off.lower(),
            )
            obs.enable()
            mat_on = trace_program(
                "materialize", fused._mat_jit, fused._mat_operands(coords)
            )
            traced_on = fused.trace(coords)
            fit_on = TracedProgram(
                name="fit", text=str(traced_on.jaxpr)
            )
        finally:
            obs.TRACER.enabled = was_enabled
    return ContractTrace(
        programs={"materialize": mat_off, "fit": fit_off},
        variants={
            "telemetry_toggle": [
                {
                    "materialize": mat_on.signature,
                    "fit": fit_on.signature,
                }
            ]
        },
        notes=[
            "telemetry on vs off traced the same materialize/fit "
            "jaxprs: the enable flag is host-side only (convergence "
            "metrics are unconditional program outputs; spans never "
            "enter a trace)",
        ],
    )


def build_trace() -> ContractTrace:
    """The timeline layer's audited zero-overhead guarantee.

    ``build_telemetry`` proves the span/metric/convergence surfaces add
    nothing to the traced programs; this contract raises the same bar
    for the TRACE layer on top of them (``obs/trace.py`` +
    ``obs/flight.py``): the fused materialize + whole-fit programs are
    traced with everything OFF (base) and then with the layer fully
    ARMED — telemetry enabled, a flight recorder installed (its
    excepthook + crash-listener chained, its counter baseline taken),
    and the event ring actively receiving instants, counter samples,
    and request records between the two traces. The
    ``trace_toggle`` variant must be byte-identical to the base:
    events are host-ring bookkeeping on the perf_counter clock, never
    a traced operand, a callback, or a program split.
    """
    import tempfile

    from photon_tpu import obs
    from photon_tpu.obs import flight
    from photon_tpu.obs import trace as obs_trace

    with _serial_ingest_env():
        est, data = _tiny_glmix()
        datasets, _ = est.prepare(data)
        coords = est._build_coordinates(
            datasets, {}, {}, data.num_samples
        )
        fused = est._fused_for(coords, datasets)
        was_enabled = obs.enabled()
        obs.disable()
        try:
            mat_off = trace_program(
                "materialize", fused._mat_jit, fused._mat_operands(coords)
            )
            traced_off = fused.trace(coords)
            fit_off = TracedProgram(
                name="fit",
                text=str(traced_off.jaxpr),
                jaxpr=traced_off.jaxpr,
                lowered=traced_off.lower(),
            )
            # Arm the whole layer (install enables telemetry) and keep
            # the ring HOT while the armed trace is taken.
            tmpdir = tempfile.mkdtemp(prefix="photon-trace-audit-")
            flight.install(tmpdir, signals=False)
            try:
                obs_trace.instant("audit.armed", cat="audit")
                obs_trace.counter("audit_gauge", 1.0)
                obs_trace.request({
                    "id": 0, "outcome": "served",
                    "submit_ts": 0.0, "done_ts": 0.0,
                })
                mat_on = trace_program(
                    "materialize", fused._mat_jit,
                    fused._mat_operands(coords),
                )
                traced_on = fused.trace(coords)
                fit_on = TracedProgram(
                    name="fit", text=str(traced_on.jaxpr)
                )
            finally:
                flight.uninstall()
                # The audit fed the PROCESS-GLOBAL ring (a phantom
                # served request, audit instants) purely to arm the
                # traced state — clean up behind it, or a later
                # in-process consumer (request_summary, the exporters)
                # sees audit debris on its timeline.
                obs_trace.reset()
                import shutil

                shutil.rmtree(tmpdir, ignore_errors=True)
        finally:
            obs.TRACER.enabled = was_enabled
    return ContractTrace(
        programs={"materialize": mat_off, "fit": fit_off},
        variants={
            "trace_toggle": [
                {
                    "materialize": mat_on.signature,
                    "fit": fit_on.signature,
                }
            ]
        },
        notes=[
            "flight recorder installed + event ring receiving "
            "instants/counters/request records traced the same "
            "materialize/fit jaxprs as the all-off base: the timeline "
            "layer is host bookkeeping only",
        ],
    )


def build_fleet() -> ContractTrace:
    """The distributed-observability layer's audited zero-overhead
    guarantee (``obs/fleet.py``).

    The fused materialize + whole-fit programs are traced with fleet
    shipping fully ARMED — telemetry enabled, the host-identity block
    stamped, the clock-alignment handshake marked (``mark_init``), and
    a whole bundle COMMITTED to disk (spans JSONL + metrics + ledger
    rows through ``ship_bundle``) between the two traces. The
    ``fleet_toggle`` variant must be byte-identical to the all-off
    base with ZERO added programs: identity is a cached host dict,
    clock samples are paired ``time()`` reads, and a bundle ship is
    ring snapshots + atomic file writes — never a traced operand, a
    host callback in the hot loop, or a cross-host exchange inside a
    program. Zero added collectives is checked explicitly: the armed
    lowered HLO must carry exactly the collective census of the base
    (both empty on the single-device fixture).
    """
    import shutil
    import tempfile

    from photon_tpu import obs
    from photon_tpu.obs import fleet
    from photon_tpu.obs import trace as obs_trace

    def _collective_census(lowered) -> list[str]:
        if lowered is None:
            return []
        try:
            text = lowered.as_text()
        except Exception:  # noqa: BLE001 — backend without HLO text
            return []
        from photon_tpu.analysis import spmd

        return spmd.collective_census(text)

    with _serial_ingest_env():
        est, data = _tiny_glmix()
        datasets, _ = est.prepare(data)
        coords = est._build_coordinates(
            datasets, {}, {}, data.num_samples
        )
        fused = est._fused_for(coords, datasets)
        was_enabled = obs.enabled()
        obs.disable()
        try:
            mat_off = trace_program(
                "materialize", fused._mat_jit, fused._mat_operands(coords)
            )
            traced_off = fused.trace(coords)
            fit_off = TracedProgram(
                name="fit",
                text=str(traced_off.jaxpr),
                jaxpr=traced_off.jaxpr,
                lowered=traced_off.lower(),
            )
            base_census = _collective_census(fit_off.lowered)
            # Arm the whole fleet layer and COMMIT a real bundle while
            # the armed trace is taken.
            obs.enable()
            tmpdir = tempfile.mkdtemp(prefix="photon-fleet-audit-")
            try:
                fleet.set_run_id("fleet-audit")
                fleet.mark_init()
                with obs.span("fleet_audit_span"):
                    pass
                obs_trace.instant("fleet.audit", cat="audit")
                fleet.ship_bundle(tmpdir)
                mat_on = trace_program(
                    "materialize", fused._mat_jit,
                    fused._mat_operands(coords),
                )
                traced_on = fused.trace(coords)
                fit_on = TracedProgram(
                    name="fit",
                    text=str(traced_on.jaxpr),
                    lowered=traced_on.lower(),
                )
                armed_census = _collective_census(fit_on.lowered)
            finally:
                fleet.reset()
                obs_trace.reset()
                obs.TRACER.reset()
                shutil.rmtree(tmpdir, ignore_errors=True)
        finally:
            obs.TRACER.enabled = was_enabled
    if armed_census != base_census:
        raise RuntimeError(
            "fleet-armed fit program changed its collective census: "
            f"base {base_census} vs armed {armed_census}"
        )
    return ContractTrace(
        programs={"materialize": mat_off, "fit": fit_off},
        variants={
            "fleet_toggle": [
                {
                    "materialize": mat_on.signature,
                    "fit": fit_on.signature,
                }
            ]
        },
        collectives=base_census,
        notes=[
            "fleet armed (identity stamped, clock handshake marked, "
            "bundle committed to disk) traced the same materialize/fit "
            "jaxprs as the all-off base; collective census identical "
            f"armed vs off ({len(base_census)} ops)",
        ],
    )


def build_ledger() -> ContractTrace:
    """The cost ledger's audited zero-overhead guarantee.

    The fused materialize + whole-fit programs are traced with the
    ledger OFF (base) and then FULLY ARMED — enabled, a program in the
    census, dispatch/compile/resident records landing through every
    recording helper between the two traces. The ``ledger_toggle``
    variant must be byte-identical to the base with ZERO added
    programs: attribution rows are host dicts under a host lock, the
    static-cost join is a lazy thunk priced at report time, and a
    ledger-DISABLED run registers nothing at all (the census stays
    empty — the profile-smoke CI job asserts that end too).
    """
    from photon_tpu import obs
    from photon_tpu.obs import ledger

    with _serial_ingest_env():
        est, data = _tiny_glmix()
        datasets, _ = est.prepare(data)
        coords = est._build_coordinates(
            datasets, {}, {}, data.num_samples
        )
        fused = est._fused_for(coords, datasets)
        was_enabled = obs.enabled()
        was_ledger = ledger.enabled()
        obs.disable()
        ledger.disable()
        try:
            mat_off = trace_program(
                "materialize", fused._mat_jit, fused._mat_operands(coords)
            )
            traced_off = fused.trace(coords)
            fit_off = TracedProgram(
                name="fit",
                text=str(traced_off.jaxpr),
                jaxpr=traced_off.jaxpr,
                lowered=traced_off.lower(),
            )
            # Arm the whole layer and keep the accumulators HOT while
            # the armed trace is taken: census, dispatch rows (with
            # per-coordinate parts + host-gap), compile ledger, and
            # the resident account all receive records.
            obs.enable()
            ledger.enable()
            try:
                ledger.register_program(
                    "audit/program", phase="audit",
                    cost={"flops": 1.0, "hbm_bytes": 1.0},
                )
                ledger.record_dispatch(
                    "audit/program", 1e-3, phase="audit",
                    start=0.0, end=1e-3,
                    parts={"audit-coord": 1e-3},
                )
                ledger.record_unattributed(1e-4)
                ledger.record_compile("audit/key", 1e-2)
                ledger.set_resident("audit/table", 128.0)
                mat_on = trace_program(
                    "materialize", fused._mat_jit,
                    fused._mat_operands(coords),
                )
                traced_on = fused.trace(coords)
                fit_on = TracedProgram(
                    name="fit", text=str(traced_on.jaxpr)
                )
            finally:
                # Audit debris must not leak into a later in-process
                # consumer's ledger (a bench attribution window, a
                # pilot cycle report).
                ledger.reset()
        finally:
            obs.TRACER.enabled = was_enabled
            if was_ledger:
                ledger.enable()
            else:
                ledger.disable()
    return ContractTrace(
        programs={"materialize": mat_off, "fit": fit_off},
        variants={
            "ledger_toggle": [
                {
                    "materialize": mat_on.signature,
                    "fit": fit_on.signature,
                }
            ]
        },
        notes=[
            "ledger armed (census + dispatch rows + compile ledger + "
            "resident account all fed) traced the same materialize/fit "
            "jaxprs as the all-off base: attribution is host "
            "bookkeeping, pricing is lazy at report time",
        ],
    )


def build_health() -> ContractTrace:
    """The model/data-health layer's audited zero-dispatch guarantee.

    The fused materialize + whole-fit programs are traced with health
    OFF (base) and then FULLY ARMED — enabled, a training DataSketch
    fed and registered, the serve tap folding sampled batches, a
    numerics sentinel parked AND materialized (the report scan), and a
    gate decision recorded — between the two traces. The
    ``health_toggle`` variant must be byte-identical to the base with
    ZERO added programs: sketches are host numpy under a host lock,
    the sentinel parks a reference to an array the fit ALREADY outputs
    (the convergence block), and PSI/ECE/movement scoring happens at
    report time, never inside (or as) a traced program.
    """
    import numpy as np

    from photon_tpu.obs import health

    with _serial_ingest_env():
        est, data = _tiny_glmix()
        datasets, _ = est.prepare(data)
        coords = est._build_coordinates(
            datasets, {}, {}, data.num_samples
        )
        fused = est._fused_for(coords, datasets)
        was_health = health.enabled()
        health.disable()
        try:
            mat_off = trace_program(
                "materialize", fused._mat_jit, fused._mat_operands(coords)
            )
            traced_off = fused.trace(coords)
            fit_off = TracedProgram(
                name="fit",
                text=str(traced_off.jaxpr),
                jaxpr=traced_off.jaxpr,
                lowered=traced_off.lower(),
            )
            # Arm the whole layer and keep every surface HOT while the
            # armed trace is taken: train sketch, serve tap, parked +
            # scanned sentinel, recorded gate decision.
            health.enable()
            try:
                sketch = health.DataSketch()
                sketch.update_window(
                    np.asarray([0.0, 1.0, 1.0]),
                    np.zeros(3),
                    np.ones(3),
                    {"audit": (
                        np.asarray([[0, 1], [1, 0], [0, 1]]),
                        np.asarray([[0.5, 1.0], [2.0, 0.0], [1.5, 0.5]]),
                    )},
                    {"audit": 4},
                )
                health.set_train_sketch(sketch)
                health.set_serve_sample_every(1)
                health.observe_serve_batch(
                    [{"audit": np.zeros(4, dtype=np.float32)}],
                    np.asarray([0.25]),
                )
                health.sentinel_watch(
                    ("audit-coord",),
                    np.asarray([[[1.0, np.nan, 0.0, 0.0, 0.0]]]),
                )
                report = health.numerics_report()
                health.record_gate({
                    "reasons": [], "nonfinite": report["nonfinite_total"],
                })
                mat_on = trace_program(
                    "materialize", fused._mat_jit,
                    fused._mat_operands(coords),
                )
                traced_on = fused.trace(coords)
                fit_on = TracedProgram(
                    name="fit", text=str(traced_on.jaxpr)
                )
            finally:
                # Audit debris (the fake sentinel, the sampled batch)
                # must not leak into a later in-process consumer's
                # health surfaces (a pilot gate, a bench drift run).
                health.reset()
        finally:
            if was_health:
                health.enable()
            else:
                health.disable()
    return ContractTrace(
        programs={"materialize": mat_off, "fit": fit_off},
        variants={
            "health_toggle": [
                {
                    "materialize": mat_on.signature,
                    "fit": fit_on.signature,
                }
            ]
        },
        notes=[
            "health armed (train sketch + serve tap + parked/scanned "
            "numerics sentinel + recorded gate) traced the same "
            "materialize/fit jaxprs as the all-off base: sketching and "
            "scoring are host bookkeeping, the sentinel reads an "
            "output the program already computes",
        ],
    )


def build_monitor() -> ContractTrace:
    """The live-monitoring layer's audited zero-overhead guarantee.

    The serving score program (the request hot path the exporter
    observes) is traced with everything OFF (base), then with the
    monitor layer FULLY ARMED AND UNDER LOAD: a ``MonitorServer`` up on
    an ephemeral port with the window-histogram/SLO/hotness collectors
    registered, a feeder thread pumping observations into the window
    ring, the sketch, and the SLO tracker the whole time, and real
    HTTP scrapes of ``/metrics`` + ``/healthz`` + ``/readyz`` issued
    before, during, and after the armed trace. The ``monitor_scrape``
    variant must be byte-identical to the base with zero added
    programs — a scrape is host bookkeeping and socket I/O, never a
    traced operand or a callback — and every scraped ``/metrics`` body
    must validate as Prometheus text exposition
    (``monitor.validate_exposition``).
    """
    import threading
    import urllib.request

    import numpy as np

    import jax.numpy as jnp

    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.obs import monitor
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables
    from photon_tpu.types import TaskType

    d, e, s, du = 4, 5, 2, 4
    rng = np.random.default_rng(20260803)
    proj = np.stack([
        np.sort(rng.permutation(du)[:s]) for _ in range(e)
    ]).astype(np.int64)
    model = GameModel({
        "global": FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(means=jnp.asarray(
                    rng.normal(size=d).astype(np.float32)
                )),
                TaskType.LOGISTIC_REGRESSION,
            ),
            "features",
        ),
        "per-user": RandomEffectModel(
            coefficients=jnp.asarray(
                rng.normal(size=(e, s)).astype(np.float32)
            ),
            random_effect_type="userId",
            feature_shard_id="userShard",
            task=TaskType.LOGISTIC_REGRESSION,
            proj_all=proj,
            entity_keys=tuple(str(i) for i in range(e)),
        ),
    })
    tables = CoefficientTables.from_game_model(model)
    programs = ScorePrograms(
        tables, ladder=ShapeLadder((8,)), compile_now=False
    )

    def trace_once() -> TracedProgram:
        traced = programs.trace(8)
        return TracedProgram(
            name="score_b8",
            text=str(traced.jaxpr),
            jaxpr=traced.jaxpr,
            lowered=traced.lower(),
        )

    base = trace_once()

    hist = monitor.RollingHistogram(window_s=0.5, num_windows=4)
    sketch = monitor.SpaceSavingSketch(8)
    slo = monitor.SloTracker(
        monitor.SloPolicy(short_window_s=0.5, long_window_s=2.0)
    )

    def collect():
        return (
            [hist.prometheus_family(
                "audit_latency_window_seconds", "audit window ring")]
            + slo.prometheus_families()
        )

    stop = threading.Event()

    def feeder():
        import time

        i = 0
        while not stop.is_set():
            hist.observe(0.001 * (1 + i % 7))
            sketch.observe(f"entity-{i % 11}")
            slo.observe_request(0.002)
            slo.observe_lookups(4, 1)
            i += 1
            # Keep the surfaces hot without pegging a CI core: the
            # audit needs concurrent writers, not maximum write rate.
            time.sleep(0.0005)

    notes: list[str] = []
    srv = monitor.MonitorServer(0, readiness=lambda: (True, {}),
                                collectors=[collect]).start()
    thread = threading.Thread(target=feeder, daemon=True)  # photon: ignore[concurrency-contract] -- audit-fixture load generator, joined before the builder returns; the shared surfaces it feeds carry their own obs-monitor contract
    thread.start()

    def scrape() -> None:
        for path in ("/metrics", "/healthz", "/readyz"):
            body = urllib.request.urlopen(
                srv.url + path, timeout=5
            ).read().decode("utf-8")
            if path == "/metrics":
                monitor.validate_exposition(body)

    # A second scraper loops CONCURRENTLY with the armed trace below —
    # "during" is exercised for real, not just claimed. Its failures
    # are collected and re-raised as a builder error (-> a
    # program-contract finding), never swallowed.
    scrape_errors: list[BaseException] = []
    during_scrapes = [0]

    def scraper():
        while not stop.is_set():
            try:
                scrape()
                during_scrapes[0] += 1
            except BaseException as exc:  # noqa: BLE001 — surfaced below
                scrape_errors.append(exc)
                return

    scraper_thread = threading.Thread(target=scraper, daemon=True)  # photon: ignore[concurrency-contract] -- audit-fixture scraper, joined before the builder returns; see the feeder waiver above
    try:
        scrape()
        scraper_thread.start()
        armed = TracedProgram(
            name="score_b8", text=str(programs.trace(8).jaxpr)
        )
        stop.set()
        scraper_thread.join(timeout=10.0)
        scrape()
        if scrape_errors:
            raise scrape_errors[0]
        notes.append(
            f"exporter scraped before, DURING ({during_scrapes[0]} "
            "concurrent scrape round(s)), and after the armed trace; "
            "every /metrics body validated as text exposition; the "
            "window ring, hotness sketch, and SLO tracker were fed "
            "from a second thread throughout"
        )
    finally:
        stop.set()
        thread.join(timeout=5.0)
        if scraper_thread.is_alive():  # pragma: no cover — start() raced
            scraper_thread.join(timeout=5.0)
        srv.stop()
    return ContractTrace(
        programs={"score_b8": base},
        variants={"monitor_scrape": [{"score_b8": armed.signature}]},
        notes=notes,
    )


def build_serving() -> ContractTrace:
    """The serving score ladder's zero-recompile contract.

    A small GLMix model (one dense fixed effect + one random effect with
    a non-trivial projector) is loaded into serving tables and its
    ladder program traced at every rung — those are the base programs
    (census bound = rung count). Two variant families then prove the
    steady state is CLOSED:

    - ``request_batch``: every request count from 1 to the top rung,
      padded through the PRODUCTION pad rule (``ShapeLadder.rung_for``),
      must trace to the signature of its rung's base program — a pad
      rule that leaked an unpadded (or wrongly padded) shape would mint
      a new program here and fail both the census and the stability
      check.
    - ``model_reload``: the tables refreshed in place with different
      coefficient VALUES (same shapes) must trace every rung to a
      byte-identical signature — coefficients are traced operands, so a
      model reload can never trigger a recompile in a serving process.

    The fit programs' ``hot_loop`` host-boundary walk applies too: no
    callback primitive may live in the request hot path.
    """
    import numpy as np

    import jax.numpy as jnp

    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables
    from photon_tpu.types import TaskType

    d, e, s, du = 5, 7, 3, 6
    rng = np.random.default_rng(20260803)

    def model_for(scale: float) -> GameModel:
        # Fixed-seed projector: the reload variant below must be a
        # VALUES-ONLY refresh (reload's in-place condition).
        prng = np.random.default_rng(1234)
        proj = np.sort(
            np.stack([
                prng.permutation(du)[:s] for _ in range(e)
            ]), axis=1,
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

    ladder = ShapeLadder((1, 8, 64))
    tables = CoefficientTables.from_game_model(model_for(1.0))
    programs = ScorePrograms(tables, ladder=ladder, compile_now=False)

    def rung_program(progs: ScorePrograms, batch: int) -> TracedProgram:
        traced = progs.trace(batch)
        return TracedProgram(
            name=f"score_b{batch}",
            text=str(traced.jaxpr),
            jaxpr=traced.jaxpr,
            lowered=traced.lower(),
        )

    base = {
        f"score_b{r}": rung_program(programs, r) for r in ladder.rungs
    }

    variants: dict[str, list[dict[str, str]]] = {
        "request_batch": [],
        "model_reload": [],
    }
    # One fresh trace per DISTINCT shape the pad rule produces (a
    # broken rung_for surfaces as a new shape here — traced at n, its
    # signature both breaks the census bound and misses the base
    # programs); re-tracing identical rungs per request count would add
    # gate wall-clock for zero signal.
    rung_sigs: dict[int, str] = {}
    for n in range(1, ladder.max_batch + 1):
        rung = ladder.rung_for(n)
        if rung not in rung_sigs:
            rung_sigs[rung] = TracedProgram(
                name="v", text=str(programs.trace(rung).jaxpr)
            ).signature
        variants["request_batch"].append(
            {f"score_b{rung}": rung_sigs[rung]}
        )
    tables.reload(model_for(2.5))
    variants["model_reload"].append({
        name: TracedProgram(
            name="v", text=str(programs.trace(r).jaxpr)
        ).signature
        for r, name in zip(ladder.rungs, base)
    })
    return ContractTrace(
        programs=base,
        variants=variants,
        notes=[
            f"ladder {ladder.rungs}: every request count 1.."
            f"{ladder.max_batch} pads into the {len(ladder.rungs)} "
            "compiled rungs; an in-place model reload re-traces to "
            "byte-identical programs (tables are traced operands)",
        ],
    )


def build_resilience() -> ContractTrace:
    """The resilience layer's zero-program-footprint contract.

    ``call_with_retry`` and ``faults.check`` are HOST machinery wrapped
    around already-built executables — they must never alter what gets
    traced. Proof by construction: one serving score program (a tiny
    GLMix structure, single rung) is the base; the SAME trace is then
    taken (a) from inside a ``call_with_retry`` wrapper and (b) with a
    full-coverage armed ``FaultPlan`` whose triggers can never fire
    (``nth`` beyond any call count) — both must be byte-identical to
    the base signature. The ``hot_loop`` walk additionally proves no
    callback primitive entered the jaxpr (a retry layer implemented as
    an in-trace ``pure_callback`` would fail here, which is the point).
    """
    import numpy as np

    import jax.numpy as jnp

    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.resilience import FaultPlan, call_with_retry, faults
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables
    from photon_tpu.types import TaskType

    d, e, s, du = 4, 5, 2, 4
    rng = np.random.default_rng(20260803)
    proj = np.stack([
        np.sort(rng.permutation(du)[:s]) for _ in range(e)
    ]).astype(np.int64)
    model = GameModel({
        "global": FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(means=jnp.asarray(
                    rng.normal(size=d).astype(np.float32)
                )),
                TaskType.LOGISTIC_REGRESSION,
            ),
            "features",
        ),
        "per-user": RandomEffectModel(
            coefficients=jnp.asarray(
                rng.normal(size=(e, s)).astype(np.float32)
            ),
            random_effect_type="userId",
            feature_shard_id="userShard",
            task=TaskType.LOGISTIC_REGRESSION,
            proj_all=proj,
            entity_keys=tuple(str(i) for i in range(e)),
        ),
    })
    tables = CoefficientTables.from_game_model(model)
    programs = ScorePrograms(
        tables, ladder=ShapeLadder((8,)), compile_now=False
    )

    def trace_once() -> TracedProgram:
        traced = programs.trace(8)
        return TracedProgram(
            name="score_b8",
            text=str(traced.jaxpr),
            jaxpr=traced.jaxpr,
            lowered=traced.lower(),
        )

    base = trace_once()
    wrapped = call_with_retry(trace_once, site="audit.resilience")
    # Full coverage, unreachable triggers: arming must be invisible to
    # tracing (the hooks are host-side, outside any trace).
    plan = FaultPlan(
        [dict(point=p, nth=10**9) for p in faults.INJECTION_POINTS],
        seed=0,
    )
    with faults.injected(plan):
        armed = trace_once()
    return ContractTrace(
        programs={"score_b8": base},
        variants={
            "retry_wrap": [{"score_b8": wrapped.signature}],
            "fault_plan_armed": [{"score_b8": armed.signature}],
        },
        notes=[
            "retry wrapper + armed FaultPlan trace byte-identical "
            "programs: the resilience layer is host-level only",
        ],
    )


def build_streaming_ingest() -> ContractTrace:
    """The streaming ingest's zero-program-perturbation contract.

    The SAME logical data is ingested two ways — the in-memory
    ``read_training_examples`` path (base) and ``StreamingIngest`` over
    a sharded on-disk copy with a multi-shard window plan (the
    ``streamed_ingest`` variant family) — and the fused materialize +
    whole-fit programs are traced from each. The checks prove the
    streamed dataset dispatches BYTE-IDENTICAL programs: identical
    census (zero added programs), identical recompile keys, and a
    callback-free hot loop. Windowed assembly, quarantine accounting,
    spill/cursor machinery are host/IO-level only, provably.
    """
    import shutil
    import tempfile

    import numpy as np

    from photon_tpu.data.random_effect import RandomEffectDataConfiguration
    from photon_tpu.data.stream import StreamingIngest
    from photon_tpu.estimators.game_estimator import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu.io.avro_data import (
        read_training_examples,
        write_training_examples,
    )
    from photon_tpu.types import DELIMITER, TaskType

    def make_estimator():
        return GameEstimator(
            TaskType.LOGISTIC_REGRESSION,
            {
                "global": FixedEffectCoordinateConfiguration(
                    "features", _l2_config(0.01)),
                "per-user": RandomEffectCoordinateConfiguration(
                    RandomEffectDataConfiguration("userId", "features"),
                    _l2_config(0.5),
                ),
            },
            num_iterations=2,
            mesh="off",
        )

    def trace_pair(est, data):
        datasets, _ = est.prepare(data)
        coords = est._build_coordinates(
            datasets, {}, {}, data.num_samples
        )
        fused = est._fused_for(coords, datasets)
        mat = trace_program(
            "materialize", fused._mat_jit, fused._mat_operands(coords)
        )
        traced = fused.trace(coords)
        fit = TracedProgram(
            name="fit",
            text=str(traced.jaxpr),
            jaxpr=traced.jaxpr,
            lowered=traced.lower(),
        )
        return mat, fit

    tmp = tempfile.mkdtemp(prefix="photon_stream_audit")
    try:
        with _serial_ingest_env():
            rng = np.random.default_rng(20260803)
            n_per, shards_n, d, e = 32, 3, 4, 7
            base = 0
            for si in range(shards_n):
                y = (rng.uniform(size=n_per) < 0.5).astype(float)
                rows = [
                    [(f"f{j}{DELIMITER}t", float(rng.normal()))
                     for j in range(d)]
                    for _ in range(n_per)
                ]
                meta = [
                    {"userId": f"u{rng.integers(0, e)}"}
                    for _ in range(n_per)
                ]
                write_training_examples(
                    os.path.join(tmp, f"part-{si:05d}.avro"),
                    y, rows, metadata=meta,
                    uids=np.arange(base, base + n_per),
                )
                base += n_per
            in_mem, imap = read_training_examples(tmp)
            mat_base, fit_base = trace_pair(make_estimator(), in_mem)
            streamed, stats = StreamingIngest(
                tmp,
                work_dir=os.path.join(tmp, "work"),
                index_maps={"features": imap},
                id_tag_names=["userId"],
                window_shards=2,
            ).run()
            mat_s, fit_s = trace_pair(make_estimator(), streamed)
        notes = [
            "streamed windows vs in-memory ingest traced the same "
            "materialize/fit jaxprs: the streaming layer (manifest, "
            "windows, spills, cursor) is host/IO machinery only",
            f"clean streamed run ingested_fraction="
            f"{stats['ingested_fraction']}, quarantined="
            f"{stats['shards_quarantined']}",
        ]
        if stats["ingested_fraction"] != 1.0:
            notes.append(
                "AUDIT FIXTURE ANOMALY: the clean streamed run did not "
                "ingest everything")
        return ContractTrace(
            programs={"materialize": mat_base, "fit": fit_base},
            variants={
                "streamed_ingest": [
                    {
                        "materialize": mat_s.signature,
                        "fit": fit_s.signature,
                    }
                ]
            },
            notes=notes,
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def build_evaluators() -> ContractTrace:
    """Evaluation + scoring entry points: shape-specialized (a row-count
    change recompiles, by design), value-stable, no host callbacks."""
    import jax

    from photon_tpu.evaluation.evaluators import auc_roc, rmse
    from photon_tpu.models.glm import Coefficients

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, "float32")

    def tr_eval(name, fn, n) -> TracedProgram:
        return trace_program(name, fn, sds(n), sds(n))

    def score(w, x):
        from photon_tpu.data.dataset import DenseFeatures

        return Coefficients(means=w).compute_score(DenseFeatures(x))

    base_auc = tr_eval("auc", auc_roc, 256)
    base_rmse = tr_eval("rmse", rmse, 256)
    scoring = trace_program("fixed_effect_score", score, sds(5), sds(256, 5))
    return ContractTrace(
        programs={
            "auc": base_auc,
            "rmse": base_rmse,
            "fixed_effect_score": scoring,
        },
        variants={
            "row_count": [
                {
                    "auc": tr_eval("auc", auc_roc, 512).signature,
                    "rmse": tr_eval("rmse", rmse, 512).signature,
                }
            ],
        },
    )


def build_pilot() -> ContractTrace:
    """The pilot's zero-recompile promotion contract.

    A promotion cycle's serving-side effect is exactly one call into
    the reload path (``MicroBatchQueue.reload_model`` →
    ``CoefficientTables.rebuild_from``, which short-circuits a
    values-only delta to the in-place reference swap). Proof: a live
    ladder's rungs are traced as the base programs; then TWO
    consecutive day-over-day promotions — refreshed coefficient VALUES
    on the same structure, the pinned-vocabulary steady state the pilot
    maintains — drive that same swap, and every post-promotion trace
    must be byte-identical to its rung's base program. The census bound
    is the rung count: a control loop that minted even one program per
    promotion would fail the round it shipped. The ``hot_loop`` walk
    applies too: supervision must add no callback to the request path.
    """
    import numpy as np

    import jax.numpy as jnp

    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables
    from photon_tpu.types import TaskType

    d, e, s, du = 5, 6, 3, 5
    rng = np.random.default_rng(20260804)

    def day_model(scale: float) -> GameModel:
        # Fixed projector/vocabulary across "days" — the pinned-vocab
        # steady state every pilot promotion relies on.
        prng = np.random.default_rng(99)
        proj = np.sort(
            np.stack([prng.permutation(du)[:s] for _ in range(e)]),
            axis=1,
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

    ladder = ShapeLadder((1, 8))
    tables = CoefficientTables.from_game_model(day_model(1.0))
    programs = ScorePrograms(tables, ladder=ladder, compile_now=False)

    def trace_rungs() -> dict[str, TracedProgram]:
        out = {}
        for r in ladder.rungs:
            traced = programs.trace(r)
            out[f"score_b{r}"] = TracedProgram(
                name=f"score_b{r}",
                text=str(traced.jaxpr),
                jaxpr=traced.jaxpr,
                lowered=traced.lower(),
            )
        return out

    base = trace_rungs()
    variants: dict[str, list[dict[str, str]]] = {"promotion_cycle": []}
    for scale in (1.7, 0.6):  # two consecutive "days"
        # The pilot's PROMOTE serving swap: rebuild_from short-circuits
        # the values-only delta to the in-place reference swap (the
        # exact call chain under MicroBatchQueue.reload_model). Were
        # the refresh NOT values-only, the re-trace below would mint
        # new signatures and fail the stability check — which is the
        # finding this contract exists to catch.
        tables.rebuild_from(day_model(scale), programs=None)
        variants["promotion_cycle"].append({
            name: prog.signature
            for name, prog in trace_rungs().items()
        })
    return ContractTrace(
        programs=base,
        variants=variants,
        notes=[
            f"2 consecutive values-only promotions over ladder "
            f"{ladder.rungs}: every post-promotion trace is "
            "byte-identical to its rung's base program — the control "
            "loop adds zero serving programs",
        ],
    )


_BUILDERS: dict[str, Callable[[], ContractTrace]] = {
    "build_fused_fit": build_fused_fit,
    "build_fused_cache_keys": build_fused_cache_keys,
    "build_unfused_update": build_unfused_update,
    "build_newton_kernel": build_newton_kernel,
    "build_segment_reduce": build_segment_reduce,
    "build_serve_kernel": build_serve_kernel,
    "build_mesh_sharding": build_mesh_sharding,
    "build_ingest_pipeline": build_ingest_pipeline,
    "build_telemetry": build_telemetry,
    "build_trace": build_trace,
    "build_fleet": build_fleet,
    "build_health": build_health,
    "build_ledger": build_ledger,
    "build_monitor": build_monitor,
    "build_pilot": build_pilot,
    "build_serving": build_serving,
    "build_resilience": build_resilience,
    "build_streaming_ingest": build_streaming_ingest,
    "build_evaluators": build_evaluators,
}

# Contracts owned by the analysis tier itself (no better home module).
_LOCAL_AUDITS = (
    dict(
        name="evaluation-scoring",
        entry="evaluation.evaluators.auc_roc / rmse; "
        "models.glm.Coefficients.compute_score",
        builder="build_evaluators",
        max_programs=3,
        recompiles_on=("row_count",),
        hot_loop=True,
    ),
)


def contract_from_declaration(spec: dict) -> ProgramContract:
    builder = spec.get("builder")
    if builder not in _BUILDERS:
        raise ValueError(
            f"PROGRAM_AUDIT declaration {spec.get('name')!r} names unknown "
            f"builder {builder!r}"
        )
    return ProgramContract(
        name=spec["name"],
        entry=spec["entry"],
        build=_BUILDERS[builder],
        max_programs=spec.get("max_programs"),
        stable_under=tuple(spec.get("stable_under", ())),
        recompiles_on=tuple(spec.get("recompiles_on", ())),
        hot_loop=bool(spec.get("hot_loop", False)),
        sharded_operands=tuple(spec.get("sharded_operands", ())),
        replicated_operands=tuple(spec.get("replicated_operands", ())),
        axis=spec.get("axis"),
        allowed_collectives=tuple(spec.get("allowed_collectives", ())),
        suppress=dict(spec.get("suppress", {})),
    )


def collect_contracts() -> list[ProgramContract]:
    """The repo's declared contract registry (module hooks + local)."""
    specs: list[dict] = []
    for modname in DECLARING_MODULES:
        mod = importlib.import_module(modname)
        decl = getattr(mod, "PROGRAM_AUDIT", None)
        if decl is None:
            raise ValueError(
                f"{modname} is a declaring module but exports no "
                "PROGRAM_AUDIT"
            )
        specs.extend(decl if isinstance(decl, (list, tuple)) else [decl])
    specs.extend(_LOCAL_AUDITS)
    return [contract_from_declaration(s) for s in specs]


# --------------------------------------------------------------------------
# the audit driver
# --------------------------------------------------------------------------


@contextlib.contextmanager
def _serial_ingest_env():
    saved = os.environ.get("PHOTON_TPU_SERIAL_INGEST")
    os.environ["PHOTON_TPU_SERIAL_INGEST"] = "1"
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop("PHOTON_TPU_SERIAL_INGEST", None)
        else:
            os.environ["PHOTON_TPU_SERIAL_INGEST"] = saved


def _ensure_virtual_devices() -> None:
    """Give the sharding audit a multi-device CPU platform when possible.

    Only effective before jax initializes; harmless on real accelerators
    (the flag only affects the host platform)."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def audit(
    contracts: Iterable[ProgramContract] | None = None,
    *,
    with_cost: bool = True,
    chip: str | None = None,
) -> tuple[list[Finding], dict]:
    """Run every contract; returns (findings, report).

    The registry builds run under ``jax.enable_x64(False)`` so the
    audited traces match the production (f32) configuration even when
    the host process enabled x64 (the test harness does).
    """
    import jax

    _ensure_virtual_devices()

    from photon_tpu.analysis import costmodel

    if chip is None:
        chip = costmodel.TARGET_CHIP
    findings: list[Finding] = []
    report: dict[str, Any] = {"contracts": {}}
    # Serial ingest for the whole audit: contract builds must be
    # deterministic, and the estimator fixtures would otherwise spawn
    # background warm compiles nobody consumes (the ingest-pipeline
    # contract invokes the warm compile explicitly, synchronously).
    with jax.enable_x64(False), _serial_ingest_env():
        resolved = (
            collect_contracts() if contracts is None else list(contracts)
        )
        for contract in resolved:
            entry: dict[str, Any] = {
                "entry": contract.entry,
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
                        "program-contract",
                        f"contract builder failed: {exc!r}",
                    )
                )
                continue
            entry["notes"] = list(trace.notes)
            for name, prog in trace.programs.items():
                pentry: dict[str, Any] = {"signature": prog.signature}
                if with_cost and prog.lowered is not None:
                    try:
                        pentry["cost"] = costmodel.program_report(
                            prog.lowered, chip
                        )
                    except Exception as exc:  # noqa: BLE001
                        pentry["cost_error"] = repr(exc)
                entry["programs"][name] = pentry
            if trace.opshardings is not None:
                entry["opshardings"] = dict(trace.opshardings)
                entry["collectives"] = list(trace.collectives or ())
            findings.extend(run_checks(contract, trace))
    findings.sort(key=lambda f: (f.path, f.rule, f.message))
    return findings, report
