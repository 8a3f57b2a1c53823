"""Tier-6 SPMD auditor: static multi-host divergence proofs for the mesh.

Multi-host SPMD bugs are the worst failure class this repo can ship: a
host whose trace diverges (a ``process_index`` baked into a shape, a
clock read in a branch predicate) compiles a DIFFERENT program than its
peers, and the first mismatched collective hangs the whole fleet with no
error on any host. PR 19's fleet ledger can observe such a hang *after*
the fact; this tier exists to make the bug a static CI finding *before*
any device sees the program. Four families of proof:

- **cross-host trace determinism** (``spmd-trace-divergence``): every
  mesh-audited entry point is traced under simulated ``process_index``
  0..N-1 (abstract shapes, no devices — CPU CI is enough) and the jaxprs
  must be byte-identical across hosts. When they are not, the first
  divergent jaxpr line names the guilty op — this is the jaxpr half of
  the host-divergence lint, and the proof that all processes compile the
  same executable.
- **host-divergence lint** (``spmd-host-divergence``): a pure-``ast``
  taint pass flagging host-varying values (``jax.process_index``, clock
  reads, unseeded RNGs, hostname/pid/env reads) flowing into
  trace-affecting positions: array-constructor shapes,
  ``jax.ShapeDtypeStruct`` shapes, and branch predicates inside
  functions that build traced programs. (Recompile-key fields are
  covered dynamically by the cross-host trace hash above: a host-varying
  static arg cannot produce byte-identical jaxprs on two hosts.)
- **collective-order deadlock census** (``spmd-collective-order`` /
  ``spmd-implicit-reshard``): the ORDERED collective sequence
  (all-reduce / all-gather / collective-permute / reduce-scatter ...)
  is extracted from each simulated host's compiled HLO; the sequences
  must match position-by-position across hosts (a mismatch is a static
  deadlock), and every op must be declared in the contract's
  ``ordered_collectives`` — an undeclared op is an implicit reshard the
  compiler inserted behind the author's back, priced as bytes over the
  interconnect via ``costmodel.collective_transfer``. This census is the
  single source of truth the tier-2 mesh audit delegates to
  (``program.hlo_collectives``), and ``obs.fleet`` joins it against the
  runtime collective ledger (``fleet.crosscheck_collective_census``).
- **partition-rule coverage** (``spmd-partition-coverage``): every
  named param/slab pytree leaf the mesh places must be matched by
  EXACTLY one regex partition rule (``parallel.mesh.PARTITION_RULES`` —
  the rule tree ROADMAP item 1's pjit rebuild will feed pjit), the
  placed sharding must agree with the matched rule (a slab the rules
  say to shard that is silently replicated is a finding, not a slow
  day), and every rule must still match at least one leaf (dead rules
  rot).

Contracts are declared beside the audited code as plain ``SPMD_AUDIT``
dicts (``photon_tpu/parallel/mesh.py``), mirroring tiers 2-5; builders
live here so the audited modules never import analysis code. Run via
``python -m photon_tpu.analysis --spmd`` (exit 0 clean, 1 findings, 2
usage); ``--hosts N`` simulates an N-process fleet (CI's multichip-smoke
job runs the 8-device gloo dryrun's 2-host config).
"""

from __future__ import annotations

import ast
import contextlib
import dataclasses
import importlib
import re
from pathlib import Path
from typing import Any, Callable, Iterable, Iterator

from photon_tpu.analysis.core import (
    Finding,
    ModuleContext,
    iter_python_files,
)

SPMD_RULES = {
    "spmd-trace-divergence": (
        "an audited entry point traces to different jaxprs on different "
        "hosts — the fleet would compile divergent programs"
    ),
    "spmd-host-divergence": (
        "a host-varying value (process_index, clock, unseeded RNG, "
        "hostname, env) flows into a trace-affecting position (shape, "
        "ShapeDtypeStruct, branch predicate around trace/jit)"
    ),
    "spmd-collective-order": (
        "the ordered collective sequence differs between hosts' compiled "
        "HLO — the first mismatched collective deadlocks the fleet"
    ),
    "spmd-implicit-reshard": (
        "compiled HLO carries a collective the contract did not declare — "
        "an implicit compiler-inserted reshard paying interconnect bytes "
        "on every dispatch"
    ),
    "spmd-partition-coverage": (
        "a placed pytree leaf is matched by zero or multiple partition "
        "rules, or its placed sharding contradicts the matched rule "
        "(e.g. a slab intended to shard is silently replicated)"
    ),
    "spmd-contract": "contract declaration or builder integrity error",
}

# Modules that declare SPMD contracts (each exports SPMD_AUDIT — one
# declaration dict or a list of them; plain data, no analysis imports).
SPMD_DECLARING_MODULES = ("photon_tpu.parallel.mesh",)

# Tier-2 program contracts that declare mesh semantics (an axis, sharded
# operands, or allowed collectives) must be covered by a tier-6 contract
# (its ``covers`` field) or waived here WITH a reason. A stale waiver —
# naming a tier-2 contract that no longer exists or is now covered — is
# itself a finding, so this table cannot rot silently.
TIER2_SPMD_WAIVERS: dict[str, str] = {}


# --------------------------------------------------------------------------
# the collective census (single source of truth; tier-2 delegates here)
# --------------------------------------------------------------------------

# Cross-device transfer ops as they appear in HLO text. Shared with the
# tier-2 sharding audit via ``program.hlo_collectives`` so the two tiers
# cannot drift.
COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "all-to-all",
    "collective-permute",
    "reduce-scatter",
    "collective-broadcast",
)

# One HLO instruction whose opcode is a collective:
#   %name = f32[128,64]{1,0} all-gather(%operand), dimensions={0} ...
# The shape region between '=' and the opcode is kept verbatim so
# costmodel.hlo_shape_bytes can price the transfer (tuple shapes of
# async pairs included). '-done' halves of async pairs are skipped —
# the '-start' already carries the transfer.
_COLLECTIVE_LINE_RE = re.compile(
    r"=\s*(?P<shape>[^=]*?)\s*"
    r"(?P<op>" + "|".join(COLLECTIVE_OPS) + r")(?P<phase>-start|-done)?\("
)


def _hlo_text(hlo: Any) -> str:
    return hlo if isinstance(hlo, str) else hlo.as_text()


def collective_sequence(hlo: Any) -> list[dict[str, str]]:
    """The ORDERED collective sequence of an HLO module.

    ``hlo`` is HLO text or anything with ``.as_text()`` (a Compiled or a
    Lowered). Returns ``[{"op", "shape"}, ...]`` in program-text order —
    the static proxy for the issue order every host must agree on. Two
    hosts whose sequences differ at any position deadlock at that
    position: each waits in a different collective.
    """
    out: list[dict[str, str]] = []
    for line in _hlo_text(hlo).splitlines():
        m = _COLLECTIVE_LINE_RE.search(line)
        if m is None or m.group("phase") == "-done":
            continue
        out.append({"op": m.group("op"), "shape": m.group("shape").strip()})
    return out


def collective_census(hlo: Any) -> list[str]:
    """Sorted set of collective op names present in HLO text.

    Deliberately a conservative substring census (an op mentioned
    anywhere counts) — this is the exact check the tier-2 mesh audit has
    gated on since PR 2, now owned here; ``collective_sequence`` is the
    stricter ordered parse layered on top.
    """
    text = _hlo_text(hlo)
    return sorted(op for op in COLLECTIVE_OPS if op in text)


# --------------------------------------------------------------------------
# simulated hosts
# --------------------------------------------------------------------------


@contextlib.contextmanager
def simulated_host(process_index: int, process_count: int):
    """Make ``jax.process_index()/process_count()`` report a simulated
    host while tracing — no distributed runtime, no devices beyond the
    virtual CPU platform. Audited entry points that consult the public
    names see host ``process_index`` of ``process_count``; a value that
    leaks into the trace then diverges the jaxpr across the simulated
    fleet, which is exactly the proof obligation.

    Clears the jit caches on entry AND exit: pjit's cache is keyed on
    the underlying function object, so re-tracing the same callable
    under the next simulated host would silently replay the previous
    host's jaxpr — a cached trace would mask exactly the divergence
    this proof exists to catch (and, symmetrically, a host-k trace
    must not leak into post-audit real traces)."""
    import jax

    saved = (jax.process_index, jax.process_count)
    jax.process_index = lambda backend=None: process_index
    jax.process_count = lambda backend=None: process_count
    jax.clear_caches()
    try:
        yield
    finally:
        jax.process_index, jax.process_count = saved
        jax.clear_caches()


# --------------------------------------------------------------------------
# data model
# --------------------------------------------------------------------------


@dataclasses.dataclass
class HostTrace:
    """One simulated host's view: traced programs + ordered collectives."""

    process_index: int
    programs: dict[str, Any]  # name -> program.TracedProgram
    sequences: dict[str, list[dict[str, str]]] = dataclasses.field(
        default_factory=dict
    )


@dataclasses.dataclass
class SpmdTrace:
    """Everything a contract's builder hands the checks.

    ``hosts`` holds one :class:`HostTrace` per simulated process;
    ``coverage`` is the partition-rule coverage table from
    :func:`partition_coverage` (None when the builder ran single-device
    or the contract declares no rules); ``notes`` surface in the report.
    """

    hosts: list[HostTrace]
    coverage: dict | None = None
    notes: list[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass(frozen=True)
class SpmdContract:
    name: str
    entry: str  # human-readable entry-point path (report/docs)
    build: Callable[[int], SpmdTrace]  # takes the simulated host count
    hosts: int = 2
    ordered_collectives: tuple[str, ...] = ()
    partition_rules: str | None = None  # attr name on the declaring module
    covers: tuple[str, ...] = ()  # tier-2 contract names this one verifies
    suppress: dict[str, str] = dataclasses.field(default_factory=dict)


def _finding(contract: SpmdContract, rule: str, message: str) -> Finding:
    return Finding(
        rule=rule, path=f"<{contract.name}>", line=0, col=0, message=message
    )


# --------------------------------------------------------------------------
# partition-rule coverage
# --------------------------------------------------------------------------


def _spec_shards(spec: Any) -> bool:
    """True when a PartitionSpec (or its str) names at least one mesh
    axis — i.e. the placement actually splits the leaf."""
    if spec is None:
        return False
    try:
        return any(ax is not None for ax in spec)
    except TypeError:
        return False


def partition_coverage(
    rules: Iterable[tuple[str, Any]], leaves: dict[str, Any]
) -> dict:
    """Match named placed leaves against the regex partition-rule tree.

    ``rules`` is ``((pattern, PartitionSpec), ...)`` (the
    ``match_partition_rules`` shape); ``leaves`` maps slash-joined pytree
    path names to the PLACED arrays. The table records, per leaf, every
    matching rule index, the matched spec, the placed spec, and whether
    each side actually shards — the checks turn disagreements into
    findings. Scalars are exempt (they are replicated by construction).
    """
    rules = list(rules)
    table: dict[str, dict] = {}
    for name, leaf in sorted(leaves.items()):
        ndim = int(getattr(leaf, "ndim", 0))
        matches = [
            i for i, (pat, _) in enumerate(rules) if re.search(pat, name)
        ]
        matched_spec = rules[matches[0]][1] if matches else None
        placed_spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        table[name] = {
            "ndim": ndim,
            "matches": matches,
            "rule": rules[matches[0]][0] if matches else None,
            "spec": None if matched_spec is None else str(matched_spec),
            "placed": None if placed_spec is None else str(placed_spec),
            "intended_sharded": _spec_shards(matched_spec),
            "placed_sharded": _spec_shards(placed_spec),
        }
    return {"rules": [pat for pat, _ in rules], "leaves": table}


# --------------------------------------------------------------------------
# contract builders
# --------------------------------------------------------------------------


def _named_mesh_leaves(batch, re_ds, w) -> dict[str, Any]:
    """Slash-named placed leaves of the mesh fixture — the pytree the
    partition-rule tree must cover exactly once each."""
    leaves: dict[str, Any] = {
        "fe/features": batch.features.x,
        "fe/labels": batch.labels,
        "fe/offsets": batch.offsets,
        "fe/weights": batch.weights,
        "coef/w": w,
    }
    uids = getattr(batch, "uids", None)
    if uids is not None:
        leaves["fe/uids"] = uids
    for i, b in enumerate(re_ds.blocks):
        for field in (
            "entity_codes", "row_ids", "row_counts", "proj",
            "intercept_slots",
        ):
            leaf = getattr(b, field, None)
            if leaf is not None:
                leaves[f"re/block{i}/{field}"] = leaf
    raw = getattr(re_ds, "raw", None)
    if raw is not None:
        raw_leaf = getattr(raw, "x", None)
        if raw_leaf is None:
            raw_leaf = raw.values
        leaves["re/raw"] = raw_leaf
    codes = getattr(re_ds, "score_codes", None)
    if codes is not None:
        leaves["re/score_codes"] = codes
    if re_ds.is_lazy:
        leaves["re/score_inv"] = re_ds.score_inv_device()
        leaves["re/passive_rows"] = re_ds.passive_rows_device()
    return leaves


def build_mesh_spmd(hosts: int) -> SpmdTrace:
    """The mesh contract: the data-parallel GLM objective traced under
    every simulated host, its ordered collective census per host, and
    the partition-rule coverage of every placed fixed-effect and
    random-effect leaf. The same fixture family as the tier-2 sharding
    audit — tier 6 proves the multi-host properties tier 2 assumes."""
    import jax
    import numpy as np

    from photon_tpu.analysis.program import _tiny_glmix, trace_program
    from photon_tpu.data.dataset import make_dense_batch
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )
    from photon_tpu.ops import glm as glm_ops
    from photon_tpu.ops import losses as losses_mod
    from photon_tpu.ops.normalization import NormalizationContext
    from photon_tpu.parallel import mesh as mesh_mod
    from photon_tpu.types import TaskType

    if len(jax.devices()) < 2:
        return SpmdTrace(
            hosts=[],
            notes=[
                "SPMD audit SKIPPED: single visible device (run under "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8, as "
                "CI does, to exercise it)",
            ],
        )

    mesh = mesh_mod.make_mesh()
    n_dev = len(mesh.devices.reshape(-1))
    n, d = 8 * n_dev, 5
    rng = np.random.default_rng(1)
    batch = mesh_mod.shard_batch(
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
        jax.numpy.zeros(d, batch.labels.dtype), mesh_mod.replicated(mesh)
    )

    host_traces: list[HostTrace] = []
    for k in range(hosts):
        with simulated_host(k, hosts):
            prog = trace_program("sharded_objective", objective, batch, w)
            seq = collective_sequence(prog.lowered.compile())
        host_traces.append(
            HostTrace(
                process_index=k,
                programs={"sharded_objective": prog},
                sequences={"sharded_objective": seq},
            )
        )

    # Random-effect placement + the named-leaf coverage table.
    est, data = _tiny_glmix(n=16 * n_dev, e=2 * n_dev)
    # A cap under the rows per entity leaves passive rows to place.
    re_ds = build_random_effect_dataset(
        data,
        RandomEffectDataConfiguration(
            "userId", "userShard", active_data_upper_bound=4),
        intercept_index=3,
    )
    re_ds = mesh_mod.shard_random_effect_dataset(re_ds, mesh)
    coverage = partition_coverage(
        mesh_mod.PARTITION_RULES, _named_mesh_leaves(batch, re_ds, w)
    )

    notes = [
        f"{hosts} simulated hosts x {n_dev} devices; "
        f"{len(coverage['leaves'])} placed leaves against "
        f"{len(coverage['rules'])} partition rules"
    ]
    return SpmdTrace(hosts=host_traces, coverage=coverage, notes=notes)


_BUILDERS: dict[str, Callable[[int], SpmdTrace]] = {
    "build_mesh_spmd": build_mesh_spmd,
}


def contract_from_declaration(spec: dict) -> SpmdContract:
    builder = spec.get("builder")
    if builder not in _BUILDERS:
        raise ValueError(
            f"SPMD_AUDIT declaration {spec.get('name')!r} names unknown "
            f"builder {builder!r}"
        )
    return SpmdContract(
        name=spec["name"],
        entry=spec["entry"],
        build=_BUILDERS[builder],
        hosts=int(spec.get("hosts", 2)),
        ordered_collectives=tuple(spec.get("ordered_collectives", ())),
        partition_rules=spec.get("partition_rules"),
        covers=tuple(spec.get("covers", ())),
        suppress=dict(spec.get("suppress", {})),
    )


def collect_contracts() -> list[SpmdContract]:
    """The repo's declared SPMD contract registry (module hooks)."""
    specs: list[dict] = []
    for modname in SPMD_DECLARING_MODULES:
        mod = importlib.import_module(modname)
        decl = getattr(mod, "SPMD_AUDIT", None)
        if decl is None:
            raise ValueError(
                f"{modname} is an SPMD declaring module but exports no "
                "SPMD_AUDIT"
            )
        specs.extend(decl if isinstance(decl, (list, tuple)) else [decl])
    return [contract_from_declaration(s) for s in specs]


# --------------------------------------------------------------------------
# contract checks
# --------------------------------------------------------------------------


_JAXPR_OP_RE = re.compile(r"=\s*([A-Za-z_][\w.\-\[\]]*)")


def _first_divergence(a: str, b: str) -> str:
    """Name the first divergent jaxpr line (and its primitive) between
    two hosts' traces — the 'statically names the divergent op' half of
    the proof."""
    la, lb = a.splitlines(), b.splitlines()
    for i, (x, y) in enumerate(zip(la, lb)):
        if x != y:
            m = _JAXPR_OP_RE.search(x) or _JAXPR_OP_RE.search(y)
            op = m.group(1) if m else "<structural>"
            return (
                f"first divergence at jaxpr line {i + 1} (op {op}): "
                f"{x.strip()!r} != {y.strip()!r}"
            )
    if len(la) != len(lb):
        return (
            f"jaxprs differ in length ({len(la)} vs {len(lb)} lines) "
            "after a common prefix"
        )
    return "texts differ (no line-level divergence found)"


def check_trace_divergence(
    contract: SpmdContract, trace: SpmdTrace
) -> Iterator[Finding]:
    if len(trace.hosts) < 2:
        return
    base = trace.hosts[0]
    for host in trace.hosts[1:]:
        for name, prog in base.programs.items():
            other = host.programs.get(name)
            if other is None:
                yield _finding(
                    contract,
                    "spmd-trace-divergence",
                    f"program '{name}' traced on host 0 but not on host "
                    f"{host.process_index} — the fleet would compile "
                    "different program sets",
                )
                continue
            if other.text != prog.text:
                yield _finding(
                    contract,
                    "spmd-trace-divergence",
                    f"program '{name}' jaxprs diverge between host 0 "
                    f"(sig {prog.signature}) and host "
                    f"{host.process_index} (sig {other.signature}); "
                    + _first_divergence(prog.text, other.text),
                )


def check_collective_order(
    contract: SpmdContract, trace: SpmdTrace
) -> Iterator[Finding]:
    if not trace.hosts:
        return
    base = trace.hosts[0]
    for host in trace.hosts[1:]:
        for name, seq in base.sequences.items():
            other = host.sequences.get(name, [])
            ops_a = [s["op"] for s in seq]
            ops_b = [s["op"] for s in other]
            if ops_a == ops_b:
                continue
            idx = next(
                (
                    i
                    for i, (x, y) in enumerate(zip(ops_a, ops_b))
                    if x != y
                ),
                min(len(ops_a), len(ops_b)),
            )
            at_a = ops_a[idx] if idx < len(ops_a) else "<end>"
            at_b = ops_b[idx] if idx < len(ops_b) else "<end>"
            yield _finding(
                contract,
                "spmd-collective-order",
                f"program '{name}' collective sequences diverge between "
                f"host 0 and host {host.process_index} at position "
                f"{idx}: {at_a} vs {at_b} (host 0: "
                f"{' -> '.join(ops_a) or 'none'}; host "
                f"{host.process_index}: {' -> '.join(ops_b) or 'none'}) "
                "— the fleet deadlocks at the first mismatched "
                "collective",
            )


def check_implicit_reshard(
    contract: SpmdContract, trace: SpmdTrace
) -> Iterator[Finding]:
    if not trace.hosts:
        return
    declared = set(contract.ordered_collectives)
    seen_any = False
    for name, seq in trace.hosts[0].sequences.items():
        seen_any = seen_any or bool(seq)
        undeclared = [s for s in seq if s["op"] not in declared]
        if not undeclared:
            continue
        from photon_tpu.analysis import costmodel

        price = costmodel.collective_transfer(undeclared)
        ici = price["min_seconds_ici"]
        yield _finding(
            contract,
            "spmd-implicit-reshard",
            f"program '{name}' HLO carries undeclared collective(s) "
            f"{', '.join(sorted({s['op'] for s in undeclared}))} "
            f"(declared: {', '.join(sorted(declared)) or 'none'}) — an "
            "implicit reshard moving "
            f"{int(price['total_bytes'])} bytes over the interconnect "
            f"per dispatch"
            + (f" (>= {ici:.2e} s at ICI peak)" if ici else ""),
        )
    if declared and trace.hosts and not seen_any:
        yield _finding(
            contract,
            "spmd-contract",
            "contract declares ordered_collectives "
            f"({', '.join(sorted(declared))}) but no traced program "
            "contains any collective — the declaration is unchecked",
        )


def check_partition_coverage(
    contract: SpmdContract, trace: SpmdTrace
) -> Iterator[Finding]:
    cov = trace.coverage
    if cov is None:
        if contract.partition_rules and trace.hosts:
            yield _finding(
                contract,
                "spmd-contract",
                f"contract declares partition rules "
                f"({contract.partition_rules}) but the builder produced "
                "no coverage table",
            )
        return
    rules_hit: set[int] = set()
    for name, row in cov["leaves"].items():
        if row["ndim"] == 0:
            continue  # scalars are replicated by construction
        if not row["matches"]:
            yield _finding(
                contract,
                "spmd-partition-coverage",
                f"placed leaf '{name}' (ndim {row['ndim']}, placed "
                f"{row['placed']}) matches NO partition rule — the "
                "pjit rebuild would have no spec for it",
            )
            continue
        if len(row["matches"]) > 1:
            pats = ", ".join(
                repr(cov["rules"][i]) for i in row["matches"]
            )
            yield _finding(
                contract,
                "spmd-partition-coverage",
                f"placed leaf '{name}' matches {len(row['matches'])} "
                f"partition rules ({pats}) — rules must partition the "
                "namespace, first-match ordering is a silent tiebreak",
            )
        rules_hit.update(row["matches"][:1])
        if row["intended_sharded"] and not row["placed_sharded"]:
            yield _finding(
                contract,
                "spmd-partition-coverage",
                f"leaf '{name}' is intended to shard (rule "
                f"{row['rule']!r} -> {row['spec']}) but was placed "
                f"{row['placed']} — a silently-replicated slab pays "
                "full-copy HBM on every device",
            )
        elif row["placed_sharded"] and not row["intended_sharded"]:
            yield _finding(
                contract,
                "spmd-partition-coverage",
                f"leaf '{name}' is placed sharded ({row['placed']}) but "
                f"its rule {row['rule']!r} says replicate ({row['spec']})"
                " — the rule tree and the placement code disagree",
            )
    for i, pat in enumerate(cov["rules"]):
        if i not in rules_hit:
            yield _finding(
                contract,
                "spmd-contract",
                f"partition rule {pat!r} matched no placed leaf as a "
                "first match — a dead rule documents sharding that no "
                "longer exists",
            )


CHECKS = (
    check_trace_divergence,
    check_collective_order,
    check_implicit_reshard,
    check_partition_coverage,
)


def run_checks(
    contract: SpmdContract, trace: SpmdTrace
) -> list[Finding]:
    """All checks over one contract's trace, suppressions applied."""
    findings: list[Finding] = []
    for unknown in sorted(set(contract.suppress) - set(SPMD_RULES)):
        findings.append(
            _finding(
                contract,
                "spmd-contract",
                f"suppression names unknown rule '{unknown}'",
            )
        )
    for check in CHECKS:
        for f in check(contract, trace):
            reason = contract.suppress.get(f.rule)
            if reason is not None:
                f = dataclasses.replace(
                    f, suppressed=True, suppress_reason=reason
                )
            findings.append(f)
    return findings


def check_tier2_alignment(
    contracts: Iterable[SpmdContract],
) -> list[Finding]:
    """Tier-2/tier-6 drift guard.

    Every tier-2 program contract that declares mesh semantics (an axis
    or allowed collectives) must be named in some tier-6 contract's
    ``covers`` — or reason-waived in :data:`TIER2_SPMD_WAIVERS` — and a
    covered contract's ``allowed_collectives`` must equal the covering
    tier-6 contract's ``ordered_collectives`` as a set (the dedup that
    keeps the PR 2 census and this tier's census one census).
    """
    from photon_tpu.analysis import program as program_mod

    findings: list[Finding] = []
    tier6 = list(contracts)
    covered = {name: c for c in tier6 for name in c.covers}
    tier2 = {c.name: c for c in program_mod.collect_contracts()}

    def orphan(rule: str, msg: str) -> Finding:
        return Finding(
            rule=rule, path="<tier2-alignment>", line=0, col=0, message=msg
        )

    for name, t2 in sorted(tier2.items()):
        is_mesh = bool(t2.axis) or bool(t2.allowed_collectives)
        if not is_mesh:
            continue
        t6 = covered.get(name)
        if t6 is None:
            if name in TIER2_SPMD_WAIVERS:
                continue
            findings.append(
                orphan(
                    "spmd-contract",
                    f"tier-2 contract '{name}' declares mesh semantics "
                    f"(axis={t2.axis!r}, allowed_collectives="
                    f"{list(t2.allowed_collectives)}) but no tier-6 "
                    "contract covers it and no waiver explains why",
                )
            )
            continue
        if set(t2.allowed_collectives) != set(t6.ordered_collectives):
            findings.append(
                orphan(
                    "spmd-contract",
                    f"tier-2 contract '{name}' allows collectives "
                    f"{sorted(t2.allowed_collectives)} but covering "
                    f"tier-6 contract '{t6.name}' orders "
                    f"{sorted(t6.ordered_collectives)} — the two tiers "
                    "have drifted apart",
                )
            )
    for name, c in covered.items():
        if name not in tier2:
            findings.append(
                orphan(
                    "spmd-contract",
                    f"tier-6 contract '{c.name}' covers tier-2 contract "
                    f"'{name}' which no longer exists",
                )
            )
    for name in sorted(TIER2_SPMD_WAIVERS):
        if name not in tier2 or name in covered:
            findings.append(
                orphan(
                    "spmd-contract",
                    f"stale TIER2_SPMD_WAIVERS entry '{name}' — the "
                    "tier-2 contract is "
                    + ("now covered" if name in covered else "gone")
                    + "; delete the waiver",
                )
            )
    return findings


# --------------------------------------------------------------------------
# the host-divergence AST lint
# --------------------------------------------------------------------------

# Calls whose return value differs between hosts of one fleet. Seeded
# RNGs (np.random.default_rng(42)) are NOT here — they are deterministic
# and host-uniform; only the unseeded form varies.
_HOST_VARYING_CALLS = frozenset(
    {
        "jax.process_index",
        "time.time",
        "time.time_ns",
        "time.monotonic",
        "time.monotonic_ns",
        "time.perf_counter",
        "time.perf_counter_ns",
        "os.getpid",
        "os.urandom",
        "os.getenv",
        "socket.gethostname",
        "socket.getfqdn",
        "uuid.uuid1",
        "uuid.uuid4",
        "random.random",
        "random.randint",
        "random.randrange",
        "random.getrandbits",
        "secrets.token_bytes",
        "secrets.token_hex",
        "secrets.randbits",
    }
)

# Array constructors whose shape argument becomes part of the compiled
# program: a host-varying shape IS a divergent trace.
_SHAPE_CONSTRUCTORS = frozenset(
    {
        "jax.numpy.zeros",
        "jax.numpy.ones",
        "jax.numpy.full",
        "jax.numpy.empty",
        "jax.numpy.arange",
        "jax.numpy.linspace",
        "jax.numpy.eye",
        "jax.numpy.tile",
        "jax.numpy.broadcast_to",
        "jax.numpy.reshape",
        "jax.ShapeDtypeStruct",
    }
)

# A branch on a host-varying value is trace-affecting when the enclosing
# function builds programs: different hosts take different sides and
# trace different jaxprs.
_TRACE_ENTRY_CALLS = frozenset(
    {
        "jax.jit",
        "jax.pmap",
        "jax.shard_map",
        "jax.experimental.shard_map.shard_map",
        "jax.experimental.pjit.pjit",
        "jax.eval_shape",
        "jax.make_jaxpr",
    }
)


def _host_varying_source(ctx: ModuleContext, node: ast.AST) -> str | None:
    """The host-varying source a single expression node IS, else None."""
    if isinstance(node, ast.Call):
        resolved = ctx.resolve(node.func)
        if resolved in _HOST_VARYING_CALLS:
            return resolved
        if resolved == "numpy.random.default_rng" and not node.args:
            return "numpy.random.default_rng()  # unseeded"
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "get"
            and ctx.resolve(node.func.value) == "os.environ"
        ):
            return "os.environ.get"
    if (
        isinstance(node, ast.Subscript)
        and ctx.resolve(node.value) == "os.environ"
    ):
        return "os.environ[...]"
    return None


def _taint_sources(
    ctx: ModuleContext, expr: ast.AST, tainted: dict[str, str]
) -> list[str]:
    """Every host-varying source reachable inside one expression: direct
    host-varying calls/env reads plus already-tainted local names."""
    out: list[str] = []
    for node in ast.walk(expr):
        src = _host_varying_source(ctx, node)
        if src is not None:
            out.append(src)
        elif (
            isinstance(node, ast.Name)
            and isinstance(node.ctx, ast.Load)
            and node.id in tainted
        ):
            out.append(f"{node.id} (from {tainted[node.id]})")
    return out


def _scope_of(ctx: ModuleContext, node: ast.AST) -> ast.AST | None:
    return ctx.enclosing_function(node)


def _function_taint(
    ctx: ModuleContext,
) -> dict[ast.AST | None, dict[str, str]]:
    """Per-scope forward taint map: local names assigned (directly or
    transitively, in line order) from host-varying sources."""
    taint: dict[ast.AST | None, dict[str, str]] = {}
    assigns: list[tuple[int, ast.AST | None, ast.AST, ast.AST]] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                assigns.append(
                    (node.lineno, _scope_of(ctx, node), tgt, node.value)
                )
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            assigns.append(
                (node.lineno, _scope_of(ctx, node), node.target, node.value)
            )
        elif isinstance(node, ast.AugAssign):
            assigns.append(
                (node.lineno, _scope_of(ctx, node), node.target, node.value)
            )
    for lineno, scope, tgt, value in sorted(assigns, key=lambda t: t[0]):
        scope_taint = taint.setdefault(scope, {})
        sources = _taint_sources(ctx, value, scope_taint)
        if not sources:
            continue
        for leaf in ast.walk(tgt):
            if isinstance(leaf, ast.Name):
                scope_taint[leaf.id] = sources[0]
    return taint


def _scope_builds_programs(ctx: ModuleContext, scope: ast.AST | None) -> bool:
    """True when a function (or the module body) contains a trace/jit
    entry call — branches inside it select which program gets traced."""
    root = scope if scope is not None else ctx.tree
    for node in ast.walk(root):
        if isinstance(node, ast.Call):
            if ctx.resolve(node.func) in _TRACE_ENTRY_CALLS:
                return True
            func = node.func
            if isinstance(func, ast.Attribute) and func.attr in (
                "trace",
                "lower",
            ):
                # obj.trace(...) / obj.lower(...) — the jax.stages
                # surface; resolves to None for local objects, so match
                # on the attribute.
                return True
    return False


def _shape_args(call: ast.Call) -> list[ast.AST]:
    out: list[ast.AST] = []
    if call.args:
        out.append(call.args[0])
    for kw in call.keywords:
        if kw.arg == "shape":
            out.append(kw.value)
    return out


def audit_source(source: str, path: str = "<string>") -> list[Finding]:
    """The spmd-host-divergence lint over one source blob.

    Flags host-varying values flowing into (a) array-constructor /
    ShapeDtypeStruct shape arguments and (b) branch predicates inside
    program-building scopes. Per-line ``# photon: ignore[...]``
    suppressions apply as in every other AST tier.
    """
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as exc:
        return [
            Finding(
                rule="syntax-error",
                path=path,
                line=exc.lineno or 1,
                col=(exc.offset or 1) - 1,
                message=f"file does not parse: {exc.msg}",
            )
        ]
    ctx = ModuleContext(path, source, tree)
    taint = _function_taint(ctx)
    builds_cache: dict[ast.AST | None, bool] = {}
    findings: list[Finding] = []
    seen: set[tuple] = set()

    def emit(node: ast.AST, message: str) -> None:
        f = Finding(
            rule="spmd-host-divergence",
            path=path,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            message=message,
        )
        key = (f.line, f.col, f.message)
        if key in seen:
            return
        seen.add(key)
        sup = ctx.suppressions.get(f.line)
        if sup is not None and sup.covers(f.rule):
            f = dataclasses.replace(
                f, suppressed=True, suppress_reason=sup.reason
            )
        findings.append(f)

    for node in ast.walk(tree):
        scope = _scope_of(ctx, node)
        scope_taint = taint.get(scope, {})
        if isinstance(node, ast.Call):
            resolved = ctx.resolve(node.func)
            if resolved in _SHAPE_CONSTRUCTORS:
                for arg in _shape_args(node):
                    sources = _taint_sources(ctx, arg, scope_taint)
                    if sources:
                        emit(
                            node,
                            f"host-varying value ({sources[0]}) flows "
                            f"into the shape of {resolved} — every host "
                            "traces a different program",
                        )
                        break
        elif isinstance(node, (ast.If, ast.While, ast.IfExp)):
            sources = _taint_sources(ctx, node.test, scope_taint)
            if not sources:
                continue
            if scope not in builds_cache:
                builds_cache[scope] = _scope_builds_programs(ctx, scope)
            if builds_cache[scope]:
                emit(
                    node,
                    f"branch predicate on a host-varying value "
                    f"({sources[0]}) in a scope that builds traced "
                    "programs — hosts taking different sides trace "
                    "divergent programs",
                )
    findings.sort(key=lambda f: (f.path, f.line, f.col))
    return findings


def audit_paths(paths: Iterable[str | Path]) -> list[Finding]:
    findings: list[Finding] = []
    for p in iter_python_files(paths):
        findings.extend(
            audit_source(p.read_text(encoding="utf-8"), path=str(p))
        )
    return findings


# --------------------------------------------------------------------------
# the audit driver
# --------------------------------------------------------------------------


def _package_paths() -> list[str]:
    """The package source root, resolved from the import (not the CWD)
    — the CLI forbids path arguments, so the lint half must find the
    code regardless of where the gate runs."""
    import photon_tpu

    return [str(Path(photon_tpu.__file__).parent)]


def audit(
    contracts: Iterable[SpmdContract] | None = None,
    *,
    hosts: int | None = None,
    lint_paths: Iterable[str | Path] | None = None,
    with_lint: bool = True,
) -> tuple[list[Finding], dict]:
    """Run the host-divergence lint + every SPMD contract.

    ``hosts`` overrides each contract's declared simulated host count
    (CI's multichip-smoke step passes the gloo dryrun's process count).
    Returns ``(findings, report)``; builds run under
    ``jax.enable_x64(False)`` so the audited traces match the production
    (f32) configuration.
    """
    import jax

    from photon_tpu.analysis import program as program_mod

    program_mod._ensure_virtual_devices()

    findings: list[Finding] = []
    report: dict[str, Any] = {"contracts": {}}
    if with_lint:
        lint = audit_paths(
            lint_paths if lint_paths is not None else _package_paths()
        )
        findings.extend(lint)
        report["lint"] = {
            "findings": len(lint),
            "suppressed": sum(1 for f in lint if f.suppressed),
        }
    with jax.enable_x64(False), program_mod._serial_ingest_env():
        resolved = (
            collect_contracts() if contracts is None else list(contracts)
        )
        findings.extend(check_tier2_alignment(resolved))
        for contract in resolved:
            n_hosts = hosts if hosts is not None else contract.hosts
            entry: dict[str, Any] = {
                "entry": contract.entry,
                "hosts": n_hosts,
                "programs": {},
                "notes": [],
            }
            report["contracts"][contract.name] = entry
            if n_hosts < 2:
                findings.append(
                    _finding(
                        contract,
                        "spmd-contract",
                        f"contract declares {n_hosts} host(s) — the "
                        "cross-host proof needs at least 2",
                    )
                )
                continue
            try:
                trace = contract.build(n_hosts)
            except Exception as exc:  # noqa: BLE001 — any builder crash is a finding
                findings.append(
                    _finding(
                        contract,
                        "spmd-contract",
                        f"contract builder failed: {exc!r}",
                    )
                )
                continue
            entry["notes"] = list(trace.notes)
            if trace.hosts:
                base = trace.hosts[0]
                for name, prog in base.programs.items():
                    sigs = {
                        h.process_index: h.programs[name].signature
                        for h in trace.hosts
                        if name in h.programs
                    }
                    entry["programs"][name] = {
                        "signatures": sigs,
                        "identical": len(set(sigs.values())) == 1
                        and len(sigs) == len(trace.hosts),
                        "collectives": [
                            s["op"] for s in base.sequences.get(name, [])
                        ],
                    }
            if trace.coverage is not None:
                leaves = trace.coverage["leaves"]
                entry["coverage"] = {
                    "rules": len(trace.coverage["rules"]),
                    "leaves": len(leaves),
                    "uncovered": sorted(
                        n
                        for n, row in leaves.items()
                        if row["ndim"] > 0 and not row["matches"]
                    ),
                }
            findings.extend(run_checks(contract, trace))
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return findings, report


def render_rule_list() -> str:
    width = max(len(r) for r in SPMD_RULES)
    return "\n".join(
        f"{rule_id.ljust(width)}  {summary}"
        for rule_id, summary in sorted(SPMD_RULES.items())
    )
