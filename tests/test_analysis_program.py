"""Tier-2 program auditor: violating fixtures, the framework, the gate.

Layout mirrors tests/test_analysis.py one tier up:
- per-check fixtures build DELIBERATELY VIOLATING contract traces (a
  λ baked into the trace, a stale recompile declaration, an f64 cast, a
  host callback inside a scanned jit body, a lost sharding axis) and
  assert the corresponding check catches each;
- framework tests pin the contract-level suppression mechanism, the
  registry declarations, and the cost model;
- the gate test runs the full semantic CLI (`--semantic`) over the
  repo's declared registry and fails on ANY unsuppressed finding.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

from photon_tpu.analysis import costmodel, program
from photon_tpu.analysis.__main__ import main as cli_main
from photon_tpu.analysis.program import (
    ContractTrace,
    ProgramContract,
    TracedProgram,
    run_checks,
    trace_program,
)


def _sds(*shape, dtype="float32"):
    return jax.ShapeDtypeStruct(shape, dtype)


def _rules(findings, *, suppressed=False):
    return sorted(
        f.rule for f in findings if f.suppressed == suppressed
    )


# ---------------------------------------------------------------------------
# violating fixtures, one per check
# ---------------------------------------------------------------------------


def _baked_lambda_trace() -> ContractTrace:
    """λ baked into the trace as a Python constant: every grid point
    mints a new program (the exact bug the census exists for)."""

    def make(lam):
        return trace_program("fit", lambda x: x * lam, _sds(4))

    return ContractTrace(
        programs={"fit": make(0.5)},
        variants={
            "lambda_grid": [{"fit": make(w).signature} for w in (1.0, 2.0)]
        },
    )


def test_census_catches_extra_dispatch():
    contract = ProgramContract(
        name="fx-extra-dispatch",
        entry="<fixture>",
        build=_baked_lambda_trace,
        max_programs=1,
        stable_under=("lambda_grid",),
    )
    findings = run_checks(contract, contract.build())
    assert "program-dispatch-census" in _rules(findings)
    census = [f for f in findings if f.rule == "program-dispatch-census"]
    assert "3 distinct compiled programs" in census[0].message


def test_recompile_key_catches_unstable_family():
    contract = ProgramContract(
        name="fx-unstable-key",
        entry="<fixture>",
        build=_baked_lambda_trace,
        stable_under=("lambda_grid",),
    )
    findings = run_checks(contract, contract.build())
    keyed = [f for f in findings if f.rule == "program-recompile-key"]
    # Both λ-grid variants perturb the key; the message names the family
    # and the program so the report is actionable.
    assert len(keyed) == 2
    assert all("lambda_grid" in f.message for f in keyed)
    assert all("fit" in f.message for f in keyed)


def test_recompile_key_catches_stale_declaration():
    def build():
        base = trace_program("fit", lambda x: x + 1.0, _sds(4))
        return ContractTrace(
            programs={"fit": base},
            # "optimizer_swap" declared as a recompile trigger but the
            # variant traces to the identical program.
            variants={"optimizer_swap": [{"fit": base.signature}]},
        )

    contract = ProgramContract(
        name="fx-stale-recompile",
        entry="<fixture>",
        build=build,
        recompiles_on=("optimizer_swap",),
    )
    findings = run_checks(contract, build())
    assert _rules(findings) == ["program-recompile-key"]
    assert "no longer perturbs" in findings[0].message


@pytest.mark.parametrize("family_kind", ["recompiles_on", "stable_under"])
def test_family_without_variants_is_a_contract_error(family_kind):
    """A declared config family with no generated variants is an
    UNCHECKED guarantee — flagged, never silently passing (a renamed
    variants key must not turn the stability check off)."""

    def build():
        return ContractTrace(
            programs={"fit": trace_program("fit", lambda x: x, _sds(2))}
        )

    contract = ProgramContract(
        name="fx-unchecked-family",
        entry="<fixture>",
        build=build,
        **{family_kind: ("optimizer_swap",)},
    )
    findings = run_checks(contract, build())
    assert _rules(findings) == ["program-contract"]
    assert "no variants" in findings[0].message


def test_host_boundary_catches_f64_cast():
    def build():
        return ContractTrace(
            programs={
                "fit": trace_program(
                    "fit", lambda x: x.astype(jnp.float64), _sds(4)
                )
            }
        )

    contract = ProgramContract(
        name="fx-f64", entry="<fixture>", build=build, hot_loop=True
    )
    findings = run_checks(contract, build())
    assert "program-f64-cast" in _rules(findings)


def test_host_boundary_catches_callback_in_scanned_body():
    """The walk recurses into sub-jaxprs: a pure_callback hidden inside a
    lax.scan body (a jitted hot loop) is still found."""

    def body(carry, x):
        y = jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct((), x.dtype), x
        )
        return carry + y, y

    def fn(xs):
        total, _ = jax.lax.scan(body, jnp.zeros((), xs.dtype), xs)
        return total

    def build():
        return ContractTrace(
            programs={"fit": trace_program("fit", fn, _sds(8))}
        )

    contract = ProgramContract(
        name="fx-callback", entry="<fixture>", build=build, hot_loop=True
    )
    findings = run_checks(contract, build())
    assert "program-host-boundary" in _rules(findings)
    assert any("pure_callback" in f.message for f in findings)
    # The same program audited as non-hot-loop passes the callback check
    # (callbacks are legal at API boundaries), but f64 stays global.
    cold = ProgramContract(
        name="fx-callback-cold", entry="<fixture>", build=build
    )
    assert "program-host-boundary" not in _rules(run_checks(cold, build()))


def test_sharding_catches_lost_axis_and_undeclared_collective():
    trace = ContractTrace(
        programs={},
        opshardings={
            "features": "PartitionSpec()",  # lost the data axis
            "re_raw": "PartitionSpec('data',)",  # should be replicated
        },
        collectives=["all-gather", "all-reduce"],
    )
    contract = ProgramContract(
        name="fx-sharding",
        entry="<fixture>",
        build=lambda: trace,
        sharded_operands=("features",),
        replicated_operands=("re_raw",),
        axis="data",
        allowed_collectives=("all-reduce",),
    )
    findings = run_checks(contract, trace)
    assert _rules(findings) == ["program-sharding"] * 3
    messages = " | ".join(f.message for f in findings)
    assert "lost the 'data' mesh axis" in messages
    assert "declared replicated" in messages
    assert "all-gather" in messages


def test_sharding_skips_cleanly_without_multi_device_trace():
    contract = ProgramContract(
        name="fx-sharding-skip",
        entry="<fixture>",
        build=lambda: ContractTrace(programs={}, opshardings=None),
        sharded_operands=("features",),
        axis="data",
    )
    assert run_checks(contract, contract.build()) == []


# ---------------------------------------------------------------------------
# framework behavior
# ---------------------------------------------------------------------------


def test_contract_suppression_carries_reason():
    def build():
        return ContractTrace(
            programs={
                "fit": trace_program(
                    "fit", lambda x: x.astype(jnp.float64), _sds(4)
                )
            }
        )

    contract = ProgramContract(
        name="fx-suppressed",
        entry="<fixture>",
        build=build,
        hot_loop=True,
        suppress={"program-f64-cast": "deliberate x64 opt-in fixture"},
    )
    findings = run_checks(contract, build())
    assert _rules(findings) == []  # nothing unsuppressed
    assert _rules(findings, suppressed=True) == ["program-f64-cast"]
    assert findings[0].suppress_reason == "deliberate x64 opt-in fixture"


def test_builder_crash_is_a_finding_not_a_skip():
    def build():
        raise RuntimeError("fixture exploded")

    contract = ProgramContract(
        name="fx-crash", entry="<fixture>", build=build
    )
    findings, report = program.audit([contract], with_cost=False)
    assert _rules(findings) == ["program-contract"]
    assert "fixture exploded" in findings[0].message
    assert report["contracts"]["fx-crash"]["programs"] == {}


def test_declaration_with_unknown_builder_rejected():
    with pytest.raises(ValueError, match="unknown builder"):
        program.contract_from_declaration(
            dict(name="x", entry="e", builder="no_such_builder")
        )


def test_registry_covers_the_declared_modules():
    contracts = {c.name: c for c in program.collect_contracts()}
    assert {
        "fused-fit",
        "fused-cache-key",
        "unfused-coordinate-update",
        "newton-kernel",
        "mesh-sharding",
        "ingest-pipeline",
        "evaluation-scoring",
    } <= set(contracts)
    # Hot-loop coverage: the programs that run inside the fit loop are
    # all subject to the host-boundary audit.
    for name in ("fused-fit", "unfused-coordinate-update", "newton-kernel"):
        assert contracts[name].hot_loop
    # Every registry suppression must carry a written reason.
    for c in contracts.values():
        for rule_id, reason in c.suppress.items():
            assert reason and reason.strip(), (c.name, rule_id)


def test_traced_program_signature_is_text_stable():
    a = trace_program("p", lambda x: x * 2.0, _sds(4))
    b = trace_program("p", lambda x: x * 2.0, _sds(4))
    c = trace_program("p", lambda x: x * 3.0, _sds(4))
    assert a.signature == b.signature
    assert a.signature != c.signature


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_costmodel_counts_matmul_flops():
    n = 64
    lowered = jax.jit(lambda a, b: a @ b).lower(
        _sds(n, n), _sds(n, n)
    )
    cost = costmodel.program_cost(lowered)
    # 2 n^3 FLOPs for the matmul; HLO cost analysis counts exactly that.
    assert cost["flops"] == pytest.approx(2.0 * n**3)
    assert cost["hbm_bytes"] >= 3 * n * n * 4  # two reads + one write


def test_costmodel_roofline_classifies_bounds():
    flops_bound = costmodel.roofline(
        {"flops": 1e15, "hbm_bytes": 1.0}, chip=costmodel.TARGET_CHIP
    )
    hbm_bound = costmodel.roofline(
        {"flops": 1.0, "hbm_bytes": 1e13}, chip=costmodel.TARGET_CHIP
    )
    assert flops_bound["bound"] == "flops"
    assert hbm_bound["bound"] == "hbm"
    for r in (flops_bound, hbm_bound):
        assert r["min_seconds"] == pytest.approx(
            max(r["min_seconds_flops"], r["min_seconds_hbm"])
        )
    assert costmodel.roofline({"flops": 0.0, "hbm_bytes": 0.0})[
        "arithmetic_intensity"
    ] is None


# ---------------------------------------------------------------------------
# the mesh-fusion report hook
# ---------------------------------------------------------------------------


def test_fuse_ineligibility_reasons_match_fuse_eligible():
    from photon_tpu.algorithm.fused_fit import (
        fuse_eligible,
        fuse_ineligibility_reasons,
    )
    from photon_tpu.parallel.mesh import make_mesh

    with jax.enable_x64(False):
        est, data = program._tiny_glmix()
        datasets, _ = est.prepare(data)
        coords = est._build_coordinates(
            datasets, {}, {}, data.num_samples
        )
    assert fuse_eligible(coords)
    assert fuse_ineligibility_reasons(coords) == []
    mesh_reasons = fuse_ineligibility_reasons(coords, mesh=make_mesh())
    assert len(mesh_reasons) == 1
    assert "mesh execution" in mesh_reasons[0]
    assert "collectives" in mesh_reasons[0]


# ---------------------------------------------------------------------------
# the repo gate (the acceptance criterion, via the real CLI)
# ---------------------------------------------------------------------------


def test_semantic_gate_zero_unsuppressed_findings(tmp_path, capsys):
    cost_out = tmp_path / "cost.json"
    rc = cli_main(
        ["--semantic", "--format", "json", "--cost-out", str(cost_out)]
    )
    payload = json.loads(capsys.readouterr().out)
    unsuppressed = [
        f for f in payload["findings"] if not f["suppressed"]
    ]
    assert rc == 0, unsuppressed
    assert unsuppressed == []
    for f in payload["findings"]:  # suppression inventory is auditable
        assert f["suppress_reason"]
    # The cost-out report carries per-program cost for the fused fit.
    report = json.loads(cost_out.read_text())
    fit = report["contracts"]["fused-fit"]["programs"]["fit"]
    assert fit["cost"]["flops"] > 0
    assert fit["cost"]["roofline"]["bound"] in ("flops", "hbm")
    # The sharding audit actually ran (the test harness forces 8 CPU
    # devices) and saw only the declared collective.
    mesh_entry = report["contracts"]["mesh-sharding"]
    assert mesh_entry["collectives"] == ["all-reduce"]
    assert any("mesh fusion blocked" in n for n in mesh_entry["notes"])


def test_semantic_cli_usage_errors():
    assert cli_main(["--semantic", "photon_tpu"]) == 2
    assert cli_main(["--cost-out", "/tmp/x.json"]) == 2
