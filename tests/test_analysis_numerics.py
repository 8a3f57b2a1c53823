"""photon_tpu.analysis tier 5: the numerics auditor.

Layout mirrors the tier-4 test file:
- unit tests pin the dtype-provenance walk (bf16 lineage through
  reductions, scan carries, cast chains) on the violating fixture
  modules under tests/fixtures/analysis/fx_numerics_*.py — one fixture
  per check, each proving its rule produces EXACTLY its finding;
- the error-budget dual gate is exercised in both directions
  (too-small formula -> numerics-undeclared-error, rotted formula ->
  numerics-stale-budget) plus the missing/stale-key contract findings;
- the determinism census is driven by an undeclared f32 scatter-add
  and by reasonless/stale declarations;
- the coverage gate is pinned clean over the repo's declarations and
  then broken three ways via the fx_numerics_stale_waiver data;
- the gate: ``python -m photon_tpu.analysis --numerics`` exits 0 over
  the repo's declared contracts.
"""

from __future__ import annotations

import importlib.util
import pathlib

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from photon_tpu.analysis import numerics as N  # noqa: E402
from photon_tpu.analysis.__main__ import main as cli_main  # noqa: E402

_FX_DIR = pathlib.Path(__file__).parent / "fixtures" / "analysis"
S = jax.ShapeDtypeStruct
BF = jnp.bfloat16
F32 = jnp.float32


def _fx(name: str):
    """Import a violating fixture module by file path (the fixture dir
    is not a package — tier-1 fixtures there are lint inputs, not
    importable code, so tier-5 fixtures load the same arms-length way)."""
    spec = importlib.util.spec_from_file_location(
        name, _FX_DIR / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _contract(**kw) -> N.NumericsContract:
    base = dict(
        name="t", entry="tests", build=N.NumericsTrace, tolerance=1.5
    )
    base.update(kw)
    return N.NumericsContract(**base)


def _rules(findings) -> list[str]:
    return sorted(f.rule for f in findings if not f.suppressed)


def _trace(name, fn, *avals, dims=None) -> N.NumericsTrace:
    jaxpr = jax.jit(fn).trace(*avals).jaxpr
    return N.NumericsTrace(
        programs={name: N.ProgramNumerics(name, jaxpr)},
        dims=dims or {},
    )


# ---------------------------------------------------------------------------
# check 1: the accumulation-dtype audit
# ---------------------------------------------------------------------------


def test_bf16_dot_is_an_accumulation_finding():
    fx = _fx("fx_numerics_downcast_accumulator")
    t = _trace("p", fx.bf16_dot, S((8, 16), BF), S((16, 4), BF))
    findings = list(N.check_flow(_contract(), t))
    assert _rules(findings) == ["numerics-bf16-accumulation"]
    assert "dot_general" in findings[0].message


def test_bf16_scan_carry_is_an_accumulation_finding():
    fx = _fx("fx_numerics_downcast_accumulator")
    t = _trace("p", fx.bf16_scan_accumulate, S((16, 32), BF))
    rules = _rules(N.check_flow(_contract(), t))
    assert "numerics-bf16-accumulation" in rules


def test_sanctioned_f32_accumulation_is_clean():
    # The policy spelling: bf16 storage, f32 accumulator, bf16 result
    # stored with a SECOND use (so the round-trip rule stays silent).
    def sanctioned(x):
        acc = jnp.sum(x.astype(F32), dtype=F32)
        stored = acc.astype(BF)
        return stored, stored.astype(F32) * 2.0

    t = _trace("p", sanctioned, S((4096,), BF))
    assert _rules(N.check_flow(_contract(), t)) == []
    flow = N.flow_program(t.programs["p"])
    assert flow.reduce_len == 4096.0
    assert flow.max_rounds >= 2  # storage rounding + result rounding


# ---------------------------------------------------------------------------
# check 2: the cast census
# ---------------------------------------------------------------------------


def test_pointless_roundtrip_is_a_finding():
    fx = _fx("fx_numerics_cast_roundtrip")
    t = _trace("p", fx.pointless_roundtrip, S((4096,), F32))
    findings = list(N.check_flow(_contract(), t))
    assert _rules(findings) == ["numerics-cast-roundtrip"]


def test_downcast_accumulator_is_a_finding():
    fx = _fx("fx_numerics_cast_roundtrip")
    t = _trace("p", fx.downcast_accumulator, S((16, 256), BF))
    rules = _rules(N.check_flow(_contract(), t))
    assert "numerics-acc-downcast" in rules
    # the downcast value is ALSO stored (second use), so the
    # round-trip rule must not double-report the same cast
    assert "numerics-cast-roundtrip" not in rules


def test_scan_recast_is_a_finding():
    fx = _fx("fx_numerics_cast_roundtrip")
    t = _trace("p", fx.scan_recast, S((8, 64), F32))
    rules = _rules(N.check_flow(_contract(), t))
    assert "numerics-scan-recast" in rules


def test_suppression_applies_with_reason():
    fx = _fx("fx_numerics_cast_roundtrip")
    t = _trace("p", fx.pointless_roundtrip, S((4096,), F32),
               dims={"m": 4096.0})
    flow = N.flow_program(t.programs["p"])
    c = _contract(
        budgets={
            "p": f"u16 * {flow.max_rounds} + u32 * {int(flow.reduce_len)}"
        },
        suppress={
            "numerics-cast-roundtrip": "quantization probe: intentional"
        },
    )
    findings = N.run_checks(c, t)
    assert _rules(findings) == []
    # the suppressed finding is KEPT, with its reason, for the report
    kept = [f for f in findings
            if f.rule == "numerics-cast-roundtrip" and f.suppressed]
    assert kept and kept[0].suppress_reason == (
        "quantization probe: intentional"
    )


# ---------------------------------------------------------------------------
# check: unstable exp (the Poisson-stability rule)
# ---------------------------------------------------------------------------


def test_unclamped_exp_into_reduction_is_a_finding():
    def raw_poisson_mass(z):
        return jnp.sum(jnp.exp(z), dtype=F32)

    t = _trace("p", raw_poisson_mass, S((512,), F32))
    findings = list(N.check_flow(_contract(), t))
    assert _rules(findings) == ["numerics-unstable-exp"]


def test_clamped_exp_is_clean():
    # the ops.losses POISSON spelling post-fix: min(z, literal)
    # dominates the exp, so the mass is statically bounded
    def clamped_poisson_mass(z):
        return jnp.sum(jnp.exp(jnp.minimum(z, 30.0)), dtype=F32)

    t = _trace("p", clamped_poisson_mass, S((512,), F32))
    assert _rules(N.check_flow(_contract(), t)) == []


# ---------------------------------------------------------------------------
# check 3: the static error budgets (dual gate)
# ---------------------------------------------------------------------------


def _busted_trace() -> N.NumericsTrace:
    fx = _fx("fx_numerics_busted_budget")
    t = _trace("p", fx.chained_roundings, S((4096,), BF))
    t.dims["m"] = 4096.0
    return t


def test_exact_budget_passes_both_gates():
    flow = N.flow_program(_busted_trace().programs["p"])
    c = _contract(
        budgets={"p": f"u16 * {flow.max_rounds} + u32 * {int(flow.reduce_len)}"}
    )
    assert _rules(N.check_error_budgets(c, _busted_trace())) == []


def test_too_small_budget_is_undeclared_error():
    c = _contract(budgets={"p": "u16"})
    findings = list(N.check_error_budgets(c, _busted_trace()))
    assert _rules(findings) == ["numerics-undeclared-error"]
    assert "exceeds the declared budget" in findings[0].message


def test_inflated_budget_is_stale():
    c = _contract(budgets={"p": "1.0"})
    findings = list(N.check_error_budgets(c, _busted_trace()))
    assert _rules(findings) == ["numerics-stale-budget"]
    assert "rotted above reality" in findings[0].message


def test_rotten_formula_is_stale():
    c = _contract(budgets={"p": "u16 * no_such_dim"})
    findings = list(N.check_error_budgets(c, _busted_trace()))
    assert _rules(findings) == ["numerics-stale-budget"]
    assert "no longer evaluates" in findings[0].message


def test_missing_budget_is_a_contract_finding():
    findings = list(N.check_error_budgets(_contract(), _busted_trace()))
    assert _rules(findings) == ["numerics-contract"]
    assert "no declared error budget" in findings[0].message


def test_stale_budget_key_is_a_contract_finding():
    t = _busted_trace()
    flow = N.flow_program(t.programs["p"])
    c = _contract(budgets={
        "p": f"u16 * {flow.max_rounds} + u32 * {int(flow.reduce_len)}",
        "ghost_*": "u16",
    })
    findings = list(N.check_error_budgets(c, t))
    assert _rules(findings) == ["numerics-contract"]
    assert "matches no traced program" in findings[0].message


# ---------------------------------------------------------------------------
# check 4: the reduction-determinism census
# ---------------------------------------------------------------------------


def _scatter_trace() -> N.NumericsTrace:
    fx = _fx("fx_numerics_nondet_scatter")
    return _trace(
        "p", fx.undeclared_scatter_add,
        S((64,), F32), S((16,), jnp.int32), S((16,), F32),
    )


def test_undeclared_scatter_add_is_a_finding():
    findings = list(N.check_determinism(_contract(), _scatter_trace()))
    assert _rules(findings) == ["numerics-nondeterministic-reduce"]
    assert "scatter-add" in findings[0].message


def test_declared_scatter_add_is_clean():
    c = _contract(deterministic={
        "p:scatter-add": "ids are unique by construction in this probe"
    })
    assert _rules(N.check_determinism(c, _scatter_trace())) == []


def test_reasonless_determinism_declaration_is_a_finding():
    fx = _fx("fx_numerics_stale_waiver")
    (key,) = fx.REASONLESS_WAIVER  # reuse the blank-reason spelling
    c = _contract(deterministic={
        "p:scatter-add": fx.REASONLESS_WAIVER[key]
    })
    findings = list(N.check_determinism(c, _scatter_trace()))
    assert "numerics-contract" in _rules(findings)
    assert any("no reason" in f.message for f in findings)


def test_stale_determinism_declaration_is_a_finding():
    c = _contract(deterministic={
        "p:scatter-add": "unique ids",
        "retired_program:*": "the program this excused is gone",
    })
    findings = list(N.check_determinism(c, _scatter_trace()))
    assert _rules(findings) == ["numerics-contract"]
    assert "matches no nondeterministic site" in findings[0].message


# ---------------------------------------------------------------------------
# check 5: the coverage gate
# ---------------------------------------------------------------------------


def test_coverage_clean_on_repo_declarations():
    assert N.check_coverage(N.collect_contracts()) == []


def test_uncovered_tier2_contract_is_a_finding():
    contracts = [
        c for c in N.collect_contracts() if c.name != "fused-fit-numerics"
    ]
    findings = N.check_coverage(contracts)
    assert findings
    assert any(
        "'fused-fit'" in f.message and "no NUMERICS_AUDIT coverage"
        in f.message
        for f in findings
    )


def test_stale_waiver_is_a_finding(monkeypatch):
    fx = _fx("fx_numerics_stale_waiver")
    for name, reason in fx.STALE_WAIVER.items():
        monkeypatch.setitem(N.TIER2_WAIVERS, name, reason)
    findings = N.check_coverage(N.collect_contracts())
    assert any(
        "stale waiver" in f.message and "long-retired-contract"
        in f.message
        for f in findings
    )


def test_reasonless_waiver_is_a_finding(monkeypatch):
    fx = _fx("fx_numerics_stale_waiver")
    for name, reason in fx.REASONLESS_WAIVER.items():
        monkeypatch.setitem(N.TIER2_WAIVERS, name, reason)
    findings = N.check_coverage(N.collect_contracts())
    assert any("has no reason" in f.message for f in findings)


def test_waiver_for_covered_contract_is_stale(monkeypatch):
    monkeypatch.setitem(
        N.TIER2_WAIVERS, "fused-fit", "left behind after coverage landed"
    )
    findings = N.check_coverage(N.collect_contracts())
    assert any(
        "covered by numerics contract" in f.message for f in findings
    )


def test_covers_unknown_tier2_name_is_a_finding():
    fx = _fx("fx_numerics_stale_waiver")
    c = _contract(covers=fx.BOGUS_COVERS)
    findings = N.check_coverage(list(N.collect_contracts()) + [c])
    assert any(
        "covers unknown tier-2 contract" in f.message for f in findings
    )


def test_unknown_builder_raises():
    with pytest.raises(ValueError, match="unknown builder"):
        N.contract_from_declaration(
            {"name": "x", "entry": "e", "builder": "no_such_builder"}
        )


# ---------------------------------------------------------------------------
# the repo audit + CLI gate
# ---------------------------------------------------------------------------


def test_repo_gate_numerics_audit_clean(capsys):
    assert cli_main(["--numerics"]) == 0
    out = capsys.readouterr().out
    for cname in (
        "precision-policy-numerics",
        "fused-fit-numerics",
        "segment-reduce-numerics",
        "serving-numerics",
    ):
        assert f"contract {cname}" in out


def test_numerics_rejects_paths():
    assert cli_main(["--numerics", "photon_tpu"]) == 2


def test_numerics_rejects_select():
    assert cli_main(["--numerics", "--select", "numerics-contract"]) == 2


def test_numerics_rejects_tier_combination():
    assert cli_main(["--numerics", "--memory"]) == 2


def test_repo_audit_reports_flow_facts():
    findings, report = N.audit()
    assert not [f for f in findings if not f.suppressed]
    # suppressions that DID fire carry their reasons into the report
    assert all(f.suppress_reason for f in findings if f.suppressed)
    contracts = report["contracts"]
    assert set(contracts) == {
        "precision-policy-numerics",
        "fused-fit-numerics",
        "segment-reduce-numerics",
        "serve-kernel-numerics",
        "serving-numerics",
    }
    fused = contracts["fused-fit-numerics"]["programs"]
    # the f32 control has ZERO bf16 lineage; the bf16 fit carries
    # per-iteration roundings and a real accumulation length
    assert fused["fit_f32"]["rounds"] == 0
    assert fused["fit_f32"]["derived_bound"] == 0.0
    assert fused["fit_bf16"]["rounds"] > 0
    assert fused["fit_bf16"]["reduce_len"] > 0
    assert 0 < fused["fit_bf16"]["derived_bound"] <= (
        fused["fit_bf16"]["budget_value"] * 1.5
    )
    serving = contracts["serving-numerics"]["programs"]
    assert {"score_b1", "score_b8"} <= set(serving)
    assert report["waivers"] == N.TIER2_WAIVERS

