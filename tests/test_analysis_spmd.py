"""photon_tpu.analysis tier 6: the SPMD auditor.

Layout mirrors the tier-4/5 test files:
- the HLO collective parsers are pinned on fabricated HLO text (the
  ordered sequence skips -done halves; the census stays the tier-2
  substring check, and tier 2 now delegates to it — parity pinned);
- every rule has a violating fixture that produces EXACTLY its
  finding: a genuinely divergent trace (process_index leaks into the
  traced math under two simulated hosts), a host-varying shape and a
  host-varying branch for the AST lint, a mismatched collective order,
  an undeclared collective priced over the interconnect, and the four
  partition-coverage failure modes (uncovered, ambiguous,
  silently-replicated, rule/placement contradiction, dead rule);
- stale-contract fixtures: unknown builder, unknown suppress key,
  tier-2/tier-6 drift (uncovered mesh contract, drifted collective
  sets, stale waiver, covers of a ghost);
- the shard_map xfail diagnosis is pinned: the auditor statically
  names 'shard_map' as the divergent op on jax 0.4.37, which is the
  citation the 6 xfailed column-sharding tests now carry;
- the gate: ``python -m photon_tpu.analysis --spmd`` exits 0 over the
  repo's declared contracts, and the satellite plumbing (costmodel
  pricing, fleet census join) is pinned here too since tier 6 feeds
  both.
"""

from __future__ import annotations

import types

import pytest

jax = pytest.importorskip("jax")

from photon_tpu.analysis import costmodel  # noqa: E402
from photon_tpu.analysis import program as program_mod  # noqa: E402
from photon_tpu.analysis import spmd as S  # noqa: E402
from photon_tpu.analysis.__main__ import main as cli_main  # noqa: E402
from photon_tpu.obs import fleet  # noqa: E402

P = pytest.importorskip("jax.sharding").PartitionSpec


def _rules(findings) -> list[str]:
    return sorted(f.rule for f in findings if not f.suppressed)


def _contract(**kw) -> S.SpmdContract:
    base = dict(name="t", entry="tests", build=lambda hosts: S.SpmdTrace([]))
    base.update(kw)
    return S.SpmdContract(**base)


def _prog(text: str, name: str = "p") -> program_mod.TracedProgram:
    return program_mod.TracedProgram(name=name, text=text)


_HLO = """\
HloModule m
ENTRY %main (p0: f32[128,64]) -> f32[128,64] {
  %p0 = f32[128,64]{1,0} parameter(0)
  %ar-start = (f32[128,64]{1,0}, f32[128,64]{1,0}) all-reduce-start(%p0)
  %ar-done = f32[128,64]{1,0} all-reduce-done(%ar-start)
  %ag = f32[256,64]{1,0} all-gather(%ar-done), dimensions={0}
  ROOT %r = f32[128,64]{1,0} slice(%ag)
}
"""


# --------------------------------------------------------------------------
# HLO collective parsing + the tier-2 delegation
# --------------------------------------------------------------------------


class TestCollectiveParsers:
    def test_sequence_is_ordered_and_skips_done_halves(self):
        seq = S.collective_sequence(_HLO)
        assert [s["op"] for s in seq] == ["all-reduce", "all-gather"]
        # The -start tuple shape rides along for transfer pricing.
        assert "f32[128,64]" in seq[0]["shape"]
        assert "f32[256,64]" in seq[1]["shape"]

    def test_census_is_the_sorted_substring_set(self):
        assert S.collective_census(_HLO) == ["all-gather", "all-reduce"]
        assert S.collective_census("no collectives here") == []

    def test_tier2_census_delegates_to_tier6(self):
        # program.hlo_collectives is now a façade over spmd — one census.
        assert program_mod.hlo_collectives(_HLO) == S.collective_census(
            _HLO
        )

    def test_transfer_pricing(self):
        b = costmodel.hlo_shape_bytes("f32[128,64]{1,0}")
        assert b == 128 * 64 * 4
        # Tuple shapes (async pairs) sum every token; layouts ignored.
        assert costmodel.hlo_shape_bytes(
            "(f32[8]{0}, f32[8]{0})"
        ) == 2 * 8 * 4
        assert costmodel.hlo_shape_bytes("pred[]") == 1
        # Unknown future dtypes price at 1 byte, never silently 0.
        assert costmodel.hlo_shape_bytes("f8e4m3fn[16]") == 16
        priced = costmodel.collective_transfer(
            [{"op": "all-gather", "shape": "f32[128,64]{1,0}"}]
        )
        assert priced["total_bytes"] == 128 * 64 * 4
        peak = costmodel.CHIP_PEAKS[costmodel.TARGET_CHIP][
            "ici_bytes_per_sec"
        ]
        assert priced["min_seconds_ici"] == pytest.approx(
            128 * 64 * 4 / peak
        )


# --------------------------------------------------------------------------
# the cross-host trace proof
# --------------------------------------------------------------------------


class TestTraceDivergence:
    def test_simulated_host_patches_and_restores(self):
        before = jax.process_index()
        with S.simulated_host(3, 4):
            assert jax.process_index() == 3
            assert jax.process_count() == 4
        assert jax.process_index() == before

    def test_host_leak_diverges_and_names_the_op(self):
        # The violating fixture: a Python-level branch on process_index
        # makes each simulated host trace a different program — the
        # exact leak the lint rule flags statically.
        def leaky(x):
            if jax.process_index() == 0:
                return x + 1.0
            return x * 2.0

        hosts = []
        for k in range(2):
            with S.simulated_host(k, 2):
                prog = program_mod.trace_program("leaky", leaky, 1.0)
            hosts.append(
                S.HostTrace(process_index=k, programs={"leaky": prog})
            )
        trace = S.SpmdTrace(hosts=hosts)
        found = list(S.check_trace_divergence(_contract(), trace))
        assert _rules(found) == ["spmd-trace-divergence"]
        msg = found[0].message
        assert "diverge" in msg and "host 1" in msg
        # The proof names the first divergent jaxpr line, not just
        # "the hashes differ".
        assert "first divergence" in msg or "differ in length" in msg

    def test_identical_traces_pass(self):
        prog = _prog("a = add b c")
        trace = S.SpmdTrace(
            hosts=[
                S.HostTrace(0, {"p": prog}),
                S.HostTrace(1, {"p": prog}),
            ]
        )
        assert list(S.check_trace_divergence(_contract(), trace)) == []

    def test_missing_program_on_one_host(self):
        trace = S.SpmdTrace(
            hosts=[
                S.HostTrace(0, {"p": _prog("a = add b c")}),
                S.HostTrace(1, {}),
            ]
        )
        found = list(S.check_trace_divergence(_contract(), trace))
        assert _rules(found) == ["spmd-trace-divergence"]
        assert "not on host 1" in found[0].message


# --------------------------------------------------------------------------
# the collective-order deadlock census
# --------------------------------------------------------------------------


class TestCollectiveOrder:
    def _trace(self, seq_a, seq_b):
        return S.SpmdTrace(
            hosts=[
                S.HostTrace(0, {}, {"p": [{"op": o, "shape": ""}
                                          for o in seq_a]}),
                S.HostTrace(1, {}, {"p": [{"op": o, "shape": ""}
                                          for o in seq_b]}),
            ]
        )

    def test_mismatched_order_names_the_position(self):
        trace = self._trace(
            ["all-reduce", "all-gather"], ["all-gather", "all-reduce"]
        )
        found = list(S.check_collective_order(_contract(), trace))
        assert _rules(found) == ["spmd-collective-order"]
        msg = found[0].message
        assert "position 0" in msg
        assert "all-reduce vs all-gather" in msg
        assert "deadlock" in msg

    def test_length_mismatch_diverges_at_end(self):
        trace = self._trace(["all-reduce"], ["all-reduce", "all-gather"])
        found = list(S.check_collective_order(_contract(), trace))
        assert _rules(found) == ["spmd-collective-order"]
        assert "<end> vs all-gather" in found[0].message

    def test_matching_order_passes(self):
        trace = self._trace(
            ["all-reduce", "all-reduce"], ["all-reduce", "all-reduce"]
        )
        assert list(S.check_collective_order(_contract(), trace)) == []


# --------------------------------------------------------------------------
# the implicit-reshard detector
# --------------------------------------------------------------------------


class TestImplicitReshard:
    def test_undeclared_collective_is_priced(self):
        trace = S.SpmdTrace(
            hosts=[
                S.HostTrace(
                    0,
                    {},
                    {"p": [
                        {"op": "all-reduce", "shape": "f32[5]{0}"},
                        {"op": "all-gather", "shape": "f32[128,64]{1,0}"},
                    ]},
                )
            ]
        )
        c = _contract(ordered_collectives=("all-reduce",))
        found = list(S.check_implicit_reshard(c, trace))
        assert _rules(found) == ["spmd-implicit-reshard"]
        msg = found[0].message
        assert "all-gather" in msg
        assert f"{128 * 64 * 4} bytes" in msg

    def test_unchecked_declaration_is_a_contract_finding(self):
        trace = S.SpmdTrace(hosts=[S.HostTrace(0, {}, {"p": []})])
        c = _contract(ordered_collectives=("all-reduce",))
        found = list(S.check_implicit_reshard(c, trace))
        assert _rules(found) == ["spmd-contract"]
        assert "unchecked" in found[0].message

    def test_declared_collectives_pass(self):
        trace = S.SpmdTrace(
            hosts=[
                S.HostTrace(
                    0, {}, {"p": [{"op": "all-reduce", "shape": "f32[5]"}]}
                )
            ]
        )
        c = _contract(ordered_collectives=("all-reduce",))
        assert list(S.check_implicit_reshard(c, trace)) == []


# --------------------------------------------------------------------------
# partition-rule coverage
# --------------------------------------------------------------------------


def _leaf(ndim: int, spec=None):
    sharding = None if spec is None else types.SimpleNamespace(spec=spec)
    return types.SimpleNamespace(ndim=ndim, sharding=sharding)


class TestPartitionCoverage:
    RULES = (
        (r"^fe/", P("data")),
        (r"^coef(/|$)", P()),
    )

    def _check(self, leaves, rules=None):
        cov = S.partition_coverage(
            self.RULES if rules is None else rules, leaves
        )
        trace = S.SpmdTrace(
            hosts=[S.HostTrace(0, {})], coverage=cov
        )
        return list(
            S.check_partition_coverage(
                _contract(partition_rules="RULES"), trace
            )
        )

    def _clean_leaves(self):
        return {
            "fe/features": _leaf(2, P("data")),
            "coef/w": _leaf(1, P()),
        }

    def test_clean_coverage_passes(self):
        assert self._check(self._clean_leaves()) == []

    def test_uncovered_leaf(self):
        leaves = self._clean_leaves()
        leaves["re/block0/proj"] = _leaf(2, P("data"))
        found = self._check(leaves)
        assert _rules(found) == ["spmd-partition-coverage"]
        assert "matches NO partition rule" in found[0].message

    def test_ambiguous_leaf(self):
        rules = self.RULES + ((r"features$", P()),)
        found = self._check(self._clean_leaves(), rules)
        assert "spmd-partition-coverage" in _rules(found)
        assert any("2 partition rules" in f.message for f in found)

    def test_silently_replicated_slab(self):
        leaves = self._clean_leaves()
        leaves["fe/features"] = _leaf(2, P())  # placed replicated
        found = self._check(leaves)
        assert _rules(found) == ["spmd-partition-coverage"]
        assert "silently-replicated slab" in found[0].message

    def test_placement_contradicts_rule(self):
        leaves = self._clean_leaves()
        leaves["coef/w"] = _leaf(1, P("data"))  # rule says replicate
        found = self._check(leaves)
        assert _rules(found) == ["spmd-partition-coverage"]
        assert "disagree" in found[0].message

    def test_dead_rule(self):
        leaves = self._clean_leaves()
        del leaves["coef/w"]
        found = self._check(leaves)
        assert _rules(found) == ["spmd-contract"]
        assert "dead rule" in found[0].message

    def test_scalars_are_exempt(self):
        leaves = self._clean_leaves()
        leaves["zz/scalar"] = _leaf(0)  # matches nothing; ndim 0
        assert self._check(leaves) == []

    def test_the_repos_mesh_leaves_match_one_rule_each(self):
        """The mesh fixture's placed leaves, the lazy scorer's inverse
        map and passive rows among them (PR 37), each match exactly one
        of ``PARTITION_RULES``, and those two shard as their rule says."""
        cov = S.build_mesh_spmd(2).coverage["leaves"]
        for name in ("re/score_inv", "re/passive_rows"):
            assert cov[name]["placed_sharded"] and cov[name][
                "intended_sharded"], name
        assert all(len(row["matches"]) == 1 for row in cov.values()
                   if row["ndim"]), {
            n: r["matches"] for n, r in cov.items() if len(r["matches"]) != 1}


# --------------------------------------------------------------------------
# the host-divergence AST lint
# --------------------------------------------------------------------------


class TestHostDivergenceLint:
    def test_host_varying_shape(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def build():\n"
            "    n = jax.process_index()\n"
            "    return jnp.zeros((n + 1, 4))\n"
        )
        found = S.audit_source(src)
        assert _rules(found) == ["spmd-host-divergence"]
        assert "shape" in found[0].message

    def test_host_varying_branch_in_program_building_scope(self):
        src = (
            "import jax\n"
            "def build(f, x):\n"
            "    if jax.process_index() == 0:\n"
            "        return jax.jit(f)(x)\n"
            "    return x\n"
        )
        found = S.audit_source(src)
        assert _rules(found) == ["spmd-host-divergence"]
        assert "branch predicate" in found[0].message

    def test_branch_outside_tracing_scope_passes(self):
        # Same predicate, but the scope never builds a traced program —
        # host-only control flow (logging, IO) is legitimate.
        src = (
            "import jax\n"
            "def log():\n"
            "    if jax.process_index() == 0:\n"
            "        print('hello')\n"
        )
        assert S.audit_source(src) == []

    def test_time_and_env_are_host_varying(self):
        src = (
            "import os, time\n"
            "import jax.numpy as jnp\n"
            "def build():\n"
            "    k = int(time.time())\n"
            "    j = int(os.environ.get('N', '1'))\n"
            "    return jnp.zeros((k,)), jnp.zeros((j,))\n"
        )
        found = S.audit_source(src)
        assert _rules(found) == ["spmd-host-divergence"] * 2

    def test_suppression_applies(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def build():\n"
            "    n = jax.process_index()\n"
            "    return jnp.zeros((n,))"
            "  # photon: ignore[spmd-host-divergence] -- test fixture\n"
        )
        found = S.audit_source(src)
        assert len(found) == 1 and found[0].suppressed
        assert found[0].suppress_reason == "test fixture"


# --------------------------------------------------------------------------
# stale contracts + tier-2 alignment drift
# --------------------------------------------------------------------------


class TestContractHygiene:
    def test_unknown_builder_is_an_error(self):
        with pytest.raises(ValueError, match="unknown\\s+builder"):
            S.contract_from_declaration(
                dict(name="ghost", entry="x", builder="no_such_builder")
            )

    def test_unknown_suppress_key_is_a_finding(self):
        c = _contract(suppress={"not-a-rule": "why"})
        found = S.run_checks(c, S.SpmdTrace(hosts=[]))
        assert _rules(found) == ["spmd-contract"]
        assert "unknown rule 'not-a-rule'" in found[0].message

    def test_contract_suppression_applies_by_rule(self):
        trace = S.SpmdTrace(
            hosts=[
                S.HostTrace(0, {}, {"p": [{"op": "all-reduce",
                                           "shape": ""}]}),
                S.HostTrace(1, {}, {"p": []}),
            ]
        )
        c = _contract(
            suppress={"spmd-collective-order": "known asymmetric fixture"}
        )
        found = S.run_checks(c, trace)
        assert all(f.suppressed for f in found
                   if f.rule == "spmd-collective-order")

    def test_repo_declarations_align_with_tier2(self):
        contracts = S.collect_contracts()
        assert [c.name for c in contracts] == ["mesh-spmd"]
        assert S.check_tier2_alignment(contracts) == []

    def test_drifted_collective_sets_are_caught(self):
        contracts = S.collect_contracts()
        import dataclasses

        drifted = [
            dataclasses.replace(
                contracts[0], ordered_collectives=("all-gather",)
            )
        ]
        found = S.check_tier2_alignment(drifted)
        assert _rules(found) == ["spmd-contract"]
        assert "drifted apart" in found[0].message

    def test_uncovered_tier2_mesh_contract_is_caught(self):
        # Strip the covers: the tier-2 mesh contract becomes an orphan.
        contracts = S.collect_contracts()
        import dataclasses

        bare = [dataclasses.replace(contracts[0], covers=())]
        found = S.check_tier2_alignment(bare)
        assert "spmd-contract" in _rules(found)
        assert any("no tier-6 contract covers it" in f.message
                   for f in found)

    def test_cover_of_ghost_contract_is_caught(self):
        contracts = S.collect_contracts()
        import dataclasses

        ghost = [
            dataclasses.replace(
                contracts[0],
                covers=contracts[0].covers + ("no-such-tier2",),
            )
        ]
        found = S.check_tier2_alignment(ghost)
        assert any("no longer exists" in f.message for f in found)

    def test_stale_waiver_is_caught(self, monkeypatch):
        monkeypatch.setattr(
            S, "TIER2_SPMD_WAIVERS", {"no-such-tier2": "gone"}
        )
        found = S.check_tier2_alignment(S.collect_contracts())
        assert any("stale TIER2_SPMD_WAIVERS" in f.message for f in found)


# --------------------------------------------------------------------------
# the fleet census join (satellite plumbing)
# --------------------------------------------------------------------------


class TestFleetCensusJoin:
    def _report(self, missing=()):
        return {
            "bundles": 2 - len(missing),
            "ranks": [r for r in (0, 1) if r not in missing],
            "missing_ranks": list(missing),
            "wall_seconds": 5.0,
            "per_rank": [],
        }

    def test_census_attached_and_counted(self):
        report = self._report()
        entry = fleet.crosscheck_collective_census(report, ["all-reduce"])
        assert report["collective_census"] is entry
        assert entry["count"] == 1 and entry["mismatches"] == []
        row = fleet.multichip_row(report, n_devices=8)
        assert row["multichip_collective_count"] == 1
        assert row["multichip_wall_seconds"] == 5.0
        assert row["multichip_hosts_reporting"] == 2

    def test_missing_rank_with_collectives_is_a_mismatch(self):
        entry = fleet.crosscheck_collective_census(
            self._report(missing=(1,)), ["all-reduce"]
        )
        assert len(entry["mismatches"]) == 1
        assert "rank 1" in entry["mismatches"][0]
        assert "--spmd" in entry["mismatches"][0]

    def test_no_collectives_no_mismatch(self):
        entry = fleet.crosscheck_collective_census(
            self._report(missing=(1,)), []
        )
        assert entry["mismatches"] == []

    def test_row_without_census_omits_the_gauge(self):
        row = fleet.multichip_row(self._report(), n_devices=8)
        assert "multichip_collective_count" not in row


# --------------------------------------------------------------------------
# the end-to-end audit + the CLI gate
# --------------------------------------------------------------------------


class TestAuditGate:
    def test_cli_spmd_exits_zero_on_repo(self, capsys):
        assert cli_main(["--spmd"]) == 0
        out = capsys.readouterr().out
        assert "contract mesh-spmd" in out
        assert "@ok" in out

    def test_cli_arg_validation(self):
        assert cli_main(["--spmd", "photon_tpu"]) == 2
        assert cli_main(["--spmd", "--hosts", "1"]) == 2
        assert cli_main(["--hosts", "2", "--memory"]) == 2
        assert cli_main(["--spmd", "--select", "spmd-contract"]) == 2

    def test_list_rules(self, capsys):
        assert cli_main(["--spmd", "--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in S.SPMD_RULES:
            assert rule in out

    def test_audit_hosts_below_two_is_a_contract_finding(self):
        # audit() also runs tier-2 alignment over the fabricated list
        # (the repo's mesh contract is then an orphan) — assert on the
        # host-count finding specifically.
        c = _contract(hosts=1)
        findings, report = S.audit([c], with_lint=False)
        assert any(
            f.rule == "spmd-contract" and "at least 2" in f.message
            for f in findings
        )
        assert report["contracts"]["t"]["hosts"] == 1

    def test_builder_crash_is_a_finding_not_a_crash(self):
        def boom(hosts):
            raise RuntimeError("fixture blew up")

        c = _contract(build=boom)
        findings, _ = S.audit([c], with_lint=False)
        assert any(
            f.rule == "spmd-contract" and "builder failed" in f.message
            for f in findings
        )
