"""The program's phase of a device operation (``photon_tpu/obs/phases.py``)
and its coverage on the CPU: every operation the fused fit's two programs
schedule is put down to a phase or is the fit's own bookkeeping, and
every program the unfused loop dispatches, on one device and on a
four-device mesh, is one the phase maps name. A program or a scope added
with no phase fails here, not silently in a trace on the chip."""

from __future__ import annotations

import glob
import os
import re

import jax
import pytest

from photon_tpu.data.game_data import make_game_dataset, make_host_game_dataset
from photon_tpu.obs import phases

from test_unfused_fit_stage import _estimator, _game, _tables

FIT = "jit(_fit_fn)/while/body/closed_call"


@pytest.mark.parametrize("tf_op, program, phase", [
    # the fused fit's scopes
    (f"{FIT}/coord.per-user/residual/sub", "jit__fit_fn", "residual"),
    (f"{FIT}/coord.per-user/score/jit(_take)/gather", "", "score"),
    (f"{FIT}/coord.global/solve.lbfgs/while", "jit__fit_fn", "fe_solve"),
    (f"{FIT}/coord.per-user/jit(_solve_block)/solve.newton_xla/dot",
     "jit__fit_fn", "re_solve"),
    (f"{FIT}/coord.per-user/jit(_solve_block)/residual/gather",
     "jit__fit_fn", "residual"),
    ("jit(_mat_fn)/coord.per-user/materialize/gather", "jit__mat_fn",
     "materialize"),
    # as a TPU trace writes it: a type after a colon, no module
    (f"{FIT}/coord.per-user/residual/gather:", "", "residual"),
    (f"{FIT}/coord.global/solve.lbfgs/while/body/mul:", "", "fe_solve"),
    ("jit(_gather_score_mesh)/shard_map/gather:", "", "score"),
    # the direct route's CG kernel, as the TPU compiler names it (its
    # op_name in a v5e-topology compile of _solve_block), in the unfused
    # loop's program and inside the fused fit's
    ("jit(_solve_block)/solve.direct_kernel/jit(spd_solve_lanes)/"
     "cg_solve_lanes/pallas_call:", "jit__solve_block", "re_solve"),
    (f"{FIT}/coord.per-user/jit(_solve_block)/solve.direct_kernel/"
     "jit(spd_solve_lanes)/cg_solve_lanes/pallas_call:", "", "re_solve"),
    # a scope beats the program's name
    ("jit(_solve_block)/residual/gather", "jit__solve_block", "residual"),
    ("jit(_solve_block)/solve.lbfgs/while", "jit__solve_block", "re_solve"),
    # the program's name where no scope names a phase
    ("jit(_solve_block)/scatter", "jit__solve_block", "re_solve"),
    ("jit(_run_impl)/while/body/jit(matmul)/dot_general", "jit__run_impl",
     "fe_solve"),
    ("", "jit__gather_score_mesh", "score"),
    ("jit(_placed_residuals)/sub", "", "residual"),
    ("", "jit__sub_add_impl", "score"),
    ("jit(matmul)/dot_general", "jit_matmul", "score"),
    ("jit(subtract)/sub", "", "residual"),
    ("", "jit__mat_fn", "materialize"),
    # neither
    ("", "", "unphased"),
    (f"{FIT}/coord.global/scatter", "jit__fit_fn", "unphased"),
    ("", "jit__fit_fn", "unphased"),
    ("", "jit_broadcast_in_dim", "unphased"),
    ("jit(other)/add", "jit_other", "unphased"),
])
def test_the_phase_of_an_operation(tf_op, program, phase):
    assert phases.phase_of(tf_op, program) == phase


def test_every_phase_is_a_word_of_the_maps():
    named = (set(phases.SCOPES.values()) | set(phases.PROGRAMS.values())
             | set(phases.EAGER.values())) - {None}
    assert named == set(phases.PHASES)
    assert phases.program_name("jit__fit_fn") == "_fit_fn"
    assert phases.program_of("jit(_fit_fn)/while/add") == "_fit_fn"
    assert phases.program_of("jit(_run_impl)/while:") == "_run_impl"
    assert phases.program_of("coord.x/residual") == ""
    assert phases.program_of("") == ""


# ---- the fused fit's programs, compiled here

_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*)$")
_NOT_A_STEP = ("parameter", "constant", "tuple", "get-tuple-element",
               "bitcast")
# What the fit program does in no phase: its loop, a coordinate's
# bookkeeping (stored scores, diagnostics, convergence sums) and the
# compiler's own copies, which carry no op_name.
_FIT_BOOKKEEPING = re.compile(
    r"^(jit\(_fit_fn\)(/while/(body|cond)(/closed_call(/coord\.[^/]+)?)?)?)?$")


def scheduled_ops(hlo_text: str) -> list:
    """(opcode, op_name) of each instruction that runs as a step of its
    own: those of the entry, loop bodies and conditions, branches and
    called computations; not a fusion's body or a reduction's
    ``to_apply`` (the calling instruction is the step)."""
    comps, inner, current = {}, set(), None
    for line in hlo_text.splitlines():
        head = _HEAD.match(line)
        if head and " = " not in line:
            current = comps.setdefault(head.group(1), [])
            continue
        found = _INSTRUCTION.match(line)
        if found is None or current is None:
            continue
        rest = found.group(1)
        opcode = re.search(r" ([a-z][a-z0-9\-]*)\(", rest).group(1)
        meta = re.search(r'op_name="([^"]*)"', rest)
        current.append((opcode, meta.group(1) if meta else ""))
        inner.update(re.findall(r"calls=%?([\w.\-]+)", rest))
        if opcode != "call":
            inner.update(re.findall(r"to_apply=%?([\w.\-]+)", rest))
    return [op for name, ops in comps.items() if name not in inner
            for op in ops if op[0] not in _NOT_A_STEP]


def _compiled_text(lowered) -> str:
    """Compiled here and now: an executable the persistent cache serves
    keeps the op_names it was compiled with (the key leaves them out)."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def fused_programs():
    """The fused fit's programs for two random effects and a fixed one."""
    with jax.enable_x64(False):
        est = _estimator(None)
        game = _game(make_game_dataset)
        datasets, _ = est.prepare(game)
        coords = est._build_coordinates(
            datasets, {}, {}, logical_rows=game.num_samples)
        fused = est._fused_for(coords, datasets)
        return {
            "_fit_fn": scheduled_ops(_compiled_text(fused.lower(coords))),
            "_mat_fn": scheduled_ops(
                _compiled_text(fused.lower_materialize(coords))),
        }


def test_every_step_of_the_fit_program_has_a_phase_or_is_its_bookkeeping(
        fused_programs):
    seen = {}
    for opcode, op_name in fused_programs["_fit_fn"]:
        phase = phases.phase_of(op_name, "jit__fit_fn")
        seen[phase] = seen.get(phase, 0) + 1
        if phase == phases.UNPHASED:
            path = op_name.rsplit("/", 1)[0] if "/" in op_name else ""
            assert _FIT_BOOKKEEPING.match(path), (opcode, op_name)
    assert set(seen) == {"residual", "score", "re_solve", "fe_solve",
                         phases.UNPHASED}
    assert seen[phases.UNPHASED] < sum(seen.values()) / 2


def test_every_step_of_the_materialize_program_is_materialize(
        fused_programs):
    assert fused_programs["_mat_fn"]
    assert {phases.phase_of(op_name, "jit__mat_fn")
            for _, op_name in fused_programs["_mat_fn"]} == {"materialize"}


# ---- the programs a fit dispatches, from a CPU trace

def dispatched_programs(est, game, trace_dir) -> set:
    """The programs (by ``phases.program_name``) whose operations ran in
    one warm fit, from the ``hlo_module`` stat of the CPU trace's op
    events."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(trace_dir))
    _tables(est.fit(game)[0])  # pulled to the host: the fit has run
    jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return {phases.program_name(dict(ev.stats)["hlo_module"])
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events
            if "hlo_module" in dict(ev.stats)}


LOOP_ON_A_MESH = {
    "_run_impl", "_solve_block", "_gather_score_mesh", "_placed_residuals",
    "_sub_add_impl", "matmul", "add", "subtract", "convert_element_type",
    "broadcast_in_dim"}


@pytest.mark.parametrize("name, mesh, maker, guard, want", [
    ("fused", None, make_game_dataset, False, {"_fit_fn"}),
    ("loop", None, make_game_dataset, True,
     LOOP_ON_A_MESH - {"_gather_score_mesh", "_placed_residuals"}
     | {"_gather_score", "_all_finite"}),
    ("mesh", 4, make_host_game_dataset, False, LOOP_ON_A_MESH),
])
def test_the_programs_a_fit_dispatches_are_the_maps_keys(
        tmp_path, name, mesh, maker, guard, want):
    with jax.enable_x64(False):
        est = _estimator(mesh, non_finite_guard=guard)
        game = _game(maker)
        est.prepare(game)
        _tables(est.fit(game)[0])  # the first fit, materialize included, ran
        got = dispatched_programs(est, game, tmp_path)
    assert got == want
    assert got <= set(phases.PROGRAMS) | set(phases.EAGER)
