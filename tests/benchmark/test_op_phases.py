"""Device time by phase (``benchmark/op_phases.py``): a trace's operations
read with their program and scope path, own seconds per event, and the
phase the program's rule (``photon_tpu/obs/phases.py``) names for each;
on hand-built events, on a hand-built XSpace file and on a trace recorded
here on the CPU."""

import pytest

from benchmark import op_phases, xplane
from photon_tpu.obs.phases import PHASES, UNPHASED, phase_of

FIT = "jit(_fit_fn)/while/body/closed_call"
# The fused fit's loop holds a random effect's residual move and solve,
# then the fixed effect's L-BFGS loop, which holds one reduction; beside
# them a mesh scorer and the loop's residual program, whose operations
# share a name and a shape; a materialize step and a copy of no scope.
OPS = [
    ("while.1", 0.0, 10.0, "jit__fit_fn", "jit(_fit_fn)/while"),
    ("fusion.2", 1.0, 2.0, "jit__fit_fn",
     f"{FIT}/coord.per-user/jit(_solve_block)/residual/gather"),
    ("newton_step_lanes.3", 2.0, 4.0, "jit__fit_fn",
     f"{FIT}/coord.per-user/jit(_solve_block)/solve.newton_kernel/x"),
    ("while.4", 5.0, 9.0, "jit__fit_fn",
     f"{FIT}/coord.global/solve.lbfgs/while"),
    ("multiply_reduce_fusion.5", 6.0, 8.0, "jit__fit_fn",
     f"{FIT}/coord.global/solve.lbfgs/while/body/mul"),
    ("%fusion.1 = f32[8]{0} fusion()", 11.0, 14.0, "jit__gather_score_mesh",
     ""),
    ("%fusion.1 = f32[8]{0} fusion()", 14.0, 15.0, "jit__placed_residuals",
     ""),
    ("fusion.7", 16.0, 17.0, "jit__mat_fn",
     "jit(_mat_fn)/coord.per-user/materialize/gather"),
    ("copy.8", 17.0, 18.0, "", ""),
]


def test_own_seconds_are_per_event_and_sum_to_busy():
    own = op_phases.own_seconds(OPS)
    assert own[:5] == pytest.approx([3.0, 1.0, 2.0, 2.0, 2.0])
    assert own[5:] == pytest.approx([3.0, 1.0, 1.0, 1.0])
    assert sum(own) == pytest.approx(
        xplane.busy_seconds([op[:3] for op in OPS]))


def test_each_event_goes_to_its_phase_and_the_loops_keep_what_is_left():
    by = op_phases.phase_seconds(OPS, phase_of)
    assert by == pytest.approx({
        "residual": 1.0 + 1.0, "re_solve": 2.0, "fe_solve": 2.0 + 2.0,
        "score": 3.0, "materialize": 1.0, UNPHASED: 3.0 + 1.0})


def test_a_scope_beats_the_programs_name_which_names_the_rest():
    ops = [("a", 0.0, 1.0, "jit__solve_block",
            "jit(_solve_block)/residual/gather"),
           ("b", 1.0, 3.0, "jit__solve_block", "jit(_solve_block)/scatter"),
           ("c", 3.0, 6.0, "", "jit(_gather_score)/gather"),
           ("d", 6.0, 10.0, "jit_other", "")]
    assert op_phases.phase_seconds(ops, phase_of) == pytest.approx({
        "residual": 1.0, "re_solve": 2.0, "score": 3.0, UNPHASED: 4.0})


def test_two_programs_with_the_same_operation_are_kept_apart():
    ops = OPS[5:7]
    assert xplane.self_times([op[:3] for op in ops]) == {
        "%fusion.1 = f32[8]{0} fusion()": pytest.approx(4.0)}
    assert op_phases.program_seconds(ops) == pytest.approx({
        "jit__gather_score_mesh": 3.0, "jit__placed_residuals": 1.0})
    assert op_phases.phase_seconds(ops, phase_of) == pytest.approx({
        "score": 3.0, "residual": 1.0})


def test_a_program_is_the_module_else_the_head_of_the_scope_path():
    by = op_phases.program_seconds(
        [("a", 0.0, 1.0, "", "jit(_run_impl)/while"),
         ("b", 1.0, 3.0, "", ""), ("c", 3.0, 6.0, "jit_add", "")])
    assert by == pytest.approx(
        {"jit(_run_impl)": 1.0, "(none)": 2.0, "jit_add": 3.0})


def test_the_six_parts_sum_to_one_hundred():
    shares = op_phases.shares_pct(op_phases.phase_seconds(OPS, phase_of))
    assert set(shares) == set(PHASES) | {UNPHASED}
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares["fe_solve"] == pytest.approx(100.0 * 4.0 / 16.0)
    assert op_phases.shares_pct({}) == {}


def test_a_clip_keeps_the_labels():
    assert op_phases.clip_ops(OPS[:2], 1.5, 20.0) == [
        ("while.1", 1.5, 10.0) + OPS[0][3:], ("fusion.2", 1.5, 2.0)
        + OPS[1][3:]]


def _space_file(tmp_path):
    """A device plane as a TPU trace lays it out: an op line, a module
    line and a plane of no events before it; the scope path a stat of an
    event's metadata, the module a stat of the event (once as a string,
    once by reference) or else the run on the module line that holds it."""
    pb = op_phases._xplane_pb2()
    space = pb.XSpace()
    space.planes.add(id=3, name="/device:CUSTOM:Megascale Trace")
    plane = space.planes.add(id=1, name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "hlo_module"), (3, "jit__fit_fn"),
                      (4, "hlo_category")):
        plane.stat_metadata[key].id = key
        plane.stat_metadata[key].name = name
    for key, name, scope in (
            (10, "%fusion.3 = f32[8]{0} fusion()",
             f"{FIT}/coord.per-user/residual/gather:"),
            (11, "%copy.4 = f32[8]{0} copy()", ""),
            (12, "jit__solve_block(7)", "")):
        meta = plane.event_metadata[key]
        meta.id, meta.name = key, name
        if scope:
            meta.stats.add(metadata_id=1, str_value=scope)
        meta.stats.add(metadata_id=4, str_value="data formatting")
    ops = plane.lines.add(id=1, name="XLA Ops", timestamp_ns=1000)
    ev = ops.events.add(metadata_id=10, offset_ps=5000, duration_ps=2000000)
    ev.stats.add(metadata_id=2, ref_value=3)
    ev = ops.events.add(metadata_id=11, offset_ps=3000000,
                        duration_ps=1000000)
    ev.stats.add(metadata_id=2, str_value="jit__mat_fn")
    ops.events.add(metadata_id=11, offset_ps=12000000, duration_ps=1000000)
    modules = plane.lines.add(id=2, name="XLA Modules", timestamp_ns=1000)
    modules.events.add(metadata_id=12, offset_ps=11000000,
                       duration_ps=3000000)
    space.planes.add(id=2, name="/host:CPU")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return str(path)


def test_the_loaded_events_are_xplanes_with_two_labels(tmp_path):
    path = _space_file(tmp_path)
    loaded = op_phases.load_ops(path)
    plain = xplane.load(path)["devices"]
    assert list(loaded) == list(plain) == ["/device:TPU:0"]
    ops = loaded["/device:TPU:0"]
    assert [op[0] for op in ops] == [ev[0] for ev in plain["/device:TPU:0"]]
    assert [op[1:3] for op in ops] == [
        pytest.approx(ev[1:]) for ev in plain["/device:TPU:0"]]
    assert [op[3:] for op in ops] == [
        ("jit__fit_fn", f"{FIT}/coord.per-user/residual/gather:"),
        ("jit__mat_fn", ""), ("jit__solve_block", "")]
    assert op_phases.phase_seconds(ops, phase_of) == pytest.approx(
        {"residual": 2e-6, "materialize": 1e-6, "re_solve": 1e-6})


def test_a_cpu_trace_has_no_device_plane_and_no_phase(tmp_path):
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    step(x).block_until_ready()
    jax.profiler.stop_trace()
    assert op_phases.load_ops(xplane.newest_xplane(str(tmp_path))) == {}
    assert op_phases.shares_pct(op_phases.phase_seconds([], phase_of)) == {}


def test_the_ml25m_cells_programs_on_a_cpu_mesh_each_have_a_phase(tmp_path):
    """``glmix_ml25m`` at its tiny size through its builder on four of the
    CPU's devices: the programs a warm fit of the unfused loop dispatches
    (the ``hlo_module`` stat of the CPU trace's op events) are the ones
    the program's maps name, and each names a phase but JAX's two
    one-primitive helpers of a train call."""
    import copy
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    from benchmark import sut
    from benchmark.manifest import Manifest
    from photon_tpu.obs import phases

    from conftest import shrink

    man = Manifest()
    config = shrink(copy.deepcopy(man.config("glmix_ml25m")))
    data = man.generator("glmix_ml25m").generate(config, 2**31 + 38)
    sut.configure(config)
    with sut.using_builder(man.builder("glmix_ml25m")):
        dataset = sut.build_dataset(data)
        est = sut.build_estimator(config)
        est.prepare(dataset)
        sut.fit_blocking(est, dataset)
        jax.profiler.start_trace(str(tmp_path))
        sut.fit_blocking(est, dataset)
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    modules = {dict(ev.stats)["hlo_module"]
               for plane in ProfileData.from_file(path).planes
               for line in plane.lines for ev in line.events
               if "hlo_module" in dict(ev.stats)}
    named = {phases.program_name(m) for m in modules}
    assert named == {
        "_run_impl", "_solve_block", "_gather_score_mesh",
        "_placed_residuals", "_sub_add_impl", "matmul", "add", "subtract",
        "convert_element_type", "broadcast_in_dim"}
    unphased = {m for m in modules
                if phases.phase_of("", m) == phases.UNPHASED}
    assert unphased == {"jit_convert_element_type", "jit_broadcast_in_dim"}
