"""The reduction from a profiler trace to numbers, on hand-built events
and on a small trace recorded here on the CPU."""

import pytest

from benchmark import xplane

# A while loop holding three operations, then a gap, then one more.
EVENTS = [
    ("while.1", 0.0, 10.0),
    ("fusion.a", 1.0, 3.0),
    ("newton_step_lanes", 3.0, 4.0),
    ("fusion.a", 6.0, 9.0),
    ("copy.c", 12.0, 13.0),
]
SPANS = [("bench.fit", 0.0, 11.0), ("bench.save", 11.0, 15.0)]


def test_busy_is_the_union_not_the_sum():
    assert xplane.busy_seconds(EVENTS) == pytest.approx(11.0)
    assert xplane.busy_seconds(EVENTS[1:]) == pytest.approx(7.0)


def test_idle_share_of_a_window():
    busy = xplane.busy_seconds(xplane.clip(EVENTS, 0.0, 15.0))
    assert 1.0 - busy / 15.0 == pytest.approx(4.0 / 15.0)
    assert xplane.gaps(EVENTS, 0.0, 15.0) == [[10.0, 12.0], [13.0, 15.0]]


def test_clip_cuts_events_at_the_window():
    assert xplane.clip(EVENTS, 2.0, 3.5) == [
        ("while.1", 2.0, 3.5), ("fusion.a", 2.0, 3.0),
        ("newton_step_lanes", 3.0, 3.5)]


def test_self_time_charges_a_loop_only_what_its_children_leave():
    own = xplane.self_times(EVENTS)
    assert own["fusion.a"] == pytest.approx(5.0)
    assert own["newton_step_lanes"] == pytest.approx(1.0)
    assert own["while.1"] == pytest.approx(4.0)
    assert own["copy.c"] == pytest.approx(1.0)
    assert sum(own.values()) == pytest.approx(xplane.busy_seconds(EVENTS))


def test_time_of_a_named_kernel():
    seconds, count = xplane.named_seconds(EVENTS, "newton_step_lanes")
    assert (seconds, count) == (pytest.approx(1.0), 1)
    assert xplane.named_seconds(EVENTS, "fusion") == (
        pytest.approx(5.0), 2)
    assert xplane.named_seconds(EVENTS, "absent") == (0, 0)


# Event names as the TPU's trace gives them: whole HLO lines.
HLO_KERNEL = (
    "%newton_step_lanes.3 = (f32[17,72448]{1,0:T(8,128)S(1)}, "
    "f32[1,72448]{1,0}) custom-call(f32[17,64,72448]{2,1,0:T(8,128)} "
    "%get-tuple-element.7356, f32[17,72448]{1,0} %get-tuple-element.7337), "
    "custom_call_target=\"tpu_custom_call\"")
HLO_FUSION = (
    "%multiply_reduce_fusion.17 = (f32[72448]{0:T(1024)S(1)}) "
    "fusion(f32[17,72448]{1,0} %jit_newton_step_lanes_.8), kind=kLoop")


def test_a_kernel_is_found_by_its_own_name_not_its_operands():
    events = [(HLO_KERNEL, 0.0, 2.0), (HLO_FUSION, 2.0, 3.0)]
    assert xplane.op_name(HLO_KERNEL) == "newton_step_lanes.3"
    assert xplane.named_seconds(events, "newton_step_lanes") == (
        pytest.approx(2.0), 1)
    assert xplane.short_name(HLO_FUSION) == (
        "multiply_reduce_fusion.17:(f32[72448]")
    assert xplane.short_name("bench.fit") == "bench.fit"


def test_newton_roofline_reads_the_slab_shape_from_the_event():
    from benchmark import costs
    from benchmark.manifest import Manifest

    read = Manifest().metric_reader("kernel.newton_roofline_pct")
    peaks = costs.chip_peaks("TPU v5 lite")

    class Trace:
        devices = {"/device:TPU:0": [(HLO_KERNEL, 0.0, 0.01),
                                     (HLO_FUSION, 0.01, 0.02)]}

        def first_device(self):
            return self.devices["/device:TPU:0"]

    class Ctx:
        trace = Trace()

    Ctx.costs, Ctx.peaks, Ctx.xplane = costs, peaks, xplane
    flops, bytes_ = costs.newton_step_cost(rows=64, dim=17, lanes=72448)
    least, bound = costs.least_seconds(flops, bytes_, peaks)
    assert bound == "hbm"
    assert read(Ctx()) == pytest.approx(100.0 * least / 0.01)
    Trace.devices = {"/device:TPU:0": [(HLO_FUSION, 0.0, 1.0)]}
    assert read(Ctx()) is None


def test_gaps_go_to_the_span_that_covered_them():
    by = xplane.attribute_gaps(EVENTS, SPANS, 0.0, 15.0)
    assert by == {"bench.fit": pytest.approx(1.0),
                  "bench.save": pytest.approx(3.0)}
    by = xplane.attribute_gaps(EVENTS, SPANS[:1], 0.0, 15.0)
    assert by["(outside spans)"] == pytest.approx(3.0)


def test_reduced_window_from_loaded_planes():
    loaded = {
        "devices": {"/device:TPU:0": EVENTS,
                    "/device:TPU:1": [("fusion.a", 0.0, 5.0)]},
        "host": SPANS + [("bench.window", 0.0, 15.0), ("other", 0.0, 99.0)],
    }
    red = xplane.Reduced(loaded)
    assert red.window_s == pytest.approx(15.0)
    assert red.busy_s == pytest.approx((11.0 + 5.0) / 2)
    assert [n for n, _, _ in red.spans] == ["bench.fit", "bench.save"]
    assert red.top_ops(1)[0][0] == "fusion.a"
    assert dict(red.idle_by_span())["bench.save"] == pytest.approx(3.0)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        xplane.Reduced({"devices": {}, "host": SPANS})


def test_recorded_cpu_trace_carries_the_benchmarks_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.fit"):
            step(x).block_until_ready()
    jax.profiler.stop_trace()
    loaded = xplane.load(xplane.newest_xplane(str(tmp_path)))
    red = xplane.Reduced(loaded)
    assert [n for n, _, _ in red.spans] == ["bench.fit"]
    assert red.window_s > 0
    # The CPU has no device plane: nothing ran "on the device", and the
    # readers of a share of the device return nothing for it.
    assert red.devices == {} and red.busy_s == 0.0
