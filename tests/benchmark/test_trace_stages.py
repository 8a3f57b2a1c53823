"""The program's stages in a recorded trace: a small trace recorded here on
the CPU holds ``photon.<path>`` beside the benchmark's own ``bench.*``."""

import pytest

from benchmark import xplane


def test_recorded_cpu_trace_holds_the_programs_stages_beside_the_spans(
        tmp_path):
    """A stage of the program is a TraceAnnotation too: ``photon.<path>``
    lands in the host plane on the profiler's clock, beside ``bench.fit``,
    telemetry disabled, from the training thread and from a worker."""
    import threading
    import time

    import jax

    from photon_tpu import obs

    def plan():
        with obs.stage("plan"):
            time.sleep(0.002)

    was = obs.enabled()
    obs.disable()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        window_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.fit"):
                worker = threading.Thread(target=plan)
                worker.start()
                worker.join()
                with obs.stage("fit") as fit:
                    with obs.stage("fit.dispatch"):
                        time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
        obs.TRACER.enabled = was
    loaded = xplane.load(xplane.newest_xplane(str(tmp_path)))
    host = {name: (s, e) for name, s, e in loaded["host"]}
    assert {"bench.fit", "photon.fit", "photon.fit/fit.dispatch",
            "photon.plan"} <= set(host)
    bench, stage = host["bench.fit"], host["photon.fit"]
    assert bench[0] <= stage[0] and stage[1] <= bench[1]
    # The ring's record, moved by (trace.lo - window_start) as
    # benchmark/stages.py does, lies on the annotation (both clocks are
    # steady; the two reads are lines apart).
    red = xplane.Reduced(loaded)
    shift = red.lo - window_start
    assert fit.t0 + shift == pytest.approx(stage[0], abs=2e-3)
    assert fit.t1 + shift == pytest.approx(stage[1], abs=2e-3)
