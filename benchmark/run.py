"""One run of one cell: ``python3 -m benchmark.run --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` from the root of a checkout.

Prints one JSON object as the last line of standard output. Fails with no
result where JAX finds no accelerator the peaks table knows, fewer chips
than the cell asks for, or no program to measure.

The order of a run, which a configuration's own files may rely on: the
generator makes host arrays from the seed; the builder the configuration
names (or ``sut.py``'s plain pair) builds estimator and data set inside the
kind's set-up and units; after the window the peak is read and the kind
releases the program's state; only then ``reference.fit(config, data)``
runs, with all of the cell's chips free, in float32 at ``highest``, and is
not counted in ``setup_s`` (``window.check_s``).
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

EXIT_NO_PROGRAM = 4
EXIT_NO_CHIP = 3


class Reading:
    """What a per-layer metric's reader may look at: the run's own fields
    (config, traffic, cell, units, window_s, window_start, spans, trace,
    compile_events, peaks, costs, xplane) and whatever the traffic kind's
    ``report()`` names, with the three reductions several readers share.
    ``chips`` is the cell's: a share of the whole fit divides by ``chips``
    x ``peaks``, one device's trace by one chip's."""

    jobs = plan_shapes = fixed_iterations = None
    chips = 1

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def span_mean(self, name: str):
        """Mean seconds of the window's spans of that name, or None."""
        seconds = self.spans.seconds(name, since=self.window_start)
        return sum(seconds) / len(seconds) if seconds else None

    def job_mean(self, value):
        """Mean over the window's jobs of ``value(job)``, or None."""
        if not self.jobs:
            return None
        return sum(value(j) for j in self.jobs) / len(self.jobs)

    def idle_share_pct(self):
        """1 - busy / traced window, in percent; None without a device
        plane (never 0 or 100 for a trace that saw no device)."""
        t = self.trace
        if t is None or not t.devices or t.window_s <= 0:
            return None
        return 100.0 * (1.0 - t.busy_s / t.window_s)


def look_for_chip(chips: int) -> dict:
    """The device as JAX reports it, or exit: no chip, no number."""
    import jax

    from benchmark import costs

    devices = jax.devices()
    first = devices[0]
    if first.platform == "cpu":
        sys.exit(_refuse(
            EXIT_NO_CHIP, "JAX found no accelerator (platform cpu): a CPU "
            "run gives no device number, so the benchmark prints none"))
    try:
        costs.chip_peaks(first.device_kind)
    except KeyError as exc:
        sys.exit(_refuse(EXIT_NO_CHIP, str(exc)))
    if len(devices) < chips:
        sys.exit(_refuse(
            EXIT_NO_CHIP,
            f"the cell asks for {chips} chips and JAX found {len(devices)}"))
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}


def _refuse(code: int, why: str) -> int:
    print(f"benchmark: {why}", file=sys.stderr)
    return code


def _memory_peak_bytes(chips: int) -> int:
    import jax

    peak = 0
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(man, cell: dict, seed: int, seconds: float, trace: bool,
             device: dict, process_start: float | None = None) -> dict:
    """Everything of a run after the look for a chip; returns the result
    object. ``device`` is what ``look_for_chip`` returned."""
    from benchmark import sut

    process_start = (time.perf_counter() if process_start is None
                     else process_start)
    with sut.using_builder(man.builder(cell["config"])):
        return _run_cell(man, cell, seed, seconds, trace, device,
                         process_start)


def _run_cell(man, cell: dict, seed: int, seconds: float, trace: bool,
              device: dict, process_start: float) -> dict:
    import jax

    from benchmark import check, costs, sut, windows, xplane

    config = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    limits = man.limits(cell["name"])
    generator = man.generator(cell["config"])
    reference = man.reference(cell["config"])
    sut.configure(config)

    # ---- set-up: data from the seed, then the kind's own warm-up
    data = generator.generate(config, seed)
    spans = windows.Spans()
    kind = man.kind(traffic["kind"])(config, traffic, data, spans)
    unit_name = kind.unit_name
    kind.setup()
    gc.collect()

    trace_dir = None
    trace_units = int(traffic["trace_units"]) if trace else 0
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="photon_bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)

    # ---- the window
    counters_before = sut.compile_counters()
    window_start = time.perf_counter()
    setup_s = window_start - process_start
    units, profiler_s = 0, 0.0
    traced = contextlib.ExitStack()
    if trace:
        traced.enter_context(jax.profiler.TraceAnnotation("bench.window"))
    while True:
        kind.unit(units)
        units += 1
        if trace and units == trace_units:
            stop_start = time.perf_counter()
            traced.close()
            jax.profiler.stop_trace()
            profiler_s = time.perf_counter() - stop_start
        elapsed = time.perf_counter() - window_start - profiler_s
        if elapsed >= seconds and units >= max(
                trace_units, int(traffic["min_units"])):
            break
    window_s = time.perf_counter() - window_start - profiler_s
    counters_after = sut.compile_counters()
    memory_peak = _memory_peak_bytes(cell["chips"])

    end_to_end = kind.end_to_end(units, window_s)
    end_to_end["setup_s"] = setup_s

    # ---- what the window produced, then the program's state goes
    answer = kind.answer()
    report = kind.report()
    kind.release()
    del kind
    gc.collect()

    # ---- the plain reference, once the window has closed
    check_start = time.perf_counter()
    ref_tables = reference.fit(config, data)
    numbers = check.compare(config, data, answer, ref_tables,
                            reference.predict)
    correct, compared = check.verdict(numbers, limits)
    check_s = time.perf_counter() - check_start

    device_out = dict(device, memory_peak_bytes=memory_peak)
    out = {
        "correct": correct,
        "attempted": units,
        "failed": 0,
        "metrics": {},
        "device": device_out,
    }
    if not trace:
        for m in man.end_to_end(cell["name"]):
            out["metrics"][m["name"]] = {
                "value": end_to_end[m["name"]], "unit": m["unit"]}
    else:
        reduced = xplane.Reduced(xplane.load(xplane.newest_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device_out["busy_s"] = reduced.busy_s
        device_out["window_s"] = reduced.window_s
        ctx = Reading(
            **report,
            config=config, traffic=traffic, cell=cell,
            chips=int(cell["chips"]), units=units,
            window_s=window_s, spans=spans, trace=reduced,
            compile_events=(
                counters_after["hits"] + counters_after["misses"]
                - counters_before["hits"] - counters_before["misses"]),
            peaks=costs.chip_peaks(device["kind"]), costs=costs,
            xplane=xplane,
            window_start=window_start,
        )
        for m in man.per_layer(cell["name"]):
            value = man.metric_reader(m["name"])(ctx)
            if value is not None:
                out["metrics"][m["name"]] = {
                    "value": float(value), "unit": m["unit"]}
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in reduced.idle_by_span(10)],
        }
    out["window"] = {
        unit_name: units, "window_s": window_s,
        "check_s": check_s, "seed": seed,
        "cache_dir": counters_after["dir"],
    }
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark.manifest import Manifest

    man = Manifest()
    cell = man.cell(args.workload)
    from benchmark import sut

    missing = sut.program_missing()
    if missing:
        return _refuse(
            EXIT_NO_PROGRAM,
            f"no program to measure beside the benchmark ({missing})")
    device = look_for_chip(int(cell["chips"]))
    out = run_cell(man, cell, args.seed, args.seconds, bool(args.trace),
                   device, process_start=_PROCESS_START)
    sys.stdout.flush()
    for name, row in out["compared"].items():
        print(f"compared {name}: {row['value']!r} limit {row['limit']!r} "
              f"{'ok' if row['ok'] else 'OVER'}", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
