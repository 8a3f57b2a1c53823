"""The program's own records of the window, for the readers that share
them: ``sut.stage_records()`` hands over the program's ring, every stage it
recorded (``obs.stage``: prepare, plan, pack, fit, save, ...) and every
compile duration JAX published, each with a name, a thread, ``seconds``
and ``[t0, t1]`` on ``time.perf_counter``, the clock of
``ctx.window_start``. A program without such records gives every reader
here nothing.

The window: a record belongs to it when it starts at or after
``ctx.window_start`` and no later than the end of the window's last unit
(``window_end``). The ring goes on filling after that: ``kind.answer()``,
the plain reference and the comparison trace, lower and load programs of
their own before the readers run, and JAX's duration events make no
difference between the harness's compiles and the program's.

The trace's clock: ``run.py`` reads ``window_start`` and enters the
``bench.window`` annotation on consecutive lines, so a record lies at
``t + (ctx.trace.lo - ctx.window_start)`` in the trace.
"""

from __future__ import annotations

from benchmark import sut


def window_end(ctx):
    """End of the window's last unit on ``time.perf_counter``: the last
    end of the harness's own spans since ``ctx.window_start`` (every call
    a unit makes of the program lies inside one of them, and the harness
    opens none once the window has closed). None for a window without
    one, which has no records either."""
    ends = [e for _, s, e in ctx.spans.closed if s >= ctx.window_start]
    return max(ends) if ends else None


def records(ctx, *names: str) -> list:
    """The window's records of those names (all names when none given),
    in the order they were recorded; gated spans are left out."""
    end = window_end(ctx)
    if end is None:
        return []
    return [
        r for r in sut.stage_records()
        if ctx.window_start <= r.t0 <= end
        and getattr(r, "kind", "span") != "span"
        and (not names or r.name in names)
    ]


def per_unit(ctx, *names: str):
    """Summed ``seconds`` of the window's records of those names over
    the window's units (jobs, fits); None without such a record."""
    found = records(ctx, *names)
    if not found or not ctx.units:
        return None
    return sum(r.seconds for r in found) / ctx.units


def wall_per_job(ctx, name: str, job_stage: str = "prepare"):
    """Mean over the window's ``job_stage`` records of the wall seconds
    from the first start to the last end of the ``name`` records that
    started inside it, whatever their thread; None without any."""
    walls = []
    inner = records(ctx, name)
    for job in records(ctx, job_stage):
        mine = [r for r in inner if job.t0 <= r.t0 <= job.t1]
        if mine:
            walls.append(max(r.t1 for r in mine) - min(r.t0 for r in mine))
    return sum(walls) / len(walls) if walls else None


def training_thread(ctx):
    """The thread that entered the window's ``fit`` stages."""
    fits = records(ctx, "fit")
    return fits[0].thread if fits else None


def deepest(found: list) -> list:
    """One thread's stages as disjoint ``(path, start, end)`` pieces, each
    moment given to the deepest stage open at it (the leaf of the open
    stages: ``prepare`` keeps only what its children leave, which is its
    wait for the planner pool)."""
    cuts = sorted({t for r in found for t in (r.t0, r.t1)})
    out = []
    for lo, hi in zip(cuts, cuts[1:]):
        open_ = [r for r in found if r.t0 <= lo and r.t1 >= hi]
        if not open_:
            continue
        leaf = max(open_, key=lambda r: (r.path.count("/"), r.t0))
        if out and out[-1][0] == leaf.path and out[-1][2] == lo:
            out[-1] = (leaf.path, out[-1][1], hi)
        else:
            out.append((leaf.path, lo, hi))
    return out


def training_leaves(ctx) -> list:
    """``deepest`` over the training thread's stages of the window, on
    the trace's clock and cut to the traced window. Finished events are
    left out: ``save.encode`` and ``save.write`` are sums over interleaved
    blocks, not intervals, so their time stays with ``save``."""
    trace = ctx.trace
    thread = training_thread(ctx)
    if trace is None or thread is None:
        return []
    shift = trace.lo - ctx.window_start
    pieces = [
        (path, s + shift, e + shift)
        for path, s, e in deepest([
            r for r in records(ctx)
            if r.thread == thread and r.kind == "stage"])
    ]
    return ctx.xplane.clip(pieces, trace.lo, trace.hi)


def idle_by_leaf(ctx):
    """stage path -> idle seconds of the traced window spent in it as the
    training thread's leaf; ``(outside spans)`` is what no stage covers.
    A trace without a device plane (the CPU) is idle throughout. None
    where the program recorded no stage."""
    leaves = training_leaves(ctx)
    if not leaves:
        return None
    trace = ctx.trace
    return ctx.xplane.attribute_gaps(
        trace.first_device(), leaves, trace.lo, trace.hi)
