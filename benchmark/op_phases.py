"""Device time by what each operation is: its program and its scope path,
and its own seconds put down to the phase the program names for them.

``xplane.load`` keeps an event as ``(name, start, end)``. This module
reads the same events of the same lines (``xplane._OP_LINES``) with two
labels more, ``(name, start, end, program, tf_op)``: ``program`` the
``hlo_module`` stat, or else the run on the plane's ``XLA Modules`` line
that holds the event (``jit__fit_fn``), ``tf_op`` the ``tf_op`` stat (the
HLO ``op_name``, ``jit(_fit_fn)/while/body/coord.per-user/residual/...``);
either may be ``""``. A TPU operation keeps ``tf_op`` among the stats of
its event's METADATA, which ``jax.profiler.ProfileEvent.stats`` does not
hold, so the serialized XSpace is read here with its protobuf module.

Own time is per EVENT: an event that holds others (a while loop) keeps
only what its children leave, and two programs whose operations share a
name and a shape stay apart (``xplane.self_times`` adds by name).

The phase rule is the program's (``photon_tpu/obs/phases.py``
``phase_of(tf_op, program)``), handed in by the caller. ``run.py`` does
not call this module yet (PERF.md 7.4 says what a ``benchmark`` PR has to
wire).
"""

from __future__ import annotations

import bisect
import functools
import importlib.util
import os

from benchmark import xplane

_MODULE_LINE = "XLA Modules"  # one event a program's run: ``jit__fit_fn(7)``


@functools.lru_cache(maxsize=None)
def _xplane_pb2():
    """TSL's ``xplane_pb2``, loaded from its file: it imports protobuf
    alone, where ``import tensorflow.tsl...`` first runs TensorFlow's own
    ``__init__`` (10 s)."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ImportError("no tensorflow package, so no xplane_pb2")
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    module_spec = importlib.util.spec_from_file_location(
        "benchmark_xplane_pb2", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def _stat_value(stat, stat_names):
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return stat_names.get(stat.ref_value, "")
    return getattr(stat, kind) if kind else ""


def _plane_ops(plane) -> list:
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}

    def stats_of(stats) -> dict:
        return {stat_names.get(s.metadata_id, ""): _stat_value(s, stat_names)
                for s in stats}

    labels = {key: (meta.name, stats_of(meta.stats))
              for key, meta in plane.event_metadata.items()}

    def events(ln):
        for ev in ln.events:
            start_ns = ln.timestamp_ns + ev.offset_ps * 1e-3
            end_ns = start_ns + ev.duration_ps * 1e-3
            yield ev, start_ns * 1e-9, end_ns * 1e-9

    lines = list(plane.lines)
    named = [ln for ln in lines if ln.name in xplane._OP_LINES]
    if not named:
        named = [ln for ln in lines if ln.name not in xplane._NOT_OP_LINES]
    # A program's runs on the device's module line, for the operations
    # whose own stats do not name their program.
    modules = sorted(
        (start, end, labels.get(ev.metadata_id, ("", {}))[0].split("(")[0])
        for ln in lines if ln.name == _MODULE_LINE
        for ev, start, end in events(ln))
    starts = [m[0] for m in modules]
    ops = []
    for ln in named:
        for ev, start, end in events(ln):
            name, stats = labels.get(ev.metadata_id, ("", {}))
            if ev.stats:
                stats = dict(stats, **stats_of(ev.stats))
            program = str(stats.get("hlo_module", ""))
            if not program and modules:
                i = bisect.bisect_right(starts, start) - 1
                if i >= 0 and modules[i][1] >= end:
                    program = modules[i][2]
            ops.append((name, start, end, program,
                        str(stats.get("tf_op", ""))))
    return ops


def load_ops(path: str) -> dict:
    """{device plane name: [(name, start, end, program, tf_op), ...]}: the
    device planes and events ``xplane.load`` keeps, in its order."""
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if plane.name.startswith("/device:"):
            ops = _plane_ops(plane)
            if ops:
                out[plane.name] = ops
    return out


def clip_ops(ops, lo: float, hi: float):
    """``xplane.clip`` for labelled events."""
    return [(n, max(s, lo), min(e, hi), p, t) for n, s, e, p, t in ops
            if e > lo and s < hi]


def own_seconds(events) -> list:
    """Each event's own seconds, in the order given: what it ran outside
    the events it holds. Sums to ``xplane.busy_seconds(events)``."""
    own = [0.0] * len(events)
    stack = []  # [index, end, cursor]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            i, end, cursor = stack.pop()
            own[i] += max(0.0, end - cursor)
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    for i in order:
        s, e = events[i][1], events[i][2]
        close(s)
        if stack:
            parent = stack[-1]
            own[parent[0]] += max(0.0, s - parent[2])
            parent[2] = max(parent[2], s)
        stack.append([i, e, s])
    close(float("inf"))
    return own


def _by(ops, key) -> dict:
    out: dict = {}
    for op, seconds in zip(ops, own_seconds(ops)):
        k = key(op)
        out[k] = out.get(k, 0.0) + seconds
    return out


def phase_seconds(ops, phase_of) -> dict:
    """phase -> own seconds, ``phase_of(tf_op, program)`` naming each
    event's phase."""
    return _by(ops, lambda op: phase_of(op[4], op[3]))


def program_seconds(ops) -> dict:
    """program -> own seconds: the program ``load_ops`` found, else the
    head of ``tf_op`` (``jit(_fit_fn)``), else ``(none)``."""
    return _by(ops, lambda op: op[3] or op[4].split("/", 1)[0] or "(none)")


def shares_pct(seconds: dict) -> dict:
    """Each entry's share of their sum, in percent; {} for no time."""
    total = sum(seconds.values())
    if total <= 0:
        return {}
    return {k: 100.0 * v / total for k, v in seconds.items()}
