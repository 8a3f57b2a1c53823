"""From a profiler trace to numbers: busy share, kernel time, idle gaps.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain tuples; everything else here is arithmetic on those tuples, so the
reduction is checked on hand-built events (tests/benchmark).

Times are seconds. An event is ``(name, start, end)``.
"""

from __future__ import annotations

import glob
import os

# Lines of a device plane that hold single operations. "XLA Modules" and
# "Steps" hold whole programs: an interval of those covers the gaps inside.
_OP_LINES = ("XLA Ops",)
_NOT_OP_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code")


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> dict:
    """{"devices": {plane name: [event, ...]}, "host": [event, ...]}.

    Device events are those of the operation lines of each ``/device:``
    plane; host events are all events of the ``/host:CPU`` plane (the
    benchmark's ``TraceAnnotation`` spans are among them)."""
    from jax.profiler import ProfileData

    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            named = [ln for ln in lines if ln.name in _OP_LINES]
            if not named:
                named = [ln for ln in lines if ln.name not in _NOT_OP_LINES]
            events = [
                (ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ln in named for ev in ln.events
            ]
            if events:
                devices[plane.name] = events
        elif plane.name == "/host:CPU":
            host.extend(
                (ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ln in plane.lines for ev in ln.events
            )
    return {"devices": devices, "host": host}


def op_name(text: str) -> str:
    """The operation's own name: a TPU trace names an event by its whole
    HLO line, ``%fusion.3 = f32[8]{0} fusion(...)``; operands may carry
    other operations' names."""
    return text.split(" = ", 1)[0].lstrip("%")


def short_name(text: str) -> str:
    """``fusion.3:f32[8]``: the operation's name and its output shape."""
    if " = " not in text:
        return text[:80]
    name, rest = text.split(" = ", 1)
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name.lstrip('%')}:{shape}"[:80]


def spans_named(events, prefix: str):
    return sorted((e for e in events if e[0].startswith(prefix)),
                  key=lambda e: e[1])


def clip(events, lo: float, hi: float):
    """The parts of ``events`` inside [lo, hi]."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events):
    """Merged, sorted [start, end] intervals that the events cover."""
    merged = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_seconds(events) -> float:
    """Seconds in which at least one of the events ran."""
    return sum(e - s for s, e in union(events))


def gaps(events, lo: float, hi: float):
    """[start, end] intervals of [lo, hi] that no event covers."""
    out, at = [], lo
    for s, e in union(clip(events, lo, hi)):
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def self_times(events) -> dict:
    """name -> seconds of the events' own time: an event that holds others
    (a while loop, a conditional) is charged only what its children leave,
    so a loop does not hide the operations inside it."""
    out: dict = {}
    stack = []  # [name, end, own seconds so far, cursor]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, own, cursor = stack.pop()
            own += max(0.0, end - cursor)
            out[name] = out.get(name, 0.0) + own
            if stack:
                stack[-1][3] = max(stack[-1][3], end)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            parent = stack[-1]
            parent[2] += max(0.0, s - parent[3])
            parent[3] = max(parent[3], s)
        stack.append([name, e, 0.0, s])
    close(float("inf"))
    return out


def kernel_events(events, kernel: str):
    """The events of the operations named ``kernel`` (``kernel.3`` too):
    by the operation's own name, not by what its operands are called."""
    return [e for e in events
            if op_name(e[0]).split(".", 1)[0] == kernel]


def named_seconds(events, kernel: str) -> tuple[float, int]:
    """(seconds, count) of the kernel's events, nested repeats counted
    once (their union)."""
    hits = kernel_events(events, kernel)
    return busy_seconds(hits), len(hits)


def attribute_gaps(device_events, host_spans, lo: float, hi: float) -> dict:
    """span name -> idle seconds of [lo, hi] that fell inside that host
    span; what no span covers goes to ``(outside spans)``. Spans are taken
    in the order given, first match wins."""
    out: dict = {}
    for gs, ge in gaps(device_events, lo, hi):
        left = ge - gs
        for name, s, e in host_spans:
            part = min(ge, e) - max(gs, s)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                left -= part
        if left > 1e-9:
            out["(outside spans)"] = out.get("(outside spans)", 0.0) + left
    return out


class Reduced:
    """One traced window, reduced. ``window`` is the [lo, hi] of the
    benchmark's ``bench.window`` annotation."""

    def __init__(self, loaded: dict, span_prefix: str = "bench."):
        host = loaded["host"]
        window = spans_named(host, span_prefix + "window")
        if not window:
            raise ValueError("the trace holds no bench.window span")
        self.lo = window[0][1]
        self.hi = max(e for _, _, e in window)
        self.window_s = self.hi - self.lo
        self.spans = [
            s for s in clip(spans_named(host, span_prefix), self.lo, self.hi)
            if not s[0].startswith(span_prefix + "window")
        ]
        self.devices = {
            name: clip(events, self.lo, self.hi)
            for name, events in sorted(loaded["devices"].items())
        }

    @property
    def busy_s(self) -> float:
        """Mean over the device planes of the seconds an operation ran."""
        if not self.devices:
            return 0.0
        per = [busy_seconds(ev) for ev in self.devices.values()]
        return sum(per) / len(per)

    def first_device(self):
        return next(iter(self.devices.values()), [])

    def top_ops(self, count: int = 10):
        own: dict = {}
        for text, seconds in self_times(self.first_device()).items():
            name = short_name(text)
            own[name] = own.get(name, 0.0) + seconds
        return sorted(own.items(), key=lambda kv: -kv[1])[:count]

    def idle_by_span(self, count: int = 10):
        by = attribute_gaps(self.first_device(), self.spans, self.lo, self.hi)
        return sorted(by.items(), key=lambda kv: -kv[1])[:count]
