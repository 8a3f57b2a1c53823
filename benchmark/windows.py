"""What a window of units is, and the spans its units leave.

A traffic kind is a file of its own, ``benchmark/kinds/<kind>.py``, found
by the ``kind`` its traffic file names; the traffic file's other keys are
its parameters. It holds a class ``Kind(config, traffic, data, spans)``
that the harness (run.py) drives the same way whatever the kind:

- ``setup()`` once; all of it is set-up time;
- ``unit(k)`` back to back while less than ``--seconds`` have passed;
- ``end_to_end(units, window_s)``: the cell's end-to-end metrics by name;
- ``report()``: whatever else its per-layer readers look at, by name
  (each key becomes an attribute of the reader's ``ctx``);
- ``answer()``: what the window produced, for check.py;
- ``release()``: the program's state goes, before the reference runs;
- ``unit_name``: what a unit is called in the result line.
"""

from __future__ import annotations

import contextlib
import time


class Spans:
    """Host-clock spans ``(name, start, end)``, each also written into the
    profiler's trace as a ``TraceAnnotation`` of the same name."""

    def __init__(self):
        self.closed: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.closed.append((name, start, time.perf_counter()))

    def seconds(self, name: str, since: float = 0.0) -> list[float]:
        return [e - s for n, s, e in self.closed if n == name and s >= since]
