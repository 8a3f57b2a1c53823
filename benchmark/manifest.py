"""``BENCHMARK.json`` and the files it names, found by name.

A cell, a configuration, a traffic mix, a traffic kind, a per-layer
metric, a cell's limits, and a configuration's plain reference, data
generator and builder each sit in a file of their own, so a later PR adds
files and entries and edits nothing here.

Adding a configuration: (1) its file ``benchmark/configs/<name>.json``
(sizes, ``tiny`` block, ``assumed``) and its entry under ``configs``;
(2) a cell under ``workloads`` (traffic ``refit`` or ``retrain_job_uniform``,
or a new ``traffic/<name>.json``, with ``kinds/<kind>.py`` if the kind is
new), appended to the ``workloads`` of every metric of its kind (and a
reader ``metrics/<name>.py`` for a metric of its own); (3) the cell's
``limits/<cell>.json`` with the chip readings they were set between; and
only where the plain ones do not do, named in the configuration's file:
(4) ``reference``: ``references/<name>.py``, (5) ``generator``:
``generators/<name>.py``, (6) ``builder``: ``builders/<name>.py``. The
three directories may hold committed files; the tests write theirs as
``mine.py``, a name no committed file takes.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read_json(path: str):
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _load_module(path: str, tag: str):
    """The Python file at ``path`` as a module of its own (a name of the
    benchmark may hold dots and dashes, so it is no import name), loaded
    once a process."""
    spec = importlib.util.spec_from_file_location(
        tag + re.sub(r"\W", "_", os.path.basename(path)[:-3]), path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # a dataclass looks its module up
    spec.loader.exec_module(module)
    return module


class Manifest:
    def __init__(self, root: str = REPO_ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmark")
        self.doc = _read_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json "
            f"(has {[w['name'] for w in self.doc['workloads']]})")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _read_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def _named_module(self, config_name: str, key: str):
        """The module a configuration names under ``key`` (``reference``,
        ``generator`` or ``builder``): ``benchmark/<key>s/<name>.py``, and
        ``FileNotFoundError`` for a name that is nowhere. Without the key,
        ``benchmark/<key>.py``, the one every configuration had so far.
        Either way a file of THIS checkout, loaded by its path."""
        name = self.config(config_name).get(key)
        path = (os.path.join(self.bench_dir, key + ".py") if name is None
                else os.path.join(self.bench_dir, key + "s", name + ".py"))
        return _load_module(path, f"benchmark_{key}_")

    def reference(self, config_name: str):
        """The configuration's plain reference: ``fit(config, data)`` and
        ``predict(config, data, tables)``, coordinate name -> host float32
        table in the data's order (``sut.model_tables``). It imports
        nothing of the program. ``run_cell`` calls it once the window has
        closed, the peak has been read and the kind has released the
        program's state, so a named reference may rely on: the generated
        host arrays, the configuration, all ``cell["chips"]`` devices free
        of the program's state (it may hold the data in blocks or over
        every one of them), and float32 at ``highest`` whatever the
        configuration's own precision. It may take its time; PERF.md says
        where it takes longer than the window."""
        return self._named_module(config_name, "reference")

    def generator(self, config_name: str):
        """The configuration's data generator: ``generate(config, seed)``
        (host arrays: ``labels``, ``features``, ``ids``) and
        ``rows_per_entity(config, coordinate)``, a function of the
        configuration alone."""
        return self._named_module(config_name, "generator")

    def builder(self, config_name: str):
        """The module the configuration names under ``builder``, which may
        give ``build_estimator(config, precision=None)`` and
        ``build_dataset(data)`` (benchmark/sut.py says what each is), or
        None: ``sut.py``'s plain pair builds it."""
        if "builder" not in self.config(config_name):
            return None
        return self._named_module(config_name, "builder")

    def traffic_path(self, name: str) -> str:
        return os.path.join(self.bench_dir, "traffic", name + ".json")

    def traffic(self, name: str) -> dict:
        return _read_json(self.traffic_path(name))

    def kind_path(self, name: str) -> str:
        return os.path.join(self.bench_dir, "kinds", name + ".py")

    def kind(self, name: str):
        """The ``Kind`` class of ``benchmark/kinds/<name>.py``."""
        return _load_module(self.kind_path(name), "benchmark_kind_").Kind

    def limits_path(self, cell_name: str) -> str:
        return os.path.join(self.bench_dir, "limits", cell_name + ".json")

    def limits(self, cell_name: str) -> dict:
        """The cell's limits at its own size; the file's ``tiny_limits``
        are the ones at the configuration's ``tiny`` size, which only the
        tests' CPU rehearsal reads."""
        return _read_json(self.limits_path(cell_name))["limits"]

    def metric_path(self, name: str) -> str:
        return os.path.join(self.bench_dir, "metrics", name + ".py")

    def metric_reader(self, name: str):
        """The ``read(ctx)`` function of ``benchmark/metrics/<name>.py``."""
        return _load_module(
            self.metric_path(name), "benchmark_metric_").read

    def _reported_in(self, metric: dict, cell_name: str) -> bool:
        cells = metric.get("workloads")
        return cells is None or cell_name in cells

    def end_to_end(self, cell_name: str) -> list[dict]:
        return [m for m in self.doc["end_to_end"]
                if self._reported_in(m, cell_name)]

    def per_layer(self, cell_name: str) -> list[dict]:
        """Per-layer metrics of a cell: those that list it, and those with
        no list whose ``moves`` metric the cell reports."""
        reported = {m["name"] for m in self.end_to_end(cell_name)}
        out = []
        for m in self.doc["per_layer"]:
            if "workloads" in m:
                if cell_name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in reported:
                out.append(m)
        return out
