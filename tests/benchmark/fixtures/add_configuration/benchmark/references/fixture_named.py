"""FIXTURE: a named reference. The plain one, and a mark that it ran."""

from benchmark import reference as plain
from benchmark.reference import kept_rows, predict  # noqa: F401

CALLS = []


def fit(config, data):
    CALLS.append(config["name"])
    return plain.fit(config, data)
