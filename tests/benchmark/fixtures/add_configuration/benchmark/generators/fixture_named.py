"""FIXTURE: a named generator. The plain one, and a mark that it ran."""

from benchmark import generator as plain
from benchmark.generator import rows_per_entity  # noqa: F401

CALLS = []


def generate(config, seed):
    CALLS.append((config["name"], seed))
    return plain.generate(config, seed)
