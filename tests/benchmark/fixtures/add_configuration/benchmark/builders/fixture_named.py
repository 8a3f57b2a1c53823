"""FIXTURE: a named builder. sut.py's plain pair, and a mark that each
ran. (A real one imports ``photon_tpu`` and builds what sut.py cannot
state.)"""

from benchmark import sut

CALLS = []


def build_estimator(config, precision=None):
    CALLS.append(("estimator", config["name"]))
    return sut.plain_estimator(config, precision)


def build_dataset(data):
    CALLS.append(("dataset", data.labels.shape[0]))
    return sut.plain_dataset(data)
