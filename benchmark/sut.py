"""The system under test: with the files under ``benchmark/builders/``,
the one place that imports ``photon_tpu``.

Everything the harness asks of the program goes through here: the
estimator a configuration file describes, the data set, one blocking fit,
save and load of a model, and the program's own counters.

``build_estimator`` and ``build_dataset`` state what the four first cells
need: L2, dense features, one optimizer, no weights, offsets or validation
data. A configuration that needs more names a builder (key ``builder``:
``benchmark/builders/<name>.py``, found by ``Manifest.builder``), which may
give ``build_estimator(config, precision=None)``, ``build_dataset(data)``
or both; what it does not give, and everything of a configuration without
the key, comes from the plain pair here (``plain_estimator``,
``plain_dataset``, which a builder may call). A builder builds a
``GameEstimator`` and a ``GameDataset`` for the program's normal entry
points (``prepare``, ``fit``), which the traffic kinds drive: it is no
place for a fit loop of its own. The kinds and the tests call
``sut.build_estimator`` / ``sut.build_dataset`` whatever the configuration
names; ``run_cell`` says which builder is in use (``using_builder``).
"""

from __future__ import annotations

import contextlib

import numpy as np

_builder = None  # the module the running cell's configuration names


@contextlib.contextmanager
def using_builder(module):
    """Inside: ``build_estimator`` / ``build_dataset`` are ``module``'s
    (``Manifest.builder``) where it gives them; None, and outside, the
    plain pair's. On the way out the builder that was in use before is in
    use again, so one use may stand inside another."""
    global _builder
    before, _builder = _builder, module
    try:
        yield
    finally:
        _builder = before


def program_missing() -> str | None:
    """Why there is no program to measure, or None where there is one."""
    try:
        import photon_tpu  # noqa: F401
    except ImportError as exc:
        return str(exc)
    return None


def configure(config: dict) -> str:
    """Process-wide settings of a configuration, before anything is
    traced: JAX's persistent compile cache at ``<checkout>/.jax_cache``
    (or where ``JAX_COMPILATION_CACHE_DIR`` says; the program's own rule),
    and the matmul precision where the configuration states one. On a TPU
    a float32 product runs in one bf16 pass unless JAX's
    ``jax_default_matmul_precision`` says otherwise; it is the only option
    the program has to compute in the float32 it states."""
    import jax

    from photon_tpu.utils import enable_compilation_cache

    if config.get("matmul_precision"):
        jax.config.update(
            "jax_default_matmul_precision", config["matmul_precision"])
    return enable_compilation_cache()


def build_estimator(config: dict, precision: str | None = None):
    """The GameEstimator ``config`` states, by the builder in use where it
    gives one. ``precision`` overrides the configuration's only for the
    low-precision control of the check."""
    build = getattr(_builder, "build_estimator", None) or plain_estimator
    return build(config, precision=precision)


def build_dataset(data):
    """Host arrays -> GameDataset, by the builder in use where it gives
    one."""
    return (getattr(_builder, "build_dataset", None) or plain_dataset)(data)


def plain_estimator(config: dict, precision: str | None = None):
    """L2 on every coordinate, each solver route's default optimizer."""
    from photon_tpu import optim
    from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
    from photon_tpu.data.random_effect import RandomEffectDataConfiguration
    from photon_tpu.estimators.game_estimator import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu.types import TaskType

    def l2(weight):
        return GLMOptimizationConfiguration(
            regularization=optim.RegularizationContext(
                optim.RegularizationType.L2),
            regularization_weight=float(weight),
        )

    coords, intercepts = {}, {}
    for c in config["coordinates"]:
        intercepts[c["shard"]] = int(c["features"]) - 1
        if c["kind"] == "fixed":
            coords[c["name"]] = FixedEffectCoordinateConfiguration(
                c["shard"], l2(c["l2"]))
        else:
            coords[c["name"]] = RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration(
                    c["id"], c["shard"],
                    active_data_upper_bound=c["active_data_upper_bound"],
                    min_bucket_entities=int(c["min_bucket_entities"]),
                ),
                l2(c["l2"]),
            )
    return GameEstimator(
        TaskType(config["task"]),
        coords,
        intercept_indices=intercepts,
        num_iterations=int(config["num_iterations"]),
        mesh=config["mesh"],
        precision=precision or config["precision"],
    )


def plain_dataset(data):
    """Dense shards and id tags, raw shards resident on the device."""
    import jax

    from photon_tpu.data.dataset import DenseFeatures
    from photon_tpu.data.game_data import make_game_dataset

    ds = make_game_dataset(
        data.labels,
        {name: DenseFeatures(x) for name, x in data.features.items()},
        id_tags=dict(data.ids),
    )
    jax.block_until_ready(
        [f.x for f in ds.feature_shards.values()] + [ds.labels])
    return ds


def coefficient_arrays(model) -> dict:
    """coordinate name -> device coefficient table."""
    return {
        name: (m.coefficients if hasattr(m, "coefficients")
               else m.model.coefficients.means)
        for name, m in model.models.items()
    }


def fit_blocking(est, dataset):
    """One whole fit, ended by ``block_until_ready`` on every table."""
    import jax

    result = est.fit(dataset)[0]
    jax.block_until_ready(list(coefficient_arrays(result.model).values()))
    return result


def fixed_effect_iterations(result):
    """Optimizer iterations a fit spent on its fixed effects, summed over
    its coordinate-descent iterations, from the diagnostics the program
    hands back with the fit (one small pull from the device); None where
    it hands back none. A random effect's diagnostics carry no such
    count."""
    history = getattr(getattr(result, "descent", None), "history", ())
    found = [record.diagnostics.iterations for record in history
             if getattr(record.diagnostics, "iterations", None) is not None]
    return int(sum(found)) if found else None


def model_tables(model, config: dict) -> dict:
    """A model as host float32 tables in the DATA's order: coordinate
    name -> [d] for the fixed effect, [entities, d] for a random effect
    with row e the entity whose id is e and column j feature j. A model
    keeps its own entity order and per-entity projectors (a loaded one
    always does); this undoes both."""
    out = {}
    for c in config["coordinates"]:
        m = model.models[c["name"]]
        if c["kind"] == "fixed":
            out[c["name"]] = np.asarray(
                m.model.coefficients.means, np.float32)
            continue
        table = np.zeros((int(c["entities"]), int(c["features"])),
                         np.float32)
        coefs = np.asarray(m.coefficients, np.float32)
        proj = np.asarray(m.proj_all)
        keys = np.asarray([int(k) for k in m.entity_keys])
        rows, slots = np.nonzero(proj >= 0)
        table[keys[rows], proj[rows, slots]] = coefs[rows, slots]
        out[c["name"]] = table
    return out


def _index_maps(config: dict) -> dict:
    from photon_tpu.data.index_map import IndexMap

    return {c["shard"]: IndexMap.identity(int(c["features"]))
            for c in config["coordinates"]}


def save_model(model, config: dict, path: str) -> None:
    from photon_tpu.io.model_io import save_game_model

    save_game_model(model, path, _index_maps(config))


def load_model_tables(config: dict, path: str) -> dict:
    """The saved model read back, as ``model_tables`` gives it."""
    from photon_tpu.io.model_io import load_game_model

    model, _ = load_game_model(path, _index_maps(config))
    return model_tables(model, config)


def plan_shapes(datasets) -> dict:
    """coordinate -> [(entities, row cap), ...] of its bucket slabs."""
    out = {}
    for name, ds in datasets.items():
        blocks = getattr(ds, "blocks", None)
        if blocks is not None:
            out[name] = [tuple(int(v) for v in b.row_ids.shape)
                         for b in blocks]
    return out


def compile_counters() -> dict:
    from photon_tpu.utils import cache_stats

    s = cache_stats()
    return {"hits": s["persistent_hits"], "misses": s["persistent_misses"],
            "dir": s["dir"]}


def stage_records() -> list:
    """Everything in the program's ring of stages and compile durations
    (``photon_tpu.obs``), oldest first; a program without the ring gives
    nothing, any other fault raises."""
    try:
        from photon_tpu import obs

        return list(obs.TRACER.completed())
    except (ImportError, AttributeError):
        return []


def pipeline_report() -> dict:
    from photon_tpu.data.pipeline import PIPELINE_STATS

    return PIPELINE_STATS.report()

