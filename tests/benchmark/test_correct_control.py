"""``correct`` has to come out false: for the low-precision control put
in the program's place, and for each fault a cell can have, planted under
the harness while it drives the rest of a run."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, generator, reference, run, sut
from benchmark.manifest import Manifest

from conftest import FAKE_DEVICE

# The nearest precision below the one each configuration states.
CONTROL_STORAGE = {"glmix_ml_logistic": jnp.bfloat16,
                   "glmix_ml_linear": jnp.float8_e4m3fn}


def _drive(tiny_root, cell, seed=11):
    man = Manifest(tiny_root)
    return run.run_cell(man, man.cell(cell), seed=seed, seconds=0.2,
                        trace=False, device=dict(FAKE_DEVICE))


@pytest.mark.parametrize("config_name", sorted(CONTROL_STORAGE))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_is_not_correct_and_the_program_is(
        tiny_root, config_name, seed):
    man = Manifest(tiny_root)
    cell = next(w for w in man.doc["workloads"]
                if w["config"] == config_name)
    # Three data sets, not one under three namings: the values from the
    # seed here, as a configuration with a null data_seed draws them.
    config = dict(man.config(config_name), data_seed=None)
    limits = {k: v for k, v in man.limits(cell["name"]).items()
              if not k.endswith("_max_abs")}
    data = generator.generate(config, seed)
    ref = reference.fit(config, data)

    control = reference.fit(config, data,
                            storage=CONTROL_STORAGE[config_name])
    ok, compared = check.verdict(
        check.compare(config, data, {"tables": control}, ref,
                      reference.predict), limits)
    assert not ok, compared

    # Built as run_cell builds it: by the configuration's builder where it
    # names one.
    with sut.using_builder(man.builder(config_name)):
        est = sut.build_estimator(config)
        dataset = sut.build_dataset(data)
    fitted = sut.fit_blocking(est, dataset)
    ok, compared = check.verdict(
        check.compare(config, data,
                      {"tables": sut.model_tables(fitted.model, config)},
                      ref, reference.predict), limits)
    assert ok, compared


def _with_tables(model, change):
    """The model with ``change`` applied to every coefficient table."""
    import dataclasses

    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel

    out = {}
    for name, m in model.models.items():
        if isinstance(m, RandomEffectModel):
            out[name] = dataclasses.replace(
                m, coefficients=change(name, m.coefficients))
        else:
            out[name] = FixedEffectModel(
                GeneralizedLinearModel(
                    Coefficients(
                        means=change(name, m.model.coefficients.means)),
                    m.model.task),
                m.feature_shard_id)
    return GameModel(out)


def _zeroed(model):
    return _with_tables(model, lambda name, w: jnp.zeros_like(w))


class _Result:
    def __init__(self, model):
        self.model = model


def test_fault_a_fit_that_returns_its_state_unchanged(
        tiny_root, monkeypatch):
    real = sut.fit_blocking

    def unchanged(est, dataset):
        return _Result(_zeroed(real(est, dataset).model))

    monkeypatch.setattr(sut, "fit_blocking", unchanged)
    out = _drive(tiny_root, "logistic.refit")
    assert out["correct"] is False
    assert not out["compared"]["coef.per-user"]["ok"]


def test_fault_half_of_the_rows_left_out(tiny_root, monkeypatch):
    real = sut.build_dataset

    def half(data):
        n = data.labels.shape[0] // 2
        return real(generator.GlmixData(
            labels=data.labels[:n],
            features={k: v[:n] for k, v in data.features.items()},
            ids={k: v[:n] for k, v in data.ids.items()}))

    monkeypatch.setattr(sut, "build_dataset", half)
    out = _drive(tiny_root, "linear.refit")
    assert out["correct"] is False
    assert not out["compared"]["score_rms"]["ok"]


def test_fault_an_answer_altered_where_it_is_produced(
        tiny_root, monkeypatch):
    """One entity comes back untrained (PR 21's layout fault left whole
    coordinates so)."""
    real = sut.fit_blocking

    def altered(est, dataset):
        return _Result(_with_tables(
            real(est, dataset).model,
            lambda name, w: w.at[3].set(0.0) if name == "per-user" else w))

    monkeypatch.setattr(sut, "fit_blocking", altered)
    out = _drive(tiny_root, "logistic.refit")
    assert out["correct"] is False
    assert not out["compared"]["coef.per-user"]["ok"]
    assert out["compared"]["coef.global"]["ok"]


def test_fault_fits_of_one_window_that_disagree(tiny_root, monkeypatch):
    real = sut.fit_blocking
    calls = []

    def drifting(est, dataset):
        calls.append(1)
        return _Result(_with_tables(
            real(est, dataset).model,
            lambda name, w: w + 1e-6 * len(calls)))

    monkeypatch.setattr(sut, "fit_blocking", drifting)
    out = _drive(tiny_root, "linear.refit")
    assert out["correct"] is False
    assert not out["compared"]["repeat_max_abs"]["ok"]


def test_fault_a_saved_model_that_is_not_the_fitted_one(
        tiny_root, monkeypatch):
    real = sut.save_model

    def stale(model, config, path):
        real(_zeroed(model), config, path)

    monkeypatch.setattr(sut, "save_model", stale)
    out = _drive(tiny_root, "linear.retrain")
    assert out["correct"] is False
    assert not out["compared"]["saved_max_abs"]["ok"]
    assert not out["compared"]["coef.per-movie"]["ok"]


def test_verdict_needs_every_number_and_finite_ones():
    ok, compared = check.verdict({"a": 1.0}, {"a": 2.0, "b": 1.0})
    assert not ok and compared["b"]["value"] is None
    assert not check.verdict({"a": float("nan")}, {"a": 2.0})[0]
    assert check.verdict({"a": 0.0, "extra": 9.0}, {"a": 0.0})[0]
    assert not check.verdict({"a": 0.0}, {})[0]
