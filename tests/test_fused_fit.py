"""Fused whole-fit program vs the unfused CoordinateDescent loop.

The fused path (algorithm/fused_fit.py) must be numerically equivalent to
the dispatch-per-update loop it replaces: same solver primitives, same
residual algebra, same warm-start semantics — one XLA program.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import optim
from photon_tpu.algorithm.fused_fit import fuse_eligible
from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
from photon_tpu.data.dataset import DenseFeatures
from photon_tpu.data.game_data import make_game_dataset
from photon_tpu.data.random_effect import RandomEffectDataConfiguration
from photon_tpu.estimators.game_estimator import (
    FixedEffectCoordinateConfiguration,
    GameEstimator,
    RandomEffectCoordinateConfiguration,
)
from photon_tpu.types import TaskType


def _l2(w):
    return GLMOptimizationConfiguration(
        regularization=optim.RegularizationContext(
            optim.RegularizationType.L2),
        regularization_weight=w,
    )


def _game(rng, task="linear", n=600, d=6, du=4, E=15):
    x = rng.normal(size=(n, d))
    x[:, -1] = 1.0
    xu = rng.normal(size=(n, du))
    xu[:, -1] = 1.0
    users = rng.integers(0, E, size=n)
    w = rng.normal(size=d) * 0.5
    wu = rng.normal(size=(E, du)) * 0.4
    z = x @ w + np.einsum("nd,nd->n", xu, wu[users])
    if task == "logistic":
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(np.float64)
    elif task == "poisson":
        y = rng.poisson(np.exp(np.clip(0.3 * z, None, 3.0))).astype(
            np.float64)
    else:
        y = z + 0.1 * rng.normal(size=n)
    return make_game_dataset(
        y,
        {"global": DenseFeatures(jnp.asarray(x)),
         "userShard": DenseFeatures(jnp.asarray(xu))},
        id_tags={"userId": users},
        dtype=jnp.float64,
    )


def _estimator(task, *, mesh, num_iterations=3):
    tt = {
        "logistic": TaskType.LOGISTIC_REGRESSION,
        "poisson": TaskType.POISSON_REGRESSION,
    }.get(task, TaskType.LINEAR_REGRESSION)
    return GameEstimator(
        tt,
        {
            "global": FixedEffectCoordinateConfiguration("global", _l2(0.01)),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "userShard"),
                _l2(0.5),
            ),
        },
        intercept_indices={"global": 5, "userShard": 3},
        num_iterations=num_iterations,
        mesh=mesh,
    )


def _coef_maps(result):
    out = {}
    for cid, m in result.model.items():
        c = (m.coefficients if hasattr(m, "coefficients")
             else m.model.coefficients.means)
        out[cid] = np.asarray(c)
    return out


@pytest.mark.parametrize("task", ["linear", "logistic", "poisson"])
class TestFusedUnfusedParity:
    def test_models_match(self, rng, task):
        game = _game(rng, task)
        est_fused = _estimator(task, mesh=None)
        est_unfused = _estimator(task, mesh=None)
        # Force the unfused path by attaching a no-op listener.
        from photon_tpu.events import EventEmitter

        est_unfused.emitter = EventEmitter([lambda e: None])
        r_fused = est_fused.fit(game)[0]
        r_unfused = est_unfused.fit(game)[0]
        assert est_fused._fused_cache is not None, "fused path did not run"
        # Both read the fixed effect's features through the feature-major
        # view (data.dataset.feature_major), as their fit stages say.
        from photon_tpu import obs

        (fused,) = est_fused._fused_cache.values()
        unfused_stage = [
            r for r in obs.TRACER.completed() if r.name == "fit"][-1]
        for attrs in (fused._fit_attrs_cache, unfused_stage.attrs):
            assert attrs["fe_layout"] == {"global": "feature_major"}
        f, u = _coef_maps(r_fused), _coef_maps(r_unfused)
        assert f.keys() == u.keys()
        for cid in f:
            np.testing.assert_allclose(
                f[cid], u[cid], rtol=1e-8, atol=1e-10, err_msg=cid)

    def test_history_diagnostics_match_shape(self, rng, task):
        game = _game(rng, task)
        est = _estimator(task, mesh=None)
        r = est.fit(game)[0]
        # 3 iterations x 2 coordinates
        assert len(r.descent.history) == 6
        from photon_tpu.algorithm.random_effect import (
            RandomEffectTrainingStats,
        )

        re_recs = [rec for rec in r.descent.history
                   if rec.coordinate_id == "per-user"]
        for rec in re_recs:
            assert isinstance(rec.diagnostics, RandomEffectTrainingStats)
            assert rec.diagnostics.num_entities > 0
        fe_recs = [rec for rec in r.descent.history
                   if rec.coordinate_id == "global"]
        for rec in fe_recs:
            assert rec.diagnostics.iterations >= 1


class TestFusedWarmStartAndGrid:
    def test_config_sequence_reuses_program_and_matches_unfused(self, rng):
        game = _game(rng, "linear")
        seq = [
            {"global": _l2(0.1), "per-user": _l2(1.0)},
            {"global": _l2(0.01), "per-user": _l2(0.2)},
        ]
        est_fused = _estimator("linear", mesh=None)
        rs_fused = est_fused.fit(game, opt_config_sequence=seq)
        from photon_tpu.events import EventEmitter

        est_unfused = _estimator("linear", mesh=None)
        est_unfused.emitter = EventEmitter([lambda e: None])
        rs_unfused = est_unfused.fit(game, opt_config_sequence=seq)
        assert len(rs_fused) == 2
        for rf, ru in zip(rs_fused, rs_unfused):
            f, u = _coef_maps(rf), _coef_maps(ru)
            for cid in f:
                np.testing.assert_allclose(
                    f[cid], u[cid], rtol=1e-8, atol=1e-10, err_msg=cid)

    def test_warm_start_initial_model(self, rng):
        """Warm-starting from a converged model must stay at (near) that
        optimum — solver tolerance, not bitwise identity: the fixed-effect
        L-BFGS stops within its gradient tolerance from any start."""
        game = _game(rng, "linear")
        est = _estimator("linear", mesh=None)
        first = est.fit(game)[0]
        warm = est.fit(game, initial_model=first.model)[0]
        f, w = _coef_maps(first), _coef_maps(warm)
        for cid in f:
            np.testing.assert_allclose(
                f[cid], w[cid], rtol=5e-2, atol=1e-3, err_msg=cid)


class TestFusedPassiveRows:
    def test_capped_reservoir_matches_unfused(self, rng):
        """A binding active_data_upper_bound creates passive rows, which
        route the fused scorer through the projector table (review
        regression: the packed layout's trailing score map was read as
        the projector)."""
        game = _game(rng, "linear", n=900, E=12)
        cfg = GLMOptimizationConfiguration(
            regularization=optim.RegularizationContext(
                optim.RegularizationType.L2),
            regularization_weight=0.5,
        )
        from photon_tpu.data.random_effect import (
            RandomEffectDataConfiguration,
        )

        def est_of():
            return GameEstimator(
                TaskType.LINEAR_REGRESSION,
                {
                    "global": FixedEffectCoordinateConfiguration(
                        "global", _l2(0.01)),
                    "per-user": RandomEffectCoordinateConfiguration(
                        RandomEffectDataConfiguration(
                            "userId", "userShard",
                            active_data_upper_bound=20,  # binds: ~75/entity
                        ),
                        cfg,
                    ),
                },
                intercept_indices={"global": 5, "userShard": 3},
                num_iterations=2,
                mesh=None,
            )

        est_f = est_of()
        r_f = est_f.fit(game)[0]
        assert est_f._fused_cache is not None, "fused path did not run"
        ds = est_f._fit_cache[1][0]["per-user"]
        _, passive = ds.covered_row_partition()
        assert passive.size > 0, "cap must create passive rows"
        est_u = est_of()
        from photon_tpu.events import EventEmitter

        est_u.emitter = EventEmitter([lambda e: None])
        r_u = est_u.fit(game)[0]
        for cid in ("global", "per-user"):
            f, u = _coef_maps(r_f), _coef_maps(r_u)
            np.testing.assert_allclose(
                f[cid], u[cid], rtol=1e-8, atol=1e-10, err_msg=cid)


class TestFusedLockedCoordinates:
    def test_partial_retrain_matches_unfused(self, rng):
        """Locked (partial-retrain) coordinates ride the fused path:
        score-only, model passed through from initial_models — parity with
        the unfused loop (review regression: the fused path used to crash
        on locked adapters)."""
        game = _game(rng, "linear")
        base = _estimator("linear", mesh=None).fit(game)[0].model

        def locked_est():
            est = _estimator("linear", mesh=None)
            est.locked_coordinates = {"global"}
            return est

        est_f = locked_est()
        r_f = est_f.fit(game, initial_model=base)[0]
        assert est_f._fused_cache is not None, "fused path did not run"
        est_u = locked_est()
        from photon_tpu.events import EventEmitter

        est_u.emitter = EventEmitter([lambda e: None])
        r_u = est_u.fit(game, initial_model=base)[0]
        f, u = _coef_maps(r_f), _coef_maps(r_u)
        assert f.keys() == u.keys()
        for cid in f:
            np.testing.assert_allclose(
                f[cid], u[cid], rtol=1e-8, atol=1e-10, err_msg=cid)
        # The locked model passes through untouched.
        np.testing.assert_array_equal(
            f["global"], np.asarray(base["global"].model.coefficients.means))


class TestFusedFallbacks:
    def test_mesh_estimator_stays_unfused(self, rng, devices):
        game = _game(rng, "linear")
        est = _estimator("linear", mesh="auto")
        r = est.fit(game)[0]
        assert getattr(est, "_fused_cache", None) is None
        assert r.model is not None

    def test_downsampling_stays_unfused(self, rng):
        game = _game(rng, "logistic")
        cfg = dataclasses.replace(_l2(0.01), down_sampling_rate=0.5)
        est = GameEstimator(
            TaskType.LOGISTIC_REGRESSION,
            {
                "global": FixedEffectCoordinateConfiguration("global", cfg),
                "per-user": RandomEffectCoordinateConfiguration(
                    RandomEffectDataConfiguration("userId", "userShard"),
                    _l2(0.5),
                ),
            },
            intercept_indices={"global": 5, "userShard": 3},
            num_iterations=2,
            mesh=None,
        )
        r = est.fit(game)[0]
        assert getattr(est, "_fused_cache", None) is None
        assert r.model is not None

    def test_validation_stays_unfused(self, rng):
        game = _game(rng, "linear")
        est = _estimator("linear", mesh=None)
        est.evaluators = ["RMSE"]
        r = est.fit(game, validation=game)[0]
        assert getattr(est, "_fused_cache", None) is None
        assert r.evaluation is not None

    def test_fuse_eligible_rejects_materialized_dataset(self, rng):
        from photon_tpu.algorithm.random_effect import (
            RandomEffectCoordinate,
        )
        from photon_tpu.data.random_effect import (
            build_random_effect_dataset,
        )

        game = _game(rng, "linear")
        ds = build_random_effect_dataset(
            game, RandomEffectDataConfiguration("userId", "userShard"),
            intercept_index=3, lazy=False,
        )
        coord = RandomEffectCoordinate(
            ds, TaskType.LINEAR_REGRESSION, _l2(0.5))
        assert not fuse_eligible({"per-user": coord})


class TestFusedHistoryAndCache:
    def test_fused_history_seconds_is_none(self, rng):
        """Per-update seconds on the fused path are None (one device
        program: no per-coordinate time exists), never a synthetic
        uniform split. The unfused path keeps measured dispatch floats
        (tests/test_events.py)."""
        game = _game(rng, "linear")
        est = _estimator("linear", mesh=None)
        r = est.fit(game)[0]
        assert est._fused_cache, "fused path did not run"
        assert len(r.descent.history) > 0
        assert all(rec.seconds is None for rec in r.descent.history)

    def test_alternating_static_keys_reuse_cached_programs(
        self, rng, monkeypatch
    ):
        """A config grid alternating static keys (L2 <-> L1 routing) must
        build each fused program ONCE and round-robin among cached
        entries — not rebuild per grid entry (the single-slot cache
        regression). Serial ingest keeps the count pure: the pipelined
        path's background AOT warm compile builds one additional
        (skeleton) FusedFit by design, which is not a cache rebuild."""
        import photon_tpu.algorithm.fused_fit as ff

        monkeypatch.setenv("PHOTON_TPU_SERIAL_INGEST", "1")

        builds = []
        real_fused_fit = ff.FusedFit

        class CountingFusedFit(real_fused_fit):
            def __init__(self, *args, **kwargs):
                builds.append(1)
                super().__init__(*args, **kwargs)

        monkeypatch.setattr(ff, "FusedFit", CountingFusedFit)
        game = _game(rng, "linear")
        est = _estimator("linear", mesh=None)
        l1 = GLMOptimizationConfiguration(
            regularization=optim.RegularizationContext(
                optim.RegularizationType.L1),
            regularization_weight=0.01,
        )
        seq = [{"global": _l2(0.01)}, {"global": l1}] * 2
        results = est.fit(game, opt_config_sequence=seq)
        assert len(results) == 4
        assert all(r.model is not None for r in results)
        assert len(builds) == 2, "each static key must compile exactly once"
        assert len(est._fused_cache) == 2
        # The dataset-scale materialized slabs are SHARED across cached
        # programs (one set per generation), not pinned once per entry.
        entries = list(est._fused_cache.values())
        assert all(f._mat_shared is est._fused_mat_share for f in entries)
        assert "ebs" in est._fused_mat_share
        assert all(f._mat_cache is None for f in entries)
