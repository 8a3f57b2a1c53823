"""Product-surface multi-device execution: GameEstimator + CLI on the mesh.

Round-2 gap: dp/ep sharding existed only in parallel/mesh.py and the tests —
the estimator and CLIs were single-device. These tests pin the integration:
``GameEstimator(mesh=...)`` shards its datasets (the distributed-by-default
semantics of GameTrainingDriver.run, photon-client
cli/game/training/GameTrainingDriver.scala:363-516, which executes on the
cluster session from SparkSessionConfiguration.scala:109) and the sharded
product path agrees with the single-device one to float tolerance.

Row counts here are deliberately NOT multiples of the 8-device mesh so the
padding + logical-row plumbing is exercised, not just the divisible case.
"""

import json

import jax
import numpy as np
import pytest

from photon_tpu import optim
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


def _glmix_game(rng, n=237, d=6, num_entities=11):
    """n=237 is coprime with the 8-device mesh: padding rows required."""
    import jax.numpy as jnp

    x = rng.normal(size=(n, d)).astype(np.float64)
    x[:, -1] = 1.0
    entities = rng.integers(0, num_entities, size=n)
    w_fixed = rng.normal(size=d)
    w_re = 0.5 * rng.normal(size=(num_entities, d))
    z = x @ w_fixed + np.einsum("nd,nd->n", x, w_re[entities])
    y = z + 0.1 * rng.normal(size=n)
    return make_game_dataset(
        y,
        {"features": DenseFeatures(jnp.asarray(x))},
        id_tags={"userId": np.asarray([f"u{e}" for e in entities])},
        dtype=jnp.float64,
    )


def _estimator(mesh):
    l2 = GLMOptimizationConfiguration(
        regularization=optim.RegularizationContext(
            optim.RegularizationType.L2
        ),
        regularization_weight=0.5,
    )
    return GameEstimator(
        TaskType.LINEAR_REGRESSION,
        {
            "global": FixedEffectCoordinateConfiguration("features", l2),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "features"), l2
            ),
        },
        num_iterations=2,
        intercept_indices={"features": 5},
        mesh=mesh,
    )


class TestEstimatorMesh:
    def test_fit_parity_sharded_vs_single_device(self, rng):
        game = _glmix_game(rng)
        val = _glmix_game(rng, n=101)

        res_local = _estimator("off").fit(game, val)[0]
        res_shard = _estimator("auto").fit(game, val)[0]

        np.testing.assert_allclose(
            np.asarray(res_shard.model["global"].model.coefficients.means),
            np.asarray(res_local.model["global"].model.coefficients.means),
            rtol=1e-7, atol=1e-9,
        )
        np.testing.assert_allclose(
            np.asarray(res_shard.model["per-user"].coefficients),
            np.asarray(res_local.model["per-user"].coefficients),
            rtol=1e-7, atol=1e-9,
        )
        assert res_shard.evaluation is not None
        np.testing.assert_allclose(
            res_shard.evaluation.primary_evaluation,
            res_local.evaluation.primary_evaluation,
            rtol=1e-7,
        )

    def test_datasets_actually_sharded(self, rng):
        """The estimator's prepared datasets must live sharded on the mesh —
        not merely produce the right numbers from one device."""
        game = _glmix_game(rng, n=240)
        est = _estimator("auto")
        datasets, _ = est.prepare(game)
        n_dev = len(jax.devices())
        assert n_dev == 8, "conftest must provide the 8-device CPU mesh"

        fe = datasets["global"]
        # Padded to a device multiple and placed row-sharded.
        assert fe.labels.shape[0] % n_dev == 0
        assert len(fe.labels.sharding.device_set) == n_dev

        re = datasets["per-user"]
        for block in re.blocks:
            assert block.entity_codes.shape[0] % n_dev == 0
            assert len(block.x_values.sharding.device_set) == n_dev

    def test_mesh_off_is_single_device(self, rng):
        game = _glmix_game(rng, n=64)
        est = _estimator("off")
        datasets, _ = est.prepare(game)
        assert datasets["global"].labels.shape[0] == 64
        assert len(datasets["global"].labels.sharding.device_set) == 1

    def test_device_count_setting(self, rng):
        game = _glmix_game(rng, n=64)
        est = _estimator(2)
        datasets, _ = est.prepare(game)
        assert len(datasets["global"].labels.sharding.device_set) == 2


class TestColumnFeatureSharding:
    """tp from the product surface: a fixed-effect coordinate routed through
    FeatureShardedSparse by ``feature_sharding: column`` — the reference's
    "hundreds of billions of coefficients" axis (README.md:56) must be
    reachable from GameEstimator/`photon train`, not only from hand-rolled
    dryrun code."""

    def _wide_game(self, rng, n=203, d=77, k=4, num_entities=9):
        import jax.numpy as jnp

        from photon_tpu.data.dataset import SparseFeatures

        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float64)
        w = rng.normal(size=d)
        entities = rng.integers(0, num_entities, size=n)
        z = (val * w[idx]).sum(axis=1)
        y = z + 0.1 * rng.normal(size=n)
        return make_game_dataset(
            y,
            {"wide": SparseFeatures(idx, val, d)},
            id_tags={"userId": np.asarray([f"u{e}" for e in entities])},
            dtype=jnp.float64,
        )

    def _estimator(self, mesh, sharding, with_re=False, variance="NONE"):
        from photon_tpu.algorithm.problems import VarianceComputationType

        l2 = GLMOptimizationConfiguration(
            regularization=optim.RegularizationContext(
                optim.RegularizationType.L2
            ),
            regularization_weight=0.5,
            variance_computation=VarianceComputationType(variance),
        )
        coords = {
            "global": FixedEffectCoordinateConfiguration(
                "wide", l2, feature_sharding=sharding
            ),
        }
        if with_re:
            coords["per-user"] = RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "wide"), l2
            )
        return GameEstimator(
            TaskType.LINEAR_REGRESSION,
            coords,
            num_iterations=2 if with_re else 1,
            mesh=mesh,
        )

    def test_column_sharded_parity(self, rng):
        """Sharded-vs-unsharded coefficient parity for the wide solve —
        the tp analog of test_fit_parity_sharded_vs_single_device."""
        game = self._wide_game(rng)
        val = self._wide_game(rng, n=101)

        res_local = self._estimator(
            "off", "replicated", variance="SIMPLE").fit(game, val)[0]
        res_tp = self._estimator(
            "auto", "column", variance="SIMPLE").fit(game, val)[0]

        local = res_local.model["global"].model.coefficients
        tp = res_tp.model["global"].model.coefficients
        # Externally visible coefficients stay at the logical d (the padded
        # device-multiple space is an internal solve detail).
        assert tp.means.shape == local.means.shape
        np.testing.assert_allclose(
            np.asarray(tp.means), np.asarray(local.means),
            rtol=1e-7, atol=1e-9,
        )
        np.testing.assert_allclose(
            np.asarray(tp.variances), np.asarray(local.variances),
            rtol=1e-7, atol=1e-9,
        )
        np.testing.assert_allclose(
            res_tp.evaluation.primary_evaluation,
            res_local.evaluation.primary_evaluation,
            rtol=1e-7,
        )

    def test_column_sharded_with_random_effect(self, rng):
        """tp fixed effect + ep random effect chained by residual routing."""
        game = self._wide_game(rng)
        res_local = self._estimator("off", "replicated", with_re=True).fit(
            game)[0]
        res_tp = self._estimator("auto", "column", with_re=True).fit(game)[0]
        np.testing.assert_allclose(
            np.asarray(res_tp.model["global"].model.coefficients.means),
            np.asarray(res_local.model["global"].model.coefficients.means),
            rtol=1e-7, atol=1e-9,
        )
        np.testing.assert_allclose(
            np.asarray(res_tp.model["per-user"].coefficients),
            np.asarray(res_local.model["per-user"].coefficients),
            rtol=1e-7, atol=1e-9,
        )

    def test_features_actually_column_sharded(self, rng):
        game = self._wide_game(rng)
        est = self._estimator("auto", "column")
        datasets, _ = est.prepare(game)
        batch = datasets["global"]
        n_dev = len(jax.devices())
        from photon_tpu.parallel.mesh import FeatureShardedSparse

        assert isinstance(batch.features, FeatureShardedSparse)
        assert batch.features.d % n_dev == 0
        assert batch.features.logical_d == 77
        assert len(batch.features.local_values.sharding.device_set) == n_dev

    def test_auto_threshold(self, rng):
        """feature_sharding: auto goes column-wise only above the PalDB-style
        feature-count threshold (FeatureIndexingDriver.scala:40-41)."""
        from photon_tpu.parallel.mesh import FeatureShardedSparse

        game = self._wide_game(rng)  # d=77: far below the threshold
        est = self._estimator("auto", "auto")
        datasets, _ = est.prepare(game)
        assert not isinstance(
            datasets["global"].features, FeatureShardedSparse)

    def test_column_warm_start_across_configs(self, rng):
        """Lambda-ladder warm starts pad the trimmed model back into the
        sharded solve space."""
        game = self._wide_game(rng)
        est = self._estimator("auto", "column")
        results = est.fit(
            game,
            opt_config_sequence=[
                {"global": est.coordinate_configs["global"]
                    .optimization.with_regularization_weight(w)}
                for w in (10.0, 0.5)
            ],
        )
        assert len(results) == 2
        assert results[1].model["global"].model.coefficients.means.shape == (
            77,)

    def test_column_incremental_training(self, rng):
        """The Gaussian prior from a trimmed (logical-d) model must pad into
        the column-sharded solve space, parity with the replicated path."""
        game = self._wide_game(rng)

        def run(mesh, sharding):
            base = self._estimator(mesh, sharding, variance="SIMPLE")
            prior_model = base.fit(game)[0].model
            import dataclasses as dc

            inc = self._estimator(mesh, sharding, variance="SIMPLE")
            inc.coordinate_configs = {
                cid: dc.replace(
                    c, optimization=dc.replace(
                        c.optimization, regularization_weight=0.1)
                )
                for cid, c in inc.coordinate_configs.items()
            }
            inc.incremental_training = True
            return inc.fit(game, initial_model=prior_model)[0]

        res_local = run("off", "replicated")
        res_tp = run("auto", "column")
        np.testing.assert_allclose(
            np.asarray(res_tp.model["global"].model.coefficients.means),
            np.asarray(res_local.model["global"].model.coefficients.means),
            rtol=1e-7, atol=1e-9,
        )

    def test_cli_config_key(self, tmp_path):
        from photon_tpu.cli.config import parse_coordinate

        spec = parse_coordinate(
            "global", {"type": "fixed", "feature_shard": "wide",
                       "feature_sharding": "column"})
        assert spec.config.feature_sharding == "column"
        with pytest.raises(ValueError, match="feature_sharding"):
            parse_coordinate(
                "global", {"type": "fixed", "feature_sharding": "rows"})


class TestCLIMesh:
    @pytest.fixture
    def avro_data(self, tmp_path, rng):
        from photon_tpu.io.avro_data import write_training_examples

        n, d = 203, 5
        x = rng.normal(size=(n, d))
        entities = rng.integers(0, 7, size=n)
        w = rng.normal(size=d)
        w_re = 0.5 * rng.normal(size=(7, d))
        y = x @ w + np.einsum("nd,nd->n", x, w_re[entities])
        y = y + 0.1 * rng.normal(size=n)
        rows = [
            [(f"f{j}", float(x[i, j])) for j in range(d)] for i in range(n)
        ]
        path = tmp_path / "train.avro"
        write_training_examples(
            str(path), y, rows,
            metadata=[{"userId": f"u{e}"} for e in entities],
            uids=[str(i) for i in range(n)],
        )
        return path

    def _cfg(self, tmp_path, train, mesh, out):
        cfg = {
            "task": "LINEAR_REGRESSION",
            "input": {
                "format": "avro",
                "train_path": str(train),
                "id_tags": ["userId"],
            },
            "coordinates": {
                "global": {
                    "type": "fixed",
                    "regularization": {"type": "L2", "weights": [0.1]},
                },
                "per-user": {
                    "type": "random",
                    "random_effect_type": "userId",
                    "regularization": {"type": "L2", "weights": [1.0]},
                },
            },
            "num_iterations": 2,
            "mesh": mesh,
            "output_dir": str(tmp_path / out),
        }
        p = tmp_path / f"cfg_{out}.json"
        p.write_text(json.dumps(cfg))
        return p

    def test_train_cli_mesh_parity(self, tmp_path, avro_data):
        """`photon train` on the 8-device mesh (the default) produces the
        same model as mesh: off — coefficient parity through the whole
        driver path (GameTrainingDriver.scala:363-516 analog)."""
        from photon_tpu.cli.train import main
        from photon_tpu.io.model_io import load_checkpoint

        for mesh, out in (("auto", "out_mesh"), ("off", "out_local")):
            cfg = self._cfg(tmp_path, avro_data, mesh, out)
            assert main(["--config", str(cfg)]) == 0

        ck_mesh = load_checkpoint(
            str(tmp_path / "out_mesh" / "models" / "best" / "checkpoint.npz"))
        ck_local = load_checkpoint(
            str(tmp_path / "out_local" / "models" / "best" / "checkpoint.npz"))
        def coefs(m):
            if hasattr(m, "model"):  # FixedEffectModel wraps a GLM
                return np.asarray(m.model.coefficients.means)
            return np.asarray(m.coefficients)

        # The CLI path trains in float32: sharded reductions reorder sums,
        # so parity is to f32 accumulation noise, not bitwise.
        for cid in ("global", "per-user"):
            np.testing.assert_allclose(
                coefs(ck_mesh[cid]), coefs(ck_local[cid]),
                rtol=1e-4, atol=2e-5,
            )
