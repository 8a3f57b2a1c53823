"""Sparse-at-scale: bounded-width ELL, feature-axis sharding, d >= 1M fits.

SURVEY §7.3 "Sparse fixed-effect matvec at scale": the design must shard
d >> 10^6 feature spaces (feature-axis sharding + psum) and bound the ELL
global-width hazard (one dense row must not inflate every row's storage).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import optim
from photon_tpu.algorithm.problems import (
    GLMOptimizationConfiguration,
    GLMOptimizationProblem,
)
from photon_tpu.data.dataset import (
    DualEllFeatures,
    GLMBatch,
    SparseFeatures,
    ell_to_dual_ell,
    rows_to_ell,
)
from photon_tpu.parallel.mesh import (
    MODEL_AXIS,
    make_mesh,
    shard_features_by_column,
)
from photon_tpu.types import TaskType

L2 = optim.RegularizationContext(optim.RegularizationType.L2)


def _random_ell(rng, n, d, k_max, heavy_rows=0, heavy_k=None):
    """ELL slab with `heavy_rows` rows at heavy_k nnz (the width hazard)."""
    heavy_k = heavy_k or k_max
    rows = []
    for i in range(n):
        k = heavy_k if i < heavy_rows else rng.integers(1, k_max + 1)
        idx = rng.choice(d, size=k, replace=False)
        rows.append([(int(j), float(rng.normal())) for j in idx])
    width = max(len(r) for r in rows)
    return rows_to_ell(rows, d, capacity=width, dtype=np.float64)


class TestDualEll:
    def test_matvecs_match_plain_ell(self, rng):
        n, d = 60, 40
        idx, val = _random_ell(rng, n, d, k_max=5, heavy_rows=3, heavy_k=25)
        plain = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)
        dual = ell_to_dual_ell(idx, val, d, width_cap=5, dtype=np.float64)
        # Storage actually bounded: slab width 5, the rest in the tail.
        assert dual.values.shape[1] == 5
        assert dual.tail_values.shape[0] > 0

        w = jnp.asarray(rng.normal(size=d))
        g = jnp.asarray(rng.normal(size=n))
        np.testing.assert_allclose(
            np.asarray(dual.matvec(w)), np.asarray(plain.matvec(w)),
            rtol=1e-12)
        np.testing.assert_allclose(
            np.asarray(dual.rmatvec(g)), np.asarray(plain.rmatvec(g)),
            rtol=1e-12)
        np.testing.assert_allclose(
            np.asarray(dual.rmatvec_sq(g)), np.asarray(plain.rmatvec_sq(g)),
            rtol=1e-12)

    def test_fit_through_dual_ell(self, rng):
        """A GLM trains against DualEllFeatures exactly as against ELL."""
        n, d = 300, 20
        idx, val = _random_ell(rng, n, d, k_max=4, heavy_rows=2, heavy_k=15)
        w_true = rng.normal(size=d)
        plain = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)
        y = np.asarray(plain.matvec(jnp.asarray(w_true)))
        y = y + 0.01 * rng.normal(size=n)
        cfg = GLMOptimizationConfiguration(
            regularization=L2, regularization_weight=1e-3)
        prob = GLMOptimizationProblem(TaskType.LINEAR_REGRESSION, cfg)

        def fit(feats):
            batch = GLMBatch(
                feats,
                jnp.asarray(y), jnp.zeros(n), jnp.ones(n),
            )
            return np.asarray(prob.run(batch).model.coefficients.means)

        w_plain = fit(plain)
        w_dual = fit(ell_to_dual_ell(idx, val, d, 4, dtype=np.float64))
        np.testing.assert_allclose(w_dual, w_plain, rtol=1e-6, atol=1e-8)


class TestScoreTableWidthCap:
    def test_capped_table_scores_identically(self, rng):
        from photon_tpu.data.dataset import DenseFeatures
        from photon_tpu.data.game_data import make_game_dataset
        from photon_tpu.data.random_effect import (
            RandomEffectDataConfiguration,
            build_random_effect_dataset,
        )
        from photon_tpu.models.game import RandomEffectModel

        n, d, E = 120, 10, 6
        x = rng.normal(size=(n, d))
        game = make_game_dataset(
            rng.normal(size=n),
            {"shard": DenseFeatures(jnp.asarray(x))},
            id_tags={"userId": rng.integers(0, E, size=n)},
            dtype=jnp.float64,
        )
        full = build_random_effect_dataset(
            game, RandomEffectDataConfiguration("userId", "shard"),
            lazy=False)
        capped = build_random_effect_dataset(
            game, RandomEffectDataConfiguration(
                "userId", "shard", score_table_width_cap=3),
            lazy=False)
        assert capped.score_values.shape[1] == 3
        assert capped.score_tail_rows is not None
        assert capped.score_tail_rows.shape[0] > 0

        w = rng.normal(size=(full.num_entities, full.max_sub_dim))
        w[full.proj_all < 0] = 0.0

        def model(ds):
            return RandomEffectModel(
                coefficients=jnp.asarray(w[:, : ds.max_sub_dim]),
                random_effect_type="userId",
                feature_shard_id="shard",
                task=TaskType.LINEAR_REGRESSION,
                proj_all=ds.proj_all,
                entity_keys=ds.entity_keys,
            )

        s_full = np.asarray(model(full).score_dataset(full))
        s_capped = np.asarray(model(capped).score_dataset(capped))
        np.testing.assert_allclose(s_capped, s_full, rtol=1e-10)

        # The lazy fused path must agree with the materialized table too.
        lazy = build_random_effect_dataset(
            game, RandomEffectDataConfiguration("userId", "shard"))
        assert lazy.is_lazy
        s_lazy = np.asarray(model(lazy).score_dataset(lazy))
        np.testing.assert_allclose(s_lazy, s_full, rtol=1e-10)


class TestFeatureAxisSharding:
    def test_sharded_matvecs_match_local(self, rng, devices):
        n, d = 64, 97  # deliberately not divisible by 8
        idx, val = _random_ell(rng, n, d, k_max=6)
        mesh = make_mesh(devices, axis_name=MODEL_AXIS)
        sharded = shard_features_by_column(idx, val, d, mesh)
        assert sharded.d % 8 == 0 and sharded.logical_d == d
        plain = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)

        w = rng.normal(size=sharded.d)
        w[d:] = 0.0
        g = jnp.asarray(rng.normal(size=n))
        np.testing.assert_allclose(
            np.asarray(sharded.matvec(jnp.asarray(w))),
            np.asarray(plain.matvec(jnp.asarray(w[:d]))),
            rtol=1e-10)
        np.testing.assert_allclose(
            np.asarray(sharded.rmatvec(g))[:d],
            np.asarray(plain.rmatvec(g)),
            rtol=1e-10)
        np.testing.assert_allclose(
            np.asarray(sharded.rmatvec_sq(g))[:d],
            np.asarray(plain.rmatvec_sq(g)),
            rtol=1e-10)
        # Padded feature range receives nothing.
        assert np.all(np.asarray(sharded.rmatvec(g))[d:] == 0.0)

    def test_million_feature_fit_over_mesh(self, rng, devices):
        """The SURVEY §7.3 bar: a fixed-effect fit at d >= 1M sparse
        features, coefficients sharded over the mesh, matching the
        replicated solve."""
        n, d, k = 2048, 1_048_576, 8
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k))
        w_true = np.zeros(d)
        hot = rng.choice(d, size=200, replace=False)
        w_true[hot] = rng.normal(size=200)
        plain = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)
        y = np.asarray(plain.matvec(jnp.asarray(w_true)))
        y = y + 0.01 * rng.normal(size=n)

        mesh = make_mesh(devices, axis_name=MODEL_AXIS)
        sharded = shard_features_by_column(
            idx, val, d, mesh, dtype=np.float64)
        cfg = GLMOptimizationConfiguration(
            optimizer=optim.OptimizerConfig.lbfgs(max_iterations=30),
            regularization=L2, regularization_weight=1e-2)
        prob = GLMOptimizationProblem(TaskType.LINEAR_REGRESSION, cfg)

        def fit(feats):
            batch = GLMBatch(
                feats, jnp.asarray(y), jnp.zeros(n), jnp.ones(n))
            return np.asarray(prob.run(batch).model.coefficients.means)

        w_sharded = fit(sharded)
        assert w_sharded.shape[0] == sharded.d
        w_plain = fit(plain)
        np.testing.assert_allclose(
            w_sharded[:d], w_plain, rtol=1e-5, atol=1e-7)


class TestDualEllConsumers:
    def test_feature_stats_include_tail(self, rng):
        from photon_tpu.stat import FeatureDataStatistics

        n, d = 40, 15
        idx, val = _random_ell(rng, n, d, k_max=4, heavy_rows=2, heavy_k=10)
        plain = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)
        dual = ell_to_dual_ell(idx, val, d, width_cap=4, dtype=np.float64)
        w = rng.uniform(0.5, 2.0, size=n)
        s_plain = FeatureDataStatistics.from_features(plain, w)
        s_dual = FeatureDataStatistics.from_features(dual, w)
        for field in ("mean", "variance", "min", "max", "num_nonzeros"):
            np.testing.assert_allclose(
                getattr(s_dual, field), getattr(s_plain, field), rtol=1e-10)

    def test_validators_see_tail_nan(self, rng):
        from photon_tpu.data.game_data import make_game_dataset
        from photon_tpu.data.validators import sanity_check_data

        n, d = 10, 8
        idx, val = _random_ell(rng, n, d, k_max=2, heavy_rows=1, heavy_k=6)
        val[0, 5] = np.nan  # lands in the tail after cap=2
        dual = ell_to_dual_ell(idx, val, d, width_cap=2, dtype=np.float64)
        assert not np.isfinite(np.asarray(dual.tail_values)).all()
        data = make_game_dataset(
            np.zeros(n), {"features": dual}, dtype=jnp.float64)
        with pytest.raises(ValueError, match="feature"):
            sanity_check_data(data, TaskType.LINEAR_REGRESSION, "FULL")

    def test_pad_batch_rejects_dual_ell(self, rng):
        from photon_tpu.data.dataset import pad_batch

        idx, val = _random_ell(rng, 6, 5, k_max=2)
        dual = ell_to_dual_ell(idx, val, 5, width_cap=1, dtype=np.float64)
        batch = GLMBatch(
            dual, jnp.zeros(6), jnp.zeros(6), jnp.ones(6))
        with pytest.raises(TypeError, match="DualEllFeatures"):
            pad_batch(batch, 8)

    def test_libsvm_with_vocab_dir_rejected(self, tmp_path, rng):
        from photon_tpu.cli.train import main
        import json

        p = tmp_path / "d.txt"
        p.write_text("\n".join(
            f"{rng.integers(0, 2) * 2 - 1} 1:{rng.normal():.4f}"
            for _ in range(20)))
        (tmp_path / "vocab").mkdir()
        (tmp_path / "vocab" / "features.index.json").write_text('{"a": 0}')
        cfg = {
            "task": "LOGISTIC_REGRESSION",
            "input": {"format": "libsvm", "train_path": str(p),
                      "feature_index_dir": str(tmp_path / "vocab")},
            "coordinates": {"global": {"type": "fixed"}},
            "output_dir": str(tmp_path / "out"),
        }
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps(cfg))
        with pytest.raises(ValueError, match="avro input only"):
            main(["--config", str(cfg_path)])


def test_validation_scorer_width_cap_parity(rng):
    """remap_for_scoring with a width cap scores identically to the
    uncapped table (tail contribution included), with unseen entities 0."""
    from photon_tpu.data.dataset import DenseFeatures
    from photon_tpu.data.game_data import make_game_dataset
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )
    from photon_tpu.models.game import RandomEffectModel
    from photon_tpu.transformers import random_effect_scorer

    n, d, E = 90, 8, 5
    x = rng.normal(size=(n, d))
    train_data = make_game_dataset(
        rng.normal(size=n),
        {"shard": DenseFeatures(jnp.asarray(x))},
        id_tags={"userId": rng.integers(0, E, size=n)},
        dtype=jnp.float64,
    )
    ds = build_random_effect_dataset(
        train_data, RandomEffectDataConfiguration("userId", "shard"))
    w = rng.normal(size=(ds.num_entities, ds.max_sub_dim))
    w[ds.proj_all < 0] = 0.0
    model = RandomEffectModel(
        coefficients=jnp.asarray(w),
        random_effect_type="userId",
        feature_shard_id="shard",
        task=TaskType.LINEAR_REGRESSION,
        proj_all=ds.proj_all,
        entity_keys=ds.entity_keys,
    )
    # Validation data includes entities unseen at training time.
    m = 60
    val = make_game_dataset(
        rng.normal(size=m),
        {"shard": DenseFeatures(jnp.asarray(rng.normal(size=(m, d))))},
        id_tags={"userId": rng.integers(0, E + 3, size=m)},
        dtype=jnp.float64,
    )
    kw = dict(re_type="userId", feature_shard_id="shard",
              entity_keys=ds.entity_keys, proj_all=ds.proj_all)
    s_full = np.asarray(random_effect_scorer(val, **kw)(model))
    s_capped = np.asarray(
        random_effect_scorer(val, width_cap=2, **kw)(model))
    np.testing.assert_allclose(s_capped, s_full, rtol=1e-10)


class TestDualEllRandomEffect:
    def test_dual_ell_shard_trains_and_scores_like_sparse(self, rng):
        """A random-effect coordinate over a DualEllFeatures shard (the
        materialized fallback path, incl. the host slab+tail view) must
        produce the same model and scores as the same data in plain ELL."""
        from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
        from photon_tpu.data.game_data import make_game_dataset
        from photon_tpu.data.random_effect import (
            RandomEffectDataConfiguration,
            build_random_effect_dataset,
        )

        n, d, E = 120, 30, 6
        idx, val = _random_ell(rng, n, d, k_max=4, heavy_rows=4, heavy_k=20)
        y = rng.normal(size=n)
        entities = rng.integers(0, E, size=n)
        dual = ell_to_dual_ell(idx, val, d, width_cap=4, dtype=np.float64)
        assert dual.tail_values.shape[0] > 0
        game_dual = make_game_dataset(
            y, {"shard": dual},
            id_tags={"userId": entities}, dtype=jnp.float64,
        )
        game_sparse = make_game_dataset(
            y, {"shard": SparseFeatures(idx, val, d)},
            id_tags={"userId": entities}, dtype=jnp.float64,
        )
        cfg = RandomEffectDataConfiguration(
            "userId", "shard", score_table_width_cap=4
        )
        ds_dual = build_random_effect_dataset(game_dual, cfg)
        assert not ds_dual.is_lazy  # DualEll -> materialized fallback
        ds_sparse = build_random_effect_dataset(game_sparse, cfg, lazy=False)
        # Identical projectors from slab + tail union.
        np.testing.assert_array_equal(ds_dual.proj_all, ds_sparse.proj_all)

        conf = GLMOptimizationConfiguration(
            regularization=L2, regularization_weight=0.5
        )
        m_dual, _ = RandomEffectCoordinate(
            ds_dual, TaskType.LINEAR_REGRESSION, conf
        ).train()
        m_sparse, _ = RandomEffectCoordinate(
            ds_sparse, TaskType.LINEAR_REGRESSION, conf
        ).train()
        np.testing.assert_allclose(
            np.asarray(m_dual.coefficients),
            np.asarray(m_sparse.coefficients),
            rtol=1e-8, atol=1e-10,
        )
        np.testing.assert_allclose(
            np.asarray(m_dual.score_dataset(ds_dual)),
            np.asarray(m_sparse.score_dataset(ds_sparse)),
            rtol=1e-8, atol=1e-10,
        )
        # Host slab view stays width-bounded (no re-widening to max row).
        si, sv, dd = game_dual.host_shard_coo("shard")
        assert si.shape[1] == 4
