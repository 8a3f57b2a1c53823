"""Mesh-sharded training parity: entity parallelism and full CD on 8 devices.

The reference validates "multi-node" logic with Spark local-mode tests
(photon-test-utils SparkTestUtils.scala:43-76); the TPU-native analog is the
8-device virtual CPU mesh from conftest. These tests shard the random-effect
entity axis (the reference's entity partitioning,
RandomEffectDatasetPartitioner.scala:44) and a full coordinate-descent run
over the mesh, and assert agreement with the unsharded program.
"""

import jax.numpy as jnp
import numpy as np

from photon_tpu import optim
from photon_tpu.algorithm.coordinate import FixedEffectCoordinate
from photon_tpu.algorithm.coordinate_descent import CoordinateDescent
from photon_tpu.algorithm.problems import (
    GLMOptimizationConfiguration,
    GLMOptimizationProblem,
)
from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
from photon_tpu.data.dataset import DenseFeatures, GLMBatch
from photon_tpu.data.game_data import make_game_dataset
from photon_tpu.data.random_effect import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_tpu.parallel.mesh import (
    make_mesh,
    shard_batch,
    shard_random_effect_dataset,
)
from photon_tpu.types import TaskType


def _glmix_data(rng, n=240, d=6, num_entities=11):
    """Synthetic GLMix data: global effect + per-entity effects."""
    x = rng.normal(size=(n, d)).astype(np.float64)
    x[:, -1] = 1.0
    entities = rng.integers(0, num_entities, size=n)
    w_fixed = rng.normal(size=d)
    w_re = 0.5 * rng.normal(size=(num_entities, d))
    z = x @ w_fixed + np.einsum("nd,nd->n", x, w_re[entities])
    y = z + 0.1 * rng.normal(size=n)
    game = make_game_dataset(
        y,
        {"shard": DenseFeatures(jnp.asarray(x))},
        id_tags={"userId": np.asarray([f"u{e}" for e in entities])},
        dtype=jnp.float64,
    )
    return game, x, y


def _l2_conf(lam=0.5):
    return GLMOptimizationConfiguration(
        regularization=optim.RegularizationContext(
            optim.RegularizationType.L2
        ),
        regularization_weight=lam,
    )


def _re_coordinate(game, sharded_mesh=None):
    cfg = RandomEffectDataConfiguration("userId", "shard")
    ds = build_random_effect_dataset(game, cfg, intercept_index=5)
    if sharded_mesh is not None:
        ds = shard_random_effect_dataset(ds, sharded_mesh)
    return RandomEffectCoordinate(
        ds, TaskType.LINEAR_REGRESSION, _l2_conf()
    )


def test_sharded_random_effect_matches_local(rng):
    """Entity-axis sharding must not change the per-entity solutions."""
    game, _, _ = _glmix_data(rng)
    mesh = make_mesh()
    local = _re_coordinate(game)
    sharded = _re_coordinate(game, sharded_mesh=mesh)

    m_local, st_local = local.train()
    m_shard, st_shard = sharded.train()

    np.testing.assert_allclose(
        np.asarray(m_shard.coefficients),
        np.asarray(m_local.coefficients),
        rtol=1e-8, atol=1e-10,
    )
    # Diagnostics must exclude the inert padding entities.
    assert st_shard.num_entities == st_local.num_entities
    # Scoring through the sharded table agrees as well.
    np.testing.assert_allclose(
        np.asarray(sharded.score(m_shard)),
        np.asarray(local.score(m_local)),
        rtol=1e-8, atol=1e-10,
    )


def test_sharded_random_effect_with_residuals(rng):
    """Residual routing (a gather across the sharded row axis) agrees."""
    game, _, _ = _glmix_data(rng, n=160, num_entities=7)
    mesh = make_mesh()
    residuals = jnp.asarray(rng.normal(size=160), dtype=jnp.float64)
    m_local, _ = _re_coordinate(game).train(residuals=residuals)
    m_shard, _ = _re_coordinate(game, sharded_mesh=mesh).train(
        residuals=residuals
    )
    np.testing.assert_allclose(
        np.asarray(m_shard.coefficients),
        np.asarray(m_local.coefficients),
        rtol=1e-8, atol=1e-10,
    )


def test_sharded_full_cd_matches_local(rng):
    """A full GAME coordinate-descent run — fixed effect (dp) + random
    effect (ep) chained by residual scores — agrees with the unsharded run
    when both coordinates live sharded on the 8-device mesh."""
    game, x, y = _glmix_data(rng)
    mesh = make_mesh()
    fe_batch = GLMBatch(
        features=DenseFeatures(jnp.asarray(x)),
        labels=game.labels,
        offsets=game.offsets,
        weights=game.weights,
    )
    problem = GLMOptimizationProblem(
        task=TaskType.LINEAR_REGRESSION,
        config=_l2_conf(),
        intercept_index=5,
    )

    def run(sharded: bool):
        batch = shard_batch(fe_batch, mesh) if sharded else fe_batch
        coords = {
            "fixed": FixedEffectCoordinate(batch, problem),
            "per-user": _re_coordinate(
                game, sharded_mesh=mesh if sharded else None
            ),
        }
        cd = CoordinateDescent(["fixed", "per-user"], num_iterations=2)
        return cd.run(coords)

    local = run(sharded=False)
    shard = run(sharded=True)

    np.testing.assert_allclose(
        np.asarray(shard.model["fixed"].coefficients.means),
        np.asarray(local.model["fixed"].coefficients.means),
        rtol=1e-7, atol=1e-9,
    )
    np.testing.assert_allclose(
        np.asarray(shard.model["per-user"].coefficients),
        np.asarray(local.model["per-user"].coefficients),
        rtol=1e-7, atol=1e-9,
    )


def test_fixed_effect_on_2d_mesh(rng, mesh):
    """Row sharding over the data axis of a 2D (4, 2) mesh: the model axis
    is replicated, psum crosses only the data axis."""
    game, x, y = _glmix_data(rng, n=240)
    fe_batch = GLMBatch(
        features=DenseFeatures(jnp.asarray(x)),
        labels=game.labels,
        offsets=game.offsets,
        weights=game.weights,
    )
    problem = GLMOptimizationProblem(
        task=TaskType.LINEAR_REGRESSION, config=_l2_conf(),
        intercept_index=5,
    )
    m_local, _ = FixedEffectCoordinate(fe_batch, problem).train()
    m_shard, _ = FixedEffectCoordinate(
        shard_batch(fe_batch, mesh), problem
    ).train()
    np.testing.assert_allclose(
        np.asarray(m_shard.coefficients.means),
        np.asarray(m_local.coefficients.means),
        rtol=1e-8, atol=1e-10,
    )


def test_dryrun_multichip_refuses_devices_that_are_not_there(monkeypatch):
    """``dryrun_multichip(n)`` with fewer than n visible devices used
    to re-execute on virtual CPU devices and return success — which is
    how multi-chip records said ok for runs no chip saw. It raises now;
    the virtual-CPU provisioning is the separately named
    ``dryrun_on_virtual_cpu`` (CI's sharding check), which it never
    falls back to."""
    import sys
    from pathlib import Path

    import jax
    import pytest

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    import __graft_entry__ as graft

    def no_fallback(n):
        raise AssertionError("fell back to virtual CPU devices")

    monkeypatch.setattr(graft, "dryrun_on_virtual_cpu", no_fallback)
    wanted = len(jax.devices()) + 1
    with pytest.raises(RuntimeError, match=f"needs {wanted} devices"):
        graft.dryrun_multichip(wanted)
