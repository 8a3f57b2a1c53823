"""A dense shard's slab holds the raw features exactly: the per-entity
selection of features into slots (``BlockPlan.materialize``) is a sum of
elementwise products with a 0/1 selector, not a matmul, so what a slab
holds does not depend on JAX's matmul precision. (As a ``dot_general`` it
held the features rounded to bf16 at the TPU's default precision, and at
``highest`` XLA built one slab of a heavy-tailed GLMix's twelve wrong;
PERF.md section 6, PR 32.)"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import DenseFeatures
from photon_tpu.data.game_data import make_game_dataset
from photon_tpu.data.random_effect import (
    BlockPlan,
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)

N, D, ENTITIES = 900, 7, 40


@pytest.fixture(scope="module")
def lazy_dataset():
    rng = np.random.default_rng(7)
    with jax.enable_x64(False):
        x = rng.normal(size=(N, D)).astype(np.float32)
        x[:, -1] = 1.0
        # Entity 3 never sees feature 2: its projector skips a slot.
        ids = rng.integers(0, ENTITIES, size=N)
        x[ids == 3, 2] = 0.0
        game = make_game_dataset(
            (rng.uniform(size=N) < 0.5).astype(np.float32),
            {"userShard": DenseFeatures(jnp.asarray(x))},
            id_tags={"userId": ids}, dtype=jnp.float32)
        ds = build_random_effect_dataset(
            game, RandomEffectDataConfiguration(
                "userId", "userShard", active_data_upper_bound=24),
            intercept_index=D - 1)
    return ds, x


def test_the_slab_is_the_raw_rows_bit_for_bit(lazy_dataset):
    ds, x = lazy_dataset
    assert ds.is_lazy and len(ds.blocks) >= 2
    with jax.enable_x64(False):
        blocks = ds.device_blocks()
    for plan, block in zip(ds.blocks, blocks):
        rows, counts = np.asarray(plan.row_ids), np.asarray(plan.row_counts)
        proj = np.asarray(plan.proj)
        valid = np.arange(rows.shape[1])[None, :] < counts[:, None]
        want = np.take_along_axis(
            x[rows], np.maximum(proj, 0)[:, None, :], axis=2)
        want = np.where((proj >= 0)[:, None, :] & valid[:, :, None], want, 0)
        got = np.asarray(block.x_values)
        assert got.dtype == np.float32 and block.x_indices is None
        np.testing.assert_array_equal(got, want)
    assert (np.asarray(ds.proj_all) < 0).any(), "no projector skipped a slot"


def test_the_selection_is_no_matmul(lazy_dataset):
    ds, _ = lazy_dataset
    with jax.enable_x64(False):
        plan = ds.device_plans()[0]
        assert isinstance(plan, BlockPlan)
        jaxpr = jax.make_jaxpr(lambda p: p.materialize(None))(plan)
    names = {eqn.primitive.name for eqn in jaxpr.jaxpr.eqns}
    assert "dot_general" not in names, sorted(names)
    assert "reduce_sum" in names
