"""A lazy random effect scored on a mesh (PR 37): one row-sharded gather
through the inverse score map, rebased to the buckets as
``shard_random_effect_dataset`` pads them, and not an add a bucket into
an ``[n]`` vector. The same floats as one device's gather and as the
scatter it replaces; the map covers every row once; the passive rows and
the map shard by rows.

Four of the suite's eight forced host devices stand in for the chips.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import DenseFeatures
from photon_tpu.data.game_data import make_game_dataset
from photon_tpu.data.random_effect import (
    PendingRandomEffectDataset,
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_tpu.models.game import (
    _gather_score,
    _score_via_buckets,
    score_programs,
    score_route,
    score_rows,
)
from photon_tpu.parallel.mesh import (
    loop_rows,
    make_mesh,
    row_sharding,
    shard_random_effect_dataset,
)

N, D, ENTITIES, DEVICES = 1003, 4, 37, 4  # N is no multiple of four


def _game():
    """Rows per entity from a power law: several buckets, a cap that
    binds (passive rows), entities under the lower bound (inactive)."""
    rng = np.random.default_rng(37)
    shares = np.arange(1, ENTITIES + 1, dtype=np.float64) ** -1.1
    counts = 1 + rng.multinomial(N - ENTITIES, shares / shares.sum())
    owners = rng.permutation(np.repeat(np.arange(ENTITIES), counts))
    x = rng.normal(size=(N, D)).astype(np.float32)
    x[:, -1] = 1.0
    return make_game_dataset(
        rng.normal(size=N).astype(np.float32),
        {"shard": DenseFeatures(jnp.asarray(x))},
        id_tags={"userId": owners}, dtype=jnp.float32)


CONFIG = RandomEffectDataConfiguration(
    "userId", "shard", active_data_upper_bound=45, active_data_lower_bound=10)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(jax.devices()[:DEVICES])


@pytest.fixture(scope="module")
def one():
    """The data set on one device: its plan in the packed buffer."""
    return build_random_effect_dataset(_game(), CONFIG, intercept_index=D - 1)


@pytest.fixture(scope="module", params=["from_host", "from_device"])
def placed(request, mesh, one):
    """The data set on the mesh: placed from the host, as
    ``GameEstimator(mesh=...)`` does, or from the one device's packed
    buffer."""
    if request.param == "from_device":
        return shard_random_effect_dataset(one, mesh)
    pending = build_random_effect_dataset(
        _game(), CONFIG, intercept_index=D - 1, defer_transfer=True)
    assert isinstance(pending, PendingRandomEffectDataset)
    ds = pending.finalize(None)
    assert isinstance(ds.score_inv, np.ndarray)
    return shard_random_effect_dataset(ds, mesh)


@pytest.fixture(scope="module")
def w(one):
    rng = np.random.default_rng(5)
    return jnp.asarray(
        rng.normal(size=(one.num_entities, one.max_sub_dim)), jnp.float32)


def test_the_fixture_has_what_the_mesh_must_pad(one):
    counts = [b.row_ids.shape[0] for b in one.blocks]
    assert len(counts) >= 2 and any(c % DEVICES for c in counts), counts
    assert N % DEVICES
    _, passive = one.covered_row_partition()
    assert passive.size % DEVICES
    assert one.plan_counts["capped_entities"] > 0  # the cap binds
    assert one.num_active_entities < ENTITIES  # some under the lower bound


def test_the_mesh_gathers_what_one_device_gathers(placed, one, w):
    got = _score_via_buckets(w, placed)
    assert got.shape == (loop_rows(N, placed.score_inv.sharding.mesh),)
    assert got.sharding.is_equivalent_to(
        row_sharding(placed.score_inv.sharding.mesh, 1), 1)
    want = _gather_score(
        w, tuple(b.x_values for b in one.device_blocks()),
        tuple(p.entity_codes for p in one.device_plans()),
        one.score_inv_device(), one.passive_rows_device(), one.score_codes,
        one.raw, one.proj_device())
    np.testing.assert_array_equal(np.asarray(got)[:N], np.asarray(want))


def test_the_mesh_gathers_what_the_scatter_added(placed, w):
    """The scatter a bucket into an ``[n]`` vector that a mesh took before
    (a data set without the map still takes it): the same floats."""
    unmapped = dataclasses.replace(placed, score_inv=None)
    assert score_route(unmapped) == "scatter"
    scattered = _score_via_buckets(w, unmapped)
    assert scattered.shape == (N,)
    got = _score_via_buckets(w, placed)
    np.testing.assert_array_equal(
        np.asarray(got)[:N], np.asarray(scattered))


def test_one_program_scores_the_mesh(placed, one):
    assert score_route(placed) == score_route(one) == "gather"
    assert score_programs(placed) == score_programs(one) == 1
    assert score_rows(one) == N
    assert score_rows(placed) == N + (-N) % DEVICES


def test_the_padded_map_covers_every_row_once(placed, one):
    """Covered rows point into their bucket's padded block, passive rows
    past all of them, in order; the padding rows point at slot 0."""
    inv = np.asarray(placed.score_inv_device())
    assert inv.shape == (N + (-N) % DEVICES,)
    assert not inv[N:].any()
    inv = inv[:N]
    seen = np.zeros(N, dtype=int)
    base = 0
    for plan in one.blocks:
        rows, counts = np.asarray(plan.row_ids), np.asarray(plan.row_counts)
        b, cap = rows.shape
        for t in range(b):
            kept = rows[t, :counts[t]]
            np.testing.assert_array_equal(
                inv[kept], base + t * cap + np.arange(counts[t]))
            seen[kept] += 1
        base += (b + (-b) % DEVICES) * cap
    _, passive = one.covered_row_partition()
    np.testing.assert_array_equal(
        inv[passive], base + np.arange(passive.size))
    seen[passive] += 1
    assert (seen == 1).all()


def test_the_map_and_the_passive_rows_shard_by_rows(placed):
    mesh = placed.score_inv.sharding.mesh
    for leaf in (placed.score_inv_device(), placed.passive_rows_device()):
        assert leaf.shape[0] % DEVICES == 0
        assert leaf.sharding.is_equivalent_to(row_sharding(mesh, 1), 1)
    _, passive = placed.covered_row_partition()
    rows = np.asarray(placed.passive_rows_device())
    np.testing.assert_array_equal(rows[:passive.size], passive)
    assert (rows[passive.size:] == passive[-1]).all()
