"""The unfused loop's ``fit`` stage and what PR 36 repaired for a GLMix
that no single device holds (``GameEstimator(mesh=4)``): the stage carries
the fused fit's attributes with equal values on the same data, plus
``programs``, ``devices`` and ``placed_bytes``; a data set left on the host
reaches the mesh with no whole copy on one device and trains the same
model; the slab budget of ``device_blocks`` is a device's, not the mesh's.

Four of the suite's eight forced host devices stand in for the chips.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import obs, optim
from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
from photon_tpu.data import random_effect as re_data
from photon_tpu.data.dataset import DenseFeatures, GLMBatch
from photon_tpu.data.game_data import (
    make_game_dataset,
    make_host_game_dataset,
)
from photon_tpu.data.random_effect import (
    BlockPlan,
    RandomEffectDataConfiguration,
)
from photon_tpu.estimators.game_estimator import (
    FixedEffectCoordinateConfiguration,
    GameEstimator,
    RandomEffectCoordinateConfiguration,
)
from photon_tpu.parallel.mesh import make_mesh, placed_bytes, shard_batch
from photon_tpu.types import TaskType

N, D, DU, DM = 6003, 6, 5, 3  # N is no multiple of four
USERS, MOVIES = 401, 23
CAPS = {"per-user": 128, "per-movie": 1024}


def _l2(w):
    return GLMOptimizationConfiguration(
        regularization=optim.RegularizationContext(
            optim.RegularizationType.L2),
        regularization_weight=w,
    )


def _arrays():
    rng = np.random.default_rng(36)

    def owners(entities, exponent):
        shares = np.arange(1, entities + 1, dtype=np.float64) ** -exponent
        counts = 1 + rng.multinomial(N - entities, shares / shares.sum())
        return rng.permutation(np.repeat(np.arange(entities), counts))

    def features(d):
        x = rng.normal(size=(N, d)).astype(np.float32)
        x[:, -1] = 1.0
        return x

    ids = {"userId": owners(USERS, 0.8), "movieId": owners(MOVIES, 1.0)}
    shards = {"global": features(D), "userShard": features(DU),
              "movieShard": features(DM)}
    z = (shards["global"] @ (0.3 * rng.normal(size=D))
         + np.einsum("nd,nd->n", shards["userShard"],
                     (0.3 * rng.normal(size=(USERS, DU)))[ids["userId"]])
         + np.einsum("nd,nd->n", shards["movieShard"],
                     (0.2 * rng.normal(size=(MOVIES, DM)))[ids["movieId"]]))
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-0.5 * z))).astype(np.float32)
    return y, shards, ids


def _estimator(mesh, **kw):
    def random(tag, shard, cap):
        return RandomEffectCoordinateConfiguration(
            RandomEffectDataConfiguration(
                tag, shard, active_data_upper_bound=cap),
            _l2(1.0))

    return GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {
            "global": FixedEffectCoordinateConfiguration("global", _l2(0.01)),
            "per-user": random("userId", "userShard", CAPS["per-user"]),
            "per-movie": random("movieId", "movieShard", CAPS["per-movie"]),
        },
        intercept_indices={"global": D - 1, "userShard": DU - 1,
                           "movieShard": DM - 1},
        num_iterations=2,
        mesh=mesh,
        **kw,
    )


def _game(maker):
    y, shards, ids = _arrays()
    return maker(
        y, {k: DenseFeatures(x) for k, x in shards.items()}, id_tags=ids,
        dtype=jnp.float32)


def _last_fit():
    return [r for r in obs.TRACER.completed() if r.name == "fit"][-1]


def _tables(result):
    return {
        cid: np.asarray(
            m.coefficients if hasattr(m, "coefficients")
            else m.model.coefficients.means)
        for cid, m in result.model.models.items()
    }


@pytest.fixture(scope="module")
def fits():
    """The same data through the fused fit, the unfused loop on one
    device, and the unfused loop on a four-device mesh from a data set left
    on the host: each one's last ``fit`` stage and tables."""
    out = {}
    with jax.enable_x64(False):
        for name, est, maker in (
            ("fused", _estimator(None), make_game_dataset),
            ("loop", _estimator(None, non_finite_guard=True),
             make_game_dataset),
            ("mesh", _estimator(4), make_host_game_dataset),
        ):
            game = _game(maker)
            datasets, _ = est.prepare(game)
            est.fit(game)
            result = est.fit(game)[0]
            out[name] = {
                "stage": _last_fit(), "tables": _tables(result),
                "datasets": datasets, "game": game, "est": est,
                "model": result.model,
            }
    return out


def test_the_loop_records_one_fit_stage_a_fit(fits):
    with jax.enable_x64(False):
        before = len([r for r in obs.TRACER.completed() if r.name == "fit"])
        fits["mesh"]["est"].fit(fits["mesh"]["game"])
        after = [r for r in obs.TRACER.completed() if r.name == "fit"]
    assert len(after) == before + 1
    assert after[-1].kind == "stage" and after[-1].seconds > 0


def test_the_loops_fit_stage_carries_the_fused_fits_attributes(fits):
    fused = fits["fused"]["stage"].attrs["coordinates"]
    for name in ("loop", "mesh"):
        loop = fits[name]["stage"].attrs["coordinates"]
        assert set(loop) == set(fused) == {"per-user", "per-movie"}
        for cid in fused:
            assert set(loop[cid]) == set(fused[cid])
            for key in ("active_rows", "passive_rows", "capped_entities"):
                assert loop[cid][key] == fused[cid][key], (name, cid, key)
            assert [r[1] for r in loop[cid]["rungs"]] == [
                r[1] for r in fused[cid]["rungs"]]
    # One device: the same slabs, so the same entities and slab rows.
    one = fits["loop"]["stage"].attrs["coordinates"]
    assert one == fused
    # A mesh pads each bucket's entities to a multiple of four.
    for cid, attrs in fits["mesh"]["stage"].attrs["coordinates"].items():
        for (b, r, route), (b1, r1, _) in zip(
                attrs["rungs"], fused[cid]["rungs"]):
            assert b == b1 + (-b1) % 4 and route == "newton_xla"
        assert attrs["slab_rows"] == sum(
            b * r for b, r, _ in attrs["rungs"])
    assert fused["per-user"]["passive_rows"] > 0


def test_every_random_effect_scores_by_one_gather(fits):
    """The inverse score map serves one device and the mesh alike: the
    ``fit`` stage's ``score_route`` (PR 37; the mesh added a bucket at a
    time into an ``[n]`` vector before)."""
    for name in ("fused", "loop", "mesh"):
        for cid, attrs in fits[name]["stage"].attrs["coordinates"].items():
            assert attrs["score_route"] == "gather", (name, cid)


def test_every_fit_reads_the_fixed_effect_feature_major(fits):
    """The fused fit, the loop on one device and the loop on the mesh all
    solve the fixed effect through the feature-major view: the ``fit``
    stage's ``fe_layout``."""
    for name in ("fused", "loop", "mesh"):
        assert fits[name]["stage"].attrs["fe_layout"] == {
            "global": "feature_major"}, name


def test_the_mesh_loop_carries_row_sharded_vectors(fits):
    """On the mesh every coordinate scores the rows padded to the device
    count, sharded by rows, and a random effect asks its residuals
    replicated (one all-gather an update, taken by the loop); one device
    keeps the canonical rows and asks nothing."""
    for name, rows in (("mesh", N + (-N) % 4), ("loop", N)):
        est, model = fits[name]["est"], fits[name]["model"]
        coords = est._build_coordinates(
            fits[name]["datasets"], {}, {}, logical_rows=N)
        for cid, coord in coords.items():
            scores = coord.score(model[cid])
            assert scores.shape == (rows,), (name, cid)
            if name == "mesh":
                assert scores.sharding.spec == jax.sharding.PartitionSpec(
                    "data"), cid
            asks = getattr(coord, "residual_sharding", lambda: None)()
            if name == "mesh" and cid != "global":
                assert asks.is_fully_replicated and len(asks.device_set) == 4
            else:
                assert asks is None, (name, cid)


def _leaves(datasets):
    out = []
    for ds in datasets.values():
        out.append(ds.device_leaves() if hasattr(ds, "device_leaves")
                   else ds)
    seen, arrays = set(), []
    for leaf in jax.tree.leaves(out):
        if isinstance(leaf, jax.Array) and id(leaf) not in seen:
            seen.add(id(leaf))
            arrays.append(leaf)
    return arrays


def test_placed_bytes_sum_to_the_data_sets_bytes_evenly(fits):
    attrs = fits["mesh"]["stage"].attrs
    assert attrs["devices"] == 4 and len(attrs["placed_bytes"]) == 4
    arrays = _leaves(fits["mesh"]["datasets"])
    want = sum(
        int(np.prod(a.sharding.shard_shape(a.shape))) * a.dtype.itemsize
        * len(a.sharding.device_set) for a in arrays)
    assert sum(attrs["placed_bytes"]) == want > 0
    mean = want / 4
    assert max(attrs["placed_bytes"]) <= 1.5 * mean
    # No leaf of the prepared data sets sits whole on one device only,
    # but for tables of an entity's size.
    rows = [a for a in arrays if a.shape and a.shape[0] >= N]
    assert rows and all(len(a.sharding.device_set) == 4 for a in rows)
    one = fits["loop"]["stage"].attrs
    assert one["devices"] == 1 and len(one["placed_bytes"]) == 1


def test_the_mesh_trains_the_model_one_device_trains(fits):
    for cid, table in fits["loop"]["tables"].items():
        np.testing.assert_allclose(
            fits["mesh"]["tables"][cid], table, rtol=0, atol=2e-3)


def test_a_data_set_left_on_the_host_serves_one_device_too():
    with jax.enable_x64(False):
        host = _game(make_host_game_dataset)
        assert host.on_host and isinstance(host.labels, np.ndarray)
        assert host.on_device() is not host
        placed = _game(make_game_dataset)
        assert not placed.on_host and placed.on_device() is placed
        a = _tables(_estimator(None).fit(host)[0])
        b = _tables(_estimator(None).fit(placed)[0])
    for cid in a:
        np.testing.assert_array_equal(a[cid], b[cid])


def test_host_rows_reach_the_mesh_as_device_rows_would():
    y, shards, _ = _arrays()
    mesh = make_mesh(jax.devices()[:4])
    host = GLMBatch(features=DenseFeatures(shards["global"]), labels=y,
                    offsets=np.zeros(N, np.float32),
                    weights=np.ones(N, np.float32))
    there = jax.tree.map(jnp.asarray, host)
    a, b = shard_batch(host, mesh), shard_batch(there, mesh)
    for x, z in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.shape == z.shape and x.shape[0] == N + 1
        assert x.sharding.is_equivalent_to(z.sharding, x.ndim)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(z))
    assert float(a.weights[-1]) == 0.0
    assert placed_bytes(a, jax.devices()[:4]) == [
        sum(x.nbytes for x in jax.tree.leaves(a)) // 4] * 4


def test_the_slab_budget_is_a_devices_not_the_meshs(fits, monkeypatch):
    """A budget that the whole mesh's slabs pass and a device's share
    does not: the mesh keeps its slabs, one device leaves some lazy."""
    sized = fits["loop"]["datasets"]["per-user"]
    whole = sum(
        4 * int(np.prod(b.x_values.shape)) * 2
        for b in sized.device_blocks())
    monkeypatch.setattr(re_data, "_DEVICE_SLAB_BUDGET_BYTES", whole // 2)
    with jax.enable_x64(False):
        for name, lazy in (("mesh", False), ("loop", True)):
            est = fits[name]["est"]
            est._fit_cache = None
            datasets, _ = est.prepare(fits[name]["game"])
            blocks = datasets["per-user"].device_blocks()
            assert any(isinstance(b, BlockPlan) for b in blocks) == lazy


def test_programs_counts_what_the_loop_dispatched(fits, monkeypatch):
    """Against JAX's own executions, counted with its fast path off; the
    one-primitive helpers (casts, zeros, a slice's index) are left out of
    both."""
    from jax._src import pjit
    from jax._src.interpreters import pxla

    names = collections.Counter()
    call = pxla.ExecuteReplicated.__call__

    def counting(self, *args, **kw):
        names[self.name] += 1
        return call(self, *args, **kw)

    with jax.enable_x64(False):
        for name in ("mesh", "loop"):
            est, game = fits[name]["est"], fits[name]["game"]
            est._fit_cache = None
            est.prepare(game)
            est.fit(game)  # every cache warm
            with monkeypatch.context() as m:
                m.setattr(pjit, "_get_fastpath_data", lambda *a, **k: None)
                m.setattr(pxla.ExecuteReplicated, "__call__", counting)
                jax.clear_caches()
                est.fit(game)  # compiles again, through Python
                names.clear()
                result = est.fit(game)
                jax.block_until_ready(list(_tables(result[0]).values()))
                seen = dict(names)
            helpers = ("jit(convert_element_type)", "jit(broadcast_in_dim)")
            counted = sum(n for k, n in seen.items() if k not in helpers)
            assert _last_fit().attrs["programs"] == counted, (name, seen)
    jax.clear_caches()
