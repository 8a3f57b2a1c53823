"""The ``fit`` stage's attributes (``FusedFit._fit_attrs``): what the
planner counted for each random-effect coordinate, and its rungs with the
route the solver took, against counts taken here from the raw ids and the
caps alone.

A refit window holds no ``plan`` stage, so these attributes are all a
trace of warm fits says about how many rows trained, how many were only
scored, and which solver served which rung (OBSERVABILITY.md).
"""

from __future__ import annotations

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import obs, optim
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

N, D, DU, DM = 6000, 6, 5, 3
USERS, MOVIES = 400, 24
CAPS = {"per-user": 128, "per-movie": 1024}
TAGS = {"per-user": "userId", "per-movie": "movieId"}


def _l2(w):
    return GLMOptimizationConfiguration(
        regularization=optim.RegularizationContext(
            optim.RegularizationType.L2),
        regularization_weight=w,
    )


def _owners(rng, entities, exponent):
    """[N] entity of each row: every entity one row, the rest by a power
    law of the entity's rank (exponent 0: uniform)."""
    shares = np.arange(1, entities + 1, dtype=np.float64) ** -exponent
    counts = 1 + rng.multinomial(N - entities, shares / shares.sum())
    return rng.permutation(np.repeat(np.arange(entities), counts))


def _game(rng, exponents):
    def features(d):
        x = rng.normal(size=(N, d))
        x[:, -1] = 1.0
        return x

    ids = {"userId": _owners(rng, USERS, exponents[0]),
           "movieId": _owners(rng, MOVIES, exponents[1])}
    x, xu, xm = features(D), features(DU), features(DM)
    z = (x @ (0.3 * rng.normal(size=D))
         + np.einsum("nd,nd->n", xu,
                     (0.3 * rng.normal(size=(USERS, DU)))[ids["userId"]])
         + np.einsum("nd,nd->n", xm,
                     (0.2 * rng.normal(size=(MOVIES, DM)))[ids["movieId"]]))
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-0.5 * z))).astype(np.float64)
    game = make_game_dataset(
        y,
        {"global": DenseFeatures(jnp.asarray(x)),
         "userShard": DenseFeatures(jnp.asarray(xu)),
         "movieShard": DenseFeatures(jnp.asarray(xm))},
        id_tags=ids,
        dtype=jnp.float32,
    )
    return game, ids


def _estimator():
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
        mesh=None,
    )


def _fit_records():
    return [r for r in obs.TRACER.completed() if r.name == "fit"]


@pytest.fixture(scope="module")
def heavy_tail():
    """One power-law data set whose caps bind, prepared and fitted three
    times; what each fit left in the ring. In float32 with x64 off, as on
    the chip: the Pallas step serves float32 slabs only."""
    rng = np.random.default_rng(32)
    with jax.enable_x64(False):
        game, ids = _game(rng, (0.8, 1.0))
        est = _estimator()
        datasets, _ = est.prepare(game)
        obs.reset()
        est.fit(game)
        first = len(obs.TRACER.completed())
        est.fit(game)
        est.fit(game)
    later = list(obs.TRACER.completed())[first:]
    return dict(est=est, game=game, ids=ids, datasets=datasets,
                fits=_fit_records(), later=later)


def test_the_counts_are_those_of_the_raw_ids_and_the_caps(heavy_tail):
    attrs = heavy_tail["fits"][-1].attrs["coordinates"]
    assert set(attrs) == set(CAPS)
    for cid, cap in CAPS.items():
        counts = np.bincount(heavy_tail["ids"][TAGS[cid]])
        kept = np.minimum(counts, cap)
        assert counts.max() > cap, "the fixture's caps have to bind"
        got = attrs[cid]
        assert got["active_rows"] == kept.sum()
        assert got["passive_rows"] == N - kept.sum() > 0
        assert got["capped_entities"] == np.count_nonzero(counts > cap) > 0
        assert set(got) == {"active_rows", "passive_rows", "capped_entities",
                            "slab_rows", "rungs", "score_route"}
        assert got["score_route"] == "gather"


def test_the_attributes_place_and_materialize_nothing(heavy_tail):
    """Shapes and host arrays give the ``fit`` stage's attributes
    (``score_route`` too, PR 37): the fused fit's data sets hold no split
    plan and no cached slab, the unfused loop's ``device_plans`` /
    ``device_blocks``, whose device memory a one-chip fit would keep."""
    for cid in CAPS:
        ds = heavy_tail["datasets"][cid]
        assert getattr(ds, "_device_plans", None) is None, cid
        assert getattr(ds, "_device_blocks", None) is None, cid


def test_home_and_the_indices_still_gathered_read_from_stage_records(
        heavy_tail):
    """As the benchmark would read them: ``sut.stage_records()``. Home is
    the coordinate with the most slab slots; a CD iteration still gathers,
    for every other one, its slab slots, the rows and its passive rows."""
    from benchmark import sut

    with jax.enable_x64(False):
        heavy_tail["est"].fit(heavy_tail["game"])
    record = [r for r in sut.stage_records() if r.name == "fit"][-1]
    assert set(record.attrs) == {
        "coordinates", "fe_layout", "home", "gather_indices"}
    slots = {cid: sum(int(np.prod(b.row_ids.shape)) for b in ds.blocks)
             for cid, ds in heavy_tail["datasets"].items() if cid in CAPS}
    home = max(slots, key=slots.get)
    assert record.attrs["home"] == home == "per-user"
    (other,) = set(CAPS) - {home}
    counts = np.bincount(heavy_tail["ids"][TAGS[other]])
    passive = N - np.minimum(counts, CAPS[other]).sum()
    assert record.attrs["gather_indices"] == slots[other] + N + passive
    assert record.attrs["coordinates"][other]["slab_rows"] == slots[other]


def test_the_rungs_hold_every_entity_under_a_row_cap_that_fits_it(heavy_tail):
    attrs = heavy_tail["fits"][-1].attrs["coordinates"]
    for cid, cap in CAPS.items():
        counts = np.bincount(heavy_tail["ids"][TAGS[cid]])
        kept = np.sort(np.minimum(counts[counts > 0], cap))
        rungs = attrs[cid]["rungs"]
        assert len(rungs) >= 3
        assert [[b, r] for b, r, _ in rungs] == [
            list(block.row_ids.shape)
            for block in heavy_tail["datasets"][cid].blocks]
        assert sum(b for b, _, _ in rungs) == kept.size
        row_caps = [r for _, r, _ in rungs]
        assert row_caps == sorted(set(row_caps))
        assert all(r & (r - 1) == 0 for r in row_caps)  # powers of two
        # Entities by size fill the rungs from the narrowest up; the widest
        # is the least power of two over the largest entity.
        at = 0
        for b, r, _ in rungs:
            assert kept[at:at + b].max() <= r
            at += b
        assert row_caps[-1] // 2 < kept[-1] <= row_caps[-1]
        assert attrs[cid]["slab_rows"] == sum(b * r for b, r, _ in rungs)
        assert attrs[cid]["slab_rows"] >= attrs[cid]["active_rows"]


def test_every_fit_of_one_prepared_data_set_records_the_same_and_compiles_nothing(
        heavy_tail):
    first, second, third = heavy_tail["fits"]
    assert first.attrs == second.attrs == third.attrs
    assert second.attrs is third.attrs  # made once, not per fit
    assert [r.path for r in heavy_tail["later"]
            if not r.thread.startswith("photon-compile")] == [
        "fit/fit.operands", "fit/fit.dispatch", "fit"] * 2
    assert not [r for r in heavy_tail["later"]
                if r.name.startswith("compile")]


def test_the_attributes_are_plain_ints_and_strings(heavy_tail):
    def plain(value):
        if isinstance(value, dict):
            return all(type(k) is str and plain(v) for k, v in value.items())
        if isinstance(value, list):
            return all(plain(v) for v in value)
        return type(value) in (int, str)

    record = heavy_tail["fits"][-1]
    assert plain(record.attrs)
    assert json.loads(json.dumps(record.to_json()))["attrs"] == record.attrs


def test_a_uniform_data_set_has_no_passive_row(rng):
    with jax.enable_x64(False):
        game, ids = _game(rng, (0.0, 0.0))
        _estimator().fit(game)
    assert np.bincount(ids["userId"]).max() <= CAPS["per-user"]
    attrs = _fit_records()[-1].attrs["coordinates"]
    for cid in CAPS:
        assert attrs[cid]["passive_rows"] == 0
        assert attrs[cid]["capped_entities"] == 0
        assert attrs[cid]["active_rows"] == N


def test_the_routes_are_the_scopes_the_program_carries(
        heavy_tail, monkeypatch):
    """With the Pallas step switched on (interpreted here), the rungs its
    VMEM gate admits are named ``newton_kernel`` and the others
    ``newton_xla``, and the lowered fit program carries those scopes and
    no other Newton scope."""
    from photon_tpu.ops import newton_kernel as nk

    monkeypatch.setenv("PHOTON_NEWTON_KERNEL", "force")
    est, game = heavy_tail["est"], heavy_tail["game"]
    with jax.enable_x64(False):
        coords = est._build_coordinates(
            heavy_tail["datasets"], {}, {}, logical_rows=game.num_samples)
        fused = est._fused_for(coords, heavy_tail["datasets"])
        monkeypatch.setattr(fused, "_fit_attrs_cache", None)
        slabs = jax.eval_shape(fused._mat_fn, fused._mat_operands(coords))
        attrs = fused._fit_attrs(coords, slabs)["coordinates"]
        fit_text = fused.lower(coords).as_text(debug_info=True)
    widths = {"per-user": DU, "per-movie": DM}
    routes = set()
    for cid, got in attrs.items():
        for _, r, route in got["rungs"]:
            fits = nk._vmem_estimate_bytes(r, widths[cid]) <= (
                nk._VMEM_BUDGET_BYTES)
            assert route == ("newton_kernel" if fits else "newton_xla")
            routes.add(route)
    assert set(re.findall(r'"solve\.(newton_\w+)/', fit_text)) == routes
