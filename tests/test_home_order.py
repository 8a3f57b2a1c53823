"""The fused fit's private row order (``FusedFit._choose_home``): the rows
stand in one random-effect coordinate's entity order, so that coordinate's
row <-> slab moves are contiguous copies (``photon_tpu/ops/ragged.py``).

Held here: the two moves against ``jnp.take`` and the score map, bit for
bit; the order itself, as the rule states it; the home slab and the
ordered batch against what today's gathers give; home chosen by the
counts the plan holds and kept only where the compiled programs fit the
device; no home, today's program; and the fit in home order
against the canonical one, the unfused loop and, on the benchmark's three
configurations at their tiny sizes, against each other (the reference:
``tests/benchmark/test_rehearsal.py``).
"""

from __future__ import annotations

import copy
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import obs, optim
from photon_tpu.algorithm import fused_fit
from photon_tpu.algorithm.fused_fit import FusedFit
from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
from photon_tpu.data.dataset import DenseFeatures, SparseFeatures
from photon_tpu.data.game_data import make_game_dataset
from photon_tpu.data.random_effect import RandomEffectDataConfiguration
from photon_tpu.estimators.game_estimator import (
    FixedEffectCoordinateConfiguration,
    GameEstimator,
    RandomEffectCoordinateConfiguration,
)
from photon_tpu.ops import ragged
from photon_tpu.types import TaskType

# ---- the two moves, against jnp.take and the score map

# name -> ([(row cap, [count of each entity])], passive rows)
LAYOUTS = {
    "one_bucket": ([(8, [3, 8, 1, 5, 8, 2])], 0),
    "one_entity": ([(16, [11])], 0),
    "every_count_is_the_cap": ([(4, [4] * 6)], 0),
    "every_count_is_one": ([(8, [1] * 7)], 0),
    "cap_and_one_side_by_side": ([(16, [16, 1, 16, 16, 1, 1, 9])], 0),
    "two_buckets_and_a_passive_tail": (
        [(4, [3, 4, 1]), (16, [9, 16, 12, 10])], 23),
    "twelve_buckets_and_a_passive_tail": (
        [(2 ** k, [2 ** k, 2 ** (k - 1) + 1, 2 ** k - 1][: 1 + k % 3])
         for k in range(1, 13)], 137),
}


def _layout(name):
    """A plan of that layout over randomly placed canonical rows: per
    bucket ``row_ids`` / ``row_counts`` as the planner packs them, the
    score map, each row's entity, and the order the rule states."""
    rng = np.random.default_rng(sorted(LAYOUTS).index(name))
    buckets, passive = LAYOUTS[name]
    active = sum(sum(counts) for _, counts in buckets)
    n = active + passive
    place = rng.permutation(n)
    row_ids, row_counts, at, code = [], [], 0, 0
    score_inv = np.empty(n, np.int32)
    codes = np.empty(n, np.int32)
    slots = 0
    for cap, counts in buckets:
        ids = np.zeros((len(counts), cap), np.int32)
        for b, count in enumerate(counts):
            rows = place[at:at + count]
            ids[b, :count] = rows
            score_inv[rows] = slots + b * cap + np.arange(count)
            codes[rows] = code
            at, code = at + count, code + 1
        slots += ids.size
        row_ids.append(ids)
        row_counts.append(np.asarray(counts, np.int32))
    tail = np.sort(place[active:])
    score_inv[tail] = slots + np.arange(passive)
    codes[tail] = rng.integers(0, code, passive)
    order = np.concatenate(
        [ids[np.arange(ids.shape[1])[None] < c[:, None]]
         for ids, c in zip(row_ids, row_counts)]
        + [tail[np.argsort(codes[tail], kind="stable")]])
    return types.SimpleNamespace(
        n=n, active=active, passive=passive, row_ids=row_ids,
        row_counts=row_counts, score_inv=score_inv, codes=codes,
        slots=slots, order=order, tail=tail,
        shapes=[ids.shape for ids in row_ids])


def _home_order(lay):
    plans = [types.SimpleNamespace(row_ids=jnp.asarray(ids),
                                   row_counts=jnp.asarray(counts))
             for ids, counts in zip(lay.row_ids, lay.row_counts)]
    vector = jnp.arange(lay.n, dtype=jnp.float32)
    shard = DenseFeatures(jnp.stack([vector, -vector], axis=1))
    return jax.jit(lambda inv, codes: FusedFit._home_order(
        {"plans": plans, "score_inv": inv},
        {"score_codes": codes, "raw": shard, "labels": vector,
         "offsets": vector, "weights": vector}))(
        jnp.asarray(lay.score_inv), jnp.asarray(lay.codes))


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_the_order_is_buckets_entities_active_rows_then_passive_by_entity(
        name):
    lay = _layout(name)
    order = _home_order(lay)
    np.testing.assert_array_equal(order["perm"], lay.order)
    np.testing.assert_array_equal(
        np.asarray(order["inv"])[lay.order], np.arange(lay.n))
    np.testing.assert_array_equal(
        order["stacked"],
        np.stack([lay.order, -lay.order] + [lay.order] * 3, axis=1))
    starts = np.cumsum([0] + [c.sum() for c in lay.row_counts[:-1]])
    assert [int(move[0]) for move in order["moves"]] == list(starts)


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_rows_enter_the_slab_as_jnp_take_gathers_them_bit_for_bit(name):
    lay = _layout(name)
    order = _home_order(lay)
    rng = np.random.default_rng(7)
    residual = jnp.asarray(rng.normal(size=lay.n).astype(np.float32))
    features = jnp.asarray(rng.normal(size=(lay.n, 3)).astype(np.float32))
    for arr in (residual, features):
        padded = FusedFit._pad_rows(
            jnp.take(arr, order["perm"], axis=0), lay.shapes)
        for ids, counts, move in zip(
                lay.row_ids, lay.row_counts, order["moves"]):
            mask = np.arange(ids.shape[1])[None] < counts[:, None]
            want = np.asarray(jnp.take(arr, jnp.asarray(ids), axis=0))
            got = np.asarray(FusedFit._slab_rows(padded, move, *ids.shape))
            np.testing.assert_array_equal(got[mask], want[mask])


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_scores_leave_the_slabs_as_the_score_map_gathers_them_bit_for_bit(
        name):
    lay = _layout(name)
    order = _home_order(lay)
    rng = np.random.default_rng(8)
    parts = [jnp.asarray(rng.normal(size=b * cap).astype(np.float32))
             for b, cap in lay.shapes]
    tail_scores = jnp.asarray(
        rng.normal(size=lay.passive).astype(np.float32))
    today = jnp.take(
        jnp.concatenate(parts + [tail_scores]), jnp.asarray(lay.score_inv))
    got = FusedFit._rows_from_slabs(
        parts, order["moves"], lay.shapes, lay.n)
    want = np.asarray(today)[lay.order]
    np.testing.assert_array_equal(
        np.asarray(got)[:lay.active], want[:lay.active])


def test_the_receive_bits_move_nothing_where_every_count_is_the_cap():
    for bits in ragged.shift_bits(jnp.full(6, 4, jnp.int32), 4):
        assert not np.asarray(bits).any()
    assert ragged.shift_steps(1, 512) == 0  # one entity never moves


# ---- the materialize program and the fit, on a small GLMix

N, D, DU, DM = 3000, 5, 4, 3
USERS, MOVIES = 150, 12


def _l2(w):
    return GLMOptimizationConfiguration(
        regularization=optim.RegularizationContext(
            optim.RegularizationType.L2),
        regularization_weight=w,
    )


def _owners(rng, entities, exponent):
    shares = np.arange(1, entities + 1, dtype=np.float64) ** -exponent
    counts = 1 + rng.multinomial(N - entities, shares / shares.sum())
    return rng.permutation(np.repeat(np.arange(entities), counts))


def _game(rng, dtype, sparse_movies=False):
    def features(d):
        x = rng.normal(size=(N, d))
        x[:, -1] = 1.0
        return x

    ids = {"userId": _owners(rng, USERS, 0.8),
           "movieId": _owners(rng, MOVIES, 1.0)}
    x, xu, xm = features(D), features(DU), features(DM)
    z = (x @ (0.3 * rng.normal(size=D))
         + np.einsum("nd,nd->n", xu,
                     (0.3 * rng.normal(size=(USERS, DU)))[ids["userId"]])
         + np.einsum("nd,nd->n", xm,
                     (0.2 * rng.normal(size=(MOVIES, DM)))[ids["movieId"]]))
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-0.5 * z))).astype(np.float64)
    movies = DenseFeatures(jnp.asarray(xm))
    if sparse_movies:
        movies = SparseFeatures(
            jnp.asarray(np.tile(np.arange(DM, dtype=np.int32), (N, 1))),
            jnp.asarray(xm), DM)
    return make_game_dataset(
        y,
        {"global": DenseFeatures(jnp.asarray(x)),
         "userShard": DenseFeatures(jnp.asarray(xu)),
         "movieShard": movies},
        id_tags=ids, dtype=dtype,
    )


def _estimator(user_cap=64, movie_cap=256, random=True):
    def coordinate(tag, shard, cap):
        return RandomEffectCoordinateConfiguration(
            RandomEffectDataConfiguration(
                tag, shard, active_data_upper_bound=cap), _l2(1.0))

    coords = {"global": FixedEffectCoordinateConfiguration(
        "global", _l2(0.01))}
    if random:
        coords["per-user"] = coordinate("userId", "userShard", user_cap)
        coords["per-movie"] = coordinate("movieId", "movieShard", movie_cap)
    return GameEstimator(
        TaskType.LOGISTIC_REGRESSION, coords,
        intercept_indices={"global": D - 1, "userShard": DU - 1,
                           "movieShard": DM - 1},
        num_iterations=2, mesh=None,
    )


def _fused(est, game):
    datasets, _ = est.prepare(game)
    coords = est._build_coordinates(
        datasets, {}, {}, logical_rows=game.num_samples)
    return est._fused_for(coords, datasets), coords, datasets


def _slab_slots(datasets):
    return {cid: sum(int(np.prod(b.row_ids.shape)) for b in ds.blocks)
            for cid, ds in datasets.items() if hasattr(ds, "blocks")}


@pytest.fixture()
def canonical_order(monkeypatch):
    """Switches the home order off: today's program."""
    def off():
        monkeypatch.setattr(FusedFit, "_choose_home", lambda *a: None)
    return off


@pytest.mark.parametrize("caps, expected", [
    ((64, 256), "per-user"), ((4, 1024), "per-movie")])
def test_home_is_the_coordinate_with_the_most_slab_slots(
        rng, caps, expected):
    fused, _, datasets = _fused(
        _estimator(*caps), _game(rng, jnp.float32))
    slots = _slab_slots(datasets)
    assert len(set(slots.values())) == 2
    assert fused._home == max(slots, key=slots.get) == expected


def test_without_a_random_effect_or_on_a_sparse_home_shard_there_is_no_home(
        rng):
    game = _game(rng, jnp.float32)
    fused, coords, _ = _fused(_estimator(random=False), game)
    assert fused._home is None
    assert fused._mat_operands(coords) == {}
    fused, coords, datasets = _fused(
        _estimator(4, 1024), _game(rng, jnp.float32, sparse_movies=True))
    slots = _slab_slots(datasets)
    assert max(slots, key=slots.get) == "per-movie"
    assert fused._home is None
    assert set(fused._mat_operands(coords)) == {"per-user", "per-movie"}
    out = jax.eval_shape(fused._mat_fn, fused._mat_operands(coords))
    assert all(m["home"] is None for m in out.values())


def test_a_batch_over_a_ninth_of_the_device_is_not_tried_in_home_order(
        rng, monkeypatch):
    """The first cut (``_HOME_MEMORY_FACTOR``), which spares a compile; the
    CPU states no limit and every test above runs in home order."""
    game = _game(rng, jnp.float32)
    batch_bytes = sum(
        leaf.nbytes
        for leaf in jax.tree.leaves(game.shard_batch("global")))
    for limit, expected in [
        (batch_bytes * fused_fit._HOME_MEMORY_FACTOR, "per-user"),
        (batch_bytes * fused_fit._HOME_MEMORY_FACTOR - 1, None),
    ]:
        monkeypatch.setattr(fused_fit, "_device_bytes_limit", lambda: limit)
        assert _fused(_estimator(), game)[0]._home == expected


def test_the_compiled_programs_have_the_last_word_on_room_for_home(
        rng, monkeypatch):
    """What passes the first cut is compiled, and the compiler's account
    of the two programs decides (``FusedFit._home_fits``): one byte short
    and both are compiled again in the canonical order, which the fit
    then runs. In the run itself where no warm compile came (to the
    byte), and through ``prepare``'s warm compile (its thread compiles
    under the suite's x64, so: twice the need, and half of it)."""
    monkeypatch.setattr(fused_fit, "_HOME_MEMORY_FACTOR", 0)
    monkeypatch.setattr(fused_fit, "_device_bytes_limit", lambda: 1 << 60)

    def check(program, expected):
        assert program._home == program._aot["home"] == expected
        assert program._jit_seen == set()  # the kept executables ran
        assert program._fit_attrs_cache["home"] == expected

    with jax.enable_x64(False):
        game = _game(rng, jnp.float32)
        fused, coords, _ = _fused(_estimator(), game)
        memory = fused.compile_programs(coords)["memory"]
        need = memory["resident"] + memory["scratch"]
        assert memory["scratch"] > 0 and memory["limit"] == 1 << 60
        tables = {}
        for limit, expected in [(need, "per-user"), (need - 1, None)]:
            monkeypatch.setattr(
                fused_fit, "_device_bytes_limit", lambda: limit)
            cold, coords, _ = _fused(_estimator(), game)
            cold._aot_future = None  # no warm compile came
            tables[expected] = _tables(cold.run(coords))
            check(cold, expected)
        for limit, expected in [(2 * need, "per-user"), (need // 2, None)]:
            monkeypatch.setattr(
                fused_fit, "_device_bytes_limit", lambda: limit)
            est = _estimator()
            result = est.fit(game)[0].descent
            check(list(est._fused_cache.values())[0], expected)
            for cid, table in _tables(result).items():
                np.testing.assert_array_equal(table, tables[expected][cid])
    for cid, table in tables["per-user"].items():
        np.testing.assert_allclose(
            table, tables[None][cid], rtol=0, atol=2e-3, err_msg=cid)


def test_the_home_slab_and_the_ordered_batch_are_todays_gathers_bit_for_bit(
        rng, canonical_order):
    with jax.enable_x64(False):
        game = _game(rng, jnp.float32)
        fused, coords, datasets = _fused(_estimator(), game)
        home = fused._home
        got = jax.jit(fused._mat_fn)(fused._mat_operands(coords))
        canonical_order()
        plain, coords0, _ = _fused(_estimator(), game)
        assert plain._home is None
        want = jax.jit(plain._mat_fn)(plain._mat_operands(coords0))
    perm = np.asarray(got[home]["home"]["perm"])
    assert sorted(perm) == list(range(N))
    inv = np.argsort(perm)
    assert got[home]["home"]["passive_x"].shape[0] == (
        datasets[home].plan_counts["passive_rows"]) > 0
    # Home: every slab as today's ids gather it, wherever a row trains.
    for eb, eb0 in zip(got[home]["ebs"], want[home]["ebs"]):
        trains = np.asarray(eb0.weights) != 0
        assert trains.any()
        np.testing.assert_array_equal(eb.weights, eb0.weights)
        np.testing.assert_array_equal(eb.x_values, eb0.x_values)
        np.testing.assert_array_equal(eb.offsets, eb0.offsets)
        np.testing.assert_array_equal(
            np.asarray(eb.labels)[trains], np.asarray(eb0.labels)[trains])
    # The batch in the fit's order; the passive tail of home's shard.
    batch = got[home]["home"]["batches"]["global"]
    for leaf, leaf0 in zip(jax.tree.leaves(batch),
                           jax.tree.leaves(coords["global"].inner.batch)):
        np.testing.assert_array_equal(leaf, np.asarray(leaf0)[perm])
    tail = perm[N - got[home]["home"]["passive_x"].shape[0]:]
    np.testing.assert_array_equal(
        got[home]["home"]["passive_x"],
        np.asarray(datasets[home].raw.x)[tail])
    np.testing.assert_array_equal(
        got[home]["home"]["passive_codes"],
        np.asarray(datasets[home].score_codes)[tail])
    # The other coordinate: the same slabs, its two maps renumbered.
    (other,) = set(got) - {home}
    assert got[other]["home"] is None
    np.testing.assert_array_equal(
        got[other]["score_inv"], np.asarray(want[other]["score_inv"])[perm])
    for eb, eb0 in zip(got[other]["ebs"], want[other]["ebs"]):
        np.testing.assert_array_equal(eb.x_values, eb0.x_values)
        np.testing.assert_array_equal(eb.row_ids, inv[np.asarray(eb0.row_ids)])


def _tables(result):
    return {cid: np.asarray(m.coefficients if hasattr(m, "coefficients")
                            else m.model.coefficients.means)
            for cid, m in result.model.items()}


def test_the_fit_in_home_order_is_the_canonical_fit_and_the_unfused_loop(
        rng, canonical_order):
    """float64: the order in which the fixed effect sums its rows is all
    that differs, so the three agree to rounding."""
    game = _game(rng, jnp.float64)
    est = _estimator()
    home = _tables(est.fit(game)[0])
    program = list(est._fused_cache.values())[0]
    assert program._home == "per-user"
    unfused_est = _estimator()
    unfused_est.non_finite_guard = True  # forces the unfused loop
    unfused = _tables(unfused_est.fit(game)[0])
    canonical_order()
    est0 = _estimator()
    canonical = _tables(est0.fit(game)[0])
    program0 = list(est0._fused_cache.values())[0]
    assert program0._home is None
    # In either order the fixed effect is read feature-major.
    for attrs in (program._fit_attrs_cache, program0._fit_attrs_cache):
        assert attrs["fe_layout"] == {"global": "feature_major"}
    for cid in home:
        np.testing.assert_allclose(
            home[cid], canonical[cid], rtol=1e-8, atol=1e-10, err_msg=cid)
        np.testing.assert_allclose(
            home[cid], unfused[cid], rtol=1e-8, atol=1e-10, err_msg=cid)


def test_the_aot_skeleton_chooses_the_same_home_and_its_programs_are_used(
        rng):
    """``prepare`` compiles both programs from PREDICTED shapes while the
    planner runs; home hangs on shapes alone, so the executables fit."""
    with jax.enable_x64(False):
        game = _game(rng, jnp.float32)
        est = _estimator()
        est.prepare(game)
        obs.reset()
        est.fit(game)
    fused = list(est._fused_cache.values())[0]
    assert fused._home == "per-user"
    assert fused._aot is not None, "the AOT executables were turned away"
    assert fused._jit_seen == set()


def test_a_sibling_program_follows_the_order_of_the_slabs_it_is_handed(rng):
    """The programs of one generation share the materialized slabs; the
    one that builds them sets the order. A sibling that would have chosen
    otherwise (another locked set) fits in that order and says so in the
    ``fit`` stage's attributes."""
    with jax.enable_x64(False):
        game = _game(rng, jnp.float32)
        first, coords, _ = _fused(_estimator(), game)
        want = _tables(first.run(coords))
        sibling = FusedFit(
            coords, first.seq, first.num_iterations,
            mat_share=first._mat_shared)
        sibling._home = None
        got = _tables(sibling.run(coords))
    assert first._fit_attrs_cache["home"] == "per-user"
    assert sibling._fit_attrs_cache == first._fit_attrs_cache
    for cid in want:
        np.testing.assert_array_equal(got[cid], want[cid])


# ---- the benchmark's three configurations at their tiny sizes

def _tiny(name):
    from benchmark.manifest import Manifest

    man = Manifest()
    config = copy.deepcopy(man.config(name))
    config["rows"] = config["tiny"]["rows"]
    for c in config["coordinates"]:
        if c["name"] in config["tiny"]["entities"]:
            c["entities"] = config["tiny"]["entities"][c["name"]]
    return config, man.generator(name).generate(config, seed=2**31 + 5)


@pytest.mark.parametrize("name, cell", [
    ("glmix_ml_logistic", "logistic.refit"),
    ("glmix_ml_linear", "linear.refit"),
    ("glmix_ml_heavytail", "heavytail.refit"),
])
def test_a_benchmark_configuration_fits_in_home_order_as_the_unfused_loop(
        name, cell):
    """In float32, as the cells run. The compared numbers are relative RMS
    gaps as ``benchmark/check.py`` takes them, each held under HALF of its
    cell's ``coef.<coordinate>`` tiny limit (``benchmark/limits``): what a
    row order may move is well inside what the rehearsal allows against
    the reference. Read on the CPU (PR 33, three data sets each): logistic
    7e-5 / 2.2e-4 / 2.0e-4 of 2e-3 / 8e-4 / 8e-4 (global, users, movies);
    linear 1.9e-4 / 2.6e-3 / 1.9e-3 of 6e-4 / 8e-3 / 6e-3, its bf16
    slabs' distance from the loop whatever the order; heavytail 7.6e-5 /
    3.6e-5 / 3.5e-4 of 2e-4 / 6e-4 / 9e-4."""
    from benchmark import sut
    from benchmark.manifest import Manifest

    with open(Manifest().limits_path(cell)) as f:
        limits = json.load(f)["tiny_limits"]
    config, data = _tiny(name)
    with jax.enable_x64(False):
        dataset = sut.build_dataset(data)
        est = sut.build_estimator(config)
        fused = sut.model_tables(
            sut.fit_blocking(est, dataset).model, config)
        program = list(est._fused_cache.values())[0]
        slots = _slab_slots(est.prepare(dataset)[0])
        loop = sut.build_estimator(config)
        loop.non_finite_guard = True
        unfused = sut.model_tables(
            sut.fit_blocking(loop, dataset).model, config)
    assert program._home == max(slots, key=slots.get)
    for cid in fused:
        gap = np.sqrt(np.mean((fused[cid] - unfused[cid]) ** 2)
                      / np.mean(unfused[cid] ** 2))
        assert gap < limits[f"coef.{cid}"] / 2, (cid, gap)
