"""photon_tpu.serve: tables, the AOT score ladder, the queue, the driver.

Covers the serving acceptance surface:
- score parity between the serving implementation and the training-time
  GameTransformer path (online single requests AND the chunked dataset
  batch route that cli/score.py now uses);
- io/model_io round trips of the random-effect tables serving consumes
  (entity present / cold entity / empty random-effect coordinate /
  model-reload-in-place), asserted by score parity;
- the shape ladder's closed pad rule (the runtime twin of the tier-2
  `serving` contract);
- the micro-batch queue's flush policy, backpressure, draining shutdown,
  and error fan-out.
"""

from __future__ import annotations

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import DenseFeatures, SparseFeatures
from photon_tpu.data.game_data import make_game_dataset
from photon_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.serve.driver import drive, synthetic_requests
from photon_tpu.serve.programs import (
    ScorePrograms,
    ShapeLadder,
    specs_from_dataset,
)
from photon_tpu.serve.queue import MicroBatchQueue, QueueClosed
from photon_tpu.serve.tables import (
    CoefficientTables,
    build_index_maps_from_model,
)
from photon_tpu.transformers import GameTransformer
from photon_tpu.types import TaskType

D, DU, E, S = 6, 5, 9, 3


def _glmix_model(rng, *, scale=1.0, entities=E, task=TaskType.LINEAR_REGRESSION):
    """One dense fixed effect + one random effect with a non-trivial
    (sorted, per-entity) projector. The projector is drawn from a FIXED
    seed so two models with equal ``entities`` differ only in
    coefficient values — the shape of a daily retrain, and the
    condition for an in-place serving reload."""
    prng = np.random.default_rng(1234)
    proj = np.sort(
        np.stack([prng.permutation(DU)[:S] for _ in range(entities)]),
        axis=1,
    ).astype(np.int64) if entities else np.zeros((0, 1), np.int64)
    return GameModel({
        "global": FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(means=jnp.asarray(
                    scale * rng.normal(size=D).astype(np.float32))),
                task,
            ),
            "features",
        ),
        "per-user": RandomEffectModel(
            coefficients=jnp.asarray(
                scale * rng.normal(size=(entities, S if entities else 1))
                .astype(np.float32)),
            random_effect_type="userId",
            feature_shard_id="userShard",
            task=task,
            proj_all=proj,
            entity_keys=tuple(str(i) for i in range(entities)),
        ),
    })


def _dataset(rng, n=257, sparse_user=False, cold_users=3):
    x = rng.normal(size=(n, D)).astype(np.float32)
    users = rng.integers(0, E + cold_users, size=n)
    if sparse_user:
        k = 3
        shard = SparseFeatures(
            jnp.asarray(rng.integers(0, DU, size=(n, k)).astype(np.int32)),
            jnp.asarray(rng.normal(size=(n, k)).astype(np.float32)),
            DU,
        )
    else:
        shard = DenseFeatures(
            jnp.asarray(rng.normal(size=(n, DU)).astype(np.float32))
        )
    return make_game_dataset(
        rng.normal(size=n).astype(np.float32),
        {"features": DenseFeatures(jnp.asarray(x)), "userShard": shard},
        id_tags={"userId": users},
    )


class TestShapeLadder:
    def test_pad_rule_is_closed(self):
        ladder = ShapeLadder((1, 8, 64))
        for n in range(1, 65):
            assert ladder.rung_for(n) in ladder.rungs
            assert ladder.rung_for(n) >= n
        # tightest rung: one below/at each boundary
        assert ladder.rung_for(1) == 1
        assert ladder.rung_for(2) == 8
        assert ladder.rung_for(8) == 8
        assert ladder.rung_for(9) == 64

    def test_overflow_and_empty_raise(self):
        ladder = ShapeLadder((4,))
        with pytest.raises(ValueError):
            ladder.rung_for(5)
        with pytest.raises(ValueError):
            ladder.rung_for(0)

    def test_chunk_plan_covers_everything_once(self):
        ladder = ShapeLadder((2, 8))
        for n in (1, 2, 7, 8, 9, 16, 21):
            plan = ladder.chunk_plan(n)
            rows = [i for lo, hi, _ in plan for i in range(lo, hi)]
            assert rows == list(range(n))
            assert all(r in ladder.rungs for _, _, r in plan)
            assert all(hi - lo <= r for lo, hi, r in plan)

    def test_rungs_normalized(self):
        assert ShapeLadder((64, 1, 8, 8)).rungs == (1, 8, 64)
        with pytest.raises(ValueError):
            ShapeLadder((0, 4))


class TestTables:
    def test_structure_and_cold_lookup(self, rng):
        tables = CoefficientTables.from_game_model(_glmix_model(rng))
        t = tables.random["per-user"]
        assert t.num_entities == E
        assert t.code_for("3") == 3
        assert t.code_for(3) == 3  # numeric keys normalize to str
        assert t.code_for("no-such-user") == -1
        assert tables.codes_for({"userId": "4"}) == {"per-user": 4}
        assert tables.codes_for({}) == {"per-user": -1}

    def test_tables_own_their_buffers(self, rng):
        """A donating reload (``_device_swap``; donation engages only
        off the CPU) deletes the table's old buffer. The table must not
        alias the caller's model arrays, or the model dies with it."""
        model = _glmix_model(rng)
        tables = CoefficientTables.from_game_model(model)
        pairs = [
            (tables.fixed["global"].weights,
             model["global"].model.coefficients.means),
            (tables.random["per-user"].weights,
             model["per-user"].coefficients),
        ]
        for table_arr, model_arr in pairs:
            np.testing.assert_array_equal(
                np.asarray(table_arr), np.asarray(model_arr))
            assert (
                table_arr.unsafe_buffer_pointer()
                != model_arr.unsafe_buffer_pointer()
            )

    def test_single_request_matches_manual_math(self, rng):
        model = _glmix_model(rng)
        tables = CoefficientTables.from_game_model(model)
        programs = ScorePrograms(tables, ladder=ShapeLadder((1, 4)))
        w_fe = np.asarray(model["global"].model.coefficients.means)
        w_re = np.asarray(model["per-user"].coefficients)
        proj = model["per-user"].proj_all
        x = rng.normal(size=D).astype(np.float32)
        xu = rng.normal(size=DU).astype(np.float32)
        feats, codes, _ = programs.pack_requests(
            [({"features": x, "userShard": xu}, {"userId": "5"})]
        )
        got = programs.score_padded(feats, codes, 1)[0]
        want = x @ w_fe + sum(
            xu[proj[5, j]] * w_re[5, j] for j in range(S)
        )
        np.testing.assert_allclose(got, want, rtol=1e-5)
        # cold entity: fixed-effect-only
        feats, codes, _ = programs.pack_requests(
            [({"features": x, "userShard": xu}, {"userId": "cold"})]
        )
        np.testing.assert_allclose(
            programs.score_padded(feats, codes, 1)[0], x @ w_fe,
            rtol=1e-5,
        )

    def test_reload_in_place_keeps_programs(self, rng):
        tables = CoefficientTables.from_game_model(_glmix_model(rng))
        programs = ScorePrograms(tables, ladder=ShapeLadder((1, 4)))
        compiled_before = programs.stats["programs_compiled"]
        x = rng.normal(size=D).astype(np.float32)
        xu = np.zeros(DU, np.float32)
        feats, codes, _ = programs.pack_requests(
            [({"features": x, "userShard": xu}, {"userId": "0"})]
        )
        before = programs.score_padded(feats, codes, 1)[0]

        model2 = _glmix_model(rng, scale=3.0)
        assert tables.reload(model2) is True  # in place
        after = programs.score_padded(feats, codes, 1)[0]
        want = x @ np.asarray(model2["global"].model.coefficients.means)
        np.testing.assert_allclose(after, want, rtol=1e-5)
        assert not np.isclose(before, after)
        # the quiesced donating variant lands the same values through
        # the in-place buffer write (donation itself is a no-op on the
        # CPU backend, but the code path and value routing are shared)
        model3 = _glmix_model(rng, scale=0.25)
        assert tables.reload(model3, donate=True) is True
        after3 = programs.score_padded(feats, codes, 1)[0]
        np.testing.assert_allclose(
            after3,
            x @ np.asarray(model3["global"].model.coefficients.means),
            rtol=1e-5,
        )
        # the ladder never recompiled: same executables serve the
        # swapped buffers (coefficients are traced operands)
        assert programs.stats["programs_compiled"] == compiled_before

    def test_reload_structure_change_rebuilds(self, rng):
        tables = CoefficientTables.from_game_model(_glmix_model(rng))
        bigger = _glmix_model(rng, entities=E + 4)
        assert tables.reload(bigger) is False
        assert tables.random["per-user"].num_entities == E + 4
        assert tables.random["per-user"].code_for(str(E + 3)) == E + 3

    def test_reload_vocab_or_projector_change_is_not_in_place(self, rng):
        """Same shapes but a different entity vocabulary (or projector)
        must take the rebuild path: old row codes would index the wrong
        entities in the new tables, so the values-only in-place
        contract excludes it."""
        base = _glmix_model(rng)
        tables = CoefficientTables.from_game_model(base)
        ruser = base["per-user"]
        shuffled = GameModel({
            "global": base["global"],
            "per-user": RandomEffectModel(
                coefficients=ruser.coefficients,
                random_effect_type=ruser.random_effect_type,
                feature_shard_id=ruser.feature_shard_id,
                task=ruser.task,
                proj_all=ruser.proj_all,
                entity_keys=tuple(reversed(ruser.entity_keys)),
            ),
        })
        assert tables.reload(shuffled) is False
        tables2 = CoefficientTables.from_game_model(base)
        reproj = GameModel({
            "global": base["global"],
            "per-user": RandomEffectModel(
                coefficients=ruser.coefficients,
                random_effect_type=ruser.random_effect_type,
                feature_shard_id=ruser.feature_shard_id,
                task=ruser.task,
                proj_all=ruser.proj_all[:, ::-1].copy(),  # same shape
                entity_keys=ruser.entity_keys,
            ),
        })
        assert tables2.reload(reproj) is False


class TestDatasetParity:
    @pytest.mark.parametrize("sparse_user", [False, True])
    def test_serve_batch_matches_game_transformer(self, rng, sparse_user):
        model = _glmix_model(rng)
        data = _dataset(rng, n=257, sparse_user=sparse_user)
        tables = CoefficientTables.from_game_model(model)
        programs = ScorePrograms(
            tables,
            ladder=ShapeLadder((1, 8, 64, 128)),
            specs=specs_from_dataset(data),
        )
        mine = programs.score_dataset(data)
        ref = np.asarray(GameTransformer(model).score(data))
        np.testing.assert_allclose(mine, ref, rtol=1e-5, atol=1e-6)

    def test_shared_re_type_distinct_vocabularies(self, rng):
        """Two random-effect coordinates may share a re_type while
        training DISTINCT entity vocabularies; each table must resolve
        row codes against its OWN entity_keys (a per-type code vector
        would silently gather the wrong entity's coefficients)."""
        base = _glmix_model(rng)
        ruser = base["per-user"]
        # second coordinate, same type/shard, REVERSED entity order
        shuffled = RandomEffectModel(
            coefficients=jnp.asarray(
                rng.normal(size=(E, S)).astype(np.float32)),
            random_effect_type="userId",
            feature_shard_id="userShard",
            task=ruser.task,
            proj_all=ruser.proj_all[::-1].copy(),
            entity_keys=tuple(reversed(ruser.entity_keys)),
        )
        model = GameModel({**base.models, "per-user-2": shuffled})
        data = _dataset(rng, n=130)
        tables = CoefficientTables.from_game_model(model)
        programs = ScorePrograms(
            tables,
            ladder=ShapeLadder((64, 128)),
            specs=specs_from_dataset(data),
        )
        np.testing.assert_allclose(
            programs.score_dataset(data),
            np.asarray(GameTransformer(model).score(data)),
            rtol=1e-5, atol=1e-6,
        )

    def test_cli_score_route_matches_transformer_route(self, rng):
        """The satellite contract: cli/score.py's batch scoring routes
        through serve/tables + the AOT ladder and produces identical
        scores (and evaluation) to the ad-hoc transform path it
        replaced."""
        from photon_tpu.cli.score import score_game_dataset

        model = _glmix_model(rng)
        data = _dataset(rng)
        serve_scores, serve_eval = score_game_dataset(
            model, data, mesh=None, evaluators=["RMSE"]
        )
        ref_scores, ref_eval = GameTransformer(model).transform(
            data, evaluators=["RMSE"]
        )
        np.testing.assert_allclose(
            np.asarray(serve_scores), np.asarray(ref_scores),
            rtol=1e-5, atol=1e-6,
        )
        assert serve_eval is not None and ref_eval is not None
        np.testing.assert_allclose(
            serve_eval.evaluations["RMSE"], ref_eval.evaluations["RMSE"],
            rtol=1e-6,
        )

    def test_cli_score_route_mesh_falls_back(self, rng, mesh):
        """With a mesh the GameTransformer route is kept (row-sharded
        score tables have no fixed per-request shape)."""
        from photon_tpu.cli.score import score_game_dataset

        model = _glmix_model(rng)
        data = _dataset(rng, n=64)
        scores, _ = score_game_dataset(model, data, mesh=mesh)
        ref = np.asarray(GameTransformer(model, mesh=mesh).score(data))
        np.testing.assert_allclose(
            np.asarray(scores), ref, rtol=1e-5, atol=1e-6
        )


class TestModelIoRoundTrip:
    """io/model_io round trips of the tables serving consumes, asserted
    by score parity with the training-time GameTransformer path."""

    def _index_maps(self):
        from photon_tpu.data.index_map import IndexMap

        return {
            "features": IndexMap({str(i): i for i in range(D)}),
            "userShard": IndexMap({str(i): i for i in range(DU)}),
        }

    def _serve_scores(self, model, data):
        tables = CoefficientTables.from_game_model(model)
        programs = ScorePrograms(
            tables,
            ladder=ShapeLadder((64, 512)),
            specs=specs_from_dataset(data),
        )
        return programs.score_dataset(data)

    def test_avro_round_trip_scores_match_transformer(self, rng, tmp_path):
        from photon_tpu.io.model_io import load_game_model, save_game_model

        model = _glmix_model(rng)
        save_game_model(model, str(tmp_path), self._index_maps())
        loaded, _ = load_game_model(str(tmp_path), self._index_maps())
        # rows include entities present in the model AND cold entities
        data = _dataset(rng, cold_users=4)
        assert (
            np.asarray(
                data.id_tags["userId"].host_codes()
            ).max() >= E
        )  # the fixture really exercises the cold path
        np.testing.assert_allclose(
            self._serve_scores(loaded, data),
            np.asarray(GameTransformer(model).score(data)),
            rtol=1e-5, atol=1e-6,
        )

    def test_empty_random_effect_coordinate(self, rng, tmp_path):
        from photon_tpu.io.model_io import load_game_model, save_game_model

        model = _glmix_model(rng, entities=0)
        save_game_model(model, str(tmp_path), self._index_maps())
        loaded, _ = load_game_model(str(tmp_path), self._index_maps())
        assert loaded["per-user"].num_entities == 0
        data = _dataset(rng, n=65)
        got = self._serve_scores(loaded, data)
        ref = np.asarray(GameTransformer(loaded).score(data))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        # every row is cold: pure fixed-effect scores
        w_fe = np.asarray(loaded["global"].model.coefficients.means)
        x = np.asarray(data.feature_shards["features"].x)
        np.testing.assert_allclose(got, x @ w_fe, rtol=1e-4, atol=1e-5)

    def test_checkpoint_round_trip_serves(self, rng, tmp_path):
        from photon_tpu.io.model_io import load_checkpoint, save_checkpoint

        model = _glmix_model(rng)
        path = str(tmp_path / "ckpt")
        save_checkpoint(model, path)
        loaded = load_checkpoint(path)
        data = _dataset(rng, n=100)
        np.testing.assert_allclose(
            self._serve_scores(loaded, data),
            np.asarray(GameTransformer(model).score(data)),
            rtol=1e-5, atol=1e-6,
        )

    def test_model_reload_in_place_from_disk(self, rng, tmp_path):
        """The serving refresh cycle: day-2 model saved, loaded, swapped
        into live tables in place; scores flip to the new model without
        a program rebuild."""
        from photon_tpu.io.model_io import load_game_model, save_game_model

        day1 = _glmix_model(rng)
        day2 = _glmix_model(rng, scale=2.0)
        # Both generations go through the disk format, as in the real
        # refresh cycle (a serving process always LOADS its model — and
        # the loaded dtype must match for the swap to stay in place).
        save_game_model(day1, str(tmp_path / "d1"), self._index_maps())
        day1_loaded, _ = load_game_model(
            str(tmp_path / "d1"), self._index_maps()
        )
        save_game_model(day2, str(tmp_path), self._index_maps())
        day2_loaded, _ = load_game_model(str(tmp_path), self._index_maps())

        data = _dataset(rng, n=64)
        tables = CoefficientTables.from_game_model(day1_loaded)
        programs = ScorePrograms(
            tables,
            ladder=ShapeLadder((64,)),
            specs=specs_from_dataset(data),
        )
        compiled = programs.stats["programs_compiled"]
        assert tables.reload(day2_loaded) is True
        np.testing.assert_allclose(
            programs.score_dataset(data),
            np.asarray(GameTransformer(day2).score(data)),
            rtol=1e-5, atol=1e-6,
        )
        assert programs.stats["programs_compiled"] == compiled

    def test_index_maps_from_model_dir(self, rng, tmp_path):
        from photon_tpu.io.model_io import load_game_model, save_game_model

        model = _glmix_model(rng)
        save_game_model(model, str(tmp_path), self._index_maps())
        maps = build_index_maps_from_model(str(tmp_path))
        assert set(maps) == {"features", "userShard"}
        # a standalone serving process can reload the model against the
        # maps recovered from its own records
        loaded, _ = load_game_model(str(tmp_path), maps)
        assert loaded["per-user"].num_entities == E


class TestQueue:
    def _programs(self, rng, rungs=(1, 4, 16)):
        tables = CoefficientTables.from_game_model(_glmix_model(rng))
        return tables, ScorePrograms(tables, ladder=ShapeLadder(rungs))

    def _request(self, rng, user="1"):
        return (
            {
                "features": rng.normal(size=D).astype(np.float32),
                "userShard": rng.normal(size=DU).astype(np.float32),
            },
            {"userId": user},
        )

    def test_batches_and_drains_on_close(self, rng):
        _, programs = self._programs(rng)
        q = MicroBatchQueue(programs, max_linger_s=10.0)  # no linger flush
        futs = [q.submit(*self._request(rng)) for _ in range(10)]
        q.close()  # drain: every future resolves despite the long linger
        vals = [f.result(timeout=5) for f in futs]
        assert all(np.isfinite(vals))
        stats = q.stats()
        assert stats["requests"] == 10
        assert stats["batched_requests"] == 10

    def test_full_batch_flushes_before_linger(self, rng):
        _, programs = self._programs(rng)
        with MicroBatchQueue(
            programs, max_batch=4, max_linger_s=30.0
        ) as q:
            futs = [q.submit(*self._request(rng)) for _ in range(4)]
            # a full batch must flush promptly despite the huge linger
            t0 = time.perf_counter()
            vals = [f.result(timeout=10) for f in futs]
            assert time.perf_counter() - t0 < 10
            assert len(vals) == 4
            assert q.stats()["batches"] >= 1

    def test_linger_flushes_partial_batch(self, rng):
        _, programs = self._programs(rng)
        with MicroBatchQueue(
            programs, max_batch=16, max_linger_s=0.01
        ) as q:
            fut = q.submit(*self._request(rng))
            assert np.isfinite(fut.result(timeout=10))
            assert q.stats()["mean_batch_size"] < 16

    def test_zero_max_batch_rejected(self, rng):
        _, programs = self._programs(rng)
        with pytest.raises(ValueError):
            MicroBatchQueue(programs, max_batch=0)

    def test_submit_after_close_raises(self, rng):
        _, programs = self._programs(rng)
        q = MicroBatchQueue(programs)
        q.close()
        with pytest.raises(QueueClosed):
            q.submit(*self._request(rng))
        q.close()  # idempotent

    def test_cold_entity_accounting(self, rng):
        _, programs = self._programs(rng)
        with MicroBatchQueue(programs, max_linger_s=0.001) as q:
            futs = [
                q.submit(*self._request(rng, user=u))
                for u in ("0", "cold-a", "1", "cold-b")
            ]
            for f in futs:
                f.result(timeout=10)
        stats = q.stats()
        assert stats["cold_lookups"] == 2
        assert stats["entity_lookups"] == 4
        assert stats["cold_entity_rate"] == 0.5

    def test_dispatch_error_fans_out_and_queue_survives(self, rng):
        _, programs = self._programs(rng)
        with MicroBatchQueue(programs, max_linger_s=0.001) as q:
            bad = q.submit({"features": "not-an-array"}, {})
            assert isinstance(bad.exception(timeout=10), Exception)
            # queue keeps serving after a poisoned batch
            good = q.submit(*self._request(rng))
            assert np.isfinite(good.result(timeout=10))
        assert q.stats()["dispatch_errors"] == 1

    def test_concurrent_producers(self, rng):
        _, programs = self._programs(rng)
        results: list[float] = []
        lock = threading.Lock()
        with MicroBatchQueue(
            programs, max_linger_s=0.001, max_queue=32
        ) as q:

            def producer(seed):
                prng = np.random.default_rng(seed)
                futs = [
                    q.submit(*self._request(prng, user=str(seed % E)))
                    for _ in range(40)
                ]
                vals = [f.result(timeout=30) for f in futs]
                with lock:
                    results.extend(vals)

            threads = [
                threading.Thread(target=producer, args=(i,))
                for i in range(4)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert len(results) == 160
        assert np.isfinite(results).all()
        assert q.stats()["requests"] == 160

    def test_raising_callback_does_not_kill_worker(self, rng):
        _, programs = self._programs(rng)
        with MicroBatchQueue(programs, max_linger_s=0.001) as q:
            bad = q.submit(*self._request(rng))
            bad.add_done_callback(
                lambda f: (_ for _ in ()).throw(RuntimeError("boom"))
            )
            assert np.isfinite(bad.result(timeout=10))
            # the worker survived the raising callback and keeps serving
            good = q.submit(*self._request(rng))
            assert np.isfinite(good.result(timeout=10))

    def test_future_callback_never_lost(self, rng):
        """Register-vs-resolve race: a callback added around resolution
        time must run exactly once (the driver's latency accounting
        depends on it)."""
        _, programs = self._programs(rng)
        fired = []
        with MicroBatchQueue(programs, max_linger_s=0.0) as q:
            for _ in range(50):
                fut = q.submit(*self._request(rng))
                fut.add_done_callback(lambda f: fired.append(1))
                fut.result(timeout=10)
        assert len(fired) == 50


class TestPipelinedStaging:
    """The double-buffered staging pipeline: batch k+1's host pack
    overlaps batch k's device dispatch. Scores, drain guarantees, and
    the hot-reload contract must be indistinguishable from the serial
    queue — only the stats may differ."""

    def _programs(self, rng, rungs=(1, 4, 16)):
        tables = CoefficientTables.from_game_model(_glmix_model(rng))
        return tables, ScorePrograms(tables, ladder=ShapeLadder(rungs))

    def _requests(self, seed, n):
        prng = np.random.default_rng(seed)
        return [
            (
                {
                    "features": prng.normal(size=D).astype(np.float32),
                    "userShard": prng.normal(size=DU).astype(np.float32),
                },
                {"userId": str(i % (E + 2))},  # some cold
            )
            for i in range(n)
        ]

    def test_pipelined_matches_serial_byte_identical(self, rng):
        model = _glmix_model(rng)
        reqs = self._requests(7, 60)
        outs = {}
        for pipelined in (False, True):
            tables = CoefficientTables.from_game_model(model)
            programs = ScorePrograms(tables, ladder=ShapeLadder((1, 4)))
            with MicroBatchQueue(
                programs, max_linger_s=0.001,
                pipeline_staging=pipelined,
            ) as q:
                futs = [q.submit(*r) for r in reqs]
                outs[pipelined] = np.asarray(
                    [f.result(timeout=30) for f in futs]
                )
            if pipelined:
                assert q.stats()["staged_batches"] >= 1
        assert np.array_equal(outs[False], outs[True])

    def test_staging_stats_surfaced(self, rng):
        _, programs = self._programs(rng)
        with MicroBatchQueue(programs, max_linger_s=0.001) as q:
            futs = [
                q.submit(*r) for r in self._requests(9, 30)
            ]
            for f in futs:
                f.result(timeout=30)
        stats = q.stats()
        assert stats["staged_batches"] >= 1
        assert 0.0 <= stats["staging_overlap_fraction"] <= 1.0
        assert stats["staging_seconds"] >= 0.0
        health = q.health()
        assert health["pipeline_staging"] is True
        fams = {f["name"] for f in q.metrics_families()}
        assert "serve_staging_overlap_fraction" in fams

    def test_hammer_quiesce_and_reload_mid_stream(self, rng):
        """Concurrent producers + a quiesce window + two values-only
        reloads against the LIVE pipelined queue: every future must
        resolve (no stranded staged batch), counters must balance."""
        tables, programs = self._programs(rng)
        futures: list = []
        lock = threading.Lock()

        with MicroBatchQueue(
            programs, max_linger_s=0.001, max_queue=64,
        ) as q:

            def producer(seed):
                prng = np.random.default_rng(seed)
                for _ in range(40):
                    fut = q.submit(
                        {
                            "features": prng.normal(size=D)
                            .astype(np.float32),
                            "userShard": prng.normal(size=DU)
                            .astype(np.float32),
                        },
                        {"userId": str(seed % E)},
                    )
                    with lock:
                        futures.append(fut)

            threads = [
                threading.Thread(target=producer, args=(i,))
                for i in range(4)
            ]
            for t in threads:
                t.start()
            # reload mid-stream: same structure (fixed projector
            # seed), fresh values -> the zero-recompile swap, while a
            # staged batch may be in the hand-off slot
            for attempt in range(2):
                out = q.reload_model(
                    _glmix_model(np.random.default_rng(100 + attempt))
                )
                assert out["values_only"] is True
                assert out["programs_compiled"] == 0
            # a quiesce window mid-stream must park the worker without
            # dropping anything queued OR staged
            with q.quiesce():
                time.sleep(0.01)
            for t in threads:
                t.join()
        # close() drained: zero stranded futures
        assert len(futures) == 160
        assert all(f.done() for f in futures)
        vals = [f.result(timeout=1) for f in futures]
        assert np.isfinite(vals).all()
        stats = q.stats()
        assert stats["requests"] == 160
        assert stats["batched_requests"] == 160
        assert stats["dispatch_errors"] == 0

    def test_serial_flag_disables_staging(self, rng):
        _, programs = self._programs(rng)
        with MicroBatchQueue(
            programs, max_linger_s=0.001, pipeline_staging=False,
        ) as q:
            futs = [q.submit(*r) for r in self._requests(5, 12)]
            for f in futs:
                assert np.isfinite(f.result(timeout=30))
        stats = q.stats()
        assert stats["staged_batches"] == 0
        assert stats["staging_overlapped_seconds"] == 0.0
        assert q.health()["pipeline_staging"] is False


class TestDriver:
    def test_drive_reports_tail_and_fill(self, rng):
        tables = CoefficientTables.from_game_model(_glmix_model(rng))
        programs = ScorePrograms(tables, ladder=ShapeLadder((1, 4, 16)))
        reqs = synthetic_requests(
            tables, programs, 300, cold_fraction=0.2, seed=3
        )
        with MicroBatchQueue(programs, max_linger_s=0.001) as q:
            out = drive(q, reqs, warmup=60)
        assert out["requests"] == 240
        assert out["errors"] == 0
        assert out["p50_ms"] <= out["p99_ms"] <= out["max_ms"]
        assert out["qps"] > 0
        assert 0 < out["batch_fill_fraction"] <= 1
        # 20% nominal cold traffic, binomial noise at n=300
        assert 0.08 < out["cold_entity_rate"] < 0.35

    def test_paced_drive(self, rng):
        tables = CoefficientTables.from_game_model(_glmix_model(rng))
        programs = ScorePrograms(tables, ladder=ShapeLadder((1, 4)))
        reqs = synthetic_requests(tables, programs, 40, seed=1)
        with MicroBatchQueue(programs, max_linger_s=0.001) as q:
            out = drive(q, reqs, warmup=8, rate=2000.0)
        assert out["offered_rate"] == 2000.0
        assert out["errors"] == 0


class TestServeCli:
    def test_serve_cli_end_to_end(self, rng, tmp_path, capsys):
        """Train-less CLI smoke: save a model, serve synthetic traffic
        against it, check the emitted JSON carries the bench fields and
        the zero-recompile evidence."""
        import json

        from photon_tpu.cli.serve import main as serve_main
        from photon_tpu.data.index_map import IndexMap
        from photon_tpu.io.model_io import save_game_model

        model = _glmix_model(rng)
        save_game_model(
            model, str(tmp_path / "model"),
            {
                "features": IndexMap({str(i): i for i in range(D)}),
                "userShard": IndexMap({str(i): i for i in range(DU)}),
            },
        )
        rc = serve_main([
            "--model-dir", str(tmp_path / "model"),
            "--synthetic", "300",
            "--batch-sizes", "1,8,32",
            "--max-linger-ms", "1",
            "--json", str(tmp_path / "serve.json"),
        ])
        assert rc == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        for key in ("p50_ms", "p99_ms", "qps", "batch_fill_fraction",
                    "cold_entity_rate"):
            assert out[key] is not None, key
        assert out["programs_compiled"] == 3
        assert out["errors"] == 0
        assert (tmp_path / "serve.json").is_file()


class TestDegradedServing:
    """The resilience layer's serving half (RESILIENCE.md): deadlines,
    shedding, the dispatch circuit breaker, bounded shutdown, retry,
    and the health snapshot. Every knob defaults OFF — the clean-path
    tests above run the queue exactly as before."""

    def _programs(self, rng, rungs=(1, 4)):
        tables = CoefficientTables.from_game_model(_glmix_model(rng))
        return tables, ScorePrograms(tables, ladder=ShapeLadder(rungs))

    def _request(self, rng, user="1"):
        return (
            {
                "features": rng.normal(size=D).astype(np.float32),
                "userShard": rng.normal(size=DU).astype(np.float32),
            },
            {"userId": user},
        )

    def test_expired_deadline_fails_fast_before_dispatch(self, rng):
        from photon_tpu.resilience import DeadlineExceededError

        _, programs = self._programs(rng)
        with MicroBatchQueue(
            programs, max_batch=4, max_linger_s=0.2
        ) as q:
            dead = q.submit(*self._request(rng), deadline_s=0.0)
            exc = dead.exception(timeout=10)
            assert isinstance(exc, DeadlineExceededError)
            # the queue keeps serving deadline-free requests
            ok = q.submit(*self._request(rng))
            assert np.isfinite(ok.result(timeout=10))
        stats = q.stats()
        assert stats["deadline_expired"] == 1
        # the expired request never reached a batch
        assert stats["batched_requests"] == 1

    def test_default_deadline_applies(self, rng):
        from photon_tpu.resilience import DeadlineExceededError

        _, programs = self._programs(rng)
        with MicroBatchQueue(
            programs, max_batch=4, max_linger_s=0.2,
            default_deadline_s=0.0,
        ) as q:
            fut = q.submit(*self._request(rng))
            assert isinstance(
                fut.exception(timeout=10), DeadlineExceededError
            )

    def test_deadline_tighter_than_linger_is_served(self, rng):
        """A deadline shorter than ``max_linger_s`` must cut the linger
        short and DISPATCH the request in time — not let it expire on an
        idle device while the worker waits out the full linger."""
        _, programs = self._programs(rng)
        with MicroBatchQueue(
            programs, max_batch=4, max_linger_s=5.0,
        ) as q:
            t0 = time.perf_counter()
            fut = q.submit(*self._request(rng), deadline_s=0.25)
            # served (not DeadlineExceededError), and well before the
            # 5s linger would have flushed it
            assert np.isfinite(fut.result(timeout=10))
            assert time.perf_counter() - t0 < 2.0
        stats = q.stats()
        assert stats["deadline_expired"] == 0
        assert stats["batched_requests"] == 1

    def test_shed_beyond_watermark(self, rng):
        from photon_tpu.resilience import OverloadedError

        _, programs = self._programs(rng)
        # A wedge dispatch holds the worker so the queue depth is
        # controlled deterministically.
        release = threading.Event()

        class Slow:
            ladder = programs.ladder
            tables = programs.tables

            def pack_requests(self, reqs):
                release.wait(30)
                return programs.pack_requests(reqs)

            def score_padded(self, *a):
                return programs.score_padded(*a)

        q = MicroBatchQueue(
            Slow(), max_batch=1, max_linger_s=0.0, shed_watermark=2
        )
        try:
            first = q.submit(*self._request(rng))  # taken by worker
            # wait until the worker holds it (pending drained)
            deadline = time.time() + 10
            while q.stats()["queued_now"] and time.time() < deadline:
                time.sleep(0.01)
            queued = [q.submit(*self._request(rng)) for _ in range(2)]
            with pytest.raises(OverloadedError):
                q.submit(*self._request(rng))
            assert q.stats()["shed"] == 1
            release.set()
            assert np.isfinite(first.result(timeout=10))
            for f in queued:
                assert np.isfinite(f.result(timeout=10))
        finally:
            release.set()
            q.close()

    def test_transient_dispatch_fault_is_retried(self, rng):
        from photon_tpu.resilience import FaultPlan, faults

        _, programs = self._programs(rng)
        plan = FaultPlan(
            [dict(point="serve.dispatch", nth=1, error="transient")]
        )
        with faults.injected(plan):
            with MicroBatchQueue(programs, max_linger_s=0.001) as q:
                fut = q.submit(*self._request(rng))
                assert np.isfinite(fut.result(timeout=10))
        stats = q.stats()
        assert stats["dispatch_retries"] == 1
        assert stats["dispatch_errors"] == 0

    def test_poison_fans_out_to_its_batch_only(self, rng):
        from photon_tpu.resilience import FaultPlan, PoisonError, faults

        _, programs = self._programs(rng)
        plan = FaultPlan(
            [dict(point="serve.dispatch", nth=1, error="poison")]
        )
        with faults.injected(plan):
            with MicroBatchQueue(
                programs, max_batch=4, max_linger_s=0.01
            ) as q:
                bad = [q.submit(*self._request(rng)) for _ in range(4)]
                for f in bad:
                    f.exception(timeout=10)
                good = [q.submit(*self._request(rng)) for _ in range(4)]
                for f in good:
                    assert np.isfinite(f.result(timeout=10))
        assert all(
            isinstance(f.exception(), PoisonError) for f in bad
        )
        stats = q.stats()
        assert stats["dispatch_errors"] == 1  # one poisoned batch
        assert stats["dispatch_retries"] == 0  # poison is never retried

    def test_breaker_trips_drains_and_resets(self, rng):
        from photon_tpu.resilience import (
            CircuitOpenError,
            FaultPlan,
            PoisonError,
            faults,
        )

        _, programs = self._programs(rng)
        plan = FaultPlan(
            [dict(point="serve.dispatch", probability=1.0,
                  error="poison")],
            seed=1,
        )
        q = MicroBatchQueue(
            programs, max_batch=1, max_linger_s=0.0,
            breaker_threshold=2,
        )
        try:
            with faults.injected(plan):
                futs = [q.submit(*self._request(rng)) for _ in range(2)]
                for f in futs:
                    assert isinstance(
                        f.exception(timeout=10), PoisonError
                    )
                with pytest.raises(CircuitOpenError):
                    q.submit(*self._request(rng))
            health = q.health()
            assert health["breaker_open"] is True
            assert health["breaker_trips"] == 1
            assert health["breaker_rejected"] == 1
            # operator intervention: reset re-arms dispatch
            q.reset_breaker()
            fut = q.submit(*self._request(rng))
            assert np.isfinite(fut.result(timeout=10))
            assert q.health()["breaker_open"] is False
        finally:
            q.close()

    def test_close_timeout_strands_queued_requests(self, rng):
        from photon_tpu.resilience import ShutdownError

        _, programs = self._programs(rng)
        release = threading.Event()

        class Wedged:
            ladder = programs.ladder
            tables = programs.tables

            def pack_requests(self, reqs):
                release.wait(60)
                raise RuntimeError("wedged dispatch released")

            def score_padded(self, *a):  # pragma: no cover
                raise AssertionError

        q = MicroBatchQueue(
            Wedged(), max_batch=1, max_linger_s=0.0,
            dispatch_retry=None,
        )
        try:
            in_flight = q.submit(*self._request(rng))
            deadline = time.time() + 10
            while q.stats()["queued_now"] and time.time() < deadline:
                time.sleep(0.01)
            queued = q.submit(*self._request(rng))
            t0 = time.time()
            assert q.close(timeout=0.3) is False
            assert time.time() - t0 < 5
            # the still-queued request failed with the typed shutdown
            # error; the in-flight one stays owned by the worker
            assert isinstance(
                queued.exception(timeout=1), ShutdownError
            )
            assert q.stats()["shutdown_stranded"] == 1
            assert not in_flight.done()
        finally:
            release.set()

    def test_wedged_dispatch_cannot_hang_context_exit(self, rng):
        """The ``with`` block exits through close(close_timeout_s) —
        without the ctor knob the bounded-shutdown machinery is
        unreachable from the context-manager path — and a later
        close() with NO timeout polls the already-stranded worker
        instead of joining it forever."""
        from photon_tpu.resilience import ShutdownError

        _, programs = self._programs(rng)
        release = threading.Event()

        class Wedged:
            ladder = programs.ladder
            tables = programs.tables

            def pack_requests(self, reqs):
                release.wait(60)
                raise RuntimeError("wedged dispatch released")

            def score_padded(self, *a):  # pragma: no cover
                raise AssertionError

        try:
            t0 = time.time()
            with MicroBatchQueue(
                Wedged(), max_batch=1, max_linger_s=0.0,
                dispatch_retry=None, close_timeout_s=0.3,
            ) as q:
                q.submit(*self._request(rng))
                deadline = time.time() + 10
                while q.stats()["queued_now"] and time.time() < deadline:
                    time.sleep(0.01)
                queued = q.submit(*self._request(rng))
            assert time.time() - t0 < 8  # __exit__ did not join forever
            assert isinstance(queued.exception(timeout=1), ShutdownError)
            # second close, unbounded by argument: must return promptly
            t0 = time.time()
            assert q.close() is False
            assert time.time() - t0 < 2
            assert q.stats()["shutdown_stranded"] == 1  # not re-counted
        finally:
            release.set()

    def test_close_without_timeout_still_drains(self, rng):
        _, programs = self._programs(rng)
        q = MicroBatchQueue(programs, max_linger_s=10.0)
        futs = [q.submit(*self._request(rng)) for _ in range(5)]
        assert q.close() is True
        assert all(np.isfinite(f.result(timeout=1)) for f in futs)

    def test_health_snapshot_fields(self, rng):
        tables, programs = self._programs(rng)
        with MicroBatchQueue(
            programs, max_linger_s=0.001, shed_watermark=100,
            breaker_threshold=8, default_deadline_s=5.0,
        ) as q:
            q.submit(*self._request(rng)).result(timeout=10)
            health = q.health()
        assert health["queue_depth"] == 0
        assert health["requests"] == 1
        assert health["breaker_open"] is False
        assert health["shed"] == 0
        assert health["deadline_expired"] == 0
        assert health["dispatch_retries"] == 0
        assert health["shed_watermark"] == 100
        assert health["breaker_threshold"] == 8
        assert health["table_generation"] == 0
        # a reload bumps the generation the snapshot reports
        tables.reload(_glmix_model(np.random.default_rng(5), scale=2.0))
        assert q.health()["table_generation"] == 1

    def test_clean_run_records_zero_degraded_events(self, rng):
        """Acceptance: a clean serve run records ZERO sheds/retries/
        deadline expiries/breaker activity."""
        tables, programs = self._programs(rng)
        reqs = synthetic_requests(tables, programs, 120, seed=3)
        with MicroBatchQueue(
            programs, max_linger_s=0.001, shed_watermark=4096,
            breaker_threshold=8, default_deadline_s=30.0,
        ) as q:
            out = drive(q, reqs, warmup=20)
        assert out["errors"] == 0
        health = q.health()
        for key in ("shed", "deadline_expired", "dispatch_retries",
                    "dispatch_errors", "breaker_trips"):
            assert health[key] == 0, (key, health)
