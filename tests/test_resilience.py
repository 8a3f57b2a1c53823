"""photon_tpu.resilience: fault injection, retry, checkpoints, resume.

The chaos contract under test (ISSUE 7 / RESILIENCE.md):

- a seeded FaultPlan is DETERMINISTIC — same seed, same call sequence,
  same faults, including under the 2-core CI box's thread pools;
- transient faults at the compile/transfer/dispatch sites are retried
  to success with backoff; poison faults are never retried;
- training checkpoints are atomic: a fault injected mid-write leaves
  the previous checkpoint loadable;
- kill-and-resume equivalence: training crashed after iteration k
  resumes from the checkpoint and converges to the uninterrupted run's
  model (within reassociation tolerance); a changed configuration is
  rejected via the manifest static key;
- the CD non-finite guard rolls a poisoned coordinate update back to
  the previous iterate instead of corrupting the model;
- corrupt model/checkpoint artifacts raise CorruptModelError naming
  the file, not codec tracebacks;
- SIGINT/SIGTERM mid-fit commits an emergency checkpoint and exits
  nonzero (in-process via the sigterm fault kind, and as a REAL
  subprocess receiving a REAL signal).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import optim
from photon_tpu.algorithm.coordinate_descent import CoordinateDescent
from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
from photon_tpu.data.dataset import DenseFeatures
from photon_tpu.data.game_data import make_game_dataset
from photon_tpu.data.random_effect import RandomEffectDataConfiguration
from photon_tpu.estimators.game_estimator import (
    FixedEffectCoordinateConfiguration,
    GameEstimator,
    RandomEffectCoordinateConfiguration,
)
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.resilience import (
    CorruptModelError,
    FaultPlan,
    FaultSpec,
    InjectedCrash,
    NonFiniteUpdateError,
    PoisonError,
    ResumeMismatchError,
    RetryPolicy,
    TrainingCheckpointer,
    TransientError,
    call_with_retry,
    faults,
    load_training_checkpoint,
    reset_retry_stats,
    retry_stats,
    training_static_key,
)
from photon_tpu.types import TaskType

REPO_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    """Every test starts disarmed with zeroed retry counters."""
    faults.disarm()
    reset_retry_stats()
    yield
    faults.disarm()
    reset_retry_stats()


# --------------------------------------------------------------------------
# shared tiny GLMix workload
# --------------------------------------------------------------------------

N, D, DU, E = 400, 5, 4, 8


def _glmix_data(rng):
    x = rng.normal(size=(N, D)).astype(np.float32)
    x[:, -1] = 1.0
    xu = rng.normal(size=(N, DU)).astype(np.float32)
    xu[:, -1] = 1.0
    users = rng.integers(0, E, size=N)
    y = (rng.uniform(size=N) < 0.5).astype(np.float32)
    return make_game_dataset(
        y,
        {"global": DenseFeatures(x), "userShard": DenseFeatures(xu)},
        id_tags={"userId": users},
    )


def _l2(w):
    return GLMOptimizationConfiguration(
        regularization=optim.RegularizationContext(
            optim.RegularizationType.L2
        ),
        regularization_weight=w,
    )


def _estimator(num_iterations=3, lam=0.5, **kwargs):
    return GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {
            "global": FixedEffectCoordinateConfiguration(
                "global", _l2(0.01)
            ),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "userShard"),
                _l2(lam),
            ),
        },
        num_iterations=num_iterations,
        mesh="off",
        **kwargs,
    )


def _weights(model, cid):
    sub = model[cid]
    if hasattr(sub, "model"):  # FixedEffectModel
        return np.asarray(sub.model.coefficients.means)
    return np.asarray(sub.coefficients)


# --------------------------------------------------------------------------
# FaultPlan
# --------------------------------------------------------------------------


class TestFaultPlan:
    def test_nth_triggers_exactly_once(self):
        plan = FaultPlan([dict(point="compile.aot", nth=3)])
        with faults.injected(plan):
            faults.check("compile.aot")
            faults.check("compile.aot")
            with pytest.raises(TransientError):
                faults.check("compile.aot")
            faults.check("compile.aot")  # one-shot: call 4 passes
            assert faults.fired() == [
                {"point": "compile.aot", "call": 3, "error": "transient"}
            ]

    def test_probability_is_seed_deterministic(self):
        def draw(seed):
            plan = FaultPlan(
                [dict(point="serve.dispatch", probability=0.3)],
                seed=seed,
            )
            hits = []
            with faults.injected(plan):
                for i in range(50):
                    try:
                        faults.check("serve.dispatch")
                        hits.append(0)
                    except TransientError:
                        hits.append(1)
            return hits

        assert draw(7) == draw(7)
        assert draw(7) != draw(8)
        assert sum(draw(7)) > 0

    def test_points_have_independent_substreams(self):
        spec = dict(point="serve.dispatch", probability=0.5)
        solo = FaultPlan([spec], seed=1)
        with faults.injected(solo):
            pattern_solo = []
            for _ in range(20):
                try:
                    faults.check("serve.dispatch")
                    pattern_solo.append(0)
                except TransientError:
                    pattern_solo.append(1)
        # Interleaving calls to ANOTHER point must not perturb the draws.
        both = FaultPlan(
            [spec, dict(point="compile.aot", probability=0.5)], seed=1
        )
        with faults.injected(both):
            pattern_both = []
            for _ in range(20):
                try:
                    faults.check("compile.aot")
                except TransientError:
                    pass
                try:
                    faults.check("serve.dispatch")
                    pattern_both.append(0)
                except TransientError:
                    pattern_both.append(1)
        assert pattern_solo == pattern_both

    def test_error_kinds_and_validation(self):
        with pytest.raises(ValueError, match="unknown injection point"):
            FaultSpec(point="nope", nth=1)
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultSpec(point="compile.aot", nth=1, error="explode")
        with pytest.raises(ValueError, match="exactly one"):
            FaultSpec(point="compile.aot")
        plan = FaultPlan([
            dict(point="fit.dispatch", nth=1, error="poison"),
            dict(point="cd.iteration", nth=1, error="crash"),
        ])
        with faults.injected(plan):
            with pytest.raises(PoisonError):
                faults.check("fit.dispatch")
            with pytest.raises(InjectedCrash):
                faults.check("cd.iteration")

    def test_disarmed_check_is_noop(self):
        faults.check("serve.dispatch")  # no plan armed: nothing happens
        assert faults.fired() == []

    def test_arm_from_env(self, monkeypatch):
        monkeypatch.setenv(
            faults.ENV_VAR,
            json.dumps({"seed": 5, "faults": [
                {"point": "transfer.packed", "nth": 1}
            ]}),
        )
        plan = faults.arm_from_env()
        try:
            assert plan is not None and plan.seed == 5
            with pytest.raises(TransientError):
                faults.check("transfer.packed")
        finally:
            faults.disarm()


# --------------------------------------------------------------------------
# retry
# --------------------------------------------------------------------------


class TestRetry:
    fast = RetryPolicy(max_attempts=3, base_delay_s=0.001)

    def test_transient_recovers_and_counts(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise TransientError("blip")
            return "ok"

        assert call_with_retry(flaky, site="t", policy=self.fast) == "ok"
        stats = retry_stats()
        assert stats["retries"] == 2
        assert stats["recovered"] == 1
        assert stats["exhausted"] == 0

    def test_exhausted_raises_last_error(self):
        def dead():
            raise TransientError("never clears")

        with pytest.raises(TransientError):
            call_with_retry(dead, site="t", policy=self.fast)
        assert retry_stats()["exhausted"] == 1

    def test_non_transient_never_retried(self):
        calls = []

        def poison():
            calls.append(1)
            raise PoisonError("deterministic")

        with pytest.raises(PoisonError):
            call_with_retry(poison, site="t", policy=self.fast)
        assert len(calls) == 1
        assert retry_stats() == {
            "retries": 0, "recovered": 0, "exhausted": 0,
            "backoff_seconds": 0.0,
        }

    def test_backoff_schedule_deterministic_and_capped(self):
        policy = RetryPolicy(
            max_attempts=6, base_delay_s=0.1, max_delay_s=0.3,
            jitter=0.5,
        )
        rng_a = np.random.default_rng(11)
        rng_b = np.random.default_rng(11)
        a = [policy.delay_for(i, rng_a) for i in range(1, 6)]
        b = [policy.delay_for(i, rng_b) for i in range(1, 6)]
        assert a == b  # same seed, same schedule
        assert all(d <= 0.3 * 1.5 for d in a)  # cap + jitter bound
        assert all(d >= 0 for d in a)

    def test_clean_run_records_zero(self):
        assert call_with_retry(lambda: 1, site="t") == 1
        assert retry_stats() == {
            "retries": 0, "recovered": 0, "exhausted": 0,
            "backoff_seconds": 0.0,
        }

    def test_real_backend_transient_is_retried(self):
        """Real faults do not arrive typed: jaxlib wraps a preemption
        blip or a flaky compile RPC in plain RuntimeError carrying a
        gRPC status string. The default classifier must retry those —
        otherwise every production retry site is dead code that only
        injected TransientError can exercise."""
        calls = []

        def preempted_once():
            calls.append(1)
            if len(calls) < 2:
                raise RuntimeError(
                    "UNAVAILABLE: Socket closed (worker preempted)")
            return "ok"

        assert call_with_retry(
            preempted_once, site="t", policy=self.fast
        ) == "ok"
        stats = retry_stats()
        assert stats["retries"] == 1
        assert stats["recovered"] == 1

    def test_deterministic_backend_error_not_retried(self):
        """A real XLA error without a transient status marker (compile
        bug, OOM, shape mismatch) fails on the FIRST attempt."""
        for exc in (
            RuntimeError("INVALID_ARGUMENT: dot shapes"),
            RuntimeError("RESOURCE_EXHAUSTED: out of memory on HBM"),
            ValueError("bad operand"),
        ):
            calls = []

            def det(exc=exc):
                calls.append(1)
                raise exc

            with pytest.raises(type(exc)):
                call_with_retry(det, site="t", policy=self.fast)
            assert len(calls) == 1

    def test_classify_none_restores_typed_only_retry(self):
        """classify=None: only ``retry_on`` types retry — chaos tests
        that must see ONLY injected faults recovered use this."""
        typed_only = RetryPolicy(
            max_attempts=3, base_delay_s=0.001, classify=None
        )
        calls = []

        def flaky():
            calls.append(1)
            raise RuntimeError("UNAVAILABLE: Socket closed")

        with pytest.raises(RuntimeError):
            call_with_retry(flaky, site="t", policy=typed_only)
        assert len(calls) == 1

    def test_is_transient_taxonomy(self):
        from photon_tpu.resilience.errors import (
            CheckpointError,
            ShutdownError,
            is_transient,
        )

        assert is_transient(TransientError("blip"))
        assert is_transient(ConnectionResetError("peer reset"))
        assert is_transient(OSError("Broken pipe"))
        assert is_transient(RuntimeError("ABORTED: slice restarting"))
        # our own typed failures are never transient, whatever the text
        assert not is_transient(PoisonError("UNAVAILABLE in message"))
        assert not is_transient(InjectedCrash("UNAVAILABLE"))
        assert not is_transient(CheckpointError("UNAVAILABLE"))
        assert not is_transient(ShutdownError("UNAVAILABLE"))
        assert not is_transient(RuntimeError("plain failure"))
        assert not is_transient(KeyError("x"))


# --------------------------------------------------------------------------
# injection points wired at the real boundaries
# --------------------------------------------------------------------------


class TestInjectionSites:
    def test_transient_fit_dispatch_is_retried(self, rng):
        data = _glmix_data(rng)
        plan = FaultPlan([dict(point="fit.dispatch", nth=1)])
        with faults.injected(plan):
            results = _estimator(num_iterations=1).fit(data)
            assert faults.fired() == [{
                "point": "fit.dispatch", "call": 1, "error": "transient"
            }]
        assert len(results) == 1
        assert retry_stats()["recovered"] == 1

    def test_transient_packed_transfer_is_retried(self, rng):
        data = _glmix_data(rng)
        plan = FaultPlan([dict(point="transfer.packed", nth=1)])
        with faults.injected(plan):
            results = _estimator(num_iterations=1).fit(data)
        assert len(results) == 1
        assert retry_stats()["recovered"] >= 1

    def test_transient_aot_compile_is_retried(self, rng):
        # The serve ladder goes through compile_cache.aot_compile.
        from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
        from photon_tpu.serve.tables import CoefficientTables

        model = _estimator(num_iterations=1).fit(_glmix_data(rng))[0].model
        tables = CoefficientTables.from_game_model(model)
        plan = FaultPlan([dict(point="compile.aot", nth=1)])
        with faults.injected(plan):
            programs = ScorePrograms(tables, ladder=ShapeLadder((1, 4)))
        assert programs.stats["programs_compiled"] == 2
        assert retry_stats()["recovered"] >= 1

    def test_transient_backend_fault_in_aot_fit_is_retried(
        self, rng, monkeypatch
    ):
        """A real backend fault (gRPC UNAVAILABLE) raised by the AOT
        fit executable must reach the retry wrapper — the stale-shape
        fallback must not swallow it, drop a perfectly good executable,
        and record zero retries for a real fault. Only the TypeError a
        compiled executable raises for other avals means the prediction
        was stale."""
        from photon_tpu.algorithm import fused_fit as ff

        calls = {"n": 0}

        class _AnyStatics:
            def __eq__(self, other):
                return True

            def __ne__(self, other):
                return False

        def fake_fit(ops, ebs_all):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("UNAVAILABLE: socket closed")
            raise TypeError("genuinely stale prediction")

        def fake_mat(mat_ops):
            raise TypeError("no AOT mat")  # falls back to jit mat

        fake = {
            "statics": _AnyStatics(), "fit": fake_fit, "mat": fake_mat
        }
        monkeypatch.setattr(
            ff.FusedFit, "_consume_aot", lambda self: fake
        )
        results = _estimator(num_iterations=1).fit(_glmix_data(rng))
        assert len(results) == 1
        # attempt 1 re-raised the transient (executable retained);
        # attempt 2 re-entered the SAME executable, whose stale-shape
        # TypeError then fell back to jit and succeeded.
        assert calls["n"] >= 2
        assert retry_stats()["recovered"] >= 1

    def test_device_failure_in_aot_fit_is_not_relabelled_stale(
        self, rng, monkeypatch
    ):
        """A deterministic failure of the AOT executable that is not a
        signature mismatch (a Mosaic fault, out of HBM) surfaces with
        its own message instead of being logged as a stale prediction
        and recompiled through the jit path."""
        from photon_tpu.algorithm import fused_fit as ff

        def fake_fit(ops, ebs_all):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

        class _AnyStatics:
            def __eq__(self, other):
                return True

        fake = {"statics": _AnyStatics(), "fit": fake_fit,
                "mat": lambda mat_ops: (_ for _ in ()).throw(
                    TypeError("no AOT mat"))}
        monkeypatch.setattr(
            ff.FusedFit, "_consume_aot", lambda self: fake
        )
        with pytest.raises(RuntimeError, match="out of HBM"):
            _estimator(num_iterations=1).fit(_glmix_data(rng))

    def test_poison_planner_thunk_propagates(self, rng):
        data = _glmix_data(rng)
        plan = FaultPlan(
            [dict(point="ingest.plan", nth=1, error="poison")]
        )
        with faults.injected(plan):
            with pytest.raises(PoisonError):
                _estimator(num_iterations=1).prepare(data)

    def test_poison_chunk_worker_propagates(self, monkeypatch):
        from photon_tpu.data import pipeline

        monkeypatch.setenv("PHOTON_TPU_INGEST_THREADS", "2")
        monkeypatch.delenv("PHOTON_TPU_SERIAL_INGEST", raising=False)
        monkeypatch.setattr(pipeline, "_CHUNK_MIN_ROWS", 8)
        out = np.zeros(64)
        plan = FaultPlan(
            [dict(point="ingest.chunk", nth=1, error="poison")]
        )
        with faults.injected(plan):
            with pytest.raises(PoisonError):
                pipeline.map_chunked(
                    lambda a: a * 2, out, np.arange(64.0)
                )


# --------------------------------------------------------------------------
# checkpoints
# --------------------------------------------------------------------------


def _tiny_model():
    from photon_tpu.models.game import FixedEffectModel, GameModel

    return GameModel({
        "g": FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(means=jnp.arange(4.0)),
                TaskType.LINEAR_REGRESSION,
            ),
            "features",
        )
    })


class TestCheckpointer:
    def test_round_trip_and_gc(self, tmp_path):
        ck = TrainingCheckpointer(str(tmp_path), "KEY")
        ck.save(_tiny_model(), config_index=0, iteration=0)
        ck.save(_tiny_model(), config_index=0, iteration=1)
        loaded = load_training_checkpoint(str(tmp_path))
        assert (loaded.config_index, loaded.iteration) == (0, 1)
        assert loaded.static_key == "KEY"
        assert not loaded.interrupted
        # superseded npz garbage-collected after the manifest commit
        npzs = [p for p in os.listdir(tmp_path) if p.endswith(".npz")]
        assert npzs == ["checkpoint-c000-i001.npz"]

    def test_mid_write_fault_leaves_previous_loadable(self, tmp_path):
        ck = TrainingCheckpointer(str(tmp_path), "KEY")
        ck.save(_tiny_model(), config_index=0, iteration=0)
        plan = FaultPlan([dict(point="checkpoint.write", nth=1)])
        with faults.injected(plan):
            with pytest.raises(TransientError):
                ck.save(_tiny_model(), config_index=0, iteration=1)
        loaded = load_training_checkpoint(str(tmp_path))
        assert loaded.iteration == 0  # previous commit intact
        # and no tmp debris was left behind
        assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]

    def test_hash_mismatch_is_corrupt(self, tmp_path):
        ck = TrainingCheckpointer(str(tmp_path), "KEY")
        path = ck.save(_tiny_model(), config_index=0, iteration=0)
        with open(path, "r+b") as f:
            f.seek(30)
            f.write(b"\xff\xff")
        with pytest.raises(CorruptModelError, match="sha256"):
            load_training_checkpoint(str(tmp_path))

    def test_missing_manifest_is_checkpoint_error(self, tmp_path):
        from photon_tpu.resilience import CheckpointError

        with pytest.raises(CheckpointError, match="manifest"):
            load_training_checkpoint(str(tmp_path))

    def test_emergency_sets_interrupted(self, tmp_path):
        ck = TrainingCheckpointer(str(tmp_path), "KEY")
        assert ck.write_emergency() is None  # nothing saved yet
        ck.save(_tiny_model(), config_index=0, iteration=2)
        assert ck.write_emergency() is not None
        assert load_training_checkpoint(str(tmp_path)).interrupted

    def test_emergency_uses_distinct_filename(self, tmp_path):
        """The emergency re-commit must never overwrite the npz the
        committed manifest references: a second kill between the npz
        os.replace and the manifest commit would otherwise leave the
        manifest's sha256 pointing at changed bytes — the crash-safety
        layer destroying its only recovery point."""
        ck = TrainingCheckpointer(str(tmp_path), "KEY")
        ck.save(_tiny_model(), config_index=0, iteration=1)
        before = json.load(open(tmp_path / "manifest.json"))
        ck.write_emergency()
        after = json.load(open(tmp_path / "manifest.json"))
        assert after["file"] != before["file"]
        assert after["file"].endswith("-interrupted.npz")
        loaded = load_training_checkpoint(str(tmp_path))
        assert loaded.interrupted
        assert (loaded.config_index, loaded.iteration) == (0, 1)

    def test_manifest_digest_comes_from_the_write(self, tmp_path):
        """save_checkpoint hashes the serialized buffer (no re-read);
        the manifest digest must still match the on-disk bytes."""
        import hashlib

        ck = TrainingCheckpointer(str(tmp_path), "KEY")
        path = ck.save(_tiny_model(), config_index=0, iteration=0)
        manifest = json.load(open(tmp_path / "manifest.json"))
        with open(path, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()
        assert manifest["sha256"] == digest

    def test_config_final_retained_and_reloadable(self, tmp_path):
        from photon_tpu.resilience import (
            CheckpointError,
            load_config_final,
        )

        ck = TrainingCheckpointer(str(tmp_path), "KEY")
        ck.save(_tiny_model(), config_index=0, iteration=1)
        ck.save_config_final(_tiny_model(), config_index=0)
        # the NEXT config's iteration saves must not GC the final
        ck.save(_tiny_model(), config_index=1, iteration=0)
        assert "config-c000-final.npz" in os.listdir(tmp_path)
        model = load_config_final(str(tmp_path), 0, "KEY")
        np.testing.assert_allclose(_weights(model, "g"), np.arange(4.0))
        with pytest.raises(ResumeMismatchError, match="static key"):
            load_config_final(str(tmp_path), 0, "OTHER")
        with pytest.raises(CheckpointError, match="missing"):
            load_config_final(str(tmp_path), 5, "KEY")
        # a FRESH run reusing the directory clears the stale final
        ck2 = TrainingCheckpointer(str(tmp_path), "KEY")
        ck2.save(_tiny_model(), config_index=0, iteration=0)
        assert "config-c000-final.npz" not in os.listdir(tmp_path)

    def test_emergency_after_config_final_retains_final(self, tmp_path):
        """A SIGTERM landing after save_config_final(ci) but before the
        next config's first iteration checkpoint re-commits at
        config_index=ci; its GC must not delete the just-retained
        final artifact the resume path rebuilds completed configs
        from (save() only blanket-retains finals at index < ci)."""
        from photon_tpu.resilience import load_config_final

        ck = TrainingCheckpointer(str(tmp_path), "KEY")
        ck.save(_tiny_model(), config_index=0, iteration=1)
        ck.save_config_final(_tiny_model(), config_index=0)
        ck.write_emergency()
        assert "config-c000-final.npz" in os.listdir(tmp_path)
        loaded = load_training_checkpoint(str(tmp_path))
        assert loaded.interrupted
        model = load_config_final(str(tmp_path), 0, "KEY")
        np.testing.assert_allclose(_weights(model, "g"), np.arange(4.0))


class TestCorruptArtifacts:
    def test_truncated_npz_names_file(self, tmp_path):
        from photon_tpu.io.model_io import load_checkpoint, save_checkpoint

        path = str(tmp_path / "m.npz")
        save_checkpoint(_tiny_model(), path)
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(size // 2)
        with pytest.raises(CorruptModelError, match="m.npz"):
            load_checkpoint(path)

    def test_truncated_avro_names_dir(self, rng, tmp_path):
        from photon_tpu.data.index_map import IndexMap
        from photon_tpu.io.model_io import (
            load_game_model,
            save_game_model,
        )

        model = _estimator(num_iterations=1).fit(_glmix_data(rng))[0].model
        maps = {
            "global": IndexMap({str(i): i for i in range(D)}),
            "userShard": IndexMap({str(i): i for i in range(DU)}),
        }
        save_game_model(model, str(tmp_path), maps)
        part = (
            tmp_path / "random-effect" / "per-user" / "coefficients"
            / "part-00000.avro"
        )
        size = os.path.getsize(part)
        with open(part, "r+b") as f:
            f.truncate(max(size // 2, 40))
        with pytest.raises(
            CorruptModelError, match="per-user"
        ) as excinfo:
            load_game_model(str(tmp_path), maps)
        assert "coefficients" in str(excinfo.value)

    def test_missing_checkpoint_stays_file_not_found(self, tmp_path):
        from photon_tpu.io.model_io import load_checkpoint

        with pytest.raises(FileNotFoundError):
            load_checkpoint(str(tmp_path / "absent.npz"))


# --------------------------------------------------------------------------
# kill-and-resume equivalence
# --------------------------------------------------------------------------


class TestResume:
    def test_crash_resume_matches_uninterrupted(self, rng, tmp_path):
        data = _glmix_data(rng)
        est = _estimator()
        key = training_static_key(est, [{}])
        ck = TrainingCheckpointer(str(tmp_path / "a"), key)
        plan = FaultPlan(
            [dict(point="cd.iteration", nth=2, error="crash")]
        )
        with faults.injected(plan):
            with pytest.raises(InjectedCrash):
                est.fit(data, checkpointer=ck)
        ckpt = load_training_checkpoint(str(tmp_path / "a"))
        assert (ckpt.config_index, ckpt.iteration) == (0, 1)

        resumed = _estimator().fit(
            data,
            checkpointer=TrainingCheckpointer(str(tmp_path / "a"), key),
            resume=ckpt,
        )[0].model
        uninterrupted = _estimator().fit(
            data,
            checkpointer=TrainingCheckpointer(str(tmp_path / "b"), key),
        )[0].model
        # Documented tolerance (RESILIENCE.md): the resumed run
        # re-accumulates the score total in sequence order, so exact
        # float equality is not promised — rtol 1e-4 is (CPU runs land
        # near 1e-5; real-device reassociation has been observed at
        # 2.4e-5 on small-magnitude coefficients).
        for cid in ("global", "per-user"):
            np.testing.assert_allclose(
                _weights(resumed, cid),
                _weights(uninterrupted, cid),
                rtol=1e-4, atol=1e-6,
            )

    def test_multi_config_resume_preserves_all_results(
        self, rng, tmp_path
    ):
        """Crash during config 1 of a 2-config grid; the resumed run
        must return a result for BOTH configs (config 0 rebuilt from
        its retained config-final checkpoint) so select_best / tuning
        observations / per-index artifact writes line up with the
        uninterrupted run instead of silently shifting."""
        data = _glmix_data(rng)
        grid = [{"per-user": _l2(0.5)}, {"per-user": _l2(2.0)}]
        est = _estimator()
        key = training_static_key(est, grid)
        ck = TrainingCheckpointer(str(tmp_path / "a"), key)
        # cd.iteration fires once per outer iteration: 3 for config 0,
        # the 4th is config 1's first — crash there, with config 0
        # complete and (1, 0) checkpointed.
        plan = FaultPlan(
            [dict(point="cd.iteration", nth=4, error="crash")]
        )
        with faults.injected(plan):
            with pytest.raises(InjectedCrash):
                est.fit(data, None, grid, checkpointer=ck)
        ckpt = load_training_checkpoint(str(tmp_path / "a"))
        assert (ckpt.config_index, ckpt.iteration) == (1, 0)

        resumed = _estimator().fit(
            data, None, grid,
            checkpointer=TrainingCheckpointer(str(tmp_path / "a"), key),
            resume=ckpt,
        )
        full = _estimator().fit(
            data, None, grid,
            checkpointer=TrainingCheckpointer(str(tmp_path / "b"), key),
        )
        assert len(resumed) == len(full) == 2
        # config 0's result is rebuilt: same model, no descent history
        # (it died with the interrupted process)
        assert resumed[0].descent is None
        assert resumed[1].descent is not None
        for j in range(2):
            for cid in ("global", "per-user"):
                np.testing.assert_allclose(
                    _weights(resumed[j].model, cid),
                    _weights(full[j].model, cid),
                    rtol=1e-4, atol=1e-6,
                )

    def test_resume_after_final_iteration_rejected(self, rng, tmp_path):
        data = _glmix_data(rng)
        est = _estimator(num_iterations=2)
        key = training_static_key(est, [{}])
        ck = TrainingCheckpointer(str(tmp_path), key)
        est.fit(data, checkpointer=ck)
        ckpt = load_training_checkpoint(str(tmp_path))
        assert ckpt.iteration == 1  # final iteration committed
        with pytest.raises(ValueError, match="already completed"):
            _estimator(num_iterations=2).fit(data, resume=ckpt)

    def test_changed_config_rejected_via_static_key(
        self, rng, tmp_path
    ):
        data = _glmix_data(rng)
        est = _estimator()
        key = training_static_key(est, [{}])
        ck = TrainingCheckpointer(str(tmp_path), key)
        plan = FaultPlan(
            [dict(point="cd.iteration", nth=1, error="crash")]
        )
        with faults.injected(plan):
            with pytest.raises(InjectedCrash):
                est.fit(data, checkpointer=ck)
        ckpt = load_training_checkpoint(str(tmp_path))
        # a different lambda is a different optimization: reject
        with pytest.raises(ResumeMismatchError, match="static key"):
            _estimator(lam=9.0).fit(data, resume=ckpt)
        # iteration-count change: also a static change
        with pytest.raises(ResumeMismatchError):
            _estimator(num_iterations=5).fit(data, resume=ckpt)

    def test_crash_before_config_final_resumes_and_heals(
        self, rng, tmp_path
    ):
        """The window AFTER the last iteration's checkpoint commits but
        BEFORE save_config_final retains the final artifact: the
        checkpoint is valid and complete, so resume must finalize from
        the chain (and heal the missing artifact) instead of refusing
        with 'nothing to resume'."""
        data = _glmix_data(rng)
        est = _estimator(num_iterations=2)
        key = training_static_key(est, [{}])
        ck = TrainingCheckpointer(str(tmp_path / "a"), key)
        # cd.iteration nth=2 fires at the END of iteration 1 (the last)
        # — iteration 1's checkpoint is already durable, the config
        # final is not yet written.
        plan = FaultPlan(
            [dict(point="cd.iteration", nth=2, error="crash")]
        )
        with faults.injected(plan):
            with pytest.raises(InjectedCrash):
                est.fit(data, checkpointer=ck)
        assert not (tmp_path / "a" / "config-c000-final.npz").exists()
        ckpt = load_training_checkpoint(str(tmp_path / "a"))
        assert (ckpt.config_index, ckpt.iteration) == (0, 1)

        resumed = _estimator(num_iterations=2).fit(
            data,
            checkpointer=TrainingCheckpointer(str(tmp_path / "a"), key),
            resume=ckpt,
        )
        uninterrupted = _estimator(num_iterations=2).fit(
            data,
            checkpointer=TrainingCheckpointer(str(tmp_path / "b"), key),
        )
        assert len(resumed) == 1 and resumed[0].descent is None
        for cid in ("global", "per-user"):
            np.testing.assert_allclose(
                _weights(resumed[0].model, cid),
                _weights(uninterrupted[0].model, cid),
                rtol=1e-4, atol=1e-6,
            )
        # healed: the config-final now exists, so a THIRD attempt gets
        # the honest 'already completed' refusal
        assert (tmp_path / "a" / "config-c000-final.npz").exists()
        with pytest.raises(ValueError, match="already completed"):
            _estimator(num_iterations=2).fit(
                data,
                resume=load_training_checkpoint(str(tmp_path / "a")),
            )

    def test_crash_before_config_final_multi_config(
        self, rng, tmp_path
    ):
        """Same window in a 2-config grid, dying at the end of config
        0's LAST iteration: resume must finalize config 0 from the
        chain and then train config 1 exactly as the uninterrupted
        run would have."""
        data = _glmix_data(rng)
        grid = [{"per-user": _l2(0.5)}, {"per-user": _l2(2.0)}]
        est = _estimator()  # 3 iterations
        key = training_static_key(est, grid)
        ck = TrainingCheckpointer(str(tmp_path / "a"), key)
        plan = FaultPlan(
            [dict(point="cd.iteration", nth=3, error="crash")]
        )
        with faults.injected(plan):
            with pytest.raises(InjectedCrash):
                est.fit(data, None, grid, checkpointer=ck)
        assert not (tmp_path / "a" / "config-c000-final.npz").exists()
        ckpt = load_training_checkpoint(str(tmp_path / "a"))
        assert (ckpt.config_index, ckpt.iteration) == (0, 2)

        resumed = _estimator().fit(
            data, None, grid,
            checkpointer=TrainingCheckpointer(str(tmp_path / "a"), key),
            resume=ckpt,
        )
        full = _estimator().fit(
            data, None, grid,
            checkpointer=TrainingCheckpointer(str(tmp_path / "b"), key),
        )
        assert len(resumed) == len(full) == 2
        assert resumed[0].descent is None  # finalized, not retrained
        assert resumed[1].descent is not None
        for j in range(2):
            for cid in ("global", "per-user"):
                np.testing.assert_allclose(
                    _weights(resumed[j].model, cid),
                    _weights(full[j].model, cid),
                    rtol=1e-4, atol=1e-6,
                )

    def test_checkpointing_forces_unfused_path(self, rng, tmp_path):
        """The fused whole-fit program has no per-iteration host
        boundary; an active checkpointer must ride the unfused loop
        (evidenced by per-iteration checkpoint commits existing at
        all — the fused path would commit nothing mid-fit)."""
        data = _glmix_data(rng)
        est = _estimator(num_iterations=2)
        key = training_static_key(est, [{}])
        ck = TrainingCheckpointer(str(tmp_path), key)
        est.fit(data, checkpointer=ck)
        manifest = json.load(open(tmp_path / "manifest.json"))
        assert manifest["iteration"] == 1
        # unfused evidence: records carry measured per-update seconds
        # (the fused path's records carry None with telemetry off)
        hist = est.fit(data, checkpointer=ck)[0].descent.history
        assert all(r.seconds is not None for r in hist)


class _IterationCoordinate:
    """Coordinate whose weight IS the per-iteration seed + 1 (cd.run
    passes seed+it), so validation quality is a pure function of the
    iteration index — lets a test pin WHICH iteration is best."""

    def __init__(self, n=8):
        self.n = n

    def train(self, residuals=None, initial_model=None, *, seed=0):
        w = float(seed + 1)
        model = GeneralizedLinearModel(
            Coefficients(means=jnp.full(2, w)),
            TaskType.LINEAR_REGRESSION,
        )
        return model, {}

    def score(self, model):
        return jnp.full(
            self.n, model.coefficients.means[0], dtype=jnp.float32
        )


class _PeakAtOneSuite:
    """Fake EvaluationSuite: primary metric -|mean(scores) - 1| — the
    iteration that scores 1.0 everywhere (iteration 0 under
    ``_IterationCoordinate``) is the best; training only gets worse."""

    class _Primary:
        @staticmethod
        def better_than(a, b):
            return a > b

    primary = _Primary()

    class _Results:
        def __init__(self, v):
            self.primary_evaluation = v
            self.evaluations = {"peak": v}

    def evaluate(self, scores):
        return self._Results(-abs(float(np.asarray(scores).mean()) - 1.0))


class TestBestModelResume:
    """Resume must not discard the pre-crash best-by-validation model:
    the best is retained as its own artifact and reseeds CD's tracking
    (review finding: checkpoints hold final-iteration state only, and
    cd.run restarted best_model from None)."""

    def _validation(self):
        from photon_tpu.algorithm.coordinate_descent import (
            ValidationContext,
        )

        return ValidationContext(
            suite=_PeakAtOneSuite(),
            scorers={"a": lambda m: jnp.full(
                4, m.coefficients.means[0], dtype=jnp.float32
            )},
        )

    def test_initial_best_seeds_cd_tracking(self):
        val = self._validation()
        cd = CoordinateDescent(["a"], 3)
        full = cd.run({"a": _IterationCoordinate()}, validation=val)
        # iteration 0 (w=1) is the best the full run ever sees
        assert float(_weights_glm(full.best_model, "a")[0]) == 1.0

        # resume after iteration 0: replayed iterations only see w=2,3
        w1 = full.best_model["a"]
        resumed_blind = CoordinateDescent(["a"], 3).run(
            {"a": _IterationCoordinate()}, {"a": w1}, val,
            start_iteration=1,
        )
        # without the seed, the resumed run picks the wrong best — the
        # failure mode under test
        assert float(
            _weights_glm(resumed_blind.best_model, "a")[0]
        ) == 2.0

        resumed = CoordinateDescent(["a"], 3).run(
            {"a": _IterationCoordinate()}, {"a": w1}, val,
            start_iteration=1,
            initial_best=(full.best_model, full.best_evaluation),
        )
        assert float(_weights_glm(resumed.best_model, "a")[0]) == 1.0
        assert resumed.best_evaluation.primary_evaluation == 0.0

    def test_on_iteration_receives_best(self):
        seen = []
        CoordinateDescent(["a"], 3).run(
            {"a": _IterationCoordinate()},
            validation=self._validation(),
            on_iteration=lambda it, model, best: seen.append(
                (it, float(_weights_glm(best, "a")[0]))
            ),
        )
        # best stays the iteration-0 model throughout
        assert seen == [(0, 1.0), (1, 1.0), (2, 1.0)]

    def test_estimator_retains_and_reuses_best_artifact(
        self, rng, tmp_path
    ):
        """End-to-end wiring: a crashed validation run leaves a best
        artifact; the resumed run returns the same best-by-validation
        model as the uninterrupted run; completion supersedes the
        artifact with the config-final."""
        data = _glmix_data(rng)
        valdata = _glmix_data(np.random.default_rng(99))
        est = _estimator()
        key = training_static_key(est, [{}])
        ck = TrainingCheckpointer(str(tmp_path / "a"), key)
        plan = FaultPlan(
            [dict(point="cd.iteration", nth=2, error="crash")]
        )
        with faults.injected(plan):
            with pytest.raises(InjectedCrash):
                est.fit(data, valdata, checkpointer=ck)
        # the crashed run committed its best-so-far as an artifact
        assert (tmp_path / "a" / "config-c000-best.npz").exists()

        ckpt = load_training_checkpoint(str(tmp_path / "a"))
        resumed = _estimator().fit(
            data, valdata,
            checkpointer=TrainingCheckpointer(str(tmp_path / "a"), key),
            resume=ckpt,
        )
        full = _estimator().fit(
            data, valdata,
            checkpointer=TrainingCheckpointer(str(tmp_path / "b"), key),
        )
        for cid in ("global", "per-user"):
            np.testing.assert_allclose(
                _weights(resumed[0].model, cid),
                _weights(full[0].model, cid),
                rtol=1e-4, atol=1e-6,
            )
        # completion superseded the best artifact with the config-final
        assert not (tmp_path / "a" / "config-c000-best.npz").exists()
        assert (tmp_path / "a" / "config-c000-final.npz").exists()


def _weights_glm(game_model, cid):
    return np.asarray(game_model[cid].coefficients.means)


# --------------------------------------------------------------------------
# non-finite guard
# --------------------------------------------------------------------------


class _SyntheticCoordinate:
    """Minimal Coordinate for CD-level guard tests: scalar weight per
    'model', scores = weight everywhere; optionally poisons a given
    update call with NaN."""

    def __init__(self, n=16, poison_calls=()):
        self.n = n
        self.calls = 0
        self.poison_calls = set(poison_calls)

    def train(self, residuals=None, initial_model=None, *, seed=0):
        self.calls += 1
        w = float(self.calls)
        if self.calls in self.poison_calls:
            w = float("nan")
        model = GeneralizedLinearModel(
            Coefficients(means=jnp.full(2, w)),
            TaskType.LINEAR_REGRESSION,
        )
        return model, {"call": self.calls}

    def score(self, model):
        return jnp.full(
            self.n, model.coefficients.means[0], dtype=jnp.float32
        )


class TestNonFiniteGuard:
    def test_rollback_keeps_previous_iterate(self):
        coord = _SyntheticCoordinate(poison_calls={2})
        cd = CoordinateDescent(["a"], 3, non_finite_guard=True)
        result = cd.run({"a": coord})
        # call 2 poisoned: final model is call 3's (finite) weights,
        # and the poisoned update left a rolled_back record behind.
        assert float(result.model["a"].coefficients.means[0]) == 3.0
        flags = [r.rolled_back for r in result.history]
        assert flags == [False, True, False]
        # the rollback record keeps the poisoned update's diagnostics
        assert result.history[1].diagnostics == {"call": 2}

    def test_rollback_emits_event_and_metric(self):
        from photon_tpu import obs
        from photon_tpu.events import (
            CoordinateRollbackEvent,
            EventEmitter,
        )

        events = []
        was_enabled = obs.enabled()
        obs.reset()
        obs.enable()
        try:
            coord = _SyntheticCoordinate(poison_calls={2})
            cd = CoordinateDescent(
                ["a"], 2, non_finite_guard=True,
                emitter=EventEmitter([events.append]),
            )
            cd.run({"a": coord})
            rollbacks = [
                e for e in events
                if isinstance(e, CoordinateRollbackEvent)
            ]
            assert len(rollbacks) == 1
            assert rollbacks[0].coordinate_id == "a"
            assert rollbacks[0].iteration == 1
            snap = obs.snapshot()
            counters = snap["metrics"]["counters"]
            assert any(
                k.startswith("coordinate_rollbacks_total")
                for k in counters
            ), counters
        finally:
            # reset() drops records but never touches the enabled flag:
            # restore it too, or the leak trips test_cli's "left as
            # found" telemetry assertion when this file runs first.
            obs.reset()
            obs.TRACER.enabled = was_enabled

    def test_first_update_non_finite_raises(self):
        coord = _SyntheticCoordinate(poison_calls={1})
        cd = CoordinateDescent(["a"], 2, non_finite_guard=True)
        with pytest.raises(NonFiniteUpdateError, match="first update"):
            cd.run({"a": coord})

    def test_guard_off_is_default(self):
        coord = _SyntheticCoordinate(poison_calls={1})
        cd = CoordinateDescent(["a"], 1)
        result = cd.run({"a": coord})  # no guard: NaN flows through
        assert np.isnan(float(result.model["a"].coefficients.means[0]))

    def test_estimator_guard_clean_run_has_no_rollbacks(self, rng):
        data = _glmix_data(rng)
        est = _estimator(num_iterations=2, non_finite_guard=True)
        hist = est.fit(data)[0].descent.history
        assert all(not r.rolled_back for r in hist)
        # guard forces the unfused loop: measured per-update seconds
        assert all(r.seconds is not None for r in hist)


# --------------------------------------------------------------------------
# CLI: SIGTERM emergency checkpoint + --resume
# --------------------------------------------------------------------------


def _write_cli_workload(tmp_path, num_iterations=3):
    from photon_tpu.io.avro_data import write_training_examples
    from photon_tpu.types import DELIMITER

    rng = np.random.default_rng(0)
    n, d, users = 300, 4, 8
    keys = [f"f{i}{DELIMITER}t" for i in range(d)]
    w = rng.normal(size=d)
    u_eff = rng.normal(size=users)
    x = rng.normal(size=(n, d))
    uid = rng.integers(0, users, size=n)
    y = x @ w + u_eff[uid]
    rows = [
        [(keys[j], float(x[i, j])) for j in range(d)] for i in range(n)
    ]
    meta = [{"userId": f"u{u}"} for u in uid]
    train = tmp_path / "train.avro"
    write_training_examples(
        str(train), y, rows, metadata=meta, uids=np.arange(n)
    )
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
                "regularization": {"type": "L2", "weights": [0.01]},
            },
            "per-user": {
                "type": "random",
                "random_effect_type": "userId",
                "regularization": {"type": "L2", "weights": [1.0]},
            },
        },
        "num_iterations": num_iterations,
        "output_dir": str(tmp_path / "out"),
        "mesh": "off",
    }
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps(cfg))
    return cfg_path


class TestTrainCliResilience:
    def test_sigterm_mid_fit_commits_emergency_checkpoint(
        self, tmp_path, monkeypatch, capsys
    ):
        """In-process: the `sigterm` fault kind delivers a REAL SIGTERM
        to the process after CD iteration 1's checkpoint; the CLI's
        handler unwinds the fit, re-commits the state flagged
        interrupted, and exits 128+15."""
        from photon_tpu.cli.train import main

        cfg_path = _write_cli_workload(tmp_path)
        monkeypatch.setenv(
            faults.ENV_VAR,
            json.dumps({"faults": [
                {"point": "cd.iteration", "nth": 2, "error": "sigterm"}
            ]}),
        )
        ckpt_dir = tmp_path / "ckpt"
        rc = main([
            "--config", str(cfg_path),
            "--checkpoint-dir", str(ckpt_dir),
        ])
        assert rc == 128 + signal.SIGTERM
        ckpt = load_training_checkpoint(str(ckpt_dir))
        assert ckpt.interrupted
        assert (ckpt.config_index, ckpt.iteration) == (0, 1)
        # resume completes the run
        faults.disarm()
        monkeypatch.delenv(faults.ENV_VAR)
        rc = main([
            "--config", str(cfg_path), "--resume", str(ckpt_dir)
        ])
        assert rc == 0
        final = load_training_checkpoint(str(ckpt_dir))
        assert not final.interrupted
        assert final.iteration == 2
        capsys.readouterr()

    def test_sigterm_subprocess(self, tmp_path):
        """The real thing: a `photon train` SUBPROCESS receives SIGTERM
        mid-fit (held there by an injected delay after iteration 0's
        checkpoint) and exits nonzero with a loadable, interrupted-
        flagged checkpoint on disk."""
        cfg_path = _write_cli_workload(tmp_path, num_iterations=3)
        ckpt_dir = tmp_path / "ckpt"
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": str(REPO_ROOT),
            faults.ENV_VAR: json.dumps({"faults": [{
                "point": "cd.iteration", "nth": 1,
                "error": "delay", "seconds": 120,
            }]}),
        })
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "photon_tpu.cli.train",
                "--config", str(cfg_path),
                "--checkpoint-dir", str(ckpt_dir),
            ],
            cwd=str(REPO_ROOT), env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        try:
            # iteration 0's checkpoint commits, then the delay fault
            # holds the main thread — the deterministic SIGTERM window.
            manifest = ckpt_dir / "manifest.json"
            deadline = time.time() + 120
            while not manifest.exists() and time.time() < deadline:
                assert proc.poll() is None, (
                    proc.communicate()[1].decode()
                )
                time.sleep(0.2)
            assert manifest.exists(), "no checkpoint within 120s"
            time.sleep(0.5)  # let the manifest commit fully settle
            proc.send_signal(signal.SIGTERM)
            out, err = proc.communicate(timeout=120)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
        assert proc.returncode == 128 + signal.SIGTERM, err.decode()
        ckpt = load_training_checkpoint(str(ckpt_dir))
        assert ckpt.interrupted
        assert ckpt.iteration == 0
        assert b"emergency checkpoint" in err or b"interrupted" in err
