"""CLI drivers: train -> model dir -> score round trip.

Mirrors GameTrainingDriverIntegTest / GameScoringDriverIntegTest: run the
full driver main() on synthetic Avro data, assert the output layout, the
frozen-threshold metric, and scoring-side parity.
"""

import json
import os

import numpy as np
import pytest

from photon_tpu.io.avro_data import write_training_examples
from photon_tpu.types import DELIMITER


@pytest.fixture
def glmix_avro(tmp_path, rng):
    """Synthetic GLMix avro train/validation files with per-user effects."""
    n, d, users = 1500, 5, 20
    keys = [f"f{i}{DELIMITER}t" for i in range(d)]
    u_eff = rng.normal(size=users)
    w = rng.normal(size=d)

    def write(path, n_rows, seed):
        r = np.random.default_rng(seed)
        x = r.normal(size=(n_rows, d))
        uid = r.integers(0, users, size=n_rows)
        y = x @ w + u_eff[uid] + 0.1 * r.normal(size=n_rows)
        rows = [
            [(keys[j], float(x[i, j])) for j in range(d)]
            for i in range(n_rows)
        ]
        meta = [{"userId": f"u{u}"} for u in uid]
        write_training_examples(
            str(path), y, rows, metadata=meta, uids=np.arange(n_rows)
        )

    train = tmp_path / "train.avro"
    val = tmp_path / "val.avro"
    write(train, n, 1)
    write(val, 500, 2)
    return train, val


def _config(tmp_path, train, val, **overrides):
    cfg = {
        "task": "LINEAR_REGRESSION",
        "input": {
            "format": "avro",
            "train_path": str(train),
            "validation_path": str(val),
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
        "num_iterations": 2,
        "evaluators": ["RMSE"],
        "output_dir": str(tmp_path / "out"),
    }
    cfg.update(overrides)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(cfg))
    return path, cfg


class TestTrainCLI:
    def test_end_to_end(self, tmp_path, glmix_avro, capsys):
        from photon_tpu.cli.train import main

        train, val = glmix_avro
        cfg_path, _ = _config(tmp_path, train, val)
        assert main(["--config", str(cfg_path)]) == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        # GLMix must land near the 0.1 noise floor (frozen threshold, the
        # GameTrainingDriverIntegTest RMSE < 1.697 pattern).
        assert out["evaluation"]["RMSE"] < 0.3

        out_dir = tmp_path / "out"
        assert (out_dir / "training-summary.json").is_file()
        model_dir = out_dir / "models" / "best"
        assert (model_dir / "model-metadata.json").is_file()
        assert (model_dir / "fixed-effect" / "global" / "id-info").is_file()
        assert (model_dir / "random-effect" / "per-user" / "id-info").is_file()
        assert (model_dir / "checkpoint.npz").is_file()

    def test_telemetry_flag_writes_schema_valid_jsonl(
        self, tmp_path, glmix_avro, capsys
    ):
        """--telemetry PATH: the JSONL stream validates against the
        documented schema, the snapshot rides training-summary.json, and
        the process is left with telemetry disabled."""
        from photon_tpu import obs
        from photon_tpu.cli.train import main

        train, val = glmix_avro
        cfg_path, _ = _config(tmp_path, train, val)
        t_path = tmp_path / "telemetry.jsonl"
        assert main(["--config", str(cfg_path),
                     "--telemetry", str(t_path)]) == 0
        capsys.readouterr()
        assert obs.validate_jsonl(str(t_path)) > 0
        lines = [json.loads(l) for l in t_path.open()]
        span_paths = {l["path"] for l in lines if l["type"] == "span"}
        # The driver's section spans and the estimator's fit tree (this
        # config has validation -> the unfused per-coordinate path).
        assert "prepare training datasets" in span_paths
        # Since PR 36 the loop's updates nest under its own `fit` stage.
        assert any("fit/config:0/fit/coord:" in p for p in span_paths)
        summary = json.loads(
            (tmp_path / "out" / "training-summary.json").read_text())
        assert summary["telemetry"]["spans"]
        assert not obs.enabled()  # left as found

    def test_trace_flag_alone_writes_nonempty_timeline(
        self, tmp_path, glmix_avro, capsys
    ):
        """--trace without --telemetry (and with the flight recorder —
        the other telemetry enabler — opted out) still records: the
        exported trace.json validates and carries host spans."""
        from photon_tpu import obs
        from photon_tpu.cli.train import main
        from photon_tpu.obs.trace import validate_chrome_trace

        train, val = glmix_avro
        cfg_path, _ = _config(tmp_path, train, val)
        t_path = tmp_path / "trace.json"
        assert main(["--config", str(cfg_path), "--no-flight",
                     "--trace", str(t_path)]) == 0
        capsys.readouterr()
        assert validate_chrome_trace(str(t_path)) > 0
        doc = json.loads(t_path.read_text())
        assert any(e["ph"] == "X" for e in doc["traceEvents"])
        assert not obs.enabled()  # left as found

    def test_distributed_flag_ships_provenanced_bundle(
        self, tmp_path, glmix_avro, capsys
    ):
        """--distributed on a single host: the rank ships a 1-rank fleet
        bundle whose host block carries a derived run id (identical on
        every rank by construction — it hashes the shared fleet dir) and
        whose clock block pairs a REAL init-time sample against the
        commit-time one (obs.reset() inside main() must not wipe the
        init half of the handshake), and the run dir merges clean."""
        from photon_tpu.cli.train import main
        from photon_tpu.obs import fleet

        train, val = glmix_avro
        cfg_path, _ = _config(tmp_path, train, val)
        try:
            assert main(["--config", str(cfg_path), "--no-flight",
                         "--distributed"]) == 0
        finally:
            fleet.reset()  # the derived run id is process state
        capsys.readouterr()

        fleet_dir = tmp_path / "out" / "fleet"
        bundle = json.loads(
            (fleet_dir / "obs-host-0" / "bundle.json").read_text())
        host, clock = bundle["host"], bundle["clock"]
        assert host["process_index"] == 0 and host["process_count"] == 1
        assert host["run_id"] and host["run_id"].startswith("train-")
        # A real pairing: init sampled at arm time, commit at ship time.
        assert (clock["commit"]["perf_counter"]
                > clock["init"]["perf_counter"])
        assert clock["skew_bound_seconds"] < 1.0

        report, _trace = fleet.merge_run(str(fleet_dir))
        assert report["gaps"] == [] and report["ranks"] == [0]
        assert report["wall_seconds"] > 0

    def test_lambda_grid_selects_best(self, tmp_path, glmix_avro, capsys):
        from photon_tpu.cli.train import main

        train, val = glmix_avro
        cfg_path, _ = _config(
            tmp_path, train, val,
            coordinates={
                "global": {
                    "type": "fixed",
                    "regularization": {
                        "type": "L2", "weights": [1000.0, 0.01]},
                },
            },
            model_output_mode="ALL",
        )
        assert main(["--config", str(cfg_path)]) == 0
        summary = json.loads(
            (tmp_path / "out" / "training-summary.json").read_text())
        assert summary["num_configurations"] == 2
        # Lambdas expand sorted descending; the weak one must win.
        lams = [c["config"]["global"]["lambda"]
                for c in summary["configurations"]]
        assert lams == [1000.0, 0.01]
        assert summary["best_configuration_index"] == 1
        # The best model always lands in best/; the rest keep config_<i>.
        assert (tmp_path / "out" / "models" / "config_0").is_dir()
        assert (tmp_path / "out" / "models" / "best").is_dir()

    def test_libsvm_input(self, tmp_path, rng, capsys):
        from photon_tpu.cli.train import main

        n, d = 400, 6
        x = rng.normal(size=(n, d))
        w = rng.normal(size=d)
        y = (x @ w + 0.5 * rng.normal(size=n) > 0).astype(int)
        lines = []
        for i in range(n):
            feats = " ".join(
                f"{j + 1}:{x[i, j]:.6f}" for j in range(d))
            lines.append(f"{2 * y[i] - 1} {feats}")
        p = tmp_path / "a1a.txt"
        p.write_text("\n".join(lines))
        cfg_path, _ = _config(
            tmp_path, p, None,
            task="LOGISTIC_REGRESSION",
            input={"format": "libsvm", "train_path": str(p),
                   "validation_path": str(p)},
            coordinates={
                "global": {
                    "type": "fixed",
                    "regularization": {"type": "L2", "weights": [0.1]},
                },
            },
            evaluators=["AUC"],
            normalization="STANDARDIZATION",
        )
        assert main(["--config", str(cfg_path)]) == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["evaluation"]["AUC"] > 0.85


class TestScoreCLI:
    def test_train_then_score(self, tmp_path, glmix_avro, capsys):
        from photon_tpu.cli.score import main as score_main
        from photon_tpu.cli.train import main as train_main
        from photon_tpu.io import avro

        train, val = glmix_avro
        cfg_path, _ = _config(tmp_path, train, val)
        assert train_main(["--config", str(cfg_path)]) == 0
        capsys.readouterr()

        score_out = tmp_path / "scores"
        rc = score_main([
            "--model-dir", str(tmp_path / "out" / "models" / "best"),
            "--input", str(val),
            "--output", str(score_out),
            "--evaluators", "RMSE",
            "--id-tags", "userId",
        ])
        assert rc == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["num_scored"] == 500
        # Scoring-side eval matches the training validation metric regime.
        assert out["evaluation"]["RMSE"] < 0.3
        recs = avro.read_container(
            str(score_out / "part-00000.avro"))[1]
        assert len(recs) == 500
        assert np.isfinite([r["predictionScore"] for r in recs]).all()
        assert (score_out / "evaluation.json").is_file()


class TestHyperparameterTuningCLI:
    def test_tuning_improves_over_bad_grid(self, tmp_path, glmix_avro,
                                           capsys):
        """runHyperparameterTuning wiring (GameTrainingDriver.scala:677-719):
        RANDOM tuning must evaluate extra configs and the selected model
        must be at least as good as the deliberately bad grid's best."""
        from photon_tpu.cli.train import main

        train, val = glmix_avro
        cfg_path, _ = _config(
            tmp_path, train, val,
            coordinates={
                "global": {
                    "type": "fixed",
                    "regularization": {
                        "type": "L2",
                        "weights": [1e4],  # terrible over-regularization
                        "weight_range": [1e-4, 1e4],
                    },
                },
            },
            hyperparameter_tuning={
                "mode": "RANDOM", "iterations": 4, "seed": 7},
        )
        assert main(["--config", str(cfg_path)]) == 0
        summary = json.loads(
            (tmp_path / "out" / "training-summary.json").read_text())
        assert summary["num_configurations"] == 5  # 1 grid + 4 tuned
        assert summary["num_tuned_configurations"] == 4
        rmses = [c["evaluation"]["RMSE"]
                 for c in summary["configurations"]]
        # The grid model is badly over-regularized; tuning must beat it.
        assert min(rmses[1:]) < rmses[0]
        assert summary["best_configuration_index"] != 0


class TestIndexCLI:
    def test_build_index_and_whitelists(self, tmp_path, glmix_avro, capsys):
        """photon index: per-shard index maps + reference feature-lists
        format (FeatureIndexingDriver / NameAndTermFeatureBagsDriver)."""
        from photon_tpu.cli.index import load_index_maps, main

        train, _ = glmix_avro
        out = tmp_path / "vocab"
        assert main(["--input", str(train), "--output", str(out)]) == 0
        summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert summary["shards"]["features"] == 6  # 5 features + intercept

        # Whitelist: "name<TAB>term" per line, sorted distinct pairs.
        lines = (out / "features").read_text().strip().splitlines()
        assert len(lines) == 5
        assert all("\t" in line for line in lines)

        maps = load_index_maps(str(out))
        assert set(maps) == {"features"}
        assert maps["features"].intercept_index is not None

    def test_train_with_prebuilt_index(self, tmp_path, glmix_avro, capsys):
        """Training with a prebuilt vocab reproduces the auto-built-vocab
        model (same features, same indices after remap)."""
        from photon_tpu.cli.index import main as index_main
        from photon_tpu.cli.train import main as train_main

        train, val = glmix_avro
        out = tmp_path / "vocab"
        assert index_main(
            ["--input", str(train), "--output", str(out)]) == 0

        cfg_path, _ = _config(
            tmp_path, train, val,
            input={"format": "avro", "train_path": str(train),
                   "validation_path": str(val), "id_tags": ["userId"],
                   "feature_index_dir": str(out)},
        )
        assert train_main(["--config", str(cfg_path)]) == 0
        res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert res["evaluation"]["RMSE"] < 0.3

    def test_multi_bag_shards(self, tmp_path):
        """Shard specs union multiple feature-bag fields (the Yahoo! Music
        userFeatures/songFeatures layout)."""
        from photon_tpu.cli.index import main

        ref = ("/root/reference/photon-client/src/integTest/resources/"
               "GameIntegTest/input/duplicateFeatures/yahoo-music-train.avro")
        if not os.path.isfile(ref):
            pytest.skip("reference fixture not mounted")
        out = tmp_path / "vocab"
        assert main([
            "--input", ref, "--output", str(out),
            "--shards", "global=features", "user=userFeatures",
            "song=songFeatures,features",
        ]) == 0
        maps_dir = sorted(p.name for p in out.iterdir())
        assert "global.index.json" in maps_dir
        assert "user.index.json" in maps_dir
        assert "song.index.json" in maps_dir
        user_lines = (out / "user").read_text().strip().splitlines()
        assert all(line.split("\t")[0] == "u" for line in user_lines)


class TestObservability:
    def test_output_modes(self, tmp_path, glmix_avro, capsys):
        """ModelOutputMode.scala:47 NONE/EXPLICIT/TUNED semantics."""
        from photon_tpu.cli.train import main

        train, val = glmix_avro
        coords = {
            "global": {
                "type": "fixed",
                "regularization": {"type": "L2", "weights": [100.0, 0.01]},
            },
        }
        # NONE: summary only, no model dirs.
        cfg_path, _ = _config(
            tmp_path, train, val, coordinates=coords,
            model_output_mode="NONE",
            output_dir=str(tmp_path / "none_out"),
        )
        assert main(["--config", str(cfg_path)]) == 0
        assert (tmp_path / "none_out" / "training-summary.json").is_file()
        assert not (tmp_path / "none_out" / "models").exists()

        # EXPLICIT: best + every grid model, none of the tuned ones.
        cfg_path, _ = _config(
            tmp_path, train, val, coordinates={
                "global": {
                    "type": "fixed",
                    "regularization": {
                        "type": "L2", "weights": [100.0, 0.01]},
                },
            },
            model_output_mode="EXPLICIT",
            hyperparameter_tuning={
                "mode": "RANDOM", "iterations": 2, "seed": 3},
            output_dir=str(tmp_path / "exp_out"),
        )
        assert main(["--config", str(cfg_path)]) == 0
        dirs = sorted(
            p.name for p in (tmp_path / "exp_out" / "models").iterdir())
        # EXPLICIT: best + the grid models (indices 0-1); tuned models
        # (indices 2-3) are never saved under their config dirs.
        assert "best" in dirs
        assert not {"config_2", "config_3"} & set(dirs)
        assert {d for d in dirs if d != "best"} <= {"config_0", "config_1"}
        summary = json.loads(
            (tmp_path / "exp_out" / "training-summary.json").read_text())
        assert summary["num_configurations"] == 4

        # TUNED: best + tuned models only.
        cfg_path, _ = _config(
            tmp_path, train, val, coordinates={
                "global": {
                    "type": "fixed",
                    "regularization": {
                        "type": "L2", "weights": [100.0, 0.01]},
                },
            },
            model_output_mode="TUNED",
            hyperparameter_tuning={
                "mode": "RANDOM", "iterations": 2, "seed": 3},
            output_dir=str(tmp_path / "tuned_out"),
        )
        assert main(["--config", str(cfg_path)]) == 0
        dirs = sorted(
            p.name for p in (tmp_path / "tuned_out" / "models").iterdir())
        assert "best" in dirs
        # Grid configs are 0 and 1; they may appear only as "best".
        assert "config_0" not in dirs and "config_1" not in dirs

    def test_per_group_evaluation_output(self, tmp_path, glmix_avro,
                                         capsys, rng):
        """savePerGroupEvaluationToHDFS equivalent: grouped AUC per group
        key written next to the models."""
        from photon_tpu.cli.train import main
        from photon_tpu.io.avro_data import write_training_examples
        from photon_tpu.types import DELIMITER

        # Binary task with a grouped AUC evaluator.
        n, d, users = 900, 4, 8
        keys = [f"f{i}{DELIMITER}t" for i in range(d)]
        w = rng.normal(size=d)

        def write(path, seed):
            r = np.random.default_rng(seed)
            x = r.normal(size=(n, d))
            uid = r.integers(0, users, size=n)
            z = x @ w + 0.5 * r.normal(size=n)
            y = (z > 0).astype(float)
            rows = [[(keys[j], float(x[i, j])) for j in range(d)]
                    for i in range(n)]
            meta = [{"userId": f"u{u}"} for u in uid]
            write_training_examples(str(path), y, rows, metadata=meta)

        tr, va = tmp_path / "t.avro", tmp_path / "v.avro"
        write(tr, 1)
        write(va, 2)
        cfg_path, _ = _config(
            tmp_path, tr, va,
            task="LOGISTIC_REGRESSION",
            coordinates={
                "global": {
                    "type": "fixed",
                    "regularization": {"type": "L2", "weights": [0.1]},
                },
            },
            evaluators=["AUC", "AUC:userId"],
        )
        assert main(["--config", str(cfg_path)]) == 0
        ge = tmp_path / "out" / "group-evaluation" / "0"
        assert ge.is_dir()
        payload = json.loads((ge / "AUC_userId.json").read_text())
        assert len(payload) == users
        assert all(0.0 <= v <= 1.0 for v in payload.values())
        assert all(k.startswith("u") for k in payload)


class TestMultiShardAvro:
    YAHOO_SCHEMA = {
        "name": "YahooStyleExample", "type": "record",
        "namespace": "test",
        "fields": [
            {"name": "userId", "type": "long"},
            {"name": "songId", "type": "long"},
            {"name": "response", "type": "double"},
            {"name": "features", "type": {"type": "array", "items": {
                "name": "F", "type": "record", "namespace": "test",
                "fields": [
                    {"name": "name", "type": "string"},
                    {"name": "term", "type": "string"},
                    {"name": "value", "type": "double"},
                ]}}},
            {"name": "userFeatures",
             "type": {"type": "array", "items": "test.F"}},
            {"name": "songFeatures",
             "type": {"type": "array", "items": "test.F"}},
        ],
    }

    def _write(self, path, rng, n=1200, users=12, songs=6):
        """Yahoo!-Music-shaped multi-bag records (readMerged semantics)."""
        from photon_tpu.io import avro

        d, du, ds_ = 4, 3, 2
        w = rng.normal(size=d)
        wu = rng.normal(size=(users, du + 1)) * 0.5  # + bias
        ws = rng.normal(size=(songs, ds_ + 1)) * 0.5

        def bag(prefix, vals):
            return [{"name": prefix, "term": str(j), "value": float(v)}
                    for j, v in enumerate(vals)]

        recs = []
        for _ in range(n):
            u = int(rng.integers(0, users))
            s_ = int(rng.integers(0, songs))
            x = rng.normal(size=d)
            xu = rng.normal(size=du)
            xs = rng.normal(size=ds_)
            y = (x @ w
                 + np.concatenate([xu, [1.0]]) @ wu[u]
                 + np.concatenate([xs, [1.0]]) @ ws[s_]
                 + 0.1 * rng.normal())
            recs.append({
                "userId": u, "songId": s_, "response": float(y),
                "features": bag("g", x),
                "userFeatures": bag("u", xu),
                "songFeatures": bag("s", xs),
            })
        avro.write_container(str(path), self.YAHOO_SCHEMA, recs)

    def test_multi_shard_glmix_end_to_end(self, tmp_path, rng, capsys):
        """readMerged semantics through the CLI: global + per-user +
        per-song coordinates, each on its own feature shard built from its
        own bags (AvroDataReader.scala:85-145)."""
        from photon_tpu.cli.train import main

        tr, va = tmp_path / "t.avro", tmp_path / "v.avro"
        self._write(tr, np.random.default_rng(0))
        self._write(va, np.random.default_rng(0), n=400)
        cfg = {
            "task": "LINEAR_REGRESSION",
            "input": {
                "format": "avro",
                "train_path": str(tr),
                "validation_path": str(va),
                "feature_shards": {
                    "globalShard": ["features"],
                    "userShard": ["userFeatures"],
                    "songShard": ["songFeatures"],
                },
                "id_columns": ["userId", "songId"],
            },
            "coordinates": {
                "global": {
                    "type": "fixed", "feature_shard": "globalShard",
                    "regularization": {"type": "L2", "weights": [1e-3]},
                },
                "per-user": {
                    "type": "random", "feature_shard": "userShard",
                    "random_effect_type": "userId",
                    "regularization": {"type": "L2", "weights": [0.1]},
                },
                "per-song": {
                    "type": "random", "feature_shard": "songShard",
                    "random_effect_type": "songId",
                    "regularization": {"type": "L2", "weights": [0.1]},
                },
            },
            "num_iterations": 3,
            "evaluators": ["RMSE"],
            "output_dir": str(tmp_path / "out"),
        }
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps(cfg))
        assert main(["--config", str(cfg_path)]) == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        # Same generating process for train/val; the GLMix must land near
        # the 0.1 noise floor, which requires ALL THREE shards to engage.
        assert out["evaluation"]["RMSE"] < 0.25
        model_dir = tmp_path / "out" / "models" / "best"
        assert (model_dir / "random-effect" / "per-user" / "id-info").is_file()
        assert (model_dir / "random-effect" / "per-song" / "id-info").is_file()

    def test_multi_shard_score_round_trip(self, tmp_path, rng, capsys):
        """Multi-shard models score via --feature-shards; without it the
        driver refuses instead of silently zeroing the random effects."""
        from photon_tpu.cli.score import main as score_main
        from photon_tpu.cli.train import main as train_main

        tr, va = tmp_path / "t.avro", tmp_path / "v.avro"
        self._write(tr, np.random.default_rng(0))
        self._write(va, np.random.default_rng(0), n=300)
        cfg = {
            "task": "LINEAR_REGRESSION",
            "input": {
                "format": "avro", "train_path": str(tr),
                "validation_path": str(va),
                "feature_shards": {
                    "globalShard": ["features"],
                    "userShard": ["userFeatures"],
                    "songShard": ["songFeatures"],
                },
                "id_columns": ["userId", "songId"],
            },
            "coordinates": {
                "global": {"type": "fixed", "feature_shard": "globalShard",
                           "regularization": {"type": "L2",
                                              "weights": [1e-3]}},
                "per-user": {"type": "random", "feature_shard": "userShard",
                             "random_effect_type": "userId",
                             "regularization": {"type": "L2",
                                                "weights": [0.1]}},
            },
            "num_iterations": 2,
            "evaluators": ["RMSE"],
            "output_dir": str(tmp_path / "out"),
        }
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps(cfg))
        assert train_main(["--config", str(cfg_path)]) == 0
        train_out = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        # The unmodeled per-song effects leave ~0.9 residual; the point of
        # this test is scoring parity, not model quality.
        train_rmse = train_out["evaluation"]["RMSE"]

        model_dir = str(tmp_path / "out" / "models" / "best")
        # Without --feature-shards: refuse.
        with pytest.raises(ValueError, match="feature-shards"):
            score_main(["--model-dir", model_dir, "--input", str(va),
                        "--output", str(tmp_path / "s0")])
        # With it: scores + evaluation.
        rc = score_main([
            "--model-dir", model_dir, "--input", str(va),
            "--output", str(tmp_path / "s1"),
            "--feature-shards", "globalShard=features",
            "userShard=userFeatures", "songShard=songFeatures",
            "--id-columns", "userId", "songId",
            "--evaluators", "RMSE",
        ])
        assert rc == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["num_scored"] == 300
        # Scoring the validation set reproduces the training-side
        # validation metric (the per-shard resolution engaged correctly).
        assert out["evaluation"]["RMSE"] == pytest.approx(
            train_rmse, rel=1e-5)


    def test_per_shard_intercept_flag(self, tmp_path, rng, capsys):
        """FeatureShardConfiguration hasIntercept: a shard may opt out of
        the intercept slot."""
        from photon_tpu.cli.train import main
        from photon_tpu.cli.index import load_index_maps  # noqa: F401
        from photon_tpu.data.index_map import IndexMap  # noqa: F401
        from photon_tpu.io.avro_data import read_merged

        tr = tmp_path / "t.avro"
        self._write(tr, np.random.default_rng(0), n=50)
        data, maps = read_merged(
            str(tr),
            feature_shards={"g": ["features"], "u": ["userFeatures"]},
            add_intercept={"g": True, "u": False},
        )
        assert maps["g"].intercept_index is not None
        assert maps["u"].intercept_index is None

        cfg = {
            "task": "LINEAR_REGRESSION",
            "input": {
                "format": "avro", "train_path": str(tr),
                "feature_shards": {
                    "globalShard": {"bags": ["features"],
                                    "intercept": True},
                    "userShard": {"bags": ["userFeatures"],
                                  "intercept": False},
                },
                "id_columns": ["userId"],
            },
            "coordinates": {
                "global": {"type": "fixed", "feature_shard": "globalShard",
                           "regularization": {"type": "L2",
                                              "weights": [0.01]}},
                "per-user": {"type": "random", "feature_shard": "userShard",
                             "random_effect_type": "userId",
                             "regularization": {"type": "L2",
                                                "weights": [0.1]}},
            },
            "output_dir": str(tmp_path / "out"),
        }
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg))
        assert main(["--config", str(p)]) == 0


class TestBaselineConfigMatrix:
    """The BASELINE.md reference config matrix through the real CLI:
    linear/logistic/Poisson GLMs with L1/L2/elastic-net + TRON, and the
    smoothed-hinge SVM with standardization."""

    def _write_task_data(self, path, rng, task, w, n=600, d=6):
        keys = [f"f{i}{DELIMITER}t" for i in range(d)]
        x = rng.normal(size=(n, d))
        z = x @ w
        if task in ("LOGISTIC_REGRESSION", "SMOOTHED_HINGE_LOSS_LINEAR_SVM"):
            y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(float)
        elif task == "POISSON_REGRESSION":
            y = rng.poisson(np.exp(np.clip(z, -4, 3))).astype(float)
        else:
            y = z + 0.1 * rng.normal(size=n)
        rows = [[(keys[j], float(x[i, j])) for j in range(d)]
                for i in range(n)]
        write_training_examples(str(path), y, rows)

    @pytest.mark.parametrize("task,reg,optimizer,metric,threshold", [
        ("POISSON_REGRESSION", {"type": "L2", "weights": [0.1]},
         {"type": "LBFGS"}, "POISSON_LOSS", None),
        ("POISSON_REGRESSION", {"type": "L2", "weights": [0.1]},
         {"type": "TRON"}, "POISSON_LOSS", None),
        ("LOGISTIC_REGRESSION", {"type": "L1", "weights": [20.0]},
         {"type": "LBFGS"}, "AUC", 0.8),
        ("LOGISTIC_REGRESSION",
         {"type": "ELASTIC_NET", "alpha": 0.5, "weights": [20.0]},
         {"type": "LBFGS"}, "AUC", 0.8),
        ("LINEAR_REGRESSION", {"type": "L2", "weights": [0.01]},
         {"type": "TRON"}, "RMSE", 0.2),
        ("SMOOTHED_HINGE_LOSS_LINEAR_SVM",
         {"type": "L2", "weights": [0.1]},
         {"type": "LBFGS"}, "AUC", 0.8),
    ])
    def test_task_reg_optimizer_combination(
        self, tmp_path, rng, capsys, task, reg, optimizer, metric, threshold
    ):
        from photon_tpu.cli.train import main

        tr = tmp_path / "t.avro"
        va = tmp_path / "v.avro"
        # Shared true model with genuinely null features so L1 sparsity is
        # observable (the objective is a SUM over rows, so lambda is on the
        # n-scale).
        w = np.random.default_rng(4).normal(size=6)
        w[3:] = 0.0
        self._write_task_data(tr, np.random.default_rng(5), task, w)
        self._write_task_data(va, np.random.default_rng(6), task, w)
        cfg = {
            "task": task,
            "input": {"format": "avro", "train_path": str(tr),
                      "validation_path": str(va)},
            "coordinates": {
                "global": {"type": "fixed", "regularization": reg,
                           "optimizer": optimizer},
            },
            # The smoothed-hinge + standardization config from BASELINE.md.
            "normalization": ("STANDARDIZATION"
                              if task == "SMOOTHED_HINGE_LOSS_LINEAR_SVM"
                              else "NONE"),
            "evaluators": [metric],
            "output_dir": str(tmp_path / "out"),
        }
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg))
        assert main(["--config", str(p)]) == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        value = out["evaluation"][metric]
        assert np.isfinite(value)
        if threshold is not None:
            if metric == "RMSE":
                assert value < threshold
            else:
                assert value > threshold
        if reg["type"] in ("L1", "ELASTIC_NET"):
            # OWL-QN must produce a genuinely sparse model.
            from photon_tpu.io import avro

            recs = avro.read_container_dir(
                str(tmp_path / "out" / "models" / "best" / "fixed-effect" /
                    "global" / "coefficients"))
            nnz = sum(1 for ntv in recs[0]["means"] if ntv["value"] != 0.0)
            assert nnz < 7  # strictly sparser than dense (d=6 + intercept)


def test_log_file_sink(tmp_path, glmix_avro, capsys):
    """--log-file writes a persistent log (PhotonLogger parity)."""
    from photon_tpu.cli.train import main

    train, val = glmix_avro
    cfg_path, _ = _config(tmp_path, train, val, num_iterations=1)
    log_path = tmp_path / "photon.log"
    assert main(["--config", str(cfg_path),
                 "--log-file", str(log_path)]) == 0
    text = log_path.read_text()
    assert "executed in" in text  # Timed sections land in the sink


def test_maybe_init_distributed_single_host_noop(monkeypatch):
    """A single-process launch never calls jax.distributed.initialize:
    on a host with TPU chips JAX's cluster auto-detection queries the
    GCE metadata server, which a sealed machine cannot reach. Only a
    configured coordinator (JAX_COORDINATOR_ADDRESS) makes the launch
    multi-host."""
    import jax

    from photon_tpu.cli.common import is_coordinator, maybe_init_distributed

    calls = []
    monkeypatch.setattr(
        jax.distributed, "initialize", lambda **kw: calls.append(kw))
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    assert maybe_init_distributed() is False
    assert maybe_init_distributed() is False  # idempotent
    assert calls == []
    assert is_coordinator() is True

    # Configured: the three variables reach initialize verbatim and
    # switch JAX's own cluster detection off.
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "localhost:1234")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "2")
    monkeypatch.setenv("JAX_PROCESS_ID", "1")
    assert maybe_init_distributed() is True
    assert calls == [dict(
        coordinator_address="localhost:1234", num_processes=2,
        process_id=1, cluster_detection_method="deactivate",
    )]


def test_feature_stats_artifact(tmp_path, glmix_avro, capsys):
    """data_summary_dir writes per-shard FeatureSummarizationResultAvro
    files (ModelProcessingUtils.writeBasicStatistics layout) that round-trip
    and match a direct numpy computation; the intercept is excluded."""
    from photon_tpu.cli.train import main
    from photon_tpu.io.model_io import load_feature_stats
    from photon_tpu.types import make_feature_key

    train, val = glmix_avro
    summary_dir = tmp_path / "summary"
    cfg_path, _ = _config(
        tmp_path, train, val, data_summary_dir=str(summary_dir),
        evaluators=["RMSE", "MAE", "MSE"],
    )
    assert main(["--config", str(cfg_path)]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "MAE" in out["evaluation"] and "MSE" in out["evaluation"]

    stats = load_feature_stats(str(summary_dir / "features"))
    # 5 named features; the intercept record is filtered out.
    assert len(stats) == 5
    key = make_feature_key("f0", "t")
    m = stats[key]
    assert set(m) == {
        "max", "min", "mean", "normL1", "normL2", "numNonzeros", "variance"}
    # Cross-check against the raw written data.
    from photon_tpu.io.avro import read_container

    _, recs = read_container(str(train))
    vals = np.array([
        f["value"] for r in recs for f in r["features"]
        if f["name"] == "f0" and f["term"] == "t"
    ])
    np.testing.assert_allclose(m["mean"], vals.mean(), rtol=1e-6)
    np.testing.assert_allclose(m["max"], vals.max(), rtol=1e-6)
    np.testing.assert_allclose(m["normL1"], np.abs(vals).sum(), rtol=1e-6)
    np.testing.assert_allclose(
        m["normL2"], np.sqrt((vals ** 2).sum()), rtol=1e-6)
    np.testing.assert_allclose(
        m["variance"], vals.var(ddof=1), rtol=1e-5)
