"""``photon train``: end-to-end GAME training driver.

TPU-native counterpart of GameTrainingDriver (photon-client
cli/game/training/GameTrainingDriver.scala:54, run :363-516): read data ->
feature index map -> warm-start model load -> feature stats -> normalization
contexts -> coordinate configs x lambda grid -> GameEstimator.fit ->
model selection -> save models (Avro layout + native checkpoint + eval
summary).

Usage:
    python -m photon_tpu.cli.train --config train.yaml [--backend tpu|cpu]
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="photon train", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--config", required=True,
                        help="YAML/JSON training configuration")
    parser.add_argument("--backend", default=None,
                        help="JAX platform override (tpu, cpu)")
    parser.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                        help="crash-safe training checkpoints: commit "
                             "an atomic recovery point (model npz + "
                             "manifest) after every outer CD iteration "
                             "(RESILIENCE.md)")
    parser.add_argument("--resume", default=None, metavar="DIR",
                        help="resume an interrupted run from DIR's "
                             "checkpoint (implies --checkpoint-dir DIR; "
                             "the manifest's config static key must "
                             "match this run's configuration)")
    parser.add_argument("--stream-dir", default=None, metavar="DIR",
                        help="fault-tolerant out-of-core streaming "
                             "ingest: train from DIR's Avro shards in "
                             "bounded-memory windows with per-shard "
                             "integrity checks, transient-I/O retry, "
                             "and a resumable cursor — instead of the "
                             "config's whole-dataset train_path load "
                             "(DATA.md)")
    parser.add_argument("--resume-ingest", action="store_true",
                        help="resume a killed streaming ingest from its "
                             "committed cursor (window spills are "
                             "reloaded; the resumed dataset is byte-"
                             "identical to the uninterrupted run). "
                             "Requires --stream-dir")
    parser.add_argument("--stream-window", type=int, default=1,
                        metavar="N",
                        help="shards per streaming window (decode of "
                             "window k+1 overlaps window k's device "
                             "transfer; default 1 = cursor commits at "
                             "every shard boundary)")
    parser.add_argument("--max-bad-shards", type=int, default=0,
                        metavar="N",
                        help="quarantine budget: tolerate up to N "
                             "corrupt shards (skip + count + surface "
                             "ingested_fraction; default 0 = abort on "
                             "the first corrupt shard)")
    parser.add_argument("--max-bad-fraction", type=float, default=0.0,
                        metavar="F",
                        help="quarantine budget as a fraction of the "
                             "shard count (combined with "
                             "--max-bad-shards via max)")
    parser.add_argument("--init-model", default=None, metavar="PATH",
                        help="day-over-day warm start: load yesterday's "
                             "GameModel (a native checkpoint .npz or an "
                             "Avro model directory) as the initial "
                             "model; its digest is recorded in the "
                             "training checkpoint manifest so crash "
                             "recovery resumes ingest-then-descent "
                             "end to end")
    parser.add_argument("--verbose", action="store_true")
    parser.add_argument("--log-file", default=None,
                        help="also write logs to this file (PhotonLogger "
                             "equivalent, util/PhotonLogger.scala:34)")
    parser.add_argument("--telemetry", default=None, metavar="PATH",
                        help="enable runtime telemetry (photon_tpu.obs) "
                             "and write the JSONL stream to PATH; the "
                             "snapshot also lands in "
                             "training-summary.json (OBSERVABILITY.md). "
                             "Resets the process's telemetry stream: "
                             "the run owns its stream end to end")
    parser.add_argument("--trace", default=None, metavar="PATH",
                        help="write the merged Chrome-trace/Perfetto "
                             "timeline (host spans, counter tracks, "
                             "resilience events) to PATH at the end of "
                             "the run (OBSERVABILITY.md)")
    parser.add_argument("--flight-dir", default=None, metavar="DIR",
                        help="crash flight recorder destination "
                             "(default: the config's output_dir): "
                             "flight-<pid>.json is dumped there when "
                             "training is interrupted by SIGINT/SIGTERM, "
                             "dies on an unhandled exception, or hits a "
                             "crash-kind injected fault")
    parser.add_argument("--no-flight", action="store_true",
                        help="disable the crash flight recorder")
    parser.add_argument("--monitor-port", type=int, default=None,
                        metavar="PORT",
                        help="serve /metrics (Prometheus text "
                             "exposition from the live metrics "
                             "registry), /healthz, and /readyz on this "
                             "port for the whole run (0 = ephemeral; "
                             "multi-process runs bind port + "
                             "process_index so ranks sharing a host "
                             "never collide). /readyz flips 200 once "
                             "the training datasets are prepared "
                             "(OBSERVABILITY.md §live monitoring)")
    parser.add_argument("--distributed", action="store_true",
                        help="arm distributed observability "
                             "(obs/fleet.py): telemetry + the cost "
                             "ledger record for the whole run and this "
                             "rank commits an atomic obs bundle into "
                             "the shared fleet dir at exit — merge the "
                             "ranks with python -m "
                             "photon_tpu.cli.fleetview "
                             "(OBSERVABILITY.md §distributed "
                             "observability). Single-process runs ship "
                             "a 1-rank fleet")
    parser.add_argument("--fleet-dir", default=None, metavar="DIR",
                        help="shared run directory for --distributed "
                             "bundles (default: $PHOTON_FLEET_DIR, "
                             "else <output_dir>/fleet)")
    args = parser.parse_args(argv)
    if (args.resume and args.checkpoint_dir
            and os.path.abspath(args.resume)
            != os.path.abspath(args.checkpoint_dir)):
        # A divergent pair would load the manifest from --resume but look
        # up config-final/best artifacts in --checkpoint-dir, silently
        # resuming without them.
        parser.error(
            "--resume and --checkpoint-dir point at different "
            f"directories ({args.resume} vs {args.checkpoint_dir}); "
            "--resume DIR already implies --checkpoint-dir DIR")
    if args.resume_ingest and not args.stream_dir:
        parser.error("--resume-ingest requires --stream-dir")

    if args.backend:
        os.environ["JAX_PLATFORMS"] = args.backend
    from photon_tpu.cli.common import cli_logging, maybe_init_distributed

    with cli_logging(args.verbose, args.log_file):
        from photon_tpu.resilience import faults
        from photon_tpu.utils import enable_compilation_cache

        # Chaos harness: PHOTON_TPU_FAULT_PLAN arms a seeded FaultPlan
        # inside this process (no-op when unset) — how the chaos-smoke
        # CI and the kill/resume tests inject faults into a real
        # training subprocess deterministically.
        faults.arm_from_env()
        enable_compilation_cache()  # persistent XLA cache: warm runs skip compiles
        maybe_init_distributed()
        from photon_tpu import obs

        was_enabled = obs.enabled()
        from photon_tpu.obs import ledger

        ledger_was_enabled = ledger.enabled()
        if args.distributed:
            # The fleet bundle wants the full attribution surface:
            # spans + events (telemetry) AND the PR 12 ledger rows the
            # straggler report rolls up. Both are audited host-only
            # layers (the tier-2 telemetry/ledger/fleet-obs contracts).
            ledger.enable()
        if args.telemetry or args.trace or args.distributed:
            # DESTRUCTIVE by design: the --telemetry/--trace run owns
            # the process's telemetry stream (a JSONL mixing a prior
            # session's records into this run's artifact would be
            # worse); only the enabled flag is restored afterwards —
            # in-process callers who need their accumulated records
            # must snapshot before invoking main(). --trace enables
            # too: an exported timeline from rings nothing ever wrote
            # to would be an empty trace.json, silently.
            obs.reset()
            obs.enable()
        if args.distributed:
            # obs.reset() above dropped fleet state too — including the
            # init clock sample maybe_init_distributed() took. Re-arm
            # the init half of the handshake NOW, or the commit-time
            # skew bound pairs a sample against itself and degrades to
            # spread-only. And pin the run id every rank will stamp:
            # explicit set_run_id / PHOTON_RUN_ID wins; otherwise
            # derive it from the shared fleet dir path, identical on
            # every rank by construction.
            from photon_tpu.obs import fleet

            fleet.mark_init()
            if fleet.run_id() is None:
                try:
                    resolved = (
                        args.fleet_dir
                        or os.environ.get("PHOTON_FLEET_DIR")
                    )
                    if not resolved:
                        from photon_tpu.cli.config import TrainingConfig

                        resolved = os.path.join(
                            TrainingConfig.load(args.config).output_dir,
                            "fleet",
                        )
                    import zlib

                    digest = zlib.crc32(
                        os.path.abspath(resolved).encode("utf-8"))
                    fleet.set_run_id(f"train-{digest & 0xffffffff:08x}")
                except Exception:
                    # A bad config fails loudly inside _run; bundles
                    # from the doomed run just ship without a run id.
                    pass
        from photon_tpu.obs import flight

        # Live monitoring (obs/monitor.py): /healthz answers as soon as
        # the exporter binds; /readyz follows the registry's
        # train_datasets_prepared gauge (set by _run after prepare) —
        # a long training run is observable by PULLING, not only from
        # its end-of-run summary/JSONL artifacts.
        mon = None
        if args.monitor_port is not None:
            from photon_tpu.obs import monitor

            def _train_ready():
                gauges = obs.REGISTRY.snapshot()["gauges"]
                prepared = gauges.get("train_datasets_prepared", 0) >= 1
                return prepared, {"datasets_prepared": prepared}

            from photon_tpu.obs import fleet

            # Rank-offset the bind (base + process_index): several
            # ranks sharing one host must not collide on one
            # --monitor-port value.
            mon = monitor.MonitorServer(
                fleet.resolve_monitor_port(args.monitor_port),
                readiness=_train_ready,
            ).start()
            logging.getLogger("photon.train").info(
                "monitor endpoints on port %d (requested %d, rank %d) "
                "(/metrics /healthz /readyz)", mon.port,
                args.monitor_port,
                fleet.host_identity()["process_index"])

        # _run installs the CLI's own recorder (unless --no-flight);
        # dump/uninstall below are gated on that install actually having
        # happened, so an embedding caller's ambient recorder is never
        # dumped to or torn down behind its back.
        prior_rec = flight.installed()
        try:
            return _run(args)
        except BaseException as exc:
            # The flight recorder's chained sys.excepthook never fires
            # for in-process callers (they catch up-stack): dump the
            # post-mortem at the unwind. A SystemExit is an exit code,
            # not a crash.
            if (not isinstance(exc, SystemExit)
                    and flight.installed() is not prior_rec):
                flight.dump(f"exception:{type(exc).__name__}")
            raise
        finally:
            if mon is not None:
                mon.stop()
            if args.distributed:
                # Ship THIS rank's bundle before the recorder teardown
                # below (its restore path may reset the rings) — a
                # failed run still leaves its half of the fleet
                # post-mortem. The merge side (cli.fleetview) joins the
                # ranks afterwards.
                try:
                    from photon_tpu.obs import fleet

                    fleet_dir = (
                        args.fleet_dir
                        or os.environ.get("PHOTON_FLEET_DIR")
                    )
                    if not fleet_dir:
                        from photon_tpu.cli.config import TrainingConfig

                        fleet_dir = os.path.join(
                            TrainingConfig.load(args.config).output_dir,
                            "fleet",
                        )
                    out_dir = fleet.ship_bundle(fleet_dir)
                    logging.getLogger("photon.train").info(
                        "fleet bundle committed to %s", out_dir)
                except Exception:
                    logging.getLogger("photon.train").exception(
                        "failed to ship the fleet bundle")
            # Uninstall FIRST: it restores the telemetry flag to the
            # state it found at install time (inside _run), and the
            # --telemetry/--trace restore below must win over it.
            if flight.installed() is not prior_rec:
                flight.uninstall()
                if prior_rec is not None:
                    # _run's default-on install replaced an embedding
                    # caller's ambient recorder: hand it back re-armed,
                    # so the caller's post-mortem coverage survives.
                    flight.reinstall(prior_rec)
                elif (not (args.telemetry or args.trace
                           or args.distributed) and not was_enabled):
                    # The flight install was the ONLY thing recording
                    # (caller had telemetry off, asked for no exports):
                    # drop this run's records instead of leaving them
                    # to pollute the caller's next snapshot/JSONL.
                    obs.reset()
            if args.trace:
                try:
                    obs.write_chrome_trace(args.trace)
                    logging.getLogger("photon.train").info(
                        "chrome trace written to %s", args.trace)
                except Exception:
                    logging.getLogger("photon.train").exception(
                        "failed to write trace to %s", args.trace)
            if args.telemetry:
                try:
                    obs.write_jsonl(args.telemetry)
                    logging.getLogger("photon.train").info(
                        "telemetry JSONL written to %s\n%s",
                        args.telemetry, obs.summary_table(),
                    )
                except Exception:
                    # Telemetry must never mask the run's own outcome:
                    # a bad --telemetry path on a failed run would
                    # otherwise replace the real training exception.
                    logging.getLogger("photon.train").exception(
                        "failed to write telemetry to %s", args.telemetry
                    )
            if args.telemetry or args.trace or args.distributed:
                # Restore the caller's prior ENABLED FLAG (the recorded
                # stream was reset above, by design) so an in-process
                # caller that keeps telemetry on — the bench's wide-d
                # block — continues recording after we return.
                obs.TRACER.enabled = was_enabled
            if args.distributed and not ledger_was_enabled:
                ledger.disable()


def _run(args) -> int:
    log = logging.getLogger("photon.train")

    # Imports follow the backend env override.
    from photon_tpu.cli.config import TrainingConfig
    from photon_tpu.data.libsvm import read_libsvm
    from photon_tpu.data.index_map import IndexMap
    from photon_tpu.io.avro_data import read_merged, read_training_examples
    from photon_tpu.io.model_io import (
        load_game_model,
        save_checkpoint,
        save_game_model,
    )
    from photon_tpu.ops.normalization import (
        NormalizationType,
        build_normalization_context,
    )
    from photon_tpu.stat import FeatureDataStatistics
    from photon_tpu.types import TaskType

    # Section timing rides the unified telemetry layer; obs.logged_span
    # keeps the reference's Timed/PhotonLogger "begin execution" /
    # "executed in" log contract for the --log-file sink.
    from photon_tpu import obs
    from photon_tpu.obs import flight

    t_start = time.time()
    cfg = TrainingConfig.load(args.config)
    os.makedirs(cfg.output_dir, exist_ok=True)

    # Crash flight recorder (obs/flight.py): the last N seconds of
    # spans/events/metric deltas land in flight-<pid>.json when the run
    # dies. Signals stay with THIS driver's own handlers below (they
    # commit the emergency checkpoint); the interrupt path and main()'s
    # unwind call flight.dump explicitly, and crash-kind injected
    # faults dump through the faults.on_crash listener. Installing
    # enables telemetry recording (host-side only — the audited
    # zero-overhead contracts); main()'s finally uninstalls.
    recorder = None
    if not args.no_flight:
        recorder = flight.install(
            args.flight_dir or cfg.output_dir, signals=False
        )

    # ------------------------------------------------------------------
    # read data (readTrainingData :537)
    # ------------------------------------------------------------------
    def read_libsvm_game(path, index_map=None):
        """libsvm -> single-shard GameDataset + identity index map."""
        from photon_tpu.data.game_data import make_game_dataset

        # -1/+1 -> 0/1 label mapping is a BINARY convention; regression
        # labels legitimately go negative and must pass through.
        binary = cfg.task in (
            TaskType.LOGISTIC_REGRESSION,
            TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
        )
        if index_map is None:
            batch = read_libsvm(path, binary_labels_to01=binary)
            imap = IndexMap.identity(
                batch.num_features - 1, add_intercept=True
            )
        else:
            imap = index_map
            batch = read_libsvm(
                path, num_features=len(imap) - 1,
                binary_labels_to01=binary,
            )
        game = make_game_dataset(
            batch.labels,
            {"features": batch.features},
            offsets=batch.offsets,
            weights=batch.weights,
        )
        return game, imap

    # Daily-format (yyyy/MM/dd) input selection; records from every selected
    # day concatenate into one dataset (IOUtils.getInputPathsWithinDateRange).
    train_records = None
    val_records = None
    if cfg.date_range or cfg.days_range:
        if cfg.input_format != "avro":
            raise ValueError("date_range/days_range apply to avro input only")
        from photon_tpu.io import avro as avro_io
        from photon_tpu.io.paths import (
            DateRange,
            DaysRange,
            paths_for_date_range,
        )

        if cfg.date_range and cfg.days_range:
            raise ValueError("set only one of date_range / days_range")
        rng_ = (DateRange.from_string(cfg.date_range) if cfg.date_range
                else DaysRange.from_string(cfg.days_range).to_date_range())

        def read_daily(base):
            day_paths = paths_for_date_range(base, rng_)
            log.info("date range %s..%s under %s -> %d daily dir(s)",
                     rng_.start, rng_.end, base, len(day_paths))
            recs = []
            for p_ in day_paths:
                recs.extend(avro_io.read_container_dir(p_))
            return recs

        train_records = read_daily(cfg.train_path)
        if cfg.validation_path:
            val_records = read_daily(cfg.validation_path)

    prebuilt_maps = None
    if cfg.feature_index_dir:
        # Prebuilt vocab from `photon index` (the FeatureIndexingDriver /
        # PalDBIndexMapLoader path): features absent from it are dropped at
        # ingest, exactly like the reference's fixed feature maps.
        from photon_tpu.cli.index import load_index_maps

        prebuilt_maps = load_index_maps(cfg.feature_index_dir)
        log.info("loaded %d feature index map(s) from %s",
                 len(prebuilt_maps), cfg.feature_index_dir)

    prebuilt_features_map = None
    if prebuilt_maps is not None and not cfg.feature_shards:
        # Single-bag ingest reads the 'features' bag; any other shard name
        # in the vocab dir cannot be consumed here and silently training on
        # the wrong vocabulary would be worse than failing. (Multi-shard
        # configs pass the whole map dict into read_merged instead.)
        if "features" not in prebuilt_maps:
            raise ValueError(
                f"feature_index_dir {cfg.feature_index_dir!r} has no "
                f"'features' index (found: {sorted(prebuilt_maps)}); "
                "training ingest reads the 'features' bag")
        prebuilt_features_map = prebuilt_maps["features"]

    if cfg.input_format != "avro" and (
        cfg.feature_index_dir or cfg.feature_shards
    ):
        raise ValueError(
            "feature_index_dir / feature_shards apply to avro input only; "
            "libsvm data is identity-indexed single-shard "
            "(IdentityIndexMapLoader semantics)")

    multi_shard_maps = None
    stream_stats = None
    stream_work_dir = None
    if args.stream_dir:
        # ------------------------------------------------------------------
        # streaming ingest (photon_tpu.data.stream; DATA.md)
        # ------------------------------------------------------------------
        if cfg.input_format != "avro":
            raise ValueError(
                "--stream-dir streams Avro shards; set input.format to "
                "avro")
        if cfg.date_range or cfg.days_range:
            raise ValueError(
                "--stream-dir does not combine with date_range/"
                "days_range; point it at the day directory instead")
        from photon_tpu.data.stream import (
            QuarantinePolicy,
            StreamingIngest,
        )

        # Co-locate the ingest work dir (manifest/vocab/spills/cursor)
        # with the training checkpoints when crash safety is on, so one
        # directory carries the WHOLE recovery chain; else the output
        # dir.
        stream_work_dir = os.path.join(
            args.checkpoint_dir or args.resume or cfg.output_dir,
            "ingest-work")
        shard_bags = cfg.shard_bags()
        ingest = StreamingIngest(
            args.stream_dir,
            work_dir=stream_work_dir,
            feature_shards=shard_bags,
            index_maps=prebuilt_maps,
            id_tag_names=cfg.id_tags,
            id_columns=cfg.id_columns,
            input_columns=cfg.input_columns,
            add_intercept=(
                cfg.shard_intercepts() if shard_bags else True
            ),
            window_shards=args.stream_window,
            quarantine=QuarantinePolicy(
                args.max_bad_shards, args.max_bad_fraction
            ),
            resume=args.resume_ingest,
        )
        with obs.logged_span("stream ingest", log):
            train, stream_stats = ingest.run()
        log.info(
            "streamed %d row(s) from %d/%d shard(s) "
            "(ingested_fraction %.4f%s)",
            stream_stats["rows_ingested"],
            stream_stats["shards_ingested"],
            stream_stats["shards_total"],
            stream_stats["ingested_fraction"],
            f", resumed at shard {stream_stats['resumed_from_shard']}"
            if stream_stats["resumed_from_shard"] is not None else "",
        )
        if stream_stats["quarantined_paths"]:
            log.warning(
                "streaming ingest quarantined %d shard(s): %s",
                stream_stats["shards_quarantined"],
                ", ".join(stream_stats["quarantined_paths"]))
        multi_shard_maps = ingest.resolved_maps
        index_map = next(iter(multi_shard_maps.values()))
        validation = None
        if cfg.validation_path:
            if shard_bags:
                validation, _ = read_merged(
                    cfg.validation_path,
                    feature_shards=shard_bags,
                    index_maps=multi_shard_maps,
                    id_columns=cfg.id_columns,
                    id_tag_names=list(ingest.id_tag_names),
                    input_columns=cfg.input_columns,
                )
            else:
                validation, _ = read_training_examples(
                    cfg.validation_path,
                    index_map=multi_shard_maps["features"],
                    id_tag_names=list(ingest.id_tag_names),
                    input_columns=cfg.input_columns,
                )
    elif cfg.input_format == "avro" and cfg.feature_shards:
        if prebuilt_maps is not None:
            missing = sorted(set(cfg.feature_shards) - set(prebuilt_maps))
            if missing:
                raise ValueError(
                    f"feature_index_dir {cfg.feature_index_dir!r} does not "
                    f"cover shard(s) {missing}; a partially prebuilt "
                    "vocabulary would silently train those shards on a "
                    "data-derived one")
        # Multi-bag layout (AvroDataReader.readMerged): one index map and
        # one ELL matrix per configured shard.
        train, multi_shard_maps = read_merged(
            cfg.train_path,
            feature_shards=cfg.shard_bags(),
            index_maps=prebuilt_maps,
            id_columns=cfg.id_columns,
            id_tag_names=cfg.id_tags,
            input_columns=cfg.input_columns,
            add_intercept=cfg.shard_intercepts(),
            records=train_records,
        )
        index_map = next(iter(multi_shard_maps.values()))
        validation = None
        if cfg.validation_path:
            validation, _ = read_merged(
                cfg.validation_path,
                feature_shards=cfg.shard_bags(),
                index_maps=multi_shard_maps,
                id_columns=cfg.id_columns,
                id_tag_names=cfg.id_tags,
                input_columns=cfg.input_columns,
                records=val_records,
            )
    elif cfg.input_format == "avro":
        train, index_map = read_training_examples(
            cfg.train_path,
            index_map=prebuilt_features_map,
            id_tag_names=cfg.id_tags,
            input_columns=cfg.input_columns,
            records=train_records,
        )
        validation = None
        if cfg.validation_path:
            validation, _ = read_training_examples(
                cfg.validation_path,
                index_map=index_map,
                id_tag_names=cfg.id_tags,
                input_columns=cfg.input_columns,
                records=val_records,
            )
    elif cfg.input_format == "libsvm":
        train, index_map = read_libsvm_game(cfg.train_path)
        validation = None
        if cfg.validation_path:
            validation, _ = read_libsvm_game(
                cfg.validation_path, index_map=index_map
            )
    else:
        raise ValueError(f"unknown input format {cfg.input_format!r}")
    log.info("read %d train rows (%d features)",
             train.num_samples, len(index_map))

    # ------------------------------------------------------------------
    # data validation (DataValidators.sanityCheckDataFrameForTraining :433)
    # ------------------------------------------------------------------
    from photon_tpu.data.validators import sanity_check_data

    sanity_check_data(train, cfg.task, cfg.data_validation)
    if validation is not None:
        sanity_check_data(validation, cfg.task, cfg.data_validation)

    shards = sorted(train.feature_shards)
    if multi_shard_maps is not None:
        index_maps = dict(multi_shard_maps)
        intercept_indices = {
            s: m.intercept_index for s, m in multi_shard_maps.items()
            if m.intercept_index is not None
        }
    else:
        index_maps = {s: index_map for s in shards}
        intercept_indices = {}
        if index_map.intercept_index is not None:
            intercept_indices = {
                s: index_map.intercept_index for s in shards
            }

    # ------------------------------------------------------------------
    # warm start (loadGameModelFromHDFS :395-404)
    # ------------------------------------------------------------------
    initial_model = None
    init_model_digest = None
    if args.init_model:
        if cfg.warm_start_model_dir:
            raise ValueError(
                "--init-model and the config's warm_start_model_dir are "
                "both set; pass exactly one warm-start source")
        from photon_tpu.io.model_io import load_initial_model

        initial_model, init_model_digest = load_initial_model(
            args.init_model, index_maps
        )
        log.info("warm start from --init-model %s (digest %s...)",
                 args.init_model, init_model_digest[:12])
    elif cfg.warm_start_model_dir:
        initial_model, _ = load_game_model(
            cfg.warm_start_model_dir, index_maps
        )
        log.info("warm start from %s", cfg.warm_start_model_dir)
    if cfg.incremental_training and initial_model is None:
        raise ValueError(
            "incremental_training is enabled but no warm_start_model_dir "
            "is configured (GameEstimator.scala:241-382)")

    # ------------------------------------------------------------------
    # feature stats + normalization (prepareNormalizationContexts :590)
    # ------------------------------------------------------------------
    norm_contexts = {}
    if (
        cfg.normalization != NormalizationType.NONE
        or cfg.data_summary_dir
    ):
        import jax.numpy as jnp

        from photon_tpu.cli.common import is_coordinator

        for s in shards:
            stats = FeatureDataStatistics.from_features(
                train.feature_shards[s],
                train.host_column("weights"),
                intercept_index=intercept_indices.get(s),
            )
            if cfg.data_summary_dir and is_coordinator():
                # calculateAndSaveFeatureShardStats :616-627: one
                # FeatureSummarizationResultAvro dir per shard.
                from photon_tpu.io.model_io import save_feature_stats

                save_feature_stats(
                    os.path.join(cfg.data_summary_dir, s),
                    stats,
                    index_maps[s],
                )
                log.info("feature stats for shard %r written to %s",
                         s, os.path.join(cfg.data_summary_dir, s))
            if cfg.normalization != NormalizationType.NONE:
                norm_contexts[s] = build_normalization_context(
                    cfg.normalization,
                    mean=jnp.asarray(stats.mean),
                    variance=jnp.asarray(stats.variance),
                    min_=jnp.asarray(stats.min),
                    max_=jnp.asarray(stats.max),
                    intercept_index=intercept_indices.get(s),
                )

    # ------------------------------------------------------------------
    # fit over the lambda grid (GameEstimator.fit :397)
    # ------------------------------------------------------------------
    estimator = cfg.build_estimator(norm_contexts, intercept_indices)
    opt_seq = cfg.opt_config_sequence()
    log.info("training %d configuration(s)", len(opt_seq))

    # ------------------------------------------------------------------
    # crash safety (photon_tpu.resilience; RESILIENCE.md)
    # ------------------------------------------------------------------
    checkpointer = None
    resume_state = None
    ckpt_dir = args.checkpoint_dir or args.resume
    if ckpt_dir:
        from photon_tpu.resilience import (
            TrainingCheckpointer,
            load_training_checkpoint,
            training_static_key,
        )

        static_key = training_static_key(estimator, opt_seq)
        checkpointer = TrainingCheckpointer(ckpt_dir, static_key)
        # Run provenance rides every manifest commit: the streaming-
        # ingest cursor (work dir + pinned shard-manifest hash) and the
        # init-model digest, so a crash at ANY point recovers end to
        # end — `--stream-dir --resume-ingest --resume DIR` replays
        # ingest from its cursor (spill reloads, byte-identical data)
        # and the descent from its checkpoint, against a verifiable
        # warm-start identity.
        run_meta = {}
        if stream_stats is not None:
            run_meta["ingest_cursor"] = {
                "stream_dir": os.path.abspath(args.stream_dir),
                "work_dir": os.path.abspath(stream_work_dir),
                "manifest_sha256": stream_stats.get("manifest_sha256"),
                "rows_ingested": stream_stats.get("rows_ingested"),
                "ingested_fraction":
                    stream_stats.get("ingested_fraction"),
                "quarantined_shards":
                    stream_stats.get("shards_quarantined"),
            }
        if init_model_digest is not None:
            run_meta["init_model"] = {
                "path": os.path.abspath(args.init_model),
                "sha256": init_model_digest,
            }
        if run_meta:
            checkpointer.set_run_meta(run_meta)
        if args.resume:
            resume_state = load_training_checkpoint(args.resume)
            log.info(
                "resuming from %s: config %d, last completed CD "
                "iteration %d%s", args.resume,
                resume_state.config_index, resume_state.iteration,
                " (interrupted run)" if resume_state.interrupted else "")

    # SIGINT/SIGTERM: unwind the fit via TrainingInterrupted so a final
    # emergency checkpoint lands before the nonzero exit — a preempted
    # host resumes instead of restarting from scratch. Installed only
    # around the training section (the handlers are process-global
    # state; an embedding process gets them back in the finally).
    import signal

    def _interrupt(signum, frame):
        raise TrainingInterrupted(signum)

    from photon_tpu.resilience import TrainingInterrupted

    prev_handlers = {}
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            prev_handlers[sig] = signal.signal(sig, _interrupt)
        except ValueError:  # pragma: no cover — non-main-thread embed
            pass
    try:
        with obs.logged_span("prepare training datasets", log):
            estimator.prepare(train, validation, initial_model)
        # Readiness signal for `--monitor-port`'s /readyz (and a useful
        # /metrics fact on its own). Registry mutations are not gated
        # on the telemetry flag, so the probe works with telemetry off.
        obs.REGISTRY.gauge("train_datasets_prepared").set(1)
        with obs.logged_span("train models", log), \
                obs.profile_session(
                    cfg.profile_dir, name="train_fit_profile"):
            results = estimator.fit(
                train, validation, opt_seq,
                initial_model=initial_model,
                checkpointer=checkpointer,
                resume=resume_state,
            )
    except TrainingInterrupted as exc:
        log.error("training interrupted by signal %d", exc.signum)
        # Post-mortem and recovery point commit together: the flight
        # dump carries the timeline that explains WHERE the run was
        # when the signal landed; the emergency checkpoint below
        # carries the state to resume from. Gated on THIS CLI's own
        # recorder — under --no-flight an embedding caller's ambient
        # recorder must not be dumped to behind its back.
        if recorder is not None:
            recorder.dump(f"signal:{exc.signum}")
        if checkpointer is not None:
            path = checkpointer.write_emergency()
            if path:
                log.error(
                    "emergency checkpoint committed to %s; resume "
                    "with: photon train --config %s --resume %s",
                    path, args.config, ckpt_dir)
            else:
                log.error(
                    "interrupted before any CD iteration completed; "
                    "no training state to checkpoint")
        return 128 + exc.signum
    finally:
        for sig, handler in prev_handlers.items():
            signal.signal(sig, handler)

    # ------------------------------------------------------------------
    # hyperparameter tuning (runHyperparameterTuning :677-719)
    # ------------------------------------------------------------------
    num_tuned = 0
    tuning = cfg.hyperparameter_tuning or {}
    tuning_mode = str(tuning.get("mode", "NONE")).upper()
    if tuning_mode != "NONE" and validation is None:
        log.warning(
            "hyperparameter tuning (%s) requested but no validation_path is "
            "configured; skipping", tuning_mode)
    elif tuning_mode != "NONE":
        from photon_tpu import hyperparameter

        base_config = results[0].config
        evaluator = results[0].evaluation.primary_evaluator
        evaluation_function = (
            hyperparameter.GameEstimatorEvaluationFunction(
                estimator, base_config, train, validation,
                is_opt_max=evaluator.bigger_is_better,
                initial_model=initial_model,
            ))
        if evaluation_function.num_params == 0:
            log.warning(
                "hyperparameter tuning requested but no coordinate has a "
                "tunable regularization; skipping")
        else:
            observations = evaluation_function.convert_observations(results)
            tuned = hyperparameter.search(
                int(tuning.get("iterations", 10)),
                evaluation_function.num_params,
                tuning_mode,
                evaluation_function,
                observations,
                seed=int(tuning.get("seed", 0)),
            )
            num_tuned = len(tuned)
            log.info("hyperparameter tuning (%s) evaluated %d candidate(s)",
                     tuning_mode, num_tuned)
            results = results + tuned

    # ------------------------------------------------------------------
    # model selection + save (selectBestModel :753, saveModelToHDFS :804)
    # ------------------------------------------------------------------
    best = estimator.select_best(results)
    best_idx = next(i for i, r in enumerate(results) if r is best)

    def config_json(r):
        return {
            cid: {
                "regularization":
                    c.regularization.regularization_type.value,
                "lambda": c.regularization_weight,
                "optimizer": c.optimizer.optimizer_type.value,
            }
            for cid, c in r.config.items()
        }

    # Multi-host runs execute this driver on every process (the compute —
    # fit, tuning, scoring — is SPMD and must run everywhere), but artifact
    # writes happen once, from process 0 (the reference writes from the
    # Spark driver only).
    from photon_tpu.cli.common import is_coordinator

    write_outputs = is_coordinator()
    summary = {
        "task": cfg.task.value,
        "num_training_rows": train.num_samples,
        "num_configurations": len(results),
        "num_tuned_configurations": num_tuned,
        "best_configuration_index": best_idx,
        "configurations": [
            {
                "config": config_json(r),
                "evaluation":
                    None if r.evaluation is None else r.evaluation.evaluations,
            }
            for r in results
        ],
        "wall_clock_seconds": round(time.time() - t_start, 2),
    }
    if stream_stats is not None:
        # The streaming-ingest health block: ingested_fraction +
        # quarantined paths land in the summary artifact (and the
        # stream_* registry gauges feed /metrics for --monitor-port).
        summary["streaming_ingest"] = stream_stats
    if args.telemetry:
        # The unified telemetry snapshot (span tree with host/device
        # split, metrics, convergence series, pipeline + compile-cache
        # reports) rides the summary artifact; the full per-record
        # stream goes to the --telemetry JSONL path in main().
        from photon_tpu import obs

        summary["telemetry"] = obs.snapshot()
    if write_outputs:
        with open(
            os.path.join(cfg.output_dir, "training-summary.json"), "w"
        ) as f:
            json.dump(summary, f, indent=2)

    # Model output modes (io/ModelOutputMode.scala:47): NONE saves nothing;
    # BEST the selected model; EXPLICIT adds the lambda-grid models; TUNED
    # adds the tuner's models; ALL saves everything. The best model always
    # lands in "best/".
    num_grid = len(results) - num_tuned
    mode = cfg.model_output_mode
    if mode == "NONE":
        to_save = []
    elif mode == "BEST":
        to_save = [(best_idx, best)]
    elif mode == "EXPLICIT":
        to_save = [(best_idx, best)] + [
            (i, r) for i, r in enumerate(results[:num_grid]) if i != best_idx
        ]
    elif mode == "TUNED":
        to_save = [(best_idx, best)] + [
            (i, r) for i, r in list(enumerate(results))[num_grid:]
            if i != best_idx
        ]
    elif mode == "ALL":
        to_save = list(enumerate(results))
    else:
        raise ValueError(f"unknown model_output_mode {mode!r}")
    if write_outputs:
        for i, r in to_save:
            subdir = "best" if r is best else f"config_{i}"
            out = os.path.join(cfg.output_dir, "models", subdir)
            save_game_model(
                r.model, out, index_maps,
                task=cfg.task,
                optimization_configurations=config_json(r),
            )
            save_checkpoint(r.model, os.path.join(out, "checkpoint.npz"))
        log.info("saved %d model(s) to %s", len(to_save),
                 os.path.join(cfg.output_dir, "models"))

    # ------------------------------------------------------------------
    # per-group evaluation output (savePerGroupEvaluationToHDFS :878-901)
    # ------------------------------------------------------------------
    grouped_specs = [e for e in cfg.evaluators if ":" in e]
    if mode != "NONE" and validation is not None and grouped_specs:
        import numpy as np

        from photon_tpu.evaluation.suite import make_suite
        from photon_tpu.transformers import GameTransformer

        group_ids = {
            name: (tag.codes, tag.num_groups)
            for name, tag in validation.id_tags.items()
        }
        suite = make_suite(
            grouped_specs, validation.labels,
            offsets=validation.offsets, weights=validation.weights,
            group_ids=group_ids, dtype=validation.labels.dtype,
        )
        for i, r in to_save:
            # Scoring is SPMD compute: every process participates; only
            # the file writes below are coordinator-gated.
            scores = GameTransformer(
                r.model, mesh=estimator.resolve_mesh()
            ).score(validation)
            per_group = suite.evaluate_per_group(scores)
            if not write_outputs:
                continue
            out_dir = os.path.join(
                cfg.output_dir, "group-evaluation", str(i))
            os.makedirs(out_dir, exist_ok=True)
            for metric, values in per_group.items():
                tag = metric.split(":", 1)[1]
                keys = validation.id_tags[tag].inverse
                payload = {
                    str(k): float(v)
                    for k, v in zip(keys, values)
                    if np.isfinite(v)
                }
                fname = metric.replace(":", "_") + ".json"
                with open(os.path.join(out_dir, fname), "w") as f:
                    json.dump(payload, f, indent=2)
        log.info("wrote per-group evaluations for %d model(s)", len(to_save))
    if write_outputs:
        print(json.dumps({
            "best_configuration": config_json(best),
            "evaluation":
                None if best.evaluation is None
                else best.evaluation.evaluations,
            "output_dir": cfg.output_dir,
            "wall_clock_seconds": summary["wall_clock_seconds"],
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
