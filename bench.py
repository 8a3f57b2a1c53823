"""GLMix end-to-end training benchmark (the BASELINE.json headline workload).

Workload: synthetic MovieLens-shaped GLMix — a dense global fixed effect plus
per-user and per-movie random effects with NON-TRIVIAL per-entity feature
shards (17-dim user shard, 9-dim movie shard, matching the reference's
userShard/songShard design in the Yahoo! Music config), trained by block
coordinate descent. Two task variants run:

- **logistic** (the HEADLINE): binarized labels; per-entity subproblems are
  solved by batched damped-Newton/IRLS — the a1a-style binary GLMix
  configuration and the reference's hard iterative path
  (RandomEffectCoordinate.scala:243-292);
- **squared loss**: exact vmapped per-entity Cholesky solves — the
  MovieLens GLMix configuration.

Per variant, phases are measured separately (the reference's Timed sections
around prepareTrainingDatasets vs CoordinateDescent.run):
- **ingest**: host-side dataset planning (PARALLEL across coordinates and
  chunked within them, data/pipeline.py) + the chunked packed plan-buffer
  transfer; the per-stage breakdown (``plan_seconds``,
  ``transfer_seconds``) rides in ``*_pipeline``;
- **compile**: the full compile cost actually paid. The whole
  coordinate-descent fit is ONE fused XLA program (algorithm/fused_fit.py)
  plus one slab materialization program; since round 6 both AOT-compile on
  a BACKGROUND thread from shape-predicted skeletons while ingest runs
  (``compile_overlap_fraction`` reports how much of that compile hid), so
  ``e2e_seconds`` is the MEASURED wall of prepare + first fit — strictly
  less than ``ingest_seconds + compile_seconds`` when the overlap is real,
  never a re-labeled sum. ``warm_cache_e2e`` reports a complete second
  prepare+fit cycle on freshly built identical-shape data in the same
  process — the daily-cadence rerun cost.
- **train**: steady-state coordinate descent, measured as an AGGREGATE of
  repeated full fits until >= MIN_MEASURE_SECONDS of wall-clock accumulates
  — no reported metric derives from a sub-100ms measurement. Each fit
  ends in ``jax.block_until_ready`` on every trained coefficient table
  (jax dispatch is asynchronous); the tables themselves stay on device,
  exactly as production scoring consumes them.

Roofline accounting, per variant:
- ``model_flops_per_sec``: analytic lower-bound count of USEFUL model FLOPs
  (matvecs, Newton/IRLS iterations, normal equations, Cholesky, scoring)
  from the run's actual iteration diagnostics, divided by aggregate train
  wall-clock. ``fraction_of_bf16_peak`` divides by the chip's bf16 peak.
- ``hbm_bytes_per_sec``: analytic count of bytes the training step must
  move through HBM (feature slabs, gathers, labels/offsets/weights, once
  per pass that touches them), divided by the same wall-clock;
  ``fraction_of_hbm_peak`` divides by the v5e HBM roofline. GLM training is
  expected to sit far closer to the HBM roofline than the FLOP one — this
  pair of numbers makes the "bandwidth-bound" claim measurable.

HONESTY NOTES (all in the output line):
- ``vs_baseline`` divides by a frozen NOMINAL anchor (50k rows/s,
  "Spark-local-equivalent", fixed in round 1). The reference publishes no
  wall-clock numbers anywhere (BASELINE.md), so this ratio's only valid use
  is cross-round movement; it does NOT measure the BASELINE.md north star
  (>= 4x vs Spark-on-16xA100 measured).
- ``vs_measured_sklearn`` is a MEASURED same-host external anchor: sklearn
  LogisticRegression(lbfgs) on the identical fixed-effect data plus a
  looped per-entity sklearn fit on a random sample of entities,
  extrapolated linearly to all entities and multiplied by the CD sweep
  count. The extrapolation (sample -> all entities) is the one estimated
  part and is labeled as such (``sklearn_entities_sampled``).
- ``regressions`` lists any frozen per-round floor this run violates.
  Floors RATCHET: each is ~1.5x off the best value achieved in any round
  so far (the previous 2x-headroom policy let an 11x compile regression
  through in round 4). Floor checks that compare a wall-clock
  MEASUREMENT (the ingest floor) are best-of-N (N=3): BENCH_r05 logged a
  spurious ingest regression from a single noisy window on the loaded
  2-core box; every sample still rides in the output.

The ``serving_*`` block is the ONLINE SCORING scenario
(photon_tpu.serve): coefficient tables at the training workload's scale,
the AOT-compiled score ladder, and the micro-batching queue driven to
saturation — p50/p99 latency, QPS, batch-fill fraction, cold-entity
rate, plus the runtime zero-recompile check (``serving_compile_events``
must be 0; the static half is the tier-2 ``serving`` contract). See
SERVING.md.
- ``yahoo_fixture_*`` is a SCHEMA-PARITY SMOKE TEST on the reference's own
  6-record Yahoo! Music Avro fixture (GameIntegTest/input/
  duplicateFeatures): it proves the reference's Avro layout trains
  end-to-end through the product estimator and stays under the
  GameTrainingDriverIntegTest RMSE threshold, and nothing more — 6 rows
  validate formats, not model quality. The real-data quality anchor is
  the ``a9a_*`` block (32,561 rows, held-out AUC).

The bench runs with runtime telemetry ENABLED (photon_tpu.obs): the
output's ``telemetry`` object carries the span tree (host/device split),
metrics registry, last fit's per-coordinate convergence series, and the
absorbed pipeline/compile-cache reports; ``--telemetry PATH`` also writes
the JSONL stream (schema: OBSERVABILITY.md) and ``--trace PATH`` the
merged Chrome-trace/Perfetto timeline (host spans + counter tracks +
serving request span trees, obs/trace.py). The zero-overhead guarantee
is audited statically (the tier-2 ``telemetry`` and ``trace`` contracts)
and enforced at runtime by this bench's own regression floors.
``measured_vs_roofline`` is a TRACKED metric since round 8: the full
bench gates it against a ratcheted ceiling (FLOORS) and the smoke run
fails if the gauge stops engaging (ROADMAP item 2).

Prints exactly ONE JSON line.
"""

import json
import os
import time

import numpy as np

# Frozen round-1 anchor (see HONESTY NOTES). Nominal Spark local[*]
# throughput on a comparable GLMix workload; the reference repo itself
# publishes no benchmark numbers.
ANCHOR_ROWS_PER_SEC = 50_000.0
# Per-chip peaks — ONE table with the static cost model's roofline
# (analysis/costmodel.py), keyed by the device_kind JAX reports. The
# full bench is a measuring path: main() looks the running device up
# and a device without a row is an error. The CI-scale --smoke run
# measures nothing about a chip, leaves this None, and reports every
# fraction-of-peak as None (not measured).
PEAKS: dict | None = None


def _fraction_of_peak(rate: float, peak: str, digits: int):
    return None if PEAKS is None else round(rate / PEAKS[peak], digits)


# MovieLens-shaped scale, round-4 sizing: the round-3 workload's steady
# state collapsed to single-digit milliseconds once the per-entity solves
# went batched-Newton, so rows/entities grew and the steady-state metric is
# an aggregate over >= MIN_MEASURE_SECONDS of repeated fits.
N_ROWS = 4_000_000
N_FEATURES = 64
N_USER_FEATURES = 16  # + bias -> 17-dim per-user subproblems
N_MOVIE_FEATURES = 8  # + bias -> 9-dim per-movie subproblems
N_USERS = 100_000
N_MOVIES = 20_000
CD_ITERATIONS = 4
MIN_MEASURE_SECONDS = 2.0

# Roofline-push knobs (ROADMAP item 2; PERFORMANCE.md). The training
# variants run the MIXED-PRECISION fused path by default — bf16 slab +
# score storage with f32 accumulators (numerical parity pinned per
# family by tests/test_precision.py) — and merge bucket tails so warm
# refits dispatch fewer, fatter programs. PHOTON_BENCH_PRECISION=float32
# restores the historical f32 measurement for A/B.
BENCH_PRECISION = os.environ.get("PHOTON_BENCH_PRECISION", "bfloat16")
BENCH_MIN_BUCKET_ENTITIES = int(
    os.environ.get("PHOTON_BENCH_MIN_BUCKET_ENTITIES", "128")
)

# Per-round wall-clock floors (regression gate): RATCHETED to ~1.5x off
# the best value achieved in rounds 1-5 (round-5 measurements, taken on
# a backend that no longer exists and not measured on this chip: 13.7M
# train rows/s with the fused Newton kernel + gather scoring, 1.5-1.7M
# ingest rows/s, cold first fit 31-90s). A violation appears in the
# output's "regressions" list.
# The old policy (~2x headroom frozen at round 4) let an 11x compile
# regression pass silently — these fail the bench instead.
FLOORS = {
    "logistic_rows_per_sec": 9.0e6,
    # Re-baselined in round 13 (was 1.0e6): the 1M floor was calibrated
    # on the round-3 container's measured 1.01-1.19M rows/s, but the
    # CI-class 2-core box the bench has actually run on since measured
    # 400k (r04) and 510k (r05) — BENCH_r05 carried the violation as an
    # advisory `regressions` entry for two rounds while the run exited
    # 0. Now that cli.benchtrend GATES embedded regressions, the floor
    # follows the standard ratchet policy against the measured series:
    # ~1.5x off the round-5 best (510028 / 1.5). The r05 entry itself
    # is waived by name in cli/benchtrend.py WAIVED_REGRESSIONS with
    # this justification; a future faster box re-ratchets upward.
    "ingest_rows_per_sec": 3.4e5,
    "logistic_compile_seconds_max": 150.0,
    # Roofline gauge (ROADMAP item 2, gating half): measured fit wall /
    # static roofline lower bound for the fused whole-fit program
    # (predict_program_costs -> costmodel.fused_fit_report). CEILING,
    # not floor: a bigger ratio means the dispatch drifted further from
    # the chip's best case. Calibrated from the round-5 device run's
    # analytic HBM fraction (0.046 of peak => ~22x the bandwidth
    # roofline) with the standard ~1.5x ratchet headroom. Applies to
    # the full TPU-scale bench only — the CPU smoke run asserts the
    # gauge EXISTS (a dead gauge is the regression there), since a CPU
    # wall clock against a v5e roofline is not a meaningful ratio.
    "logistic_measured_vs_roofline_max": 35.0,
    # Cost-ledger attribution (obs/ledger.py): the fraction of the
    # measured steady-state fit wall that lands on NAMED
    # (coordinate, phase, program) rows — the residual rides as the
    # explicit `unattributed` row. FLOOR at TPU scale: an attribution
    # layer that names less than 95% of the wall is not an instrument.
    # The CPU smoke asserts the block ENGAGED (rows + a non-None
    # fraction); per-fit host overhead is proportionally larger at
    # smoke scale, so the 0.95 bar applies to the full bench only.
    "logistic_attributed_fraction_min": 0.95,
}
# Floor checks compare the BEST of this many ingest measurements (first
# prepare + the warm-cycle prepare + one extra replan): BENCH_r05 logged
# a spurious ingest regression because the floor compared a SINGLE
# measurement on the loaded 2-core box — one noisy scheduler window
# looked like a real regression. The mean and every sample still ride
# in the output; only the gate uses the best.
INGEST_FLOOR_SAMPLES = 3

# Serving scenario sizing (shrunk by --smoke like the training workload).
N_SERVE_REQUESTS = 20_000
SERVE_COLD_FRACTION = 0.05
SERVE_RUNGS = (1, 8, 64, 512)
SERVE_MAX_LINGER_MS = 1.0

# Streaming scenario sizing (photon_tpu.data.stream; DATA.md). Day-1
# stream-ingests Avro shards from disk and trains; day-2 re-streams and
# warm-starts from day-1's model — `incremental_rows_per_sec` is the
# daily-cadence retrain cost the out-of-core path exists for.
STREAM_ROWS = 120_000
STREAM_SHARDS = 8
STREAM_FEATURES = 8
STREAM_USERS = 2_000
STREAM_WINDOW_SHARDS = 2

# Drift scenario sizing (photon_tpu.obs.health; OBSERVABILITY.md §
# Model & data health): a three-day pilot replay with health gates
# ARMED — day 0 bootstraps and commits the reference sketch, day 1
# replays the IDENTICAL distribution (must promote cleanly), day 2
# replays a SHIFTED distribution (feature values translated by
# DRIFT_SHIFT) and the promotion must be REFUSED with a `health:*`
# reason. The end-to-end proof that the gate fires on real drift and
# stays quiet without it.
DRIFT_USERS = 12
DRIFT_FEATURES = 6
DRIFT_ROWS_PER_USER_DAY = 24
DRIFT_SHIFT = 4.0
DRIFT_MAX_PSI = 0.25

# Pilot scenario sizing (photon_tpu.pilot; PILOT.md): a multi-"day"
# replay of the production control loop — day 1 bootstraps a serving
# generation, each later day drops a shard and the pilot ingests →
# warm-start retrains → gates → hot-reloads the LIVE queue while a
# traffic thread scores against it continuously. Measured: staleness
# (shard-landed → model-serving seconds), promotions, and the two
# zero-gates (reload compile events, dropped/errored requests).
PILOT_DAYS = 4
PILOT_USERS = 16
PILOT_FEATURES = 6
PILOT_ROWS_PER_USER_DAY = 24
PILOT_TRAFFIC_QPS = 250.0
PILOT_RUNGS = (1, 8, 32)

YAHOO_TRAIN = (
    "/root/reference/photon-client/src/integTest/resources/GameIntegTest/"
    "input/duplicateFeatures/yahoo-music-train.avro"
)


def _synth_arrays(task="linear"):
    """The MovieLens-shaped synthetic workload as raw numpy (shared by the
    framework's ingest AND the measured sklearn baseline — identical data
    by construction: same seed, same draws)."""
    rng = np.random.default_rng(20260729)
    x = rng.normal(size=(N_ROWS, N_FEATURES)).astype(np.float32)
    x[:, -1] = 1.0
    xu = rng.normal(size=(N_ROWS, N_USER_FEATURES + 1)).astype(np.float32)
    xu[:, -1] = 1.0
    xm = rng.normal(size=(N_ROWS, N_MOVIE_FEATURES + 1)).astype(np.float32)
    xm[:, -1] = 1.0
    users = rng.integers(0, N_USERS, size=N_ROWS)
    movies = rng.integers(0, N_MOVIES, size=N_ROWS)
    w = rng.normal(size=N_FEATURES).astype(np.float32) * 0.3
    wu = rng.normal(size=(N_USERS, N_USER_FEATURES + 1)).astype(np.float32) * 0.3
    wm = rng.normal(size=(N_MOVIES, N_MOVIE_FEATURES + 1)).astype(np.float32) * 0.2
    z = (
        x @ w
        + np.einsum("nd,nd->n", xu, wu[users])
        + np.einsum("nd,nd->n", xm, wm[movies])
    )
    if task == "logistic":
        y = (
            rng.uniform(size=N_ROWS) < 1.0 / (1.0 + np.exp(-0.5 * z))
        ).astype(np.float32)
    else:
        y = (z + 0.2 * rng.normal(size=N_ROWS)).astype(np.float32)
    return x, xu, xm, users, movies, y


def build_data(task="linear"):
    from photon_tpu.data.dataset import DenseFeatures
    from photon_tpu.data.game_data import make_game_dataset

    x, xu, xm, users, movies, y = _synth_arrays(task)
    # Numpy-backed shards: make_game_dataset pushes the device copy once and
    # keeps host mirrors for the (host-side) dataset-build planner.
    return make_game_dataset(
        y,
        {
            "global": DenseFeatures(x),
            "userShard": DenseFeatures(xu),
            "movieShard": DenseFeatures(xm),
        },
        id_tags={"userId": users, "movieId": movies},
    )


def run_sklearn_baseline(our_per_fit_seconds: float) -> dict:
    """MEASURED same-host external anchor (sklearn, CPU).

    Measures on the IDENTICAL logistic workload:
    - one full fixed-effect LogisticRegression(lbfgs) fit on all 4M x 64
      rows;
    - per-entity LogisticRegression fits on a random sample of users and
      movies (their actual row subsets), timed per entity.

    A GLMix block-coordinate sweep solves the fixed effect once plus every
    per-entity subproblem, CD_ITERATIONS times; the estimate below
    composes exactly that from the measured pieces. The per-entity cost is
    extrapolated linearly from ``sklearn_entities_sampled`` entities — the
    one estimated step, and the reason the headline ratio is labeled an
    estimate. Single-class entities (sklearn refuses them) count at the
    sampled mean.
    """
    try:
        from sklearn.linear_model import LogisticRegression
    except Exception:  # pragma: no cover
        return {"sklearn_skipped": "scikit-learn not available"}

    x, xu, xm, users, movies, y = _synth_arrays("logistic")
    t0 = time.perf_counter()
    LogisticRegression(C=1.0, solver="lbfgs", max_iter=100).fit(x, y)
    fe_seconds = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    sample = 400

    def per_entity_seconds(codes, feats, n_groups):
        order = np.argsort(codes, kind="stable")
        sorted_codes = codes[order]
        starts = np.searchsorted(sorted_codes, np.arange(n_groups))
        ends = np.append(starts[1:], codes.shape[0])
        picks = rng.choice(n_groups, size=min(sample, n_groups),
                           replace=False)
        t0 = time.perf_counter()
        fitted = 0
        for e in picks:
            rows = order[starts[e]:ends[e]]
            if rows.size == 0:
                continue
            ye = y[rows]
            if ye.min() == ye.max():
                continue  # single-class: counted at the sampled mean
            LogisticRegression(C=1.0, solver="lbfgs", max_iter=100).fit(
                feats[rows], ye)
            fitted += 1
        dt = time.perf_counter() - t0
        return dt / max(fitted, 1)

    user_s = per_entity_seconds(users, xu, N_USERS)
    movie_s = per_entity_seconds(movies, xm, N_MOVIES)
    sweep = fe_seconds + user_s * N_USERS + movie_s * N_MOVIES
    total = sweep * CD_ITERATIONS
    return {
        "sklearn_fe_fit_seconds": round(fe_seconds, 3),
        "sklearn_re_seconds_per_user": round(user_s, 6),
        "sklearn_re_seconds_per_movie": round(movie_s, 6),
        "sklearn_entities_sampled": 2 * sample,
        "sklearn_glmix_fit_seconds_est": round(total, 1),
        # measured-sklearn wall / our measured steady-state fit wall.
        "vs_measured_sklearn": round(total / our_per_fit_seconds, 1),
    }


def build_estimator(task_name="linear"):
    from photon_tpu import optim
    from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
    from photon_tpu.data.random_effect import RandomEffectDataConfiguration
    from photon_tpu.estimators.game_estimator import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu.types import TaskType

    def l2(w):
        return GLMOptimizationConfiguration(
            regularization=optim.RegularizationContext(
                optim.RegularizationType.L2
            ),
            regularization_weight=w,
        )

    task = (
        TaskType.LOGISTIC_REGRESSION
        if task_name == "logistic"
        else TaskType.LINEAR_REGRESSION
    )
    return GameEstimator(
        task,
        {
            "global": FixedEffectCoordinateConfiguration("global", l2(1e-3)),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration(
                    "userId", "userShard", active_data_upper_bound=512,
                    min_bucket_entities=BENCH_MIN_BUCKET_ENTITIES,
                ),
                l2(1.0),
            ),
            "per-movie": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration(
                    "movieId", "movieShard", active_data_upper_bound=2048,
                    min_bucket_entities=BENCH_MIN_BUCKET_ENTITIES,
                ),
                l2(1.0),
            ),
        },
        intercept_indices={
            "global": N_FEATURES - 1,
            "userShard": N_USER_FEATURES,
            "movieShard": N_MOVIE_FEATURES,
        },
        num_iterations=CD_ITERATIONS,
        precision=BENCH_PRECISION,
    )


def _kept_rows(ds):
    return float(np.minimum(
        np.bincount(
            np.asarray(ds.score_codes), minlength=ds.num_entities
        ),
        ds.config.active_data_upper_bound or np.iinfo(np.int64).max,
    ).sum())


def estimate_model_flops(result, datasets, task_name) -> float:
    """Analytic USEFUL-FLOP count of one fit, from its actual diagnostics.

    Counted per coordinate update (CoordinateUpdateRecord):
    - fixed effect: iters x (value+grad = 2 matvecs) = iters * 4 n d;
    - random effect, direct (squared loss): per entity 2 r S^2 (normal
      equations) + S^3/3 (Cholesky), summed over kept rows;
    - random effect, Newton/IRLS: mean_iters x (6 r S margins/grad/line
      search + 2 r S^2 Hessian + S^3/3 Cholesky);
    - scoring after each update: 2 n d_coord.
    Padding rows/slots are excluded — this is model work, not device work.
    """
    from photon_tpu.algorithm.random_effect import (
        RandomEffectTrainingStats,
    )

    flops = 0.0
    for rec in result.descent.history:
        cid = rec.coordinate_id
        diag = rec.diagnostics
        if cid == "global":
            iters = float(np.asarray(getattr(diag, "iterations", 100)))
            flops += iters * 4.0 * N_ROWS * N_FEATURES
            flops += 2.0 * N_ROWS * N_FEATURES  # scoring pass
            continue
        ds = datasets[cid]
        s = ds.max_sub_dim
        kept = _kept_rows(ds)
        if isinstance(diag, RandomEffectTrainingStats):
            if task_name == "linear":
                flops += 2.0 * kept * s * s + ds.num_entities * (s ** 3) / 3.0
            else:
                it = float(np.asarray(diag.iterations_mean))
                flops += it * (
                    6.0 * kept * s
                    + 2.0 * kept * s * s
                    + ds.num_entities * (s ** 3) / 3.0
                )
        flops += 2.0 * N_ROWS * s  # scoring pass
    return flops


def estimate_hbm_bytes(result, datasets, task_name) -> float:
    """Analytic HBM traffic of one fit (4-byte f32 elements).

    Counts each pass over the resident arrays: the fixed-effect matvec and
    its transpose read x once each per solver iteration; every scoring pass
    reads the coordinate's feature slab once; random-effect solves gather
    their kept rows' slab once per materialization and re-read it ~2x per
    Newton iteration (margins + Hessian contraction). Written outputs
    (margins, tables) are small next to the feature reads and are ignored —
    this is a LOWER bound, so achieved/peak is conservative.
    """
    from photon_tpu.algorithm.random_effect import (
        RandomEffectTrainingStats,
    )

    bytes_ = 0.0
    x_bytes = 4.0 * N_ROWS * N_FEATURES
    for rec in result.descent.history:
        cid = rec.coordinate_id
        diag = rec.diagnostics
        if cid == "global":
            iters = float(np.asarray(getattr(diag, "iterations", 100)))
            bytes_ += iters * 2.0 * x_bytes  # matvec + rmatvec per iter
            bytes_ += x_bytes  # scoring pass
            continue
        ds = datasets[cid]
        s = ds.max_sub_dim
        kept = _kept_rows(ds)
        slab = 4.0 * kept * s
        if isinstance(diag, RandomEffectTrainingStats):
            # Feature slabs are cached on device across solves
            # (device_blocks); per-solve traffic is the slab re-reads.
            if task_name == "linear":
                bytes_ += 2.0 * slab  # margins + normal-equations pass
            else:
                it = float(np.asarray(diag.iterations_mean))
                bytes_ += it * 2.0 * slab
        bytes_ += 4.0 * N_ROWS * s  # scoring pass reads the raw shard
    return bytes_


def predict_program_costs(est, datasets, per_fit_seconds, rows) -> dict:
    """Static per-program cost predictions for the fit just measured.

    Lowers (never executes) the fused whole-fit + slab-materialization
    programs through the analysis cost model (analysis/costmodel.py:
    XLA's HLO cost analysis + a v5e roofline), so the output carries
    predicted FLOPs/HBM-bytes per program next to the measured
    throughput. ``measured_vs_roofline`` is measured fit wall-clock over
    the roofline lower bound — how far the real dispatch sits from the
    chip's best case. Never fails the bench: an ineligible path (mesh)
    or a backend without cost analysis reports the reason instead.
    """
    try:
        from photon_tpu.analysis import costmodel

        cache = getattr(est, "_fused_cache", None)
        if not cache:
            return {"skipped": "no fused program (unfused/mesh path)"}
        fused = next(reversed(cache.values()))
        coords = est._build_coordinates(datasets, {}, {}, rows)
        # The full bench prices against the device it ran on; the CPU
        # smoke names the abstract tiers' target (its gauge only has to
        # exist there, see FLOORS).
        report = costmodel.fused_fit_report(
            fused, coords,
            chip=(costmodel.TARGET_CHIP if PEAKS is None
                  else costmodel.device_chip()),
        )
        pred = report["fused_fit"]["roofline"]["min_seconds"]
        if pred:
            report["measured_vs_roofline"] = round(
                per_fit_seconds / pred, 2)
        return report
    except Exception as exc:  # the bench must keep printing its line
        return {"error": repr(exc)}


def predict_fused_fit_memory(est, datasets, rows) -> dict:
    """Static HBM prediction for the fit's resident slab set, joined to
    the ledger's measured booking for the SAME run.

    Predicted: aval bytes of ``eval_shape`` over the slab-materialization
    program (the exact call FusedFit.trace makes — no device, no
    execution). Measured: the ``fused_fit/slabs`` resident row the fused
    fit books when it lands the materialized slabs (obs/ledger.py). The
    two must agree — this is the runtime half of the tier-4 memory
    contract (analysis/memory.py), and the smoke/full gates hold the
    ratio inside [1/1.5, 1.5]. Never fails the bench: ineligible paths
    report why.
    """
    try:
        import jax

        from photon_tpu.analysis.memory import aval_nbytes
        from photon_tpu.obs import ledger

        cache = getattr(est, "_fused_cache", None)
        if not cache:
            return {"skipped": "no fused program (unfused/mesh path)"}
        fused = next(reversed(cache.values()))
        coords = est._build_coordinates(datasets, {}, {}, rows)
        ebs_avals = jax.eval_shape(
            fused._mat_fn, fused._mat_operands(coords)
        )
        predicted = float(
            sum(
                aval_nbytes(leaf)
                for leaf in jax.tree_util.tree_leaves(ebs_avals)
            )
        )
        measured = ledger.snapshot()["resident_bytes"].get(
            "fused_fit/slabs"
        )
        out = {
            "predicted_bytes": predicted,
            "measured_bytes": measured,
        }
        if measured:
            out["predicted_vs_measured"] = round(
                predicted / measured, 3
            )
        return out
    except Exception as exc:  # the bench must keep printing its line
        return {"error": repr(exc)}


def _fit_blocking(est, data):
    """One full fit, ended by ``block_until_ready`` on every trained
    coefficient table (training dispatch is asynchronous). The tables
    stay on device — the state production scoring consumes."""
    import jax

    r = est.fit(data)[0]
    jax.block_until_ready([
        m.coefficients if hasattr(m, "coefficients")
        else m.model.coefficients.means
        for m in r.model.models.values()
    ])
    return r


def _flush_device_queue(data):
    """Wait for the dataset's raw-shard transfers.

    make_game_dataset's device pushes are asynchronous; without this, the
    NEXT phase's timer absorbs the transfer backlog of the synthetic-data
    build.
    """
    import gc

    import jax

    gc.collect()  # drop the previous variant's device arrays first
    arrays = [data.labels]
    for feats in data.feature_shards.values():
        x = getattr(feats, "x", None)
        arrays.append(feats.values if x is None else x)
    jax.block_until_ready(arrays)


def run_variant(task_name):
    from photon_tpu.data.pipeline import PIPELINE_STATS

    data = build_data(task_name)
    est = build_estimator(task_name)
    _flush_device_queue(data)

    t0 = time.perf_counter()
    datasets, _ = est.prepare(data)
    t1 = time.perf_counter()
    _fit_blocking(est, data)
    t2 = time.perf_counter()
    ingest_seconds = t1 - t0
    first_fit_seconds = t2 - t1
    # MEASURED wall clock of the pipelined prepare + first fit — NOT the
    # sum of phases. With the overlapped AOT compile, the compile work
    # runs during ingest, so e2e < ingest + compile whenever the overlap
    # is real (the round-6 acceptance criterion).
    e2e_seconds = t2 - t0
    pipeline_stats = PIPELINE_STATS.report()
    # compile_seconds reports the full compile cost actually paid: the
    # background AOT warm compile's duration when it ran (its
    # non-overlapped remainder shows up inside first_fit_seconds as
    # compile_wait), else the first fit's wall clock (the legacy serial
    # meaning — compile dominates a cold first fit).
    compile_seconds = (
        pipeline_stats["compile_seconds"] or first_fit_seconds
    )

    # Steady state: aggregate whole fits until the measurement window is
    # long enough that per-fit dispatch jitter is noise. The cost
    # ledger windows the same loop: every second of it must come back
    # as a named (coordinate, phase, program) row or the explicit
    # `unattributed` residual (obs/ledger.py; gated via FLOORS).
    from photon_tpu.obs import ledger

    ledger_mark = ledger.mark()
    fits = 0
    result = None
    t0 = time.perf_counter()
    while True:
        result = _fit_blocking(est, data)
        fits += 1
        train_seconds_total = time.perf_counter() - t0
        if train_seconds_total >= MIN_MEASURE_SECONDS and fits >= 3:
            break
    per_fit = train_seconds_total / fits
    attribution = ledger.attribution_since(
        ledger_mark, wall_seconds=train_seconds_total
    )

    # Warm-cache e2e: a COMPLETE second cycle — fresh data objects, fresh
    # estimator, prepare + first fit — in the same process, where the jit
    # and transfer-shape caches are warm. This is the daily-cadence rerun
    # cost the persistent compile cache is for. The warm prepare is also
    # ingest measurement 2 of INGEST_FLOOR_SAMPLES.
    data2 = build_data(task_name)
    est2 = build_estimator(task_name)
    _flush_device_queue(data2)
    t0 = time.perf_counter()
    est2.prepare(data2)
    warm_prepare_seconds = time.perf_counter() - t0
    _fit_blocking(est2, data2)
    warm_e2e = time.perf_counter() - t0
    del data2, est2

    # Remaining ingest samples (best-of-N floor): COMPLETE fresh-data
    # prepares, the same shape of work as the warm-cycle sample, so the
    # best-of-N compares like with like. The floor therefore gates the
    # steady (warm-process) ingest throughput — the daily-cadence
    # planning cost; the cold first prepare still rides separately as
    # `ingest_seconds`/`e2e_seconds`, where a cold-only regression
    # (first-call jit of transfer helpers) remains visible.
    ingest_samples = [ingest_seconds, warm_prepare_seconds]
    while len(ingest_samples) < INGEST_FLOOR_SAMPLES:
        data_n = build_data(task_name)
        est_n = build_estimator(task_name)
        _flush_device_queue(data_n)
        t0 = time.perf_counter()
        est_n.prepare(data_n)
        ingest_samples.append(time.perf_counter() - t0)
        # prepare() launched a background AOT warm compile that this
        # estimator will never fit-consume; drain it OUTSIDE the timed
        # window so its straggler compile-cache events (and its CPU
        # time) cannot bleed into the next scenario's measurement —
        # notably the serving block's compile_events==0 gate.
        fut = getattr(est_n, "_aot_future", None)
        if fut is not None:
            fut.result()
        del data_n, est_n

    flops = estimate_model_flops(result, datasets, task_name)
    hbm = estimate_hbm_bytes(result, datasets, task_name)
    cost_model = predict_program_costs(
        est, datasets, per_fit, data.num_samples)
    memory = predict_fused_fit_memory(est, datasets, data.num_samples)
    return dict(
        cost_model=cost_model,
        memory=memory,
        attribution=attribution,
        ingest_seconds=ingest_seconds,
        compile_seconds=compile_seconds,
        first_fit_seconds=first_fit_seconds,
        pipeline=pipeline_stats,
        train_seconds=per_fit,
        measured_fits=fits,
        measure_window_seconds=train_seconds_total,
        rows_per_sec=N_ROWS * CD_ITERATIONS / per_fit,
        model_flops_per_sec=flops / per_fit,
        hbm_bytes_per_sec=hbm / per_fit,
        e2e_seconds=e2e_seconds,
        warm_cache_e2e_seconds=warm_e2e,
        ingest_samples=ingest_samples,
    )


def build_serving_model(seed: int = 20260803):
    """A GameModel shaped like the training workload's trained output.

    Serving latency depends on table SHAPES, not on how the weights were
    learned, so the scenario builds the coefficient tables directly at
    workload scale (N_USERS x 17, N_MOVIES x 9 — the bench estimator's
    trained layout) instead of paying a full training run per bench.
    Quality-side serving parity with real trained/saved models is pinned
    by tests/test_serve.py.
    """
    import jax.numpy as jnp

    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(seed)
    du, dm = N_USER_FEATURES + 1, N_MOVIE_FEATURES + 1

    def re_model(re_type, shard, e, s):
        return RandomEffectModel(
            coefficients=jnp.asarray(
                rng.normal(size=(e, s)).astype(np.float32) * 0.3
            ),
            random_effect_type=re_type,
            feature_shard_id=shard,
            task=TaskType.LOGISTIC_REGRESSION,
            proj_all=np.tile(np.arange(s), (e, 1)).astype(np.int64),
            entity_keys=tuple(str(i) for i in range(e)),
        )

    return GameModel({
        "global": FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(means=jnp.asarray(
                    rng.normal(size=N_FEATURES).astype(np.float32) * 0.3
                )),
                TaskType.LOGISTIC_REGRESSION,
            ),
            "global",
        ),
        "per-user": re_model("userId", "userShard", N_USERS, du),
        "per-movie": re_model("movieId", "movieShard", N_MOVIES, dm),
    })


def run_serving() -> dict:
    """The `serving` scenario: online scoring through photon_tpu.serve.

    HBM-resident coefficient tables at the training workload's scale, the
    AOT-compiled score ladder, and the micro-batching queue driven to
    saturation by the synchronous driver. Reported: p50/p99 latency, QPS,
    batch-fill fraction, cold-entity rate — and the runtime half of the
    zero-recompile guarantee: compile-cache activity across the measured
    window must be ZERO (`serving_compile_events`; the static half is the
    tier-2 `serving` contract). A violation lands in `regressions`.
    """
    from photon_tpu.obs.monitor import SloPolicy
    from photon_tpu.serve.driver import drive, synthetic_requests
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.queue import MicroBatchQueue
    from photon_tpu.serve.tables import CoefficientTables
    from photon_tpu.utils import compile_event_count

    model = build_serving_model()
    # Serving rides the SAME precision policy as training: bf16 tables
    # halve the resident footprint and the per-request gather width
    # (PERFORMANCE.md; f32 accumulators in the score kernels).
    tables = CoefficientTables.from_game_model(
        model, precision=BENCH_PRECISION
    )
    # Tier-4 admission join (analysis/memory.py): the oracle's predicted
    # table residency (shapes only, no device) next to the ledger's
    # measured `table/*` rows the build just booked — byte-for-byte the
    # same accounting, gated in `regressions` via memory_regressions.
    from photon_tpu.analysis.memory import predict_resident_bytes
    from photon_tpu.obs import ledger

    predicted_tables = predict_resident_bytes(
        model, precision=BENCH_PRECISION
    )["tables_total_bytes"]
    measured_tables = sum(
        v
        for k, v in ledger.snapshot()["resident_bytes"].items()
        if k.startswith("table/")
    )
    t0 = time.perf_counter()
    programs = ScorePrograms(tables, ladder=ShapeLadder(SERVE_RUNGS))
    ladder_seconds = time.perf_counter() - t0
    requests = synthetic_requests(
        tables, programs, N_SERVE_REQUESTS,
        cold_fraction=SERVE_COLD_FRACTION, seed=7,
    )
    ledger_mark = ledger.mark()
    before = compile_event_count()
    with MicroBatchQueue(
        programs, max_linger_s=SERVE_MAX_LINGER_MS / 1e3,
        # Declared SLOs (obs/monitor.py): the error budget is the
        # gated one — a clean bench must burn ZERO of it
        # (serving_regressions). The latency target is generous by
        # design: this drive floods to saturation, so its p99 measures
        # queueing depth, not service latency, and a tight target here
        # would gate the box's load, not the code.
        slo=SloPolicy(
            p99_ms=10_000.0, error_rate=0.001, cold_entity_rate=0.2,
            short_window_s=2.0, long_window_s=24.0,
        ),
    ) as queue:
        summary = drive(queue, requests)
        # Values-only hot reload UNDER THE SAME QUEUE, then the same
        # request replay: the serving half of the roofline push must
        # survive a model refresh with zero compile events, and the
        # p99 delta across the reload rides the output so a reload
        # that silently degrades the tail is visible in the JSON
        # comparison (benchtrend tracks serving_p99_ms itself).
        reload_before = compile_event_count()
        reload_info = queue.reload_model(build_serving_model(seed=7042))
        summary_reload = drive(queue, requests)
        reload_events = compile_event_count() - reload_before
        health = queue.health()
        queue_stats = queue.stats()
    compile_events = compile_event_count() - before
    attribution = ledger.attribution_since(ledger_mark)
    # Dispatch-gap attribution: the fraction of the serve rows' wall
    # the host spent BETWEEN device dispatches (pack, queue pop, fetch
    # turnaround). The staging pipeline exists to shrink exactly this
    # number, so it is measured against a SERIAL baseline
    # (pipeline_staging=False) driven in the same round with the same
    # programs and requests — benchtrend ratchets the pipelined
    # fraction (`serving_dispatch_gap_fraction`).
    serial_mark = ledger.mark()
    with MicroBatchQueue(
        programs, max_linger_s=SERVE_MAX_LINGER_MS / 1e3,
        pipeline_staging=False,
    ) as serial_queue:
        summary_serial = drive(serial_queue, requests)
    serial_attribution = ledger.attribution_since(serial_mark)
    parity = _serve_kernel_parity()
    return {
        "serving_dispatch_gap_fraction": _serve_gap_fraction(attribution),
        "serving_dispatch_gap_fraction_serial": _serve_gap_fraction(
            serial_attribution),
        "serving_p99_ms_serial": summary_serial["p99_ms"],
        "serving_staging_overlap_fraction": queue_stats[
            "staging_overlap_fraction"],
        "serving_staged_batches": queue_stats["staged_batches"],
        **parity,
        "serving_reload_values_only": bool(
            reload_info.get("values_only")),
        "serving_reload_compile_events": reload_events,
        "serving_p99_ms_after_reload": summary_reload["p99_ms"],
        "serving_reload_p99_delta_ms": round(
            summary_reload["p99_ms"] - summary["p99_ms"], 3),
        "serving_reload_errors": summary_reload["errors"],
        # Cost-ledger view of the drive: per-rung dispatch rows
        # (seconds, dispatch counts, host gaps) — which rung the wall
        # actually went to, next to the latency percentiles.
        "serving_attribution": attribution,
        "serving_requests": summary["requests"],
        "serving_p50_ms": summary["p50_ms"],
        "serving_p90_ms": summary["p90_ms"],
        "serving_p99_ms": summary["p99_ms"],
        "serving_qps": summary["qps"],
        "serving_batch_fill_fraction": summary["batch_fill_fraction"],
        "serving_mean_batch_size": summary["mean_batch_size"],
        "serving_cold_entity_rate": summary["cold_entity_rate"],
        # Live-monitoring block (PR 9, obs/monitor.py): per-coordinate
        # cold rates (the aggregate above stays for compatibility),
        # sliding-window p50/p99 next to the whole-run percentiles,
        # the SLO burn report, and the hotness sketches' top entities.
        "serving_cold_entity_rate_by_coordinate": summary[
            "cold_entity_rate_by_coordinate"
        ],
        "serving_window_latency": summary["window_latency"],
        "serving_slo": summary.get("slo"),
        "serving_hot_entities": summary["hot_entities"],
        "serving_batches": summary["batches"],
        "serving_errors": summary["errors"],
        "serving_predicted_hbm_bytes": predicted_tables,
        "serving_measured_hbm_bytes": measured_tables,
        "serving_rungs": list(programs.ladder.rungs),
        "serving_max_linger_ms": SERVE_MAX_LINGER_MS,
        "serving_programs_compiled": programs.stats["programs_compiled"],
        "serving_ladder_compile_seconds": round(ladder_seconds, 3),
        "serving_compile_events": compile_events,
        # Degraded-mode snapshot (resilience layer): on this CLEAN
        # bench run every shed/deadline/retry/breaker counter must be
        # zero — gated in serving_regressions.
        "serving_health": health,
    }


def _serve_gap_fraction(attribution: dict) -> float | None:
    """Host-gap share of the serve rows' accounted wall: sum of the
    per-rung ``host_gap_seconds`` over (gap + measured dispatch
    seconds). 0 = every accounted second was device execution; the
    staging pipeline's job is to push this toward 0."""
    gap = seconds = 0.0
    for row in attribution.get("rows", []):
        if row.get("phase") != "serve":
            continue
        gap += row.get("host_gap_seconds", 0.0)
        seconds += row.get("seconds", 0.0)
    total = gap + seconds
    return round(gap / total, 4) if total > 0.0 else None


def _serve_kernel_parity() -> dict:
    """Fused-serve-kernel vs jitted-chain parity on ONE packed rung at
    the bench precision (the runtime twin of tests/test_serve_kernel.py:
    same model structure, production pack path, forced kernel —
    interpreted off-TPU). Gated at 5e-2 in serving_regressions; bf16
    tables round identically on both paths so the observed gap is the
    accumulation-order delta only."""
    from photon_tpu.serve.driver import synthetic_requests
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables

    model = build_serving_model(seed=1311)
    prev = os.environ.get("PHOTON_SERVE_KERNEL")
    outs = {}
    try:
        for mode in ("off", "force"):
            os.environ["PHOTON_SERVE_KERNEL"] = mode
            tables = CoefficientTables.from_game_model(
                model, precision=BENCH_PRECISION
            )
            progs = ScorePrograms(
                tables, ladder=ShapeLadder((8,)), compile_now=False
            )
            progs.compile_rung(8)
            reqs = synthetic_requests(
                tables, progs, 8, cold_fraction=0.25, seed=11
            )
            feats, codes, _ = progs.pack_requests(reqs)
            outs[mode] = np.asarray(
                progs.score_padded(feats, codes, len(reqs)),
                dtype=np.float64,
            )
    finally:
        if prev is None:
            os.environ.pop("PHOTON_SERVE_KERNEL", None)
        else:
            os.environ["PHOTON_SERVE_KERNEL"] = prev
    return {
        "serving_kernel_parity_maxdiff": float(
            np.max(np.abs(outs["off"] - outs["force"]))
        ),
        "serving_kernel_parity_tolerance": 5e-2,
    }


def run_serve_kernel_micro() -> dict:
    """Standalone fused-serve-kernel dispatch at the top rung: achieved
    bytes/s next to the kernel's analytic HBM traffic (the
    benchtrend-tracked ``serve_kernel_bytes_per_sec`` gauge). Skipped
    where the kernel does not serve this backend — interpret mode would
    measure the Pallas interpreter, not HBM."""
    from photon_tpu.ops import serve_kernel
    from photon_tpu.serve.driver import synthetic_requests
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables

    if serve_kernel.interpret_required() or not (
        serve_kernel.kernel_supported(BENCH_PRECISION)
    ):
        return {}
    import jax

    rung = max(SERVE_RUNGS)
    tables = CoefficientTables.from_game_model(
        build_serving_model(seed=1312), precision=BENCH_PRECISION
    )
    progs = ScorePrograms(
        tables, ladder=ShapeLadder((rung,)), compile_now=False
    )
    assert progs.use_kernel
    progs.compile_rung(rung)
    reqs = synthetic_requests(
        tables, progs, rung, cold_fraction=SERVE_COLD_FRACTION, seed=12
    )
    feats, codes, _ = progs.pack_requests(reqs)
    jax.block_until_ready(
        progs.dispatch_padded(feats, codes, rung).out
    )
    reps = 20
    t0 = time.perf_counter()
    for _ in range(reps):
        handle = progs.dispatch_padded(feats, codes, rung)
    jax.block_until_ready(handle.out)
    dt = time.perf_counter() - t0
    info = serve_kernel.traced_sites().get("serve_kernel/score") or {}
    bytes_per_call = (info.get("cost") or {}).get("hbm_bytes", 0.0)
    return {
        "serve_kernel_rung": rung,
        "serve_kernel_bytes_per_call": bytes_per_call,
        "serve_kernel_bytes_per_sec": round(
            bytes_per_call * reps / dt, 1) if dt else None,
        "serve_kernel_fraction_of_hbm_peak": (
            _fraction_of_peak(
                bytes_per_call * reps / dt, "hbm_bytes_per_sec", 6)
            if dt else None
        ),
    }


def run_kernel_micro() -> dict:
    """Standalone segment-reduce dispatch at the scoring shape: the
    kernel's ACHIEVED bytes/s next to its analytic traffic (the
    benchtrend-tracked ``segment_reduce_bytes_per_sec`` gauge — a
    ratchet the round it first reports). Skipped where the kernel does
    not serve this backend: interpret mode would measure the Pallas
    interpreter, not HBM, and a fallback measurement would masquerade
    as kernel throughput."""
    from photon_tpu.ops import segment_reduce as sr

    m = N_ROWS
    if sr.interpret_required() or not sr.kernel_supported(
        m, N_ROWS, np.float32
    ):
        return {}
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(20260804)
    # Sorted ids with an EXACT multiplicity bound of 2 (the kernel's
    # coverage contract is static).
    ids = jnp.asarray(
        np.repeat(np.arange(N_ROWS // 2, dtype=np.int32), 2)[:m]
    )
    vals = jnp.asarray(rng.normal(size=m).astype(np.float32))
    out = sr.sorted_segment_sum(
        vals, ids, N_ROWS, multiplicity=2,
        site="segment_reduce/micro",
    )
    jax.block_until_ready(out)
    reps = 20
    t0 = time.perf_counter()
    for _ in range(reps):
        out = sr.sorted_segment_sum(
            vals, ids, N_ROWS, multiplicity=2,
            site="segment_reduce/micro",
        )
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    info = sr.traced_sites().get("segment_reduce/micro") or {}
    bytes_per_call = (info.get("cost") or {}).get("hbm_bytes", 0.0)
    return {
        "segment_reduce_elements": m,
        "segment_reduce_bytes_per_call": bytes_per_call,
        "segment_reduce_bytes_per_sec": round(
            bytes_per_call * reps / dt, 1) if dt else None,
        "segment_reduce_fraction_of_hbm_peak": (
            _fraction_of_peak(
                bytes_per_call * reps / dt, "hbm_bytes_per_sec", 6)
            if dt else None
        ),
    }


def run_parity() -> dict:
    """The `parity` scenario: per-family bf16-vs-f32 coefficient gap.

    Fits each GLM family twice through the fused path — f32 reference
    and bf16 policy — on a small fixed workload (the
    tests/test_precision.py shape) and reports the max relative
    coefficient error as ``parity_gap_{family}``. The FIXED per-family
    ceilings live in tests/test_precision.py / PERFORMANCE.md; these
    gauges feed benchtrend so a gap that quietly WIDENS (a new cast, a
    changed solver route) fails the trend gate long before it climbs to
    the fixed tolerance. Full bench only — two fits per family is waste
    at smoke scale, and the tier-5 numerics audit plus the kernel-smoke
    parity tests gate the policy in CI."""
    from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
    from photon_tpu.data.dataset import DenseFeatures
    from photon_tpu.data.game_data import make_game_dataset
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfiguration,
    )
    from photon_tpu.estimators.game_estimator import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu import optim
    from photon_tpu.types import TaskType

    def l2(w):
        return GLMOptimizationConfiguration(
            regularization=optim.RegularizationContext(
                optim.RegularizationType.L2
            ),
            regularization_weight=w,
        )

    def workload(task):
        rng = np.random.default_rng(20260806)
        n, d, du, users = 3_000, 8, 5, 40
        x = rng.normal(size=(n, d)).astype(np.float32)
        x[:, -1] = 1.0
        xu = rng.normal(size=(n, du)).astype(np.float32)
        xu[:, -1] = 1.0
        uid = rng.integers(0, users, n)
        w = 0.3 * rng.normal(size=d).astype(np.float32)
        wu = 0.3 * rng.normal(size=(users, du)).astype(np.float32)
        z = x @ w + np.einsum("nd,nd->n", xu, wu[uid])
        if task == TaskType.LOGISTIC_REGRESSION:
            y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(
                np.float32)
        elif task == TaskType.POISSON_REGRESSION:
            y = rng.poisson(np.exp(np.clip(0.3 * z, -3, 3))).astype(
                np.float32)
        elif task == TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM:
            y = (z > 0).astype(np.float32)
        else:
            y = (z + 0.2 * rng.normal(size=n)).astype(np.float32)
        return make_game_dataset(
            y, {"g": DenseFeatures(x), "u": DenseFeatures(xu)},
            id_tags={"userId": uid},
        )

    def fit(task, data, precision):
        est = GameEstimator(
            task,
            {
                "global": FixedEffectCoordinateConfiguration(
                    "g", l2(1e-2)),
                "per-user": RandomEffectCoordinateConfiguration(
                    RandomEffectDataConfiguration("userId", "u"),
                    l2(1.0),
                ),
            },
            num_iterations=2,
            mesh="off",
            precision=precision,
        )
        return est.fit(data)[0].model

    def rel_err(a, b):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        scale = max(float(np.abs(b).max()), 1e-9)
        return float(np.abs(a - b).max()) / scale

    families = {
        "linear": TaskType.LINEAR_REGRESSION,
        "logistic": TaskType.LOGISTIC_REGRESSION,
        "poisson": TaskType.POISSON_REGRESSION,
        "smoothed_hinge": TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
    }
    out = {}
    for fam, task in families.items():
        data = workload(task)
        m32 = fit(task, data, "float32")
        m16 = fit(task, data, "bfloat16")
        gap = max(
            rel_err(
                m16.models["global"].model.coefficients.means,
                m32.models["global"].model.coefficients.means,
            ),
            rel_err(
                m16.models["per-user"].coefficients,
                m32.models["per-user"].coefficients,
            ),
        )
        out[f"parity_gap_{fam}"] = round(gap, 6)
    return out


def _write_stream_shards(shard_dir: str) -> None:
    """STREAM_ROWS synthetic TrainingExampleAvro rows across
    STREAM_SHARDS part files (sparse power-law-ish features + a userId
    metadata tag) — the on-disk workload the streaming scenario reads
    back out-of-core."""
    from photon_tpu.io.avro_data import write_training_examples
    from photon_tpu.types import DELIMITER

    os.makedirs(shard_dir, exist_ok=True)
    rng = np.random.default_rng(20260803)
    per = STREAM_ROWS // STREAM_SHARDS
    base = 0
    for si in range(STREAM_SHARDS):
        n = per if si < STREAM_SHARDS - 1 else STREAM_ROWS - base
        feats = rng.integers(0, STREAM_FEATURES, size=(n, 3))
        vals = rng.normal(size=(n, 3))
        z = vals.sum(axis=1) * 0.4
        y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-z))).astype(float)
        rows = [
            [(f"f{feats[i, j]}{DELIMITER}t", float(vals[i, j]))
             for j in range(3)]
            for i in range(n)
        ]
        meta = [
            {"userId": f"u{rng.integers(0, STREAM_USERS)}"}
            for _ in range(n)
        ]
        write_training_examples(
            os.path.join(shard_dir, f"part-{si:05d}.avro"),
            y, rows, metadata=meta, uids=np.arange(base, base + n),
        )
        base += n


def _stream_estimator():
    from photon_tpu import optim
    from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
    from photon_tpu.data.random_effect import RandomEffectDataConfiguration
    from photon_tpu.estimators.game_estimator import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu.types import TaskType

    def l2(w):
        return GLMOptimizationConfiguration(
            regularization=optim.RegularizationContext(
                optim.RegularizationType.L2
            ),
            regularization_weight=w,
        )

    return GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {
            "global": FixedEffectCoordinateConfiguration(
                "features", l2(1e-2)),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "features"),
                l2(1.0),
            ),
        },
        num_iterations=2,
        mesh="off",
    )


def run_streaming() -> dict:
    """The `streaming` scenario: out-of-core ingest + warm-start retrain.

    Day 1 streams STREAM_SHARDS Avro shards from disk through
    ``StreamingIngest`` (bounded-memory windows, integrity manifest,
    resumable cursor) and trains a GLMix model; day 2 re-streams and
    warm-starts from day-1's model (``fit(init_model=...)``) — the
    reported ``streaming_incremental_rows_per_sec`` is rows over the
    WHOLE day-2 wall (ingest + warm fit), the daily-cadence retrain
    cost. ``streaming_ingested_fraction`` must be 1.0 and the
    quarantine counters 0 on this clean run (gated in
    streaming_regressions); peak host RSS rides along as the
    out-of-core memory gauge.
    """
    import resource
    import shutil
    import tempfile

    from photon_tpu.data.stream import StreamingIngest
    from photon_tpu.io.model_io import save_checkpoint

    tmp = tempfile.mkdtemp(prefix="photon_stream_bench")
    try:
        shard_dir = os.path.join(tmp, "shards")
        t0 = time.perf_counter()
        _write_stream_shards(shard_dir)
        write_seconds = time.perf_counter() - t0

        def ingest(work):
            return StreamingIngest(
                shard_dir,
                work_dir=os.path.join(tmp, work),
                window_shards=STREAM_WINDOW_SHARDS,
            ).run()

        t0 = time.perf_counter()
        day1, stats1 = ingest("work-day1")
        est1 = _stream_estimator()
        result1 = est1.fit(day1)[0]
        day1_seconds = time.perf_counter() - t0
        ckpt = os.path.join(tmp, "day1-model.npz")
        save_checkpoint(result1.model, ckpt)

        # Day 2: fresh process state (new estimator, re-streamed data),
        # warm-started from yesterday's model — jit/compile caches are
        # warm, which is exactly the daily-cadence cost being measured.
        t0 = time.perf_counter()
        day2, stats2 = ingest("work-day2")
        est2 = _stream_estimator()
        est2.fit(day2, init_model=ckpt)
        day2_seconds = time.perf_counter() - t0

        rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return {
            "streaming_rows": STREAM_ROWS,
            "streaming_shards": STREAM_SHARDS,
            "streaming_window_shards": STREAM_WINDOW_SHARDS,
            "streaming_shard_write_seconds": round(write_seconds, 3),
            "streaming_ingest_rows_per_sec": stats1["rows_per_sec"],
            "streaming_ingest_seconds": stats1["wall_seconds"],
            "streaming_day1_seconds": round(day1_seconds, 3),
            "streaming_day2_seconds": round(day2_seconds, 3),
            "streaming_incremental_rows_per_sec": round(
                STREAM_ROWS / day2_seconds, 1),
            "streaming_ingested_fraction": stats2["ingested_fraction"],
            "streaming_quarantined_shards": stats2["shards_quarantined"],
            "streaming_peak_host_rss_mb": round(rss_kb / 1024.0, 1),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def streaming_regressions(streaming: dict) -> list[str]:
    """Streaming entries for the output's `regressions` list: a clean
    run must ingest EVERYTHING (fraction 1.0, zero quarantines) and the
    incremental gauge must engage."""
    out = []
    if streaming.get("streaming_ingested_fraction") != 1.0:
        out.append(
            "clean streaming run ingested fraction "
            f"{streaming.get('streaming_ingested_fraction')} != 1.0")
    if streaming.get("streaming_quarantined_shards", 0) != 0:
        out.append(
            f"clean streaming run quarantined "
            f"{streaming['streaming_quarantined_shards']} shard(s)")
    if not streaming.get("streaming_incremental_rows_per_sec"):
        out.append(
            "streaming scenario missing "
            "streaming_incremental_rows_per_sec (gauge dead)")
    return out


def _write_pilot_day(shard_dir: str, day: int, rng) -> None:
    """One day's shard. Day 0 SATURATES every user's feature support
    (fixed triples covering all PILOT_FEATURES features) so later
    retrains keep the random-effect projector — and therefore the
    compiled score ladder — byte-identical: the pinned-vocabulary
    values-only steady state the zero-recompile gate measures. Later
    days draw features at random from the same universe."""
    from photon_tpu.io.avro_data import write_training_examples
    from photon_tpu.types import DELIMITER

    os.makedirs(shard_dir, exist_ok=True)
    cover = [[0, 1, 2], [3, 4, 5], [0, 3, 5], [1, 2, 4]]
    rows, y, meta = [], [], []
    for u in range(PILOT_USERS):
        for r in range(PILOT_ROWS_PER_USER_DAY):
            if day == 0 and r < len(cover):
                fs = cover[r]
            else:
                fs = list(rng.choice(PILOT_FEATURES, size=3,
                                     replace=False))
            vals = rng.normal(size=len(fs))
            rows.append([
                (f"f{j}{DELIMITER}t", float(v))
                for j, v in zip(fs, vals)
            ])
            z = float(vals.sum()) * 0.5
            y.append(float(rng.uniform() < 1.0 / (1.0 + np.exp(-z))))
            meta.append({"userId": f"u{u}"})
    write_training_examples(
        os.path.join(shard_dir, f"part-{day:03d}.avro"),
        np.array(y), rows, metadata=meta,
    )


def _pilot_traffic(pilot, rate: float, stop, counts: dict) -> None:
    """Closed-loop synthetic traffic against whatever generation is
    live — every promotion in the replay happens UNDER load, which is
    what makes the zero-dropped-requests number evidence rather than
    vacuously true. The loop is the shared
    ``serve.driver.traffic_loop`` (same generator the pilot CLI's
    ``--traffic-qps`` runs); the counter dict is this thread's, read
    after the join."""
    from photon_tpu.serve.driver import traffic_loop

    traffic_loop(
        lambda: pilot.server, rate, stop, counts,
        batch=32, idle_sleep=0.01,
    )


def run_pilot() -> dict:
    """The `pilot` scenario: the production control loop replayed over
    PILOT_DAYS "days" (photon_tpu.pilot; PILOT.md).

    Day 0 bootstraps generation 1 and starts the live queue; each later
    day drops one shard and the pilot runs a full
    ingest→train→validate→promote→observe cycle while the traffic
    thread keeps scoring. Reported: staleness per drop (shard-landed →
    model-serving seconds, max + mean), promotions, and the scenario's
    two zero-gates — serving reload compile events (values-only
    promotions must add NO programs; the tier-2 ``pilot`` contract is
    the static half) and dropped/errored requests across every
    promotion."""
    import shutil
    import tempfile
    import threading

    from photon_tpu.pilot import (
        ObservePolicy,
        Pilot,
        PilotConfig,
        PilotServer,
        PromotionGate,
    )

    tmp = tempfile.mkdtemp(prefix="photon_pilot_bench")
    try:
        shard_dir = os.path.join(tmp, "shards")
        rng = np.random.default_rng(20260804)
        _write_pilot_day(shard_dir, 0, rng)

        cfg = PilotConfig(
            stream_dir=shard_dir,
            work_dir=os.path.join(tmp, "work"),
            estimator_factory=_pilot_estimator,
            keep_generations=3,
            # The replay benches the MECHANISM (a tiny synthetic model
            # retrained on near-identical data wobbles either way), so
            # the gate grants a wide regression allowance; the gate's
            # refusal path is exercised by chaos CI, not here.
            gate=PromotionGate(min_delta={"AUC": -1.0}),
            observe=ObservePolicy(window_s=0.2, poll_s=0.05),
        )
        pilot = Pilot(cfg, server_factory=lambda m: PilotServer(
            m, rungs=PILOT_RUNGS, max_linger_s=0.001,
        ))
        t0 = time.perf_counter()
        boot = pilot.run_cycle()
        boot_seconds = time.perf_counter() - t0
        if "error" in boot:
            raise RuntimeError(
                f"pilot bootstrap cycle failed: {boot['error']}")

        stop = threading.Event()
        counts = {
            "served": 0, "errors": 0, "submit_errors": 0,
            "stranded": 0, "last_error": None,
        }
        traffic = threading.Thread(
            target=_pilot_traffic,
            args=(pilot, PILOT_TRAFFIC_QPS, stop, counts),
            name="pilot-bench-traffic", daemon=True,
        )
        traffic.start()
        staleness = []
        cycle_seconds = []
        try:
            for day in range(1, PILOT_DAYS):
                _write_pilot_day(shard_dir, day, rng)
                t0 = time.perf_counter()
                report = pilot.run_cycle()
                cycle_seconds.append(time.perf_counter() - t0)
                if "error" in report:
                    raise RuntimeError(
                        f"pilot day-{day} cycle failed at stage "
                        f"{report['stage']}: {report['error']}")
                if report.get("staleness_seconds") is not None:
                    staleness.append(report["staleness_seconds"])
        finally:
            stop.set()
            traffic.join(timeout=60.0)
        health = pilot.server.health()
        reload_events = pilot.server.reload_compile_events
        pilot.server.close(timeout=30.0)

        return {
            "pilot_days": PILOT_DAYS,
            "pilot_rows_per_day": PILOT_USERS * PILOT_ROWS_PER_USER_DAY,
            "pilot_users": PILOT_USERS,
            "pilot_promotions": pilot.state.promotions,
            "pilot_rollbacks": pilot.state.rollbacks,
            "pilot_refusals": pilot.state.refusals,
            "pilot_bootstrap_seconds": round(boot_seconds, 3),
            "pilot_cycle_seconds_mean": round(
                sum(cycle_seconds) / len(cycle_seconds), 3
            ) if cycle_seconds else None,
            "pilot_staleness_seconds": (
                round(max(staleness), 3) if staleness else None
            ),
            "pilot_staleness_mean_seconds": (
                round(sum(staleness) / len(staleness), 3)
                if staleness else None
            ),
            "pilot_serving_compile_events": reload_events,
            "pilot_requests_served": counts["served"],
            "pilot_request_errors": (
                counts["errors"] + counts["submit_errors"]
                + counts["stranded"]
            ),
            "pilot_traffic_qps_offered": PILOT_TRAFFIC_QPS,
            "pilot_breaker_trips": health["breaker_trips"],
            "pilot_generation_live": pilot.ring.live,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _pilot_estimator():
    from photon_tpu import optim
    from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
    from photon_tpu.data.random_effect import RandomEffectDataConfiguration
    from photon_tpu.estimators.game_estimator import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu.types import TaskType

    def l2(w):
        return GLMOptimizationConfiguration(
            regularization=optim.RegularizationContext(
                optim.RegularizationType.L2
            ),
            regularization_weight=w,
        )

    return GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {
            "global": FixedEffectCoordinateConfiguration(
                "features", l2(1e-2)),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "features"),
                l2(1.0),
            ),
        },
        num_iterations=2,
        evaluators=["AUC"],
        mesh="off",
    )


def _write_drift_day(shard_dir: str, day: int, rng,
                     shift: float = 0.0) -> None:
    """One drift-scenario day: DRIFT_USERS x DRIFT_ROWS_PER_USER_DAY
    logistic rows with N(0,1) feature values translated by ``shift`` —
    day 0 saturates feature support like the pilot writer so the
    steady state stays values-only."""
    from photon_tpu.io.avro_data import write_training_examples
    from photon_tpu.types import DELIMITER

    os.makedirs(shard_dir, exist_ok=True)
    cover = [[0, 1, 2], [3, 4, 5], [0, 3, 5], [1, 2, 4]]
    rows, y, meta = [], [], []
    for u in range(DRIFT_USERS):
        for r in range(DRIFT_ROWS_PER_USER_DAY):
            if day == 0 and r < len(cover):
                fs = cover[r]
            else:
                fs = list(rng.choice(DRIFT_FEATURES, size=3,
                                     replace=False))
            vals = rng.normal(size=len(fs)) + shift
            rows.append([
                (f"f{j}{DELIMITER}t", float(v))
                for j, v in zip(fs, vals)
            ])
            z = float((vals - shift).sum()) * 0.5
            y.append(float(rng.uniform() < 1.0 / (1.0 + np.exp(-z))))
            meta.append({"userId": f"u{u}"})
    write_training_examples(
        os.path.join(shard_dir, f"part-{day:03d}.avro"),
        np.array(y), rows, metadata=meta,
    )


def run_drift() -> dict:
    """The `drift` scenario: the health promotion gate, end to end.

    A three-day pilot replay with ``PilotConfig.health`` armed
    (photon_tpu.obs.health; the metric gate is granted a wide
    allowance so only the HEALTH gate decides): day 0 bootstraps and
    commits the drift reference sketch, day 1 replays the identical
    distribution and must PROMOTE cleanly, day 2 replays a
    DRIFT_SHIFT-translated distribution and must be REFUSED with a
    recorded ``health:*`` reason (plus the flight post-mortem the
    refusal machinery always dumps). The gate firing on real drift AND
    staying quiet without it are both regression-gated
    (drift_regressions)."""
    import shutil
    import tempfile

    from photon_tpu.obs import health
    from photon_tpu.pilot import (
        HealthGatePolicy,
        ObservePolicy,
        Pilot,
        PilotConfig,
        PilotServer,
        PromotionGate,
    )

    tmp = tempfile.mkdtemp(prefix="photon_drift_bench")
    was_health = health.enabled()
    try:
        shard_dir = os.path.join(tmp, "shards")
        rng = np.random.default_rng(20260804)
        _write_drift_day(shard_dir, 0, rng)
        cfg = PilotConfig(
            stream_dir=shard_dir,
            work_dir=os.path.join(tmp, "work"),
            estimator_factory=_pilot_estimator,
            keep_generations=3,
            # The metric gate is deliberately permissive: this replay
            # proves the HEALTH gate's decision, and a tiny synthetic
            # retrain's AUC wobbles either way.
            gate=PromotionGate(min_delta={"AUC": -1.0}),
            observe=ObservePolicy(window_s=0.1, poll_s=0.05),
            health=HealthGatePolicy(
                max_drift_psi=DRIFT_MAX_PSI,
                forbid_nonfinite=True,
            ),
        )
        pilot = Pilot(cfg, server_factory=lambda m: PilotServer(
            m, rungs=PILOT_RUNGS, max_linger_s=0.001,
        ))
        boot = pilot.run_cycle()
        if "error" in boot:
            raise RuntimeError(
                f"drift bootstrap cycle failed: {boot['error']}")

        _write_drift_day(shard_dir, 1, rng, shift=0.0)
        clean = pilot.run_cycle()
        if "error" in clean:
            raise RuntimeError(
                f"drift clean-day cycle failed: {clean['error']}")

        _write_drift_day(shard_dir, 2, rng, shift=DRIFT_SHIFT)
        shifted = pilot.run_cycle()
        if "error" in shifted:
            raise RuntimeError(
                f"drift shifted-day cycle failed: {shifted['error']}")

        refusal_reasons = list(shifted.get("refused") or ())
        health_block = shifted.get("health") or {}
        if pilot.server is not None:
            pilot.server.close(timeout=30.0)
        return {
            "drift_days": 3,
            "drift_rows_per_day": DRIFT_USERS * DRIFT_ROWS_PER_USER_DAY,
            "drift_shift": DRIFT_SHIFT,
            "drift_max_psi_ceiling": DRIFT_MAX_PSI,
            "drift_clean_promoted": "promotion" in clean,
            "drift_clean_refusals": list(clean.get("refused") or ()),
            "drift_gate_fired": any(
                r.startswith("health:") for r in refusal_reasons
            ),
            "drift_refusal_reasons": refusal_reasons,
            "drift_measured_psi": (health_block.get("drift") or {}).get(
                "max_psi"),
            "drift_psi_surface": (health_block.get("drift") or {}).get(
                "max_psi_surface"),
            "drift_promotions": pilot.state.promotions,
            "drift_refusals": pilot.state.refusals,
        }
    finally:
        # The scenario armed the process-global health layer through
        # the pilot; hand the flag (and the tap/sentinel state) back so
        # later scenarios measure exactly what they always did.
        health.reset()
        if was_health:
            health.enable()
        else:
            health.disable()
        shutil.rmtree(tmp, ignore_errors=True)


def drift_regressions(drift: dict) -> list[str]:
    """Drift entries for the output's `regressions` list: the health
    gate must FIRE on the shifted day (with a recorded health:*
    reason) and stay QUIET on the identical day."""
    out = []
    if not drift.get("drift_gate_fired"):
        out.append(
            "health gate did not refuse the distribution-shifted day "
            f"(reasons: {drift.get('drift_refusal_reasons')}; "
            f"measured PSI {drift.get('drift_measured_psi')})")
    if not drift.get("drift_clean_promoted"):
        out.append(
            "identical-distribution day did not promote cleanly "
            f"(refusals: {drift.get('drift_clean_refusals')})")
    if drift.get("drift_promotions", 0) < 2:
        out.append(
            f"drift replay promoted {drift.get('drift_promotions')} "
            "of 2 clean day(s)")
    return out


def pilot_regressions(pilot: dict) -> list[str]:
    """Pilot entries for the output's `regressions` list: the replay
    must promote EVERY day, reload with zero compile events, and drop
    zero requests across every promotion."""
    out = []
    if pilot.get("pilot_promotions", 0) < PILOT_DAYS:
        out.append(
            f"pilot promoted {pilot.get('pilot_promotions')} of "
            f"{PILOT_DAYS} day(s) — the control loop stopped promoting")
    if pilot.get("pilot_serving_compile_events") != 0:
        out.append(
            f"pilot promotions triggered "
            f"{pilot.get('pilot_serving_compile_events')} serving "
            "compile event(s) (zero-recompile promotion contract)")
    if pilot.get("pilot_request_errors", 0) != 0:
        out.append(
            f"{pilot['pilot_request_errors']} request(s) dropped/"
            "errored across the pilot's promotions")
    if pilot.get("pilot_rollbacks", 0) or pilot.get("pilot_refusals", 0):
        out.append(
            "clean pilot replay recorded "
            f"{pilot.get('pilot_rollbacks')} rollback(s) / "
            f"{pilot.get('pilot_refusals')} refusal(s)")
    if pilot.get("pilot_staleness_seconds") is None:
        out.append(
            "pilot scenario missing pilot_staleness_seconds "
            "(staleness gauge dead)")
    return out


def attribution_regressions(name: str, attribution: dict) -> list[str]:
    """The cost-ledger acceptance gate (full TPU-scale bench only):
    >= `logistic_attributed_fraction_min` of the measured steady-state
    fit wall must carry a (coordinate, phase, program) name, with the
    residual reported as the explicit `unattributed` row. The CPU
    smoke gates ENGAGEMENT instead (run_smoke)."""
    floor_key = f"{name}_attributed_fraction_min"
    floor = FLOORS.get(floor_key)
    if floor is None or not isinstance(attribution, dict):
        return []
    fraction = attribution.get("attributed_fraction")
    if fraction is None:
        return [
            f"{name} attribution produced no attributed_fraction "
            "(cost ledger dead)"
        ]
    if fraction < floor:
        return [
            f"{name}_attributed_fraction {fraction:.3f} < {floor:.2f} "
            "(the ledger left wall clock unnamed beyond the "
            "unattributed budget)"
        ]
    return []


def roofline_regressions(name: str, cost_model: dict) -> list[str]:
    """The ``measured_vs_roofline`` gate (a tracked bench metric since
    round 8, not just a report field). A missing ratio is NOT a
    violation here — the cost model legitimately skips on the
    unfused/mesh paths and reports why; the smoke job separately
    asserts the gauge engaged on the fused CI workload."""
    floor_key = f"{name}_measured_vs_roofline_max"
    ceiling = FLOORS.get(floor_key)
    if ceiling is None or not isinstance(cost_model, dict):
        return []
    ratio = cost_model.get("measured_vs_roofline")
    if ratio is None or ratio <= ceiling:
        return []
    return [
        f"{name}_measured_vs_roofline {ratio:.1f} > {ceiling:.1f} "
        "(measured fit wall drifted past the roofline ceiling; "
        "ROADMAP item 2 gate)"
    ]


def resilience_regressions() -> list[str]:
    """Clean-run resilience gate: the bench injects NO faults, so every
    retry counter (and any CD rollback) recorded during the run means a
    real transient failure — or a resilience-layer bug — either way a
    regression to surface."""
    from photon_tpu.resilience import retry_stats

    out = []
    stats = retry_stats()
    for key in ("retries", "recovered", "exhausted"):
        if stats.get(key, 0):
            out.append(
                f"clean bench run recorded {stats[key]} retry-layer "
                f"{key} event(s) (expected zero without injected "
                "faults)")
    return out


def hbm_prediction_join(variant: dict, serving: dict) -> dict:
    """The admission-oracle acceptance join: tier-4 static HBM
    predictions (analysis/memory.py) against the ledger's measured
    resident bytes from the SAME run — the fused fit's slab set and the
    serving tables. The tracked `*_peak_hbm_bytes` gauges are the
    MEASURED values (benchtrend ratchets them); `predicted_vs_measured_
    hbm` carries the ratios the regression gate holds inside
    [1/1.5, 1.5]."""
    out = {}
    ratios = {}
    mem = variant.get("memory") if isinstance(variant, dict) else None
    mem = mem if isinstance(mem, dict) else {}
    measured = mem.get("measured_bytes")
    if measured:
        out["fused_fit_peak_hbm_bytes"] = measured
        if mem.get("predicted_vs_measured") is not None:
            ratios["fused_fit"] = mem["predicted_vs_measured"]
    s_meas = serving.get("serving_measured_hbm_bytes")
    s_pred = serving.get("serving_predicted_hbm_bytes")
    if s_meas:
        out["serving_peak_hbm_bytes"] = s_meas
        if s_pred:
            ratios["serving"] = round(s_pred / s_meas, 3)
    out["predicted_vs_measured_hbm"] = ratios
    return out


def memory_regressions(join: dict) -> list[str]:
    """HBM-admission entries for the output's `regressions` list: both
    joins must ENGAGE (a missing ratio means the oracle or the ledger
    feed died) and both ratios must hold inside [1/1.5, 1.5] — outside,
    the static admission answer has drifted from the measured watermark
    and ROADMAP item 3's "will it fit" call can no longer be trusted."""
    out = []
    ratios = join.get("predicted_vs_measured_hbm") or {}
    for name in ("fused_fit", "serving"):
        ratio = ratios.get(name)
        if ratio is None:
            out.append(
                f"{name} HBM join produced no predicted_vs_measured "
                "ratio (admission oracle or ledger resident feed dead)")
        elif not (1 / 1.5 <= ratio <= 1.5):
            out.append(
                f"predicted_vs_measured_hbm[{name}] {ratio:.2f} outside "
                "[0.67, 1.5] (admission oracle drifted from the "
                "measured watermark)")
    return out


def serving_regressions(serving: dict) -> list[str]:
    """Serving entries for the output's `regressions` list."""
    out = []
    if serving.get("serving_compile_events", 0) != 0:
        out.append(
            f"serving loop triggered {serving['serving_compile_events']} "
            "compile-cache events after warmup (zero-recompile contract)")
    if serving.get("serving_errors", 0) != 0:
        out.append(
            f"{serving['serving_errors']} serving request(s) errored")
    # The hot-reload half of the zero-recompile contract: the refreshed
    # model must swap values-only (structure unchanged by construction)
    # with zero compile-cache events, and the replay must stay clean.
    if serving.get("serving_reload_values_only") is False:
        out.append(
            "serving reload was NOT values-only (structure drift on an "
            "identical-shape model)")
    if serving.get("serving_reload_compile_events", 0) != 0:
        out.append(
            f"serving reload triggered "
            f"{serving['serving_reload_compile_events']} compile-cache "
            "events (zero-recompile reload contract)")
    if serving.get("serving_reload_errors", 0) != 0:
        out.append(
            f"{serving['serving_reload_errors']} serving request(s) "
            "errored after the hot reload")
    health = serving.get("serving_health") or {}
    for key in ("shed", "deadline_expired", "dispatch_retries",
                "breaker_trips", "dispatch_errors"):
        if health.get(key, 0) != 0:
            out.append(
                f"clean serving run recorded {health[key]} "
                f"{key} event(s) (degraded-mode counters must be zero "
                "without injected faults)")
    # SLO burn gate (obs/monitor.py): with no injected faults, the
    # ERROR budget must burn zero — any error burn on a clean run is a
    # real failure the counters above would have caught, now phrased
    # as the SLO the serving fleet would page on.
    err = ((serving.get("serving_slo") or {}).get("error_rate")) or {}
    if err.get("burn_short", 0) or err.get("burn_long", 0):
        out.append(
            "clean serving run burned error-rate SLO budget "
            f"(burn short={err.get('burn_short')} "
            f"long={err.get('burn_long')}; must be zero without "
            "injected faults)")
    # Fused-kernel score parity: the forced kernel and the jitted
    # per-coordinate chain score the same packed rung within the bf16
    # accumulation-order band. A wider gap means the kernel computes a
    # DIFFERENT model, not a slower one.
    maxdiff = serving.get("serving_kernel_parity_maxdiff")
    tol = serving.get("serving_kernel_parity_tolerance", 5e-2)
    if maxdiff is not None and maxdiff > tol:
        out.append(
            f"serve-kernel parity maxdiff {maxdiff:.3e} > {tol:.0e} "
            "(fused kernel diverges from the jitted score chain)")
    # The pipelined queue must never strand a staged batch: the serial
    # replay and the pipelined drive answer the same requests, so both
    # summaries' request counts match by construction — but a staging
    # pipeline that silently fell back to serial would report zero
    # staged batches here.
    if serving.get("serving_staged_batches", 0) == 0:
        out.append(
            "pipelined queue staged zero batches (double-buffered "
            "staging silently disabled)")
    return out


def run_yahoo_music():
    """SCHEMA-PARITY SMOKE TEST on the reference's Yahoo! Music fixture.

    The fixture (GameIntegTest/input/duplicateFeatures) is a 6-record
    schema-edge-case file; training it as a 3-coordinate GLMix (global +
    per-user + per-song) through the product estimator proves the
    reference's Avro layout ingests and trains end-to-end. The RMSE
    threshold (GameTrainingDriverIntegTest.scala:78-79) is kept as the
    smoke gate, but 6 rows validate FORMATS, not model quality — the
    real-data quality anchor is the a9a block.
    """
    if not os.path.exists(YAHOO_TRAIN):
        return {"yahoo_fixture_skipped": "fixture not mounted"}
    import jax.numpy as jnp

    from photon_tpu import optim
    from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
    from photon_tpu.data.dataset import rows_to_ell, SparseFeatures
    from photon_tpu.data.game_data import make_game_dataset
    from photon_tpu.data.index_map import IndexMap
    from photon_tpu.data.random_effect import RandomEffectDataConfiguration
    from photon_tpu.estimators.game_estimator import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu.io import avro
    from photon_tpu.types import TaskType, make_feature_key

    t0 = time.perf_counter()
    recs = avro.read_container_dir(YAHOO_TRAIN)

    def shard_rows(field):
        keys = sorted({
            make_feature_key(f["name"], f["term"])
            for r in recs for f in r[field]
        })
        imap = IndexMap({k: i for i, k in enumerate(keys)})
        rows = [
            [(imap.get_index(make_feature_key(f["name"], f["term"])),
              f["value"]) for f in r[field]]
            for r in recs
        ]
        idx, val = rows_to_ell(rows, len(imap))
        return SparseFeatures(idx, val, len(imap))

    data = make_game_dataset(
        [r["response"] for r in recs],
        {
            "global": shard_rows("features"),
            "userShard": shard_rows("userFeatures"),
            "songShard": shard_rows("songFeatures"),
        },
        id_tags={
            "userId": np.asarray([r["userId"] for r in recs]),
            "songId": np.asarray([r["songId"] for r in recs]),
        },
    )

    def l2(w):
        return GLMOptimizationConfiguration(
            regularization=optim.RegularizationContext(
                optim.RegularizationType.L2
            ),
            regularization_weight=w,
        )

    est = GameEstimator(
        TaskType.LINEAR_REGRESSION,
        {
            "global": FixedEffectCoordinateConfiguration("global", l2(0.1)),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "userShard"), l2(1.0)
            ),
            "per-song": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("songId", "songShard"), l2(1.0)
            ),
        },
        num_iterations=2,
        evaluators=["RMSE"],
    )
    result = est.fit(data, validation=data)[0]
    seconds = time.perf_counter() - t0
    rmse = float(result.evaluation.primary_evaluation)
    return {
        "yahoo_fixture_rows": len(recs),
        "yahoo_fixture_seconds": round(seconds, 3),
        "yahoo_fixture_rmse": round(rmse, 4),
        # GameTrainingDriverIntegTest.scala:78-79 threshold as a SMOKE
        # gate on the 6-row fixture (schema parity, not model quality).
        "yahoo_fixture_schema_smoke_ok": bool(rmse < 1.697),
    }


A9A_TRAIN = (
    "/root/reference/photon-client/src/integTest/resources/DriverIntegTest/"
    "input/a9a"
)
A9A_TEST = A9A_TRAIN + ".t"


def run_a1a_logistic():
    """BASELINE.json config 1: fixed-effect logistic, L-BFGS + L2, on the
    a1a-family libsvm fixture (a9a, the reference's own DriverIntegTest
    dataset) — timed end-to-end with held-out AUC."""
    if not (os.path.exists(A9A_TRAIN) and os.path.exists(A9A_TEST)):
        return {"a9a_skipped": "fixture not mounted"}
    from photon_tpu import optim
    from photon_tpu.algorithm.problems import (
        GLMOptimizationConfiguration,
        GLMOptimizationProblem,
    )
    from photon_tpu.data.libsvm import read_libsvm
    from photon_tpu.evaluation.evaluators import auc_roc
    from photon_tpu.types import TaskType

    t0 = time.perf_counter()
    train = read_libsvm(A9A_TRAIN)
    # num_features is the PRE-intercept width (read_libsvm appends the
    # intercept column itself; cli/train.py:97 convention).
    test = read_libsvm(A9A_TEST, num_features=train.features.d - 1)
    problem = GLMOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION,
        GLMOptimizationConfiguration(
            regularization=optim.RegularizationContext(
                optim.RegularizationType.L2
            ),
            regularization_weight=10.0,
        ),
        intercept_index=train.features.d - 1,
    )
    model = problem.run(train).model
    scores = model.compute_score(test.features)
    value = float(np.asarray(auc_roc(scores, test.labels)))
    seconds = time.perf_counter() - t0
    return {
        "a9a_rows": int(train.labels.shape[0]),
        "a9a_seconds": round(seconds, 3),
        "a9a_test_auc": round(value, 4),
        # sklearn-anchored threshold (test_golden_parity a9a anchor ~0.90).
        "a9a_auc_ok": bool(value > 0.88),
    }


def run_wide_d():
    """Huge-d sparse fixed effect on the real chip, through `photon train`.

    The reference's headline capability claim is coefficient-vector scale
    ("hundreds of billions of coefficients" across a cluster,
    /root/reference/README.md:56); its single-chip unit of proof here is a
    d = 10^7 sparse logistic fixed effect — power-law feature draws (the
    long-tail shape hashed vocabularies exist for), ELL layout, L-BFGS —
    driven end-to-end by the CLI training driver. Reported: d, nnz,
    wall-clock, held-in AUC, and the resident coefficient + data bytes.
    """
    import json as json_mod
    import tempfile

    d = 10_000_000
    rows = 100_000
    k = 20
    rng = np.random.default_rng(7)
    # Power-law ids: density ~ 1/sqrt(u) concentrates mass on low ids.
    idx = np.minimum(
        (d * rng.uniform(size=(rows, k)) ** 2.2).astype(np.int64), d - 1
    )
    val = rng.normal(size=(rows, k)).astype(np.float32)
    w_true = np.zeros(100_000, np.float32)
    w_true[:] = rng.normal(size=100_000) * 0.5
    planted = np.where(idx < 100_000, w_true[np.minimum(idx, 99_999)], 0.0)
    z = (val * planted).sum(axis=1)
    y = (rng.uniform(size=rows) < 1.0 / (1.0 + np.exp(-z))).astype(np.int8)

    tmp = tempfile.mkdtemp(prefix="photon_wide_d")
    train_path = os.path.join(tmp, "wide.libsvm")
    t0 = time.perf_counter()
    with open(train_path, "w") as f:
        for i in range(rows):
            order = np.argsort(idx[i])
            feats = " ".join(
                f"{int(idx[i][j]) + 1}:{val[i][j]:.5f}" for j in order
            )
            f.write(f"{int(y[i])} {feats}\n")
    write_seconds = time.perf_counter() - t0
    cfg = {
        "task": "logistic_regression",
        "output_dir": os.path.join(tmp, "out"),
        "input": {
            "format": "libsvm",
            "train_path": train_path,
            # Held-IN evaluation (same file): the block proves scale, and
            # the AUC is a sanity signal that the d=1e7 solve actually
            # learned the planted signal — not a generalization claim.
            "validation_path": train_path,
        },
        "coordinates": {
            "global": {
                "type": "fixed",
                "feature_shard": "features",
                "regularization": {"type": "L2", "weight": 1.0},
            }
        },
        "evaluators": ["AUC"],
        "mesh": "off",
    }
    cfg_path = os.path.join(tmp, "cfg.json")
    with open(cfg_path, "w") as f:
        json_mod.dump(cfg, f)
    from photon_tpu.cli.train import main as train_main

    t0 = time.perf_counter()
    rc = train_main(["--config", cfg_path])
    seconds = time.perf_counter() - t0
    summary = {}
    spath = os.path.join(tmp, "out", "training-summary.json")
    if os.path.exists(spath):
        with open(spath) as f:
            summary = json_mod.load(f)
    auc = None
    configs = summary.get("configurations") or []
    if configs:
        ev = configs[summary.get("best_configuration_index", 0)].get(
            "evaluation") or {}
        auc = ev.get("AUC")
    return {
        "wide_d_features": d,
        "wide_d_rows": rows,
        "wide_d_nnz": rows * k,
        "wide_d_write_seconds": round(write_seconds, 2),
        "wide_d_train_seconds": round(seconds, 2),
        "wide_d_rc": rc,
        "wide_d_heldin_auc": (
            None if auc is None else round(float(auc), 4)),
        # Device-resident footprint of the solve: ELL data + indices +
        # the [d] coefficient/gradient vectors (f32).
        "wide_d_resident_mb": round(
            (rows * k * 8 + 2 * d * 4) / 1e6, 1),
    }


def _variant_fields(name: str, v: dict) -> dict:
    return {
        f"{name}_precision": BENCH_PRECISION,
        f"{name}_rows_per_sec": round(v["rows_per_sec"], 1),
        f"{name}_train_seconds": round(v["train_seconds"], 4),
        f"{name}_measured_fits": v["measured_fits"],
        f"{name}_measure_window_seconds": round(
            v["measure_window_seconds"], 3),
        f"{name}_ingest_seconds": round(v["ingest_seconds"], 3),
        f"{name}_ingest_rows_per_sec": round(
            N_ROWS / v["ingest_seconds"], 1),
        # Best-of-N ingest throughput (the FLOOR's input) next to the
        # mean and the raw samples — one loaded-box outlier must not
        # read as a regression, and a real one shows in every sample.
        f"{name}_ingest_rows_per_sec_best": round(
            N_ROWS / min(v["ingest_samples"]), 1),
        f"{name}_ingest_rows_per_sec_mean": round(
            N_ROWS * len(v["ingest_samples"])
            / sum(v["ingest_samples"]), 1),
        f"{name}_ingest_sample_seconds": [
            round(s, 3) for s in v["ingest_samples"]],
        f"{name}_compile_seconds": round(v["compile_seconds"], 3),
        f"{name}_first_fit_seconds": round(v["first_fit_seconds"], 3),
        # e2e is the MEASURED wall of prepare + first fit; the ingest
        # pipeline's per-stage breakdown (plan/pack/transfer/compile +
        # the measured compile-overlap fraction) rides next to it.
        f"{name}_e2e_seconds": round(v["e2e_seconds"], 3),
        f"{name}_plan_seconds": v["pipeline"]["plan_seconds"],
        f"{name}_transfer_seconds": v["pipeline"]["transfer_seconds"],
        f"{name}_compile_overlap_fraction": (
            v["pipeline"]["compile_overlap_fraction"]),
        f"{name}_pipeline": v["pipeline"],
        f"{name}_warm_cache_e2e_seconds": round(
            v["warm_cache_e2e_seconds"], 3),
        f"{name}_model_flops_per_sec": round(
            v["model_flops_per_sec"], 1),
        f"{name}_fraction_of_bf16_peak": _fraction_of_peak(
            v["model_flops_per_sec"], "flops_per_sec", 8),
        f"{name}_hbm_bytes_per_sec": round(v["hbm_bytes_per_sec"], 1),
        f"{name}_fraction_of_hbm_peak": _fraction_of_peak(
            v["hbm_bytes_per_sec"], "hbm_bytes_per_sec", 6),
        # Static cost model (analysis/costmodel.py): per-program
        # predicted FLOPs/HBM-bytes + roofline bound for the fused
        # fit and slab materialization programs. measured_vs_roofline
        # is ALSO surfaced top-level: it is a tracked bench metric with
        # a regression ceiling (FLOORS), not just a report field.
        f"{name}_cost_model": v["cost_model"],
        f"{name}_measured_vs_roofline": (
            v["cost_model"].get("measured_vs_roofline")
            if isinstance(v["cost_model"], dict) else None),
        # Cost-ledger attribution of the steady-state window
        # (obs/ledger.py): named rows + the explicit unattributed
        # residual. The fraction is ALSO surfaced top-level — it is a
        # benchtrend-tracked metric with a FLOORS gate, not just a
        # report field.
        # Tier-4 admission join (analysis/memory.py): the statically
        # predicted slab residency next to the ledger's measured
        # booking — the ratio is gated in `regressions`.
        f"{name}_memory": v["memory"],
        f"{name}_attribution": v["attribution"],
        f"{name}_attributed_fraction": v["attribution"].get(
            "attributed_fraction"),
    }


def _apply_smoke():
    """Shrink the workload to CI scale (CPU runners, ~a minute).

    The smoke line exists to prove the INGEST PIPELINE machinery end to
    end — parallel planning, packed transfer, the AOT warm compile and
    its PIPELINE_STATS accounting — not to measure throughput, so the
    TPU-scale regression floors do not apply to it.
    """
    global N_ROWS, N_USERS, N_MOVIES, MIN_MEASURE_SECONDS
    global N_SERVE_REQUESTS, STREAM_ROWS, STREAM_SHARDS, STREAM_USERS
    global PILOT_USERS, PILOT_ROWS_PER_USER_DAY, PILOT_TRAFFIC_QPS
    N_ROWS = 20_000
    N_USERS = 500
    N_MOVIES = 100
    MIN_MEASURE_SECONDS = 0.2
    N_SERVE_REQUESTS = 1_500
    # The 2-core CI box pays only a tiny shard set (--streaming opt-in).
    STREAM_ROWS = 6_000
    STREAM_SHARDS = 6
    STREAM_USERS = 120
    # Pilot replay at CI scale (--pilot opt-in): same day count — the
    # promotion COUNT is the gate — tiny per-day data + gentler load.
    PILOT_USERS = 8
    PILOT_ROWS_PER_USER_DAY = 6
    PILOT_TRAFFIC_QPS = 120.0


def run_smoke(streaming: bool = False, pilot: bool = False,
              drift: bool = False) -> dict:
    """`bench.py --smoke`: the linear variant at CI scale, one JSON line.

    Asserts (in the output, for the CI job to check) that the pipeline
    stats were emitted with every per-stage field present and that the
    telemetry layer actually engaged (span tree recorded, convergence
    series captured from inside the fused fit). ``streaming`` adds the
    out-of-core scenario at CI scale — behind a flag so the default
    smoke wall stays bounded on the 2-core box."""
    from photon_tpu import obs

    lin = run_variant("linear")
    pipe = lin["pipeline"]
    stats_ok = all(
        k in pipe
        for k in (
            "plan_seconds", "pack_seconds", "transfer_seconds",
            "compile_seconds", "compile_overlap_fraction",
        )
    )
    # TPU-scale throughput floors don't apply at CI scale; the smoke
    # regression list checks the PIPELINE itself actually engaged — a
    # silent fallback to the serial/unfused path would otherwise pass
    # this job while the feature is dead.
    regressions = []
    if not stats_ok:
        regressions.append("pipeline stats missing per-stage fields")
    if pipe.get("plan_seconds", 0) <= 0:
        regressions.append("planner recorded no plan stage")
    if pipe.get("compile_seconds", 0) <= 0:
        regressions.append(
            "AOT warm compile never ran (compile stage empty)")
    # The roofline gauge must ENGAGE on the fused CI workload (its
    # VALUE is only gated at TPU scale — FLOORS ceiling — because a CPU
    # wall against a v5e roofline is not a meaningful ratio; a missing
    # gauge here means the tracked metric silently died).
    cm = lin["cost_model"] if isinstance(lin["cost_model"], dict) else {}
    if cm.get("measured_vs_roofline") is None:
        regressions.append(
            "cost model produced no measured_vs_roofline "
            f"(roofline gauge dead: {cm.get('error') or cm.get('skipped')!r})")
    # The cost ledger must ENGAGE on the CI workload (its 0.95
    # attribution floor is judged at TPU scale only — smoke fits are
    # milliseconds, so per-fit host overhead is proportionally large):
    # named rows recorded, a computable fraction, and the explicit
    # unattributed residual present.
    attr = lin.get("attribution") or {}
    named = [
        r for r in attr.get("rows", ())
        if r.get("program") != "unattributed"
    ]
    if not named:
        regressions.append(
            "cost ledger recorded no named attribution rows "
            "(ledger feed dead)")
    if attr.get("attributed_fraction") is None:
        regressions.append(
            "cost ledger produced no attributed_fraction "
            "(attribution gauge dead)")
    if not any(
        r.get("program") == "unattributed" for r in attr.get("rows", ())
    ):
        regressions.append(
            "cost ledger dropped its explicit unattributed row")
    # Serving smoke: the full online path (tables -> AOT ladder -> queue
    # -> driver) at CI scale; its zero-recompile + error checks join the
    # smoke regression list. Runs BEFORE the telemetry snapshot so the
    # serve spans/metrics land in the smoke output's telemetry too.
    serving = run_serving()
    regressions.extend(serving_regressions(serving))
    hbm_join = hbm_prediction_join(lin, serving)
    regressions.extend(memory_regressions(hbm_join))
    streaming_out = {}
    if streaming:
        streaming_out = run_streaming()
        regressions.extend(streaming_regressions(streaming_out))
    pilot_out = {}
    if pilot:
        pilot_out = run_pilot()
        regressions.extend(pilot_regressions(pilot_out))
    drift_out = {}
    if drift:
        drift_out = run_drift()
        regressions.extend(drift_regressions(drift_out))
    regressions.extend(resilience_regressions())
    for key in ("serving_p50_ms", "serving_p99_ms", "serving_qps"):
        if serving.get(key) is None:
            regressions.append(f"serving scenario missing {key}")
    # Live-monitoring surfaces must ENGAGE on the CI workload (their
    # values are judged at TPU scale; a dead surface is the smoke
    # regression, same policy as the roofline gauge above).
    if not serving.get("serving_slo"):
        regressions.append(
            "serving scenario missing serving_slo (SLO tracker dead)")
    if not (serving.get("serving_window_latency") or {}).get("count"):
        regressions.append(
            "sliding latency window recorded nothing (window ring dead)")
    if not any(
        (serving.get("serving_hot_entities") or {}).values()
    ):
        regressions.append(
            "hotness sketches recorded no entities (sketch feed dead)")
    telemetry = obs.snapshot()
    if not telemetry["spans"]:
        regressions.append("telemetry recorded no spans")
    if not telemetry["convergence"]["fits_recorded"]:
        regressions.append(
            "no convergence trace captured (fused fit telemetry dead)")

    out = {
        "metric": "glmix_ingest_pipeline_smoke",
        "smoke": True,
        "workload": {
            "rows": N_ROWS, "users": N_USERS, "movies": N_MOVIES,
            "cd_iterations": CD_ITERATIONS,
            "serve_requests": N_SERVE_REQUESTS,
        },
        "pipeline_stats_ok": bool(stats_ok),
        "regressions": regressions,
    }
    out.update(_variant_fields("linear", lin))
    out.update(serving)
    out.update(hbm_join)
    out.update(streaming_out)
    out.update(pilot_out)
    out.update(drift_out)
    out["telemetry"] = telemetry
    return out


def main(argv=None):
    import argparse

    from photon_tpu.utils import enable_compilation_cache

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--smoke", action="store_true",
        help="CI-scale run: linear variant only, pipeline-stats assertion, "
        "no TPU-scale floors",
    )
    parser.add_argument(
        "--streaming", action="store_true",
        help="with --smoke: also run the out-of-core streaming "
        "scenario (write synthetic shards, stream-train day 1, "
        "warm-start retrain day 2) at CI scale; the full bench always "
        "includes it",
    )
    parser.add_argument(
        "--pilot", action="store_true",
        help="with --smoke: also run the pilot control-loop replay "
        "(multi-day promote-under-traffic with staleness + "
        "zero-recompile + zero-drop gates) at CI scale; the full "
        "bench always includes it",
    )
    parser.add_argument(
        "--drift", action="store_true",
        help="with --smoke: also run the health-gate drift scenario "
        "(identical day promotes, distribution-shifted day is REFUSED "
        "with a health:* reason); the full bench always includes it",
    )
    parser.add_argument(
        "--telemetry", default=None, metavar="PATH",
        help="also write the telemetry JSONL stream to PATH "
        "(schema: OBSERVABILITY.md)",
    )
    parser.add_argument(
        "--trace", default=None, metavar="PATH",
        help="also write the merged Chrome-trace/Perfetto timeline "
        "(host spans, counter tracks, serving request span trees) to "
        "PATH — loadable in Perfetto / chrome://tracing",
    )
    args = parser.parse_args(argv)

    # Persistent XLA compile cache: cold runs pay compile_seconds once per
    # machine; repeat runs (and re-runs across rounds) hit the disk cache.
    enable_compilation_cache()

    # Telemetry rides every bench run: the snapshot (span tree with the
    # host/device split, metrics, per-coordinate convergence series) is
    # part of the output line, and the zero-overhead contract is audited
    # statically (`--semantic`, the `telemetry` contract) — the bench's
    # e2e floors are the runtime half of that guarantee.
    from photon_tpu import obs
    from photon_tpu.obs import ledger

    obs.enable()
    # The cost ledger rides every bench run next to telemetry: each
    # scenario windows it (`attribution` blocks) and the logistic
    # steady-state fraction is a FLOORS-gated, benchtrend-tracked
    # metric. Zero-overhead is audited (the tier-2 `ledger` contract)
    # and runtime-gated (cli.profile --overhead-check in CI).
    ledger.enable()

    if args.smoke:
        _apply_smoke()
    else:
        from photon_tpu.analysis import costmodel

        global PEAKS
        PEAKS = costmodel.CHIP_PEAKS[costmodel.device_chip()]
        out = run_smoke(
            streaming=args.streaming, pilot=args.pilot,
            drift=args.drift,
        )
        from photon_tpu.utils import cache_stats

        out["compile_cache"] = cache_stats()
        if args.telemetry:
            obs.write_jsonl(args.telemetry)
        if args.trace:
            obs.write_chrome_trace(args.trace)
        print(json.dumps(out))
        return

    logi = run_variant("logistic")
    lin = run_variant("linear")
    serving = run_serving()
    streaming = run_streaming()
    pilot = run_pilot()
    drift = run_drift()
    kernel_micro = run_kernel_micro()
    serve_kernel_micro = run_serve_kernel_micro()
    parity = run_parity()
    sklearn_anchor = run_sklearn_baseline(logi["train_seconds"])
    yahoo = run_yahoo_music()
    a9a = run_a1a_logistic()
    wide = run_wide_d()

    regressions = []
    if logi["rows_per_sec"] < FLOORS["logistic_rows_per_sec"]:
        regressions.append(
            f"logistic_rows_per_sec {logi['rows_per_sec']:.0f} < "
            f"{FLOORS['logistic_rows_per_sec']:.0f}")
    ingest_best = N_ROWS / min(logi["ingest_samples"])
    if ingest_best < FLOORS["ingest_rows_per_sec"]:
        regressions.append(
            f"ingest_rows_per_sec_best {ingest_best:.0f} < "
            f"{FLOORS['ingest_rows_per_sec']:.0f} (best of "
            f"{len(logi['ingest_samples'])} measurements)")
    if logi["compile_seconds"] > FLOORS["logistic_compile_seconds_max"]:
        regressions.append(
            f"logistic_compile_seconds {logi['compile_seconds']:.1f} > "
            f"{FLOORS['logistic_compile_seconds_max']:.1f}")
    regressions.extend(roofline_regressions("logistic", logi["cost_model"]))
    regressions.extend(
        attribution_regressions("logistic", logi["attribution"]))
    regressions.extend(serving_regressions(serving))
    regressions.extend(
        memory_regressions(hbm_prediction_join(logi, serving)))
    regressions.extend(streaming_regressions(streaming))
    regressions.extend(pilot_regressions(pilot))
    regressions.extend(drift_regressions(drift))
    regressions.extend(resilience_regressions())

    out = {
        "metric": "glmix_logistic_train_throughput",
        "value": round(logi["rows_per_sec"], 1),
        "unit": "rows/s",
        # Cross-round movement signal ONLY — nominal anchor, not a measured
        # reference baseline (see module docstring HONESTY NOTES).
        "vs_baseline": round(logi["rows_per_sec"] / ANCHOR_ROWS_PER_SEC, 3),
        "baseline_kind": "nominal-round1-anchor-50k-rows-per-sec",
        "workload": {
            "rows": N_ROWS, "users": N_USERS, "movies": N_MOVIES,
            "cd_iterations": CD_ITERATIONS,
            "serve_requests": N_SERVE_REQUESTS,
        },
        "regressions": regressions,
    }
    for name, v in (("logistic", logi), ("linear", lin)):
        out.update(_variant_fields(name, v))
    out.update(serving)
    out.update(hbm_prediction_join(logi, serving))
    out.update(streaming)
    out.update(pilot)
    out.update(drift)
    out.update(kernel_micro)
    out.update(serve_kernel_micro)
    out.update(parity)
    out.update(sklearn_anchor)
    out.update(yahoo)
    out.update(a9a)
    out.update(wide)
    # Persistent compile-cache effectiveness for THIS process: hit/miss
    # counts + disk footprint (utils/compile_cache.cache_stats). The
    # first instrumentation aimed at the BENCH_r05 anomaly where
    # linear_warm_cache_e2e (14.1s) exceeded cold (11.0s) — a warm rerun
    # with a zero hit-rate means the cache never served, and that is now
    # visible in the output instead of inferred.
    from photon_tpu.utils import cache_stats

    out["compile_cache"] = cache_stats()
    # The unified telemetry snapshot (photon_tpu.obs): span tree with
    # host/device split, metrics registry, last fit's per-coordinate
    # convergence series, pipeline + compile-cache reports.
    out["telemetry"] = obs.snapshot()
    if args.telemetry:
        obs.write_jsonl(args.telemetry)
    if args.trace:
        obs.write_chrome_trace(args.trace)
    # NOTE: this prints `regressions` and still exits 0 — a pattern not
    # to copy (chip_smoke.py exits non-zero when any phase fails).
    # ROADMAP A0.ii owns replacing it with cells that carry their bounds.
    print(json.dumps(out))


if __name__ == "__main__":
    main()
