"""chip_smoke.py: the quickest proof that photon_tpu still starts on the chip.

Drives the system's main path once through the entry points a user
calls, at the FULL WIDTH of the one model the repo has a chip number
for — logistic GLMix, global (64 dense) + per-user (100 000 entities x
17) + per-movie (20 000 x 9) — with random data made from a seed:

- full-width leg: generator -> ``make_game_dataset`` ->
  ``GameEstimator.fit`` (2 CD iterations, the fused program) ->
  ``save_game_model`` -> ``CoefficientTables.from_game_model`` ->
  ``ScorePrograms`` (ladder 1,8,64,512) -> ``MicroBatchQueue`` +
  ``drive`` -> one values-only ``tables.reload`` -> drive again;
- kernel leg: each of the three Pallas kernels, compiled (never
  interpreted) at this model's shapes, against its XLA route;
- CLI leg: a small Avro set -> ``photon_tpu.cli.train.main`` (with a
  validation split: the unfused loop) -> ``photon_tpu.cli.serve.main``,
  both in this process;
- mesh leg (four or more devices): the same three coordinates on a
  four-device mesh plus the column-sharded wide sparse solve, against
  the one-device fit.

It is a smoke, not a benchmark: it claims no speed. It FAILS (exit code
other than 0, no result line) when JAX finds no TPU — it never runs on
the CPU — and it starts no child process: one process owns the chip.
Standard output ends with two JSON lines: the report (sizes, seconds per
phase, kernels, cache, failures) and then, LAST, the verdict
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with exactly
those keys; ``ok`` is true only if every phase passed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import tempfile
import time
import traceback

import numpy as np

SEED = 20260926
CD_ITERATIONS = 2
RUNGS = (1, 8, 64, 512)
COLD_FRACTION = 0.05
# tests/test_serve.py's tolerance for online-vs-GameTransformer scores.
SCORE_RTOL, SCORE_ATOL = 1e-5, 1e-6


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The model's width is fixed; only ``rows`` may shrink (to fit the
    time limit), never entities or features — and the number used is
    printed."""

    rows: int = 4_000_000
    features: int = 64
    users: int = 100_000
    user_features: int = 17  # 16 + intercept
    movies: int = 20_000
    movie_features: int = 9  # 8 + intercept
    score_rows: int = 300  # rows scored online AND by GameTransformer
    drive_requests: int = 400
    cli_rows: int = 3_000
    cli_serve_requests: int = 500
    mesh_rows: int = 200_000
    mesh_users: int = 20_000
    mesh_movies: int = 4_000


class Smoke:
    """One run: phases record their seconds and their failures here."""

    def __init__(self, sizes: Sizes):
        self.sizes = sizes
        self.seconds: dict[str, float] = {}
        self.failures: list[str] = []
        self.out: dict = {}

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
        return bool(ok)

    @contextlib.contextmanager
    def timed(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = round(
                self.seconds.get(name, 0.0) + time.perf_counter() - t0, 3)

    def leg(self, name: str, fn) -> None:
        """Run one leg; a leg that raises is a failed leg, and the legs
        after it still run so one chip call reports everything."""
        before = len(self.failures)
        try:
            status = fn()
        except Exception as exc:  # noqa: BLE001 — recorded, ok=false
            tb = traceback.format_exc()
            print(tb, file=sys.stderr, flush=True)
            self.failures.append(
                f"{name}: {type(exc).__name__}: {str(exc)[:2000]}")
            status = None
        if status is None:
            status = (
                "passed" if len(self.failures) == before else "failed")
        self.out.setdefault("legs", {})[name] = status


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------


def synth_arrays(rows, features, users, user_features, movies,
                 movie_features, seed=SEED):
    """MovieLens-shaped logistic GLMix data from a seed (entities drawn
    uniformly, last column the intercept)."""
    rng = np.random.default_rng(seed)

    def shard(d):
        x = rng.normal(size=(rows, d)).astype(np.float32)
        x[:, -1] = 1.0
        return x

    x, xu, xm = shard(features), shard(user_features), shard(movie_features)
    uid = rng.integers(0, users, size=rows)
    mid = rng.integers(0, movies, size=rows)
    w = rng.normal(size=features).astype(np.float32) * 0.3
    wu = rng.normal(size=(users, user_features)).astype(np.float32) * 0.3
    wm = rng.normal(size=(movies, movie_features)).astype(np.float32) * 0.2
    z = (
        x @ w
        + np.einsum("nd,nd->n", xu, wu[uid])
        + np.einsum("nd,nd->n", xm, wm[mid])
    )
    y = (
        rng.uniform(size=rows) < 1.0 / (1.0 + np.exp(-0.5 * z))
    ).astype(np.float32)
    return dict(x=x, xu=xu, xm=xm, uid=uid, mid=mid, y=y)


def game_dataset(a):
    from photon_tpu.data.dataset import DenseFeatures
    from photon_tpu.data.game_data import make_game_dataset

    return make_game_dataset(
        a["y"],
        {
            "global": DenseFeatures(a["x"]),
            "userShard": DenseFeatures(a["xu"]),
            "movieShard": DenseFeatures(a["xm"]),
        },
        id_tags={"userId": a["uid"], "movieId": a["mid"]},
    )


def estimator(sizes: Sizes, mesh):
    """The GLMix estimator of the benchmark's configurations
    (``benchmark/sut.py``) at the package defaults: precision float32,
    so all three kernels are eligible."""
    from photon_tpu import optim
    from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
    from photon_tpu.data.random_effect import RandomEffectDataConfiguration
    from photon_tpu.estimators.game_estimator import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu.types import TaskType

    def l2(weight):
        return GLMOptimizationConfiguration(
            regularization=optim.RegularizationContext(
                optim.RegularizationType.L2),
            regularization_weight=weight,
        )

    return GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {
            "global": FixedEffectCoordinateConfiguration("global", l2(1e-3)),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration(
                    "userId", "userShard", active_data_upper_bound=512),
                l2(1.0),
            ),
            "per-movie": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration(
                    "movieId", "movieShard", active_data_upper_bound=2048),
                l2(1.0),
            ),
        },
        intercept_indices={
            "global": sizes.features - 1,
            "userShard": sizes.user_features - 1,
            "movieShard": sizes.movie_features - 1,
        },
        num_iterations=CD_ITERATIONS,
        mesh=mesh,
    )


def coefficient_arrays(model) -> dict:
    return {
        name: (m.coefficients if hasattr(m, "coefficients")
               else m.model.coefficients.means)
        for name, m in model.models.items()
    }


def scaled_model(model, factor: float):
    """Same structure, other values: the daily-refresh shape a
    values-only reload swaps in."""
    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel

    out = {}
    for name, m in model.models.items():
        if isinstance(m, RandomEffectModel):
            out[name] = dataclasses.replace(
                m, coefficients=m.coefficients * factor)
        else:
            out[name] = FixedEffectModel(
                GeneralizedLinearModel(
                    Coefficients(means=m.model.coefficients.means * factor),
                    m.model.task,
                ),
                m.feature_shard_id,
            )
    return GameModel(out)


def pallas_census(jaxpr_text: str) -> dict:
    """Which Pallas kernels a traced program embeds, by their stable
    names, and whether any is interpreted."""
    return {
        "pallas_calls": jaxpr_text.count("pallas_call"),
        "newton": "newton_step_lanes" in jaxpr_text,
        "segment_reduce": "segment_reduce" in jaxpr_text,
        "serve": "serve_score" in jaxpr_text,
        "interpreted": "interpret=True" in jaxpr_text,
    }


# --------------------------------------------------------------------------
# leg 1: full width, fit -> save -> serve
# --------------------------------------------------------------------------


def full_width_leg(s: Smoke) -> None:
    import jax

    from photon_tpu.data.index_map import IndexMap
    from photon_tpu.data.pipeline import PIPELINE_STATS
    from photon_tpu.io.model_io import save_game_model
    from photon_tpu.utils import cache_stats

    z = s.sizes
    with s.timed("generate"):
        arrays = synth_arrays(
            z.rows, z.features, z.users, z.user_features, z.movies,
            z.movie_features)
    with s.timed("dataset"):
        data = game_dataset(arrays)
        jax.block_until_ready(
            [f.x for f in data.feature_shards.values()] + [data.labels])

    # One device, the fused program: on a four-chip host "auto" would
    # take the mesh (unfused) path, which is the mesh leg's subject.
    est = estimator(z, mesh="off")
    with s.timed("prepare"):
        datasets, _ = est.prepare(data)
    with s.timed("fit"):
        result = est.fit(data)[0]
        coefs = coefficient_arrays(result.model)
        jax.block_until_ready(list(coefs.values()))
    pipe = PIPELINE_STATS.report()

    fit = s.out["fit"] = {}
    cache = getattr(est, "_fused_cache", None)
    if s.check(bool(cache), "fit did not take the fused program"):
        fused = next(reversed(cache.values()))
        fit["fused"] = True
        # Whether the executables compiled ahead of time, overlapped
        # with ingest, were the ones dispatched (a stale shape
        # prediction drops them for the jit path).
        fit["aot_dispatched"] = fused._aot is not None
        coords = est._build_coordinates(datasets, {}, {}, z.rows)
        fit["kernels_in_program"] = pallas_census(
            str(fused.trace(coords).jaxpr))
    fit["aot_compiles"] = cache_stats()["aot_compiles"]
    fit["aot_compile_seconds"] = pipe["compile_seconds"]
    fit["compile_overlap_fraction"] = pipe["compile_overlap_fraction"]
    host = {k: np.asarray(v) for k, v in coefs.items()}
    fit["coefficients_finite"] = bool(
        all(np.isfinite(v).all() for v in host.values()))
    s.check(fit["coefficients_finite"], "non-finite coefficients")
    fit["coefficient_shapes"] = {k: list(v.shape) for k, v in host.items()}
    s.check(
        host["global"].shape == (z.features,)
        and host["per-user"].shape == (z.users, z.user_features)
        and host["per-movie"].shape == (z.movies, z.movie_features),
        f"coefficient shapes {fit['coefficient_shapes']}",
    )

    with tempfile.TemporaryDirectory(prefix="photon_smoke_") as tmp:
        with s.timed("save_model"):
            save_game_model(
                result.model, os.path.join(tmp, "model"),
                {
                    "global": IndexMap.identity(z.features),
                    "userShard": IndexMap.identity(z.user_features),
                    "movieShard": IndexMap.identity(z.movie_features),
                },
            )
        s.out["saved_model_bytes"] = sum(
            os.path.getsize(os.path.join(root, f))
            for root, _, files in os.walk(tmp) for f in files)

    serve_full_width(s, result.model, arrays, host)


def serve_full_width(s: Smoke, model, arrays, host_coefs) -> None:
    import jax

    from photon_tpu.serve.driver import drive, synthetic_requests
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.queue import MicroBatchQueue
    from photon_tpu.serve.tables import CoefficientTables
    from photon_tpu.transformers import GameTransformer
    from photon_tpu.utils import compile_event_count

    z = s.sizes
    serving = s.out["serving"] = {}
    with s.timed("tables"):
        tables = CoefficientTables.from_game_model(model)
        jax.block_until_ready(
            [t.weights for t in tables.random.values()])
    with s.timed("compile_ladder"):
        programs = ScorePrograms(tables, ladder=ShapeLadder(RUNGS))
    serving["kernel_engaged"] = bool(programs.use_kernel)
    serving["kernel_in_program"] = pallas_census(
        str(programs.trace(RUNGS[-1]).jaxpr))
    serving["programs_compiled"] = programs.stats["programs_compiled"]

    # The rows scored twice: the first score_rows rows of the training
    # set, 5% of them with an entity the model never trained (cold).
    n = z.score_rows
    rng = np.random.default_rng(SEED + 1)
    uid = arrays["uid"][:n].copy()
    mid = arrays["mid"][:n].copy()
    cold = rng.uniform(size=n) < COLD_FRACTION
    uid[cold] = z.users + np.arange(n)[cold]
    sub = dict(
        x=arrays["x"][:n], xu=arrays["xu"][:n], xm=arrays["xm"][:n],
        uid=uid, mid=mid, y=arrays["y"][:n],
    )
    requests = [
        (
            {"global": sub["x"][i], "userShard": sub["xu"][i],
             "movieShard": sub["xm"][i]},
            {"userId": str(int(uid[i])), "movieId": str(int(mid[i]))},
        )
        for i in range(n)
    ]
    synthetic = synthetic_requests(
        tables, programs, z.drive_requests,
        cold_fraction=COLD_FRACTION, seed=7)

    windows = []
    with MicroBatchQueue(programs, max_linger_s=2e-3) as queue:
        with s.timed("score_online"):
            online = np.asarray(
                [queue.submit(f, ids).result() for f, ids in requests],
                dtype=np.float64)
        for label, factor in (("first", None), ("after_reload", 0.5)):
            if factor is not None:
                with s.timed("reload"):
                    # Donating values-only swap, queue idle between the
                    # drives (the quiesced condition donation needs).
                    serving["reload_values_only"] = bool(tables.reload(
                        scaled_model(model, factor), donate=True))
                s.check(serving["reload_values_only"],
                        "reload was not values-only")
            before = compile_event_count()
            with s.timed(f"drive_{label}"):
                summary = drive(queue, synthetic)
            windows.append({
                "window": label,
                "requests": summary["requests"],
                "errors": summary["errors"],
                "compile_events": compile_event_count() - before,
                "cold_entity_rate": summary["cold_entity_rate"],
            })
        with s.timed("score_online"):
            reloaded = np.asarray(
                [queue.submit(f, ids).result() for f, ids in requests[:32]],
                dtype=np.float64)
    serving["windows"] = windows
    serving["errors"] = sum(w["errors"] for w in windows)
    s.check(serving["errors"] == 0, f"serving errors: {windows}")
    s.check(all(w["compile_events"] == 0 for w in windows),
            f"compile events inside a driven window: {windows}")
    serving["dispatches"] = programs.stats["dispatches"]

    # The reference: GameTransformer on the same rows (batch scoring,
    # the training-time path) and a float64 host recomputation.
    with s.timed("score_transformer"):
        want = np.asarray(
            GameTransformer(model).score(game_dataset(sub)),
            dtype=np.float64)
    known = ~cold
    f64 = {k: v.astype(np.float64) for k, v in host_coefs.items()}
    x64 = {k: sub[k].astype(np.float64) for k in ("x", "xu", "xm")}
    safe_uid = np.where(known, uid, 0)
    terms = [
        x64["x"] * f64["global"][None, :],
        x64["xu"] * f64["per-user"][safe_uid] * known[:, None],
        x64["xm"] * f64["per-movie"][mid],
    ]
    exact = sum(t.sum(axis=1) for t in terms)
    # What float32 arithmetic with the TPU's default (bfloat16-operand)
    # matmul passes may lose: 2^-7 of the summed term magnitudes.
    bound = 2.0 ** -7 * sum(np.abs(t).sum(axis=1) for t in terms) + 1e-6
    diff = np.abs(online - want)
    serving["score_rows"] = int(n)
    serving["score_cold_rows"] = int(cold.sum())
    serving["score_max_abs_diff_vs_transformer"] = float(diff.max())
    serving["score_matches_transformer_at_test_tolerance"] = bool(
        np.allclose(online, want, rtol=SCORE_RTOL, atol=SCORE_ATOL))
    serving["score_max_abs_err_online_vs_float64"] = float(
        np.abs(online - exact).max())
    serving["score_max_abs_err_transformer_vs_float64"] = float(
        np.abs(want - exact).max())
    serving["score_error_bound_max"] = float(bound.max())
    s.check(bool(np.isfinite(online).all()), "non-finite online scores")
    s.check(
        bool((np.abs(online - exact) <= bound).all()
             and (np.abs(want - exact) <= bound).all()),
        "scores off the float64 reference beyond the float32 bound: "
        f"online {serving['score_max_abs_err_online_vs_float64']:.3g}, "
        f"transformer "
        f"{serving['score_max_abs_err_transformer_vs_float64']:.3g}, "
        f"bound {serving['score_error_bound_max']:.3g}",
    )
    # The reload took: every coefficient halved, so every score halved.
    serving["reload_score_ratio_max_abs_err"] = float(
        np.abs(reloaded - 0.5 * online[:32]).max())
    s.check(
        bool((np.abs(reloaded - 0.5 * online[:32]) <= bound[:32]).all()),
        "scores after the reload are not the refreshed model's")
    # And the model is a model: it ranks its own training rows.
    pos, neg = exact[sub["y"] > 0.5], exact[sub["y"] <= 0.5]
    auc = float((pos[:, None] > neg[None, :]).mean())
    serving["train_rows_auc"] = round(auc, 4)
    s.check(auc > 0.6, f"fitted model does not rank its rows (AUC {auc})")


# --------------------------------------------------------------------------
# leg 2: each kernel, compiled, against its XLA route
# --------------------------------------------------------------------------


def kernel_leg(s: Smoke) -> None:
    kernels = s.out["kernels"] = {}
    for name, fn in (
        ("newton", newton_parity),
        ("segment_reduce", segment_reduce_parity),
        ("serve", serve_parity),
    ):
        with s.timed(f"kernel_{name}"):
            kernels[name] = report = fn(s)
        if report["engaged"]:
            s.check(not report["interpreted"], f"{name} kernel interpreted")
            s.check(
                report["max_abs_diff"] <= report["tolerance"],
                f"{name} kernel off its XLA route: "
                f"{report['max_abs_diff']:.3g} > {report['tolerance']:.3g}",
            )
        else:
            s.check(bool(report.get("why_not")),
                    f"{name} kernel closed without a reason")


def _flag(name: str, value: str | None):
    """Set / restore one PHOTON_*_KERNEL variable (read at trace time).
    Used ONLY to trace the XLA route ("off") next to the kernel route,
    which always runs under the environment as found."""
    prev = os.environ.get(name)
    if value is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = value
    return prev


def newton_parity(s: Smoke) -> dict:
    """The whole per-bucket damped-Newton solve, Pallas step against
    the batch-minor XLA step, at the user bucket's shape."""
    import jax
    import jax.numpy as jnp

    from photon_tpu import optim
    from photon_tpu.algorithm import random_effect as re_mod
    from photon_tpu.algorithm.problems import VarianceComputationType
    from photon_tpu.ops import newton_kernel as nk
    from photon_tpu.types import TaskType

    z = s.sizes
    b, r, sd = 2048, 64, z.user_features
    task = TaskType.LOGISTIC_REGRESSION
    if not nk.kernel_supported(task, jnp.float32, r, sd):
        return {"engaged": False, "why_not": (
            f"gate closed for r={r}, s={sd} on backend "
            f"{jax.default_backend()}")}
    rng = np.random.default_rng(SEED + 2)
    x = rng.normal(size=(b, r, sd)).astype(np.float32)
    x[:, :, -1] = 1.0
    w_true = rng.normal(size=(b, sd)).astype(np.float32) * 0.5
    margins = np.einsum("brs,bs->br", x, w_true)
    y = (rng.uniform(size=(b, r)) < 1 / (1 + np.exp(-margins))).astype(
        np.float32)
    ones = np.ones((b, sd), np.float32)

    def solve(x, y):
        return re_mod._solve_newton_batched(
            x, y, jnp.zeros((b, r)), jnp.ones((b, r)), ones, ones,
            None, None, jnp.full((b,), sd - 1, jnp.int32),
            jnp.zeros((b, sd)), None,
            sub_dim=sd, task=task,
            opt_config=optim.OptimizerConfig.lbfgs(),
            variance_computation=VarianceComputationType.NONE,
            l2_weight=jnp.float32(1.0),
            incremental_weight=jnp.float32(1.0),
        )[0]

    kernel_fn = jax.jit(solve)
    census = pallas_census(str(kernel_fn.trace(x, y).jaxpr))
    got = np.asarray(kernel_fn(x, y))
    prev = _flag("PHOTON_NEWTON_KERNEL", "off")
    try:
        xla_fn = jax.jit(lambda x, y: solve(x, y))
        assert not pallas_census(str(xla_fn.trace(x, y).jaxpr))["newton"]
        want = np.asarray(xla_fn(x, y))
    finally:
        _flag("PHOTON_NEWTON_KERNEL", prev)
    return {
        "engaged": census["newton"],
        "interpreted": census["interpreted"],
        "shape": {"entities": b, "rows": r, "sub_dim": sd},
        "max_abs_diff": float(np.abs(got - want).max()),
        # Both routes stop at the optimizer's tolerance, the XLA step
        # through bfloat16-operand matmul passes: coefficients of
        # magnitude ~0.5 agree to a few 1e-3 (tests/test_newton_kernel
        # uses rtol 2e-3 for ONE exact-arithmetic step).
        "tolerance": 2e-2,
        "finite": bool(np.isfinite(got).all()),
    }


def segment_reduce_parity(s: Smoke) -> dict:
    """The bucket scorer's scatter (models/game._bucket_score_add) at a
    user bucket's shape into this model's row count, Pallas windowed
    reduce against ``.at[].add``; and the sorted tail reduce against
    ``segment_sum``."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.ops import segment_reduce as sr

    z = s.sizes
    n = z.rows
    b, r = min(z.users, max(n // 64, 1)), 64
    if not sr.kernel_supported(b * r, n, jnp.float32):
        return {"engaged": False, "why_not": (
            f"gate closed for {b * r} values into {n} segments on "
            f"backend {jax.default_backend()}")}
    rng = np.random.default_rng(SEED + 3)
    # Distinct row ids (each kept row belongs to one entity), ~40 valid
    # lanes of 64 per entity, the rest masked.
    row_ids = rng.permutation(max(n, b * r))[: b * r].reshape(b, r) % n
    row_ids = row_ids.astype(np.int32)
    valid = rng.uniform(size=(b, r)) < 0.6
    zb = rng.normal(size=(b, r)).astype(np.float32)
    base = rng.normal(size=n).astype(np.float32)

    scatter = jax.jit(sr.scatter_add_rows)
    census = pallas_census(
        str(scatter.trace(base, row_ids, zb, valid).jaxpr))
    got = np.asarray(scatter(base, row_ids, zb, valid))
    want = np.asarray(jax.jit(
        lambda z_, ids, v, ok: z_.at[ids].add(jnp.where(ok, v, 0.0))
    )(base, row_ids, zb, valid))
    diff = float(np.abs(got - want).max())

    m = min(b * r, 200_000)
    ids = np.sort(rng.integers(0, n, size=m)).astype(np.int32)
    vals = rng.normal(size=m).astype(np.float32)
    mult = int(np.bincount(ids).max())
    tail = np.asarray(jax.jit(
        lambda v, i: sr.sorted_segment_sum(v, i, n, multiplicity=mult)
    )(vals, ids))
    tail_want = np.asarray(jax.jit(
        lambda v, i: jax.ops.segment_sum(
            v, i, num_segments=n, indices_are_sorted=True)
    )(vals, ids))
    return {
        "engaged": census["segment_reduce"],
        "interpreted": census["interpreted"],
        "shape": {"values": b * r, "segments": n,
                  "tail_values": m, "tail_multiplicity": mult},
        "max_abs_diff": max(diff, float(np.abs(tail - tail_want).max())),
        # float32 sums of at most `mult` float32 terms per segment.
        "tolerance": 1e-5,
        "on_main_path": (
            "no: the fused fit and GameTransformer score through the "
            "inverse-map gather (models/game._gather_score); this "
            "kernel serves datasets without a packed score map, "
            "width-capped score tails and wide-ELL buckets"),
    }


def serve_parity(s: Smoke) -> dict:
    """The score ladder through the fused kernel against the jitted
    per-coordinate chain, every rung, on this model's table shapes."""
    import jax

    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.serve.driver import synthetic_requests
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables
    from photon_tpu.types import TaskType

    z = s.sizes
    rng = np.random.default_rng(SEED + 4)
    task = TaskType.LOGISTIC_REGRESSION

    def random_effect(re_type, shard, e, sd):
        return RandomEffectModel(
            coefficients=jax.numpy.asarray(
                rng.normal(size=(e, sd)).astype(np.float32) * 0.3),
            random_effect_type=re_type, feature_shard_id=shard, task=task,
            proj_all=np.tile(np.arange(sd), (e, 1)).astype(np.int64),
            entity_keys=tuple(str(i) for i in range(e)),
        )

    model = GameModel({
        "global": FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(means=jax.numpy.asarray(
                    rng.normal(size=z.features).astype(np.float32) * 0.3)),
                task),
            "global"),
        "per-user": random_effect(
            "userId", "userShard", z.users, z.user_features),
        "per-movie": random_effect(
            "movieId", "movieShard", z.movies, z.movie_features),
    })
    # The kernel route compiles and runs the whole ladder; the XLA
    # route scores the SAME requests through its top rung only (a score
    # does not depend on the rung it was padded to, and each rung of
    # the chain costs tens of seconds of XLA compile at 100 000
    # entities — its per-dispatch [E, d + 1] scatter).
    scores = {}
    ladder_compile_seconds = {}
    census = None
    for route, rungs in (("kernel", RUNGS), ("off", RUNGS[-1:])):
        if route == "off":
            prev = _flag("PHOTON_SERVE_KERNEL", "off")
        try:
            tables = CoefficientTables.from_game_model(model)
            programs = ScorePrograms(tables, ladder=ShapeLadder(rungs))
        finally:
            if route == "off":
                _flag("PHOTON_SERVE_KERNEL", prev)
        if route == "kernel":
            if not programs.use_kernel:
                return {"engaged": False, "why_not": (
                    "gate closed for this model structure on backend "
                    f"{jax.default_backend()}")}
            census = pallas_census(str(programs.trace(RUNGS[-1]).jaxpr))
        else:
            assert not programs.use_kernel
        ladder_compile_seconds[route] = round(
            programs.stats["aot_compile_seconds"], 3)
        out = []
        for n in RUNGS:
            reqs = synthetic_requests(
                tables, programs, n, cold_fraction=0.25, seed=n)
            feats, codes, _ = programs.pack_requests(reqs)
            out.append(np.asarray(
                programs.score_padded(feats, codes, n), dtype=np.float64))
        scores[route] = np.concatenate(out)
    return {
        "engaged": census["serve"],
        "interpreted": census["interpreted"],
        "rungs": list(RUNGS),
        # Host clock around each route's AOT compiles (four rungs of
        # the kernel route, the top rung of the chain): set-up time,
        # reported so that what the smoke's seconds went to is visible;
        # not a serving metric.
        "ladder_compile_seconds": ladder_compile_seconds,
        "max_abs_diff": float(
            np.abs(scores["kernel"] - scores["off"]).max()),
        # float32 sums of 64 + 17 + 9 terms of magnitude ~1 in two
        # orders. (Measured 1.9e-6 on the v5e, PR 21: at these shapes
        # the chain's float32 contractions do not round their operands
        # to bfloat16.)
        "tolerance": 1e-4,
        "finite": bool(np.isfinite(scores["kernel"]).all()),
    }


# --------------------------------------------------------------------------
# leg 3: the CLIs, in this process
# --------------------------------------------------------------------------


def cli_leg(s: Smoke) -> None:
    from photon_tpu.cli import serve as serve_cli
    from photon_tpu.cli import train as train_cli
    from photon_tpu.io.avro_data import write_training_examples
    from photon_tpu.native import get_avro_decoder
    from photon_tpu.types import DELIMITER

    z = s.sizes
    d, users, movies = 8, 40, 15
    rng = np.random.default_rng(SEED + 5)
    keys = [f"f{j}{DELIMITER}t" for j in range(d)]
    w = rng.normal(size=d)
    u_eff, m_eff = rng.normal(size=users), rng.normal(size=movies)

    def write(path, rows):
        x = rng.normal(size=(rows, d))
        uid = rng.integers(0, users, size=rows)
        mid = rng.integers(0, movies, size=rows)
        margin = x @ w + u_eff[uid] + m_eff[mid]
        y = (rng.uniform(size=rows) < 1 / (1 + np.exp(-margin))).astype(
            np.float64)
        write_training_examples(
            path, y,
            [[(keys[j], float(x[i, j])) for j in range(d)]
             for i in range(rows)],
            metadata=[{"userId": f"u{u}", "movieId": f"m{m}"}
                      for u, m in zip(uid, mid)],
            uids=np.arange(rows),
        )

    with tempfile.TemporaryDirectory(prefix="photon_smoke_cli_") as tmp:
        train, val = (os.path.join(tmp, f) for f in
                      ("train.avro", "val.avro"))
        with s.timed("cli_write_avro"):
            write(train, z.cli_rows)
            write(val, max(z.cli_rows // 3, 50))
        out_dir = os.path.join(tmp, "out")

        def coordinate(kind, **kw):
            return {"type": kind,
                    "regularization": {"type": "L2", "weights": [1.0]},
                    **kw}

        config = os.path.join(tmp, "config.json")
        with open(config, "w") as f:
            json.dump({
                "task": "LOGISTIC_REGRESSION",
                "input": {
                    "format": "avro", "train_path": train,
                    "validation_path": val,
                    "id_tags": ["userId", "movieId"],
                },
                "coordinates": {
                    "global": coordinate("fixed"),
                    "per-user": coordinate(
                        "random", random_effect_type="userId"),
                    "per-movie": coordinate(
                        "random", random_effect_type="movieId"),
                },
                "num_iterations": CD_ITERATIONS,
                "evaluators": ["AUC"],
                "output_dir": out_dir,
            }, f)
        cli = s.out["cli"] = {}
        with s.timed("cli_train"):
            cli["train_rc"] = train_cli.main(
                ["--config", config, "--flight-dir", tmp])
        s.check(cli["train_rc"] == 0, f"cli.train rc {cli['train_rc']}")
        with open(os.path.join(out_dir, "training-summary.json")) as f:
            summary = json.load(f)
        cli["validation_auc"] = _find_number(summary, "AUC")
        s.check(
            cli["validation_auc"] is not None
            and cli["validation_auc"] > 0.6,
            f"cli.train validation AUC {cli['validation_auc']}")
        serve_json = os.path.join(tmp, "serve.json")
        with s.timed("cli_serve"):
            cli["serve_rc"] = serve_cli.main([
                "--model-dir", os.path.join(out_dir, "models", "best"),
                "--synthetic", str(z.cli_serve_requests),
                "--json", serve_json, "--flight-dir", tmp,
            ])
        s.check(cli["serve_rc"] == 0, f"cli.serve rc {cli['serve_rc']}")
        with open(serve_json) as f:
            served = json.load(f)
        cli["serve_errors"] = served["errors"]
        cli["serve_compile_events"] = served[
            "compile_events_during_serving"]
        cli["serve_rungs"] = served["rungs"]
        s.check(served["errors"] == 0, "cli.serve errors")
        s.check(served["compile_events_during_serving"] == 0,
                "cli.serve compiled inside its serving window")
    # The Avro files above were read back through the native block
    # decoder, not the interpreter codec it falls back to.
    s.out["native_avro_decoder"] = get_avro_decoder() is not None
    s.check(s.out["native_avro_decoder"],
            "native Avro decoder did not build/load (cc missing?)")


def _find_number(tree, key):
    """First numeric value stored under ``key`` anywhere in a JSON
    tree (the training summary nests its evaluation block)."""
    if isinstance(tree, dict):
        if isinstance(tree.get(key), (int, float)):
            return float(tree[key])
        tree = list(tree.values())
    if isinstance(tree, list):
        for item in tree:
            found = _find_number(item, key)
            if found is not None:
                return found
    return None


# --------------------------------------------------------------------------
# leg 4: four chips
# --------------------------------------------------------------------------


def mesh_leg(s: Smoke):
    import jax

    n_dev = len(jax.devices())
    if n_dev < 4:
        return f"skipped: {n_dev} device" + ("" if n_dev == 1 else "s")

    from photon_tpu import optim
    from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
    from photon_tpu.data.dataset import SparseFeatures
    from photon_tpu.data.game_data import make_game_dataset
    from photon_tpu.estimators.game_estimator import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
    )
    from photon_tpu.parallel.mesh import FeatureShardedSparse, make_mesh
    from photon_tpu.types import TaskType

    z = s.sizes
    mesh_sizes = dataclasses.replace(
        z, rows=z.mesh_rows, users=z.mesh_users, movies=z.mesh_movies)
    devices = jax.devices()[:4]
    mesh = make_mesh(devices)
    report = s.out["mesh_report"] = {"devices": [str(d) for d in devices]}
    arrays = synth_arrays(
        mesh_sizes.rows, z.features, mesh_sizes.users, z.user_features,
        mesh_sizes.movies, z.movie_features, seed=SEED + 6)
    data = game_dataset(arrays)

    est = estimator(mesh_sizes, mesh=mesh)
    with s.timed("mesh_prepare"):
        datasets, _ = est.prepare(data)
    # Sharded, not merely run: the fixed-effect batch and every
    # random-effect block live on four distinct devices
    # (__graft_entry__.dryrun_multichip's assertions).
    placed = {
        "global/labels": datasets["global"].labels,
        **{f"{cid}/block{i}/row_ids": blk.row_ids
           for cid in ("per-user", "per-movie")
           for i, blk in enumerate(datasets[cid].blocks)},
    }
    report["placement"] = {
        k: len(v.sharding.device_set) for k, v in placed.items()}
    s.check(all(c == 4 for c in report["placement"].values()),
            f"not on four devices: {report['placement']}")
    with s.timed("mesh_fit"):
        sharded = coefficient_arrays(est.fit(data)[0].model)
        jax.block_until_ready(list(sharded.values()))
    with s.timed("mesh_reference_fit"):
        single = coefficient_arrays(
            estimator(mesh_sizes, mesh="off").fit(data)[0].model)
        jax.block_until_ready(list(single.values()))
    report["vs_one_device"] = agree = fit_agreement(arrays, sharded, single)
    s.check(
        all(np.isfinite(np.asarray(v)).all() for v in sharded.values())
        and agree["coefficient_max_abs_diff"] <= agree["coefficient_tolerance"]
        and agree["probability_max_abs_diff"]
        <= agree["probability_tolerance"],
        f"mesh fit off the one-device fit: {agree}")

    # Column-sharded wide sparse fixed effect (FeatureShardedSparse).
    rng = np.random.default_rng(SEED + 7)
    n, d_wide, k = 20_000, 4096, 8
    idx = rng.integers(0, d_wide, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    w_true = rng.normal(size=d_wide).astype(np.float32)
    margin = (val * w_true[idx]).sum(axis=1)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    wide = make_game_dataset(
        y, {"wide": SparseFeatures(idx, val, d_wide)}, id_tags={})
    l2 = GLMOptimizationConfiguration(
        regularization=optim.RegularizationContext(
            optim.RegularizationType.L2),
        regularization_weight=1.0,
    )

    def wide_estimator(mesh_, sharding):
        return GameEstimator(
            TaskType.LOGISTIC_REGRESSION,
            {"global": FixedEffectCoordinateConfiguration(
                "wide", l2, feature_sharding=sharding)},
            mesh=mesh_,
        )

    wide_est = wide_estimator(mesh, "column")
    feats = wide_est.prepare(wide)[0]["global"].features
    s.check(isinstance(feats, FeatureShardedSparse),
            f"wide shard is {type(feats).__name__}")
    report["column_sharded_devices"] = len(
        feats.local_values.sharding.device_set)
    s.check(report["column_sharded_devices"] == 4,
            "local_values not on four devices")
    with s.timed("mesh_column_fit"):
        w_col = np.asarray(
            wide_est.fit(wide)[0].model["global"].model.coefficients.means)
        w_one = np.asarray(
            wide_estimator("off", "replicated").fit(wide)[0]
            .model["global"].model.coefficients.means)
    report["column_max_abs_diff_vs_one_device"] = float(
        np.abs(w_col - w_one).max())
    s.check(
        w_col.shape == (d_wide,) and bool(np.isfinite(w_col).all())
        and report["column_max_abs_diff_vs_one_device"] <= 5e-2,
        "column-sharded solve off the one-device solve: "
        f"{report['column_max_abs_diff_vs_one_device']}")
    return None


def fit_agreement(arrays, fit_a, fit_b) -> dict:
    """How far two fits of the same data are apart, where the question
    has an answer.

    The one-device fit runs the Pallas Newton step and the mesh fit the
    XLA step (the kernels close on a mesh); both stop at the optimizer's
    tolerance. An entity whose rows are all one class has NO finite
    optimum — the intercept is not penalized and runs off to infinity,
    so each route stops somewhere else (measured on the v5e, PR 21: one
    user with 2 rows, intercept 16.77 against 15.77, every other
    difference above 1e-2 also on a single-class entity). Coefficients
    are therefore compared on entities that have both classes, and the
    whole model in prediction space, on every row."""
    a = {k: np.asarray(v, dtype=np.float64) for k, v in fit_a.items()}
    b = {k: np.asarray(v, dtype=np.float64) for k, v in fit_b.items()}
    out = {"single_class_entities": {}}
    worst = float(np.abs(a["global"] - b["global"]).max())
    for cid, ids in (("per-user", arrays["uid"]), ("per-movie", arrays["mid"])):
        n = a[cid].shape[0]
        rows = np.bincount(ids, minlength=n)
        positives = np.bincount(ids, weights=arrays["y"], minlength=n)
        two_class = (positives > 0) & (positives < rows)
        out["single_class_entities"][cid] = int(n - two_class.sum())
        worst = max(worst, float(
            np.abs(a[cid] - b[cid])[two_class].max(initial=0.0)))

    def probabilities(c):
        margin = (
            arrays["x"] @ c["global"]
            + np.einsum("nd,nd->n", arrays["xu"], c["per-user"][arrays["uid"]])
            + np.einsum("nd,nd->n", arrays["xm"], c["per-movie"][arrays["mid"]])
        )
        return 1.0 / (1.0 + np.exp(-margin))

    out["coefficient_max_abs_diff"] = worst
    out["coefficient_tolerance"] = 5e-2
    out["probability_max_abs_diff"] = float(
        np.abs(probabilities(a) - probabilities(b)).max())
    out["probability_tolerance"] = 1e-2
    return out


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------


def run(sizes: Sizes) -> dict:
    """All legs on whatever device JAX has; ``main`` is the only caller
    outside tests and refuses anything but a TPU first."""
    import jax
    import jaxlib

    from photon_tpu.utils import cache_stats, enable_compilation_cache

    s = Smoke(sizes)
    enable_compilation_cache()
    dev = jax.devices()[0]
    s.out.update({
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "versions": {
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": _libtpu_version(),
        },
        "x64": bool(jax.config.jax_enable_x64),
        "model": {
            "task": "logistic GLMix", "rows": sizes.rows,
            "global_features": sizes.features,
            "users": sizes.users, "user_features": sizes.user_features,
            "movies": sizes.movies,
            "movie_features": sizes.movie_features,
            "cd_iterations": CD_ITERATIONS, "precision": "float32",
        },
    })
    t0 = time.perf_counter()
    s.leg("full_width", lambda: full_width_leg(s))
    s.leg("kernels", lambda: kernel_leg(s))
    s.leg("cli", lambda: cli_leg(s))
    s.leg("mesh", lambda: mesh_leg(s))
    s.out["mesh"] = s.out["legs"]["mesh"]
    s.seconds["total"] = round(time.perf_counter() - t0, 3)
    s.out["seconds"] = s.seconds
    cache = s.out["compile_cache"] = cache_stats()
    s.check(cache["dir"] is not None and cache["entries"] > 0,
            f"persistent compile cache holds nothing: {cache}")
    s.out["failures"] = s.failures
    return {"ok": not s.failures, **s.out}


def _libtpu_version():
    from importlib import metadata

    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return None


def main() -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU: JAX found {len(devices)} "
            f"{devices[0].platform} device(s). This script proves the "
            "system on the chip and never runs on the CPU.",
            file=sys.stderr,
        )
        return 2
    report = run(Sizes())
    # The report is the second-to-last line; the LAST line is the verdict
    # and carries exactly these keys.
    verdict = {
        "ok": report["ok"],
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }
    print(json.dumps(report), flush=True)
    print(json.dumps(verdict), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
