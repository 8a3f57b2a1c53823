"""``photon profile``: the cost ledger's top-k report — who burns the time.

Drives a tiny-but-real workload (a fused GLMix fit plus a serve-ladder
scoring pass) under the cost ledger (``photon_tpu.obs.ledger``) and
prints the top-k ``(coordinate, phase, program)`` rows ranked by
wasted-seconds-vs-roofline, each with its blocking reason — dispatch
gap vs bandwidth vs compute — plus the attribution fraction of the
measured fit wall. This is the instrument the roofline push steers by:
``measured_vs_roofline`` says the gap exists; this names it.

Three gates ride along (the profile-smoke CI job's contract):

- **off-census**: the same fit runs FIRST with the ledger disabled and
  the census must stay EMPTY — a disabled ledger adds zero programs
  (and, conveniently, the warm-up makes the overhead A/B honest);
- **engagement**: the top-k table must be non-empty and the fused-fit
  wall must attribute to named rows (exit 1 otherwise — a dead
  instrument must not report "clean");
- **overhead** (``--overhead-check``): warm per-fit wall, ledger off vs
  on, best-of-N in-process A/B (interleaved arms — the only honest
  protocol on a noisy shared box); the on/off ratio must stay under
  ``--overhead-budget`` (default 5%).

Usage:
    python -m photon_tpu.cli.profile [--top N] [--json PATH]
        [--rows N] [--entities N] [--iterations N]
        [--overhead-check] [--overhead-samples N] [--overhead-budget F]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _tiny_workload(rows: int, entities: int, iterations: int):
    """A miniature single-device GLMix estimator + dataset (one dense
    fixed effect, one random effect, logistic task) — the smallest
    structure that exercises the fused materialize/fit programs and a
    servable model. Mirrors the analysis tier's audit fixture; kept
    local so the CLI never imports audit machinery."""
    import numpy as np

    from photon_tpu.data.dataset import DenseFeatures
    from photon_tpu.data.game_data import make_game_dataset
    from photon_tpu.data.random_effect import RandomEffectDataConfiguration
    from photon_tpu.estimators.game_estimator import (
        FixedEffectCoordinateConfiguration,
        GameEstimator,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu.optim import RegularizationContext, RegularizationType
    from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
    from photon_tpu.types import TaskType

    def l2(w):
        return GLMOptimizationConfiguration(
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=w,
        )

    d, du = 6, 4
    rng = np.random.default_rng(20260804)
    x = rng.normal(size=(rows, d)).astype(np.float32)
    x[:, -1] = 1.0
    xu = rng.normal(size=(rows, du)).astype(np.float32)
    xu[:, -1] = 1.0
    users = rng.integers(0, entities, size=rows)
    y = (rng.uniform(size=rows) < 0.5).astype(np.float32)
    data = make_game_dataset(
        y,
        {"global": DenseFeatures(x), "userShard": DenseFeatures(xu)},
        id_tags={"userId": users},
    )
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {
            "global": FixedEffectCoordinateConfiguration(
                "global", l2(0.01)
            ),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "userShard"),
                l2(0.5),
            ),
        },
        intercept_indices={"global": d - 1, "userShard": du - 1},
        num_iterations=iterations,
        mesh="off",
    )
    return est, data


def _fit_once(est, data):
    """One blocking fit (checksum-forced completion — enqueue times
    are not measurements)."""
    import jax.numpy as jnp
    import numpy as np

    r = est.fit(data)[0]
    for m in r.model.models.values():
        c = (m.coefficients if hasattr(m, "coefficients")
             else m.model.coefficients.means)
        float(np.asarray(jnp.sum(c)))
    return r


def _serve_pass(result, data):
    """Score the training rows through the REAL serve ladder (tables →
    AOT rungs → padded dispatch), so serve-phase rows and the compile
    ledger engage."""
    from photon_tpu.serve.programs import ScorePrograms, specs_from_dataset
    from photon_tpu.serve.tables import CoefficientTables

    tables = CoefficientTables.from_game_model(result.model)
    programs = ScorePrograms(
        tables, specs=specs_from_dataset(data), compile_now=False
    )
    return programs.score_dataset(data)


def _overhead_ab(
    est, data, samples: int, fits_per_sample: int = 3
) -> dict:
    """Warm fit wall, ledger off vs on: interleaved arms, best-of-N
    each (the 2-core CI box is noisy; the BEST of an interleaved series
    is the only stable estimator of the true floor in-process). Each
    sample times a small BATCH of fits — a single warm fit is
    milliseconds, where one scheduler hiccup masquerades as overhead."""
    from photon_tpu.obs import ledger

    k = max(fits_per_sample, 1)
    off: list[float] = []
    on: list[float] = []
    for _ in range(max(samples, 1)):
        ledger.disable()
        t0 = time.perf_counter()
        for _ in range(k):
            _fit_once(est, data)
        off.append(time.perf_counter() - t0)
        ledger.enable()
        t0 = time.perf_counter()
        for _ in range(k):
            _fit_once(est, data)
        on.append(time.perf_counter() - t0)
    best_off, best_on = min(off), min(on)
    return {
        "samples": len(off),
        "fits_per_sample": k,
        "off_best_seconds": round(best_off, 6),
        "on_best_seconds": round(best_on, 6),
        "overhead_fraction": (
            round(best_on / best_off - 1.0, 4) if best_off > 0 else None
        ),
    }


def _kernel_probe() -> dict | None:
    """One REAL dispatch of the tiled segment-reduce kernel under the
    armed ledger (ops/segment_reduce): registers its census row with the
    analytic cost and records the measured dispatch->fetch window, so
    the priced report carries the kernel's own roofline row. Returns
    None where the kernel does not serve this backend (auto mode off
    TPU) — the profile-smoke job forces it with
    ``PHOTON_SEGMENT_KERNEL=force`` to exercise the interpreter path.
    """
    import numpy as np

    from photon_tpu.obs import ledger
    from photon_tpu.ops import segment_reduce as sr

    m = n = 8_192
    if not sr.kernel_supported(m, n, np.float32):
        return None
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(np.arange(m, dtype=np.int32))
    vals = jnp.asarray(
        np.random.default_rng(0).normal(size=m).astype(np.float32))
    site = "segment_reduce/probe"
    # warm (compile outside the measured window)
    jax.block_until_ready(sr.sorted_segment_sum(
        vals, ids, n, multiplicity=1, site=site))
    t0 = time.perf_counter()
    out = np.asarray(sr.sorted_segment_sum(
        vals, ids, n, multiplicity=1, site=site))
    t1 = time.perf_counter()
    info = sr.traced_sites()[site]
    ledger.register_program(site, phase="score", cost=info["cost"])
    ledger.record_dispatch(
        site, t1 - t0, phase="score", start=t0, end=t1)
    return {
        "program": site,
        "elements": m,
        "segments": n,
        "seconds": round(t1 - t0, 6),
        "checksum": float(out.sum()),
    }


def _serve_kernel_probe() -> dict | None:
    """One REAL dispatch of the fused serve-score kernel under the
    armed ledger (ops/serve_kernel): a tiny model's tables are loaded
    at serving precision, one padded rung is scored through the fused
    pallas path, and the kernel's trace-time census entry prices its
    roofline row next to the jit-chain serve rows. Returns None where
    the kernel does not serve this backend (auto mode off TPU) — the
    profile-smoke job forces it with ``PHOTON_SERVE_KERNEL=force`` to
    exercise the interpreter path."""
    import numpy as np

    from photon_tpu.obs import ledger
    from photon_tpu.ops import serve_kernel as sk

    if not sk.kernel_supported(np.float32):
        return None
    import jax.numpy as jnp

    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import (
        Coefficients,
        GeneralizedLinearModel,
    )
    from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
    from photon_tpu.serve.tables import CoefficientTables
    from photon_tpu.types import TaskType

    d, e, s, du, rung = 6, 16, 3, 4, 64
    rng = np.random.default_rng(20260806)
    proj = np.stack([
        np.sort(rng.choice(du, size=s, replace=False))
        for _ in range(e)
    ]).astype(np.int64)
    model = GameModel({
        "global": FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(means=jnp.asarray(
                    rng.normal(size=d).astype(np.float32)
                )),
                TaskType.LOGISTIC_REGRESSION,
            ),
            "features",
        ),
        "per-user": RandomEffectModel(
            coefficients=jnp.asarray(
                rng.normal(size=(e, s)).astype(np.float32)
            ),
            random_effect_type="userId",
            feature_shard_id="userShard",
            task=TaskType.LOGISTIC_REGRESSION,
            proj_all=proj,
            entity_keys=tuple(str(i) for i in range(e)),
        ),
    })
    tables = CoefficientTables.from_game_model(model)
    programs = ScorePrograms(tables, ladder=ShapeLadder((rung,)))
    if not programs.use_kernel:
        return None
    reqs = [
        (
            {
                "features": rng.normal(size=d).astype(np.float32),
                "userShard": rng.normal(size=du).astype(np.float32),
            },
            {"userId": str(i % e)},
        )
        for i in range(rung)
    ]
    feats, codes, _ = programs.pack_requests(reqs)
    # warm (the AOT ladder compiled at construction; this pays the
    # first-dispatch transfer outside the measured window)
    programs.score_padded(feats, codes, rung)
    site = "serve_kernel/score"
    t0 = time.perf_counter()
    out = programs.score_padded(feats, codes, rung)
    t1 = time.perf_counter()
    info = sk.traced_sites().get(site)
    if info is None:
        return None
    probe_site = "serve_kernel/probe"
    ledger.register_program(probe_site, phase="serve", cost=info["cost"])
    ledger.record_dispatch(
        probe_site, t1 - t0, phase="serve", start=t0, end=t1)
    return {
        "program": probe_site,
        "rung": rung,
        "seconds": round(t1 - t0, 6),
        "checksum": float(np.asarray(out).sum()),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="photon profile", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--top", type=int, default=5,
                        help="rows in the top-k table")
    parser.add_argument("--json", default=None, metavar="PATH",
                        help="also write the full priced report to PATH")
    parser.add_argument("--rows", type=int, default=512,
                        help="workload rows")
    parser.add_argument("--entities", type=int, default=16,
                        help="random-effect entities")
    parser.add_argument("--iterations", type=int, default=2,
                        help="coordinate-descent iterations")
    parser.add_argument("--fits", type=int, default=3,
                        help="warm fits inside the measured window")
    parser.add_argument("--overhead-check", action="store_true",
                        help="A/B the warm fit ledger-off vs ledger-on "
                        "and gate the overhead fraction")
    parser.add_argument("--overhead-samples", type=int, default=25,
                        help="best-of-N samples per A/B arm (the fits "
                        "are milliseconds warm; a deep N is what makes "
                        "the best-of estimator stable on a loaded box)")
    parser.add_argument("--overhead-budget", type=float, default=0.05,
                        help="max tolerated on/off overhead fraction")
    parser.add_argument("--chip", default=None,
                        help="costmodel.CHIP_PEAKS key to price rows "
                        "against. Default: the device this runs on (an "
                        "error if it has no peaks row). CI on the CPU "
                        "names the target it checks the census for.")
    args = parser.parse_args(argv)

    from photon_tpu import obs
    from photon_tpu.obs import ledger

    failures: list[str] = []
    obs.enable()
    ledger.disable()
    ledger.reset()

    est, data = _tiny_workload(args.rows, args.entities, args.iterations)
    # Gate 1 — off-census: the ledger-disabled run must register NOTHING
    # (zero added programs in the dispatch census). Doubles as warm-up:
    # this pays the compiles, so the A/B and the attribution window
    # below measure dispatch, not tracing.
    result = _fit_once(est, data)
    _serve_pass(result, data)
    off_snap = ledger.snapshot()
    if off_snap["programs"] or off_snap["rows"] or off_snap["compiles"]:
        failures.append(
            "ledger-disabled run polluted the census: "
            f"{len(off_snap['programs'])} program(s), "
            f"{len(off_snap['rows'])} row(s), "
            f"{len(off_snap['compiles'])} compile key(s)"
        )

    overhead = None
    if args.overhead_check:
        overhead = _overhead_ab(est, data, args.overhead_samples)
        ledger.reset()  # the A/B's on-arm rows are not the profile
        if (
            overhead["overhead_fraction"] is not None
            and overhead["overhead_fraction"] > args.overhead_budget
        ):
            failures.append(
                f"ledger-on overhead {overhead['overhead_fraction']:.2%}"
                f" > budget {args.overhead_budget:.2%} "
                f"(best-of-{overhead['samples']} per arm)"
            )

    # The profiled window: warm fits + a serve pass, ledger armed.
    ledger.enable()
    mark = ledger.mark()
    t0 = time.perf_counter()
    for _ in range(max(args.fits, 1)):
        result = _fit_once(est, data)
    fit_wall = time.perf_counter() - t0
    # The fit-window attribution closes BEFORE the serve pass: serve
    # rows recorded after the fit wall must not count as attributed
    # fit seconds, or a dead fused-fit feed would hide behind them.
    fit_attr = ledger.attribution_since(mark, wall_seconds=fit_wall)
    _serve_pass(result, data)
    # Kernel probes: where the segment-reduce / fused serve kernels
    # serve this backend, one real dispatch each prices its census/
    # roofline row into the report (the profile-smoke job forces the
    # kernels and asserts the rows).
    kernel_probe = _kernel_probe()
    serve_kernel_probe = _serve_kernel_probe()
    attribution = ledger.attribution_since(mark, wall_seconds=None)

    table = ledger.render_top_k(args.top, args.chip)
    rows = ledger.top_k(args.top, args.chip)
    print(table)
    if rows:
        worst = rows[0]
        print(
            f"worst program: {worst['program']} "
            f"(coordinate={worst['coordinate']}, phase={worst['phase']}) "
            f"— wasted {worst['wasted_seconds']:.4f}s vs its roofline, "
            f"blocking: {worst['blocking']}"
        )
    print(
        "fit-window attribution: "
        f"{fit_attr['attributed_fraction']} of {fit_wall:.4f}s named "
        f"({fit_attr['unattributed_seconds']:.4f}s unattributed)"
    )
    if overhead is not None:
        print(
            f"ledger overhead: {overhead['overhead_fraction']} "
            f"(off {overhead['off_best_seconds']:.4f}s / on "
            f"{overhead['on_best_seconds']:.4f}s, "
            f"best-of-{overhead['samples']})"
        )

    # Gate 2 — engagement: an empty table or a dead attribution means
    # the instrument is broken, and a broken instrument exiting 0 is
    # how tracked metrics rot.
    if not rows:
        failures.append("top-k table is empty (no dispatches recorded)")
    if not fit_attr["attributed_fraction"]:
        failures.append(
            "fused-fit wall attributed nothing (ledger feed dead)")
    if kernel_probe is not None:
        probe_rows = [
            r for r in ledger.report(args.chip)["rows"]
            if r.get("program") == kernel_probe["program"]
        ]
        if not probe_rows:
            failures.append(
                "segment-reduce kernel dispatched but its census row is "
                "missing from the priced report")
        elif probe_rows[0].get("vs_roofline") is None:
            failures.append(
                "segment-reduce census row carries no priced roofline "
                "(vs_roofline is None — analytic cost missing)")
    if serve_kernel_probe is not None:
        probe_rows = [
            r for r in ledger.report(args.chip)["rows"]
            if r.get("program") == serve_kernel_probe["program"]
        ]
        if not probe_rows:
            failures.append(
                "serve kernel dispatched but its census row is missing "
                "from the priced report")
        elif probe_rows[0].get("vs_roofline") is None:
            failures.append(
                "serve-kernel census row carries no priced roofline "
                "(vs_roofline is None — analytic cost missing)")

    if args.json:
        doc = {
            "report": ledger.report(args.chip),
            "attribution": attribution,
            "fit_window": {
                "wall_seconds": round(fit_wall, 6),
                "fits": max(args.fits, 1),
                **fit_attr,
            },
            "overhead": overhead,
            "kernel_probe": kernel_probe,
            "serve_kernel_probe": serve_kernel_probe,
            "failures": failures,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
    for msg in failures:
        print(f"FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
