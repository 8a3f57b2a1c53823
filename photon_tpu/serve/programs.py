"""AOT-compiled fixed-shape score programs: the serving shape ladder.

Online traffic arrives one request at a time; XLA wants fixed shapes.
The bridge is a LADDER of batch rungs (default 1/8/64/512): one jitted
scoring function per model structure, ahead-of-time compiled at server
start for every rung through ``utils.compile_cache.aot_compile`` (the
persistent-cache wiring makes warm server starts skip the compiles
entirely), with each request batch padded up to the nearest rung.
Padded rows carry zero features and code -1, so they score 0 and are
sliced away — and because every batch size maps into the closed rung
set, the steady-state serving loop adds ZERO programs. That is the
tier-2 ``serving`` PROGRAM_AUDIT contract (declared in
``serve/__init__``, machinery in ``analysis/program.build_serving``),
which also pins that a model reload (new coefficient VALUES, same
shapes) re-enters the same executables: tables are traced operands of
the score function, never baked constants.

The scoring math is the SAME fused kernels batch scoring uses
(``models/game._score_raw_dense`` / ``_score_raw_sparse``), summed over
coordinates — online, dataset-batch, and training-time scores agree by
construction. ``score_dataset`` chunks an arbitrary ``GameDataset``
through the ladder, which is how ``cli/score.py`` routes batch scoring.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from photon_tpu.serve.tables import CoefficientTables

# Memory contract (audited by `python -m photon_tpu.analysis --memory`,
# machinery in analysis/memory.py): the expected peak-HBM of a score
# rung as a formula over the audit fixture's dims. One formula covers
# every rung (the `score_b*` pattern): the resident tables (weights at
# storage width + int32 projector) plus a fixed program scaffold, plus
# a per-row live set — the padded feature payloads, gathered per-row
# coefficients, row codes, and partial scores. The reload path's
# donating swap (tables._swap_values) must alias in compiled HLO or a
# structure reload holds both table generations resident.
MEMORY_AUDIT = dict(
    name="serving-memory",
    entry="serve.programs.ScorePrograms (score ladder rungs)",
    covers=("serving",),
    builder="build_serving_memory",
    budgets={
        "score_b*": (
            "e * s * (wbytes + 4) + d * wbytes + 120 * wbytes"
            " + rung * (d + du + 2 * s + 16) * wbytes"
        ),
    },
    donations={"serve.tables._swap_values": (0,)},
    tolerance=1.5,
)

# Tier-5 numerics contract (`--numerics`, ANALYSIS.md): the score
# ladder traced over bf16 CoefficientTables — the production serving
# precision. Score reductions against the bf16 tables must accumulate
# f32 (models/game.py acc_sum/acc_einsum); request payloads stay f32.
# Budget per rung: one table storage rounding + one f32 accumulation
# step per reduced coefficient column.
NUMERICS_AUDIT = dict(
    name="serving-numerics",
    entry="serve.programs.ScorePrograms (score ladder rungs)",
    covers=("serving",),
    builder="build_serving_numerics",
    budgets={
        "score_b*": "u16 + u32 * (d + du + 2 * s)",
    },
    deterministic={
        "score_b*:scatter": (
            "the passive-row score set (models/game.py "
            "_passive_score_set_*) scatters into unique request-row "
            "indices — each row is written at most once per batch, so "
            "no colliding writes exist to order"
        ),
    },
    tolerance=1.5,
)


@dataclasses.dataclass(frozen=True)
class ShapeLadder:
    """The closed set of batch shapes the server compiles."""

    rungs: tuple[int, ...] = (1, 8, 64, 512)

    def __post_init__(self):
        rungs = tuple(sorted(set(int(r) for r in self.rungs)))
        if not rungs or rungs[0] < 1:
            raise ValueError(f"ladder rungs must be >= 1, got {self.rungs}")
        object.__setattr__(self, "rungs", rungs)

    @property
    def max_batch(self) -> int:
        return self.rungs[-1]

    def rung_for(self, n: int) -> int:
        """Smallest rung that holds ``n`` requests."""
        if n < 1:
            raise ValueError("empty batch has no rung")
        for r in self.rungs:
            if n <= r:
                return r
        raise ValueError(
            f"batch of {n} exceeds the ladder max {self.max_batch}; "
            "split it (the queue's max_batch is clamped to the ladder)"
        )

    def chunk_plan(self, n: int) -> list[tuple[int, int, int]]:
        """(lo, hi, rung) chunks covering ``n`` rows: full max-batch
        chunks plus one padded tail rung."""
        plan: list[tuple[int, int, int]] = []
        lo = 0
        while n - lo > self.max_batch:
            plan.append((lo, lo + self.max_batch, self.max_batch))
            lo += self.max_batch
        if n - lo > 0:
            plan.append((lo, n, self.rung_for(n - lo)))
        return plan


@dataclasses.dataclass(frozen=True)
class FeatureSpec:
    """Static request layout of one feature shard.

    ``dense``: requests carry a [d] vector (stacked to [B, d]).
    ``sparse``: requests carry an ELL row pair ([k] int32 indices,
    [k] values) — the dataset batch path's layout.
    """

    kind: str  # "dense" | "sparse"
    d: int
    k: int = 0

    def __post_init__(self):
        if self.kind not in ("dense", "sparse"):
            raise ValueError(f"unknown feature spec kind {self.kind!r}")

    def sds(self, batch: int, dtype):
        import jax

        if self.kind == "dense":
            return jax.ShapeDtypeStruct((batch, self.d), dtype)
        return (
            jax.ShapeDtypeStruct((batch, self.k), np.int32),
            jax.ShapeDtypeStruct((batch, self.k), dtype),
        )

    def stack(self, rows: list, batch: int, dtype):
        """Pad ``rows`` (one request leaf each) up to [batch, ...].

        Padding rows are all-zero: zero values contribute zero margin
        whatever the padded code ends up gathering."""
        if self.kind == "dense":
            out = np.zeros((batch, self.d), dtype=dtype)
            for i, r in enumerate(rows):
                out[i] = np.asarray(r, dtype=dtype)
            return out
        idx = np.zeros((batch, self.k), dtype=np.int32)
        val = np.zeros((batch, self.k), dtype=dtype)
        for i, r in enumerate(rows):
            ri, rv = r
            idx[i] = np.asarray(ri, dtype=np.int32)
            val[i] = np.asarray(rv, dtype=dtype)
        return idx, val

    def slice_rows(self, host_leaf, lo: int, hi: int, batch: int, dtype):
        """Padded [batch, ...] chunk of a full host array set."""
        if self.kind == "dense":
            out = np.zeros((batch, self.d), dtype=dtype)
            out[: hi - lo] = host_leaf[lo:hi]
            return out
        hi_idx, hi_val = host_leaf
        idx = np.zeros((batch, self.k), dtype=np.int32)
        val = np.zeros((batch, self.k), dtype=dtype)
        idx[: hi - lo] = hi_idx[lo:hi]
        val[: hi - lo] = hi_val[lo:hi]
        return idx, val


def default_specs(tables: CoefficientTables) -> dict[str, FeatureSpec]:
    """Dense request layout per shard, sized by the widest consumer.

    A random table's implied width (max projected feature id + 1) is a
    lower bound on the true shard width; the fixed effect's is exact.
    Features beyond a random table's implied width have no subspace
    slot, so clipping there drops only coefficients that do not exist.
    """
    dims: dict[str, int] = {}
    for t in tables.fixed.values():
        dims[t.feature_shard_id] = max(
            dims.get(t.feature_shard_id, 1), t.num_features
        )
    for t in tables.random.values():
        if t.num_entities:
            dims[t.feature_shard_id] = max(
                dims.get(t.feature_shard_id, 1), t.num_features
            )
    return {s: FeatureSpec("dense", d) for s, d in dims.items()}


def specs_from_dataset(data) -> dict[str, FeatureSpec]:
    """Request layout matching a GameDataset's shards (batch path)."""
    from photon_tpu.data.dataset import DenseFeatures, SparseFeatures

    specs: dict[str, FeatureSpec] = {}
    for name, feats in data.feature_shards.items():
        if isinstance(feats, DenseFeatures):
            specs[name] = FeatureSpec("dense", int(feats.x.shape[1]))
        elif isinstance(feats, SparseFeatures):
            specs[name] = FeatureSpec(
                "sparse", int(feats.d), k=int(feats.indices.shape[1])
            )
        else:
            raise TypeError(
                f"shard {name!r}: {type(feats).__name__} has no fixed "
                "per-row serving layout (DualEll tails span rows); "
                "score it through GameTransformer"
            )
    return specs


@dataclasses.dataclass(frozen=True)
class _Inflight:
    """One dispatched-but-unfetched rung: the device value, its rung,
    the caller's live row count, and the dispatch timestamp the ledger
    window opens at."""

    out: object
    batch: int
    n: int
    t0: float


class ScorePrograms:
    """The compiled score ladder for one model structure.

    Coefficient tables are TRACED OPERANDS: ``tables.reload`` with an
    unchanged structure needs no recompile and no rebuild here — the
    next dispatch simply passes the swapped buffers. A structure change
    (``reload`` returned False) requires constructing a fresh
    ``ScorePrograms``.
    """

    def __init__(
        self,
        tables: CoefficientTables,
        *,
        ladder: ShapeLadder | None = None,
        specs: dict[str, FeatureSpec] | None = None,
        compile_now: bool = True,
    ):
        import jax

        self.tables = tables
        self.ladder = ladder or ShapeLadder()
        # Active coordinates: an EMPTY random-effect table (a model saved
        # before any entity trained, photon-ml's partial-retrain layout)
        # contributes identically zero — it is dropped from the program
        # statically rather than gathered from a zero-row array.
        self._fe_names = tuple(tables.fixed)
        self._re_names = tuple(
            n for n, t in tables.random.items() if t.num_entities
        )
        fe_shards = [tables.fixed[n].feature_shard_id for n in self._fe_names]
        re_shards = [
            tables.random[n].feature_shard_id for n in self._re_names
        ]
        self.shard_order = tuple(dict.fromkeys(fe_shards + re_shards))
        self.retype_order = tuple(
            dict.fromkeys(
                tables.random[n].random_effect_type for n in self._re_names
            )
        )
        self.specs = dict(
            specs if specs is not None else default_specs(tables)
        )
        missing = [s for s in self.shard_order if s not in self.specs]
        if missing:
            raise ValueError(f"no FeatureSpec for shard(s) {missing}")
        if not self._fe_names and not self._re_names:
            raise ValueError("model has no active coordinates to serve")
        w0 = (
            tables.fixed[self._fe_names[0]].weights
            if self._fe_names
            else tables.random[self._re_names[0]].weights
        )
        # Request/feature payload dtype: always a numpy-native float —
        # bf16-stored TABLES narrow the gathered coefficient rows, not
        # the request payloads (the score kernels cast features to the
        # table dtype at the contraction and accumulate f32).
        self.dtype = (
            np.dtype(np.float32)
            if str(w0.dtype) == "bfloat16"
            else np.dtype(str(w0.dtype))
        )

        shard_idx = {s: i for i, s in enumerate(self.shard_order)}
        fe_feat = tuple(shard_idx[s] for s in fe_shards)
        re_feat = tuple(shard_idx[s] for s in re_shards)
        # One code vector PER RANDOM-EFFECT COORDINATE, never per
        # re_type: two coordinates may share a type while training
        # distinct entity vocabularies, so a row index is only
        # meaningful against the table whose entity_keys produced it.
        re_code = tuple(range(len(self._re_names)))
        spec_kinds = tuple(
            self.specs[s].kind for s in self.shard_order
        )
        # Fused-kernel engagement is decided ONCE, at construction (the
        # PHOTON_SERVE_KERNEL auto/force/off gate + table dtype + the
        # model's static widths): the choice is baked into the traced
        # program, so the AOT ladder, the zero-recompile contract and
        # values-only reloads behave identically on both paths — tables
        # stay traced operands.
        from photon_tpu.ops import serve_kernel as serve_kernel_mod

        self.use_kernel = serve_kernel_mod.kernel_supported(
            str(w0.dtype),
            fe_dims=tuple(
                (self.specs[s].kind, tables.fixed[n].num_features,
                 self.specs[s].k)
                for n, s in zip(self._fe_names, fe_shards)
            ),
            re_dims=tuple(
                (self.specs[s].kind, self.specs[s].d, self.specs[s].k,
                 int(tables.random[n].weights.shape[1]))
                for n, s in zip(self._re_names, re_shards)
            ),
        )

        def score_fn(fe_ws, re_ws, re_projs, feats, codes):
            import jax.numpy as jnp

            from photon_tpu.models.game import (
                _score_raw_dense,
                _score_raw_sparse,
            )
            from photon_tpu.ops import precision as precision_mod

            if self.use_kernel:
                # One fusion-boundary-free dispatch for the whole rung
                # (ops/serve_kernel.py); the per-coordinate chain below
                # stays as the fallback and the parity reference.
                return serve_kernel_mod.fused_score(
                    fe_ws, re_ws, re_projs, feats, codes,
                    spec_kinds=spec_kinds,
                    fe_feat=fe_feat,
                    re_feat=re_feat,
                )
            total = None
            for w, fi in zip(fe_ws, fe_feat):
                if spec_kinds[fi] == "dense":
                    z = precision_mod.acc_einsum(
                        "bd,d->b", feats[fi].astype(w.dtype), w
                    )
                else:
                    idx, val = feats[fi]
                    z = precision_mod.acc_sum(
                        val.astype(w.dtype) * jnp.take(w, idx), axis=-1
                    )
                total = z if total is None else total + z
            for w, proj, fi, ci in zip(re_ws, re_projs, re_feat, re_code):
                if spec_kinds[fi] == "dense":
                    z = _score_raw_dense(w, codes[ci], feats[fi], proj)
                else:
                    idx, val = feats[fi]
                    z = _score_raw_sparse(w, codes[ci], idx, val, proj)
                total = z if total is None else total + z
            if total is None:
                raise ValueError("model has no active coordinates")
            return total

        self._jitted = jax.jit(score_fn)
        self._compiled: dict[int, object] = {}
        self.stats = {
            "programs_compiled": 0,
            "aot_compile_seconds": 0.0,
            "dispatches": {int(r): 0 for r in self.ladder.rungs},
        }
        if compile_now:
            self.compile_all()

    # -- operand assembly (shared by compile, trace, and dispatch) --------

    def _table_args(self):
        t = self.tables
        # Each coordinate's table object is read ONCE so a concurrent
        # table rebuild can never pair one generation's weights with
        # another's projector within a coordinate.
        rand = [t.random[n] for n in self._re_names]
        fe_ws = tuple(t.fixed[n].weights for n in self._fe_names)
        re_ws = tuple(x.weights for x in rand)
        re_projs = tuple(x.proj for x in rand)
        return fe_ws, re_ws, re_projs

    def _sds_args(self, batch: int):
        import jax

        fe_ws, re_ws, re_projs = self._table_args()
        feats = tuple(
            self.specs[s].sds(batch, self.dtype) for s in self.shard_order
        )
        codes = tuple(
            jax.ShapeDtypeStruct((batch,), np.int32)
            for _ in self._re_names
        )
        return fe_ws, re_ws, re_projs, feats, codes

    def trace(self, batch: int):
        """Abstract trace of one rung's program — the audit entry
        (analysis/program.build_serving); the SAME operand assembly
        ``compile_rung`` lowers, so the audited jaxpr is the production
        program by construction."""
        return self._jitted.trace(*self._sds_args(batch))

    # -- compile ----------------------------------------------------------

    def compile_rung(self, batch: int):
        from photon_tpu.utils import compile_cache

        compiled = self._compiled.get(batch)
        if compiled is None:
            t0 = time.perf_counter()
            lowered = self._jitted.lower(*self._sds_args(batch))
            compiled = compile_cache.aot_compile(
                lowered, ledger_key=f"serve/score@{batch}"
            )
            self._compiled[batch] = compiled
            self.stats["programs_compiled"] += 1
            self.stats["aot_compile_seconds"] += time.perf_counter() - t0
            from photon_tpu.obs import ledger

            if ledger.enabled():
                from photon_tpu.analysis import costmodel

                # The cost thunk RE-lowers at report time rather than
                # closing over `lowered` (holding every rung's Lowered
                # alive for the server's lifetime costs more than one
                # off-path re-lower).
                ledger.register_program(
                    f"serve/score@{batch}", phase="serve",
                    cost_thunk=lambda b=batch: costmodel.program_cost(
                        self._jitted.lower(*self._sds_args(b))),
                )
        return compiled

    def compile_all(self) -> None:
        """AOT-compile every rung (server start). Warm starts hit the
        persistent compile cache; either way the request loop never
        compiles again."""
        from photon_tpu import obs

        with obs.span("serve/compile_ladder"):
            for r in self.ladder.rungs:
                self.compile_rung(r)

    # -- dispatch ---------------------------------------------------------

    def dispatch_padded(self, feats: dict, codes: dict, n: int):
        """Dispatch ``n`` stacked requests WITHOUT syncing: returns an
        in-flight handle whose device value ``fetch_padded`` pulls.

        The split exists for the queue's double-buffered staging: batch
        k+1's host pack runs while batch k is in flight, and the
        ledger's measured device window must exclude that overlapped
        host time (``fetch_padded(exclude_seconds=...)``) or staging
        would silently inflate ``vs_roofline`` on the serve rows.
        Operand validation and assembly happen HERE, before the timing
        window opens.
        """
        if not feats and not codes:
            raise ValueError("score dispatch needs at least one operand")
        some = next(iter(feats.values())) if feats else None
        batch = (
            some.shape[0]
            if isinstance(some, np.ndarray)
            else some[0].shape[0]
            if some is not None
            else next(iter(codes.values())).shape[0]
        )
        if batch not in self._compiled:
            raise ValueError(
                f"batch {batch} is not a compiled rung "
                f"{self.ladder.rungs}; pad with FeatureSpec.stack first"
            )
        fe_ws, re_ws, re_projs = self._table_args()
        f = tuple(feats[s] for s in self.shard_order)
        c = tuple(
            np.asarray(codes[nm], dtype=np.int32) for nm in self._re_names
        )
        t0 = time.perf_counter()
        out = self._compiled[batch](fe_ws, re_ws, re_projs, f, c)
        self.stats["dispatches"][batch] += 1
        return _Inflight(out=out, batch=batch, n=n, t0=t0)

    def fetch_padded(
        self, handle: "_Inflight", *, exclude_seconds: float = 0.0
    ) -> np.ndarray:
        """Block on an in-flight dispatch; returns the first ``n``
        scores as numpy (the fetch is the one host sync of the request
        path).

        ``exclude_seconds`` is host time the CALLER spent between
        dispatch and fetch on work that was overlapped with the device
        (the queue's staging pack): it is subtracted from the ledger's
        measured window so the booked seconds stay device execution,
        not an enqueue-to-fetch wall span.
        """
        scores = np.asarray(handle.out)
        t1 = time.perf_counter()
        from photon_tpu.obs import ledger

        if ledger.enabled():
            seconds = max(
                (t1 - handle.t0) - max(exclude_seconds, 0.0), 0.0
            )
            ledger.record_dispatch(
                f"serve/score@{handle.batch}", seconds, phase="serve",
                start=handle.t0, end=t1,
            )
        return scores[: handle.n]

    def score_padded(self, feats: dict, codes: dict, n: int) -> np.ndarray:
        """Score ``n`` requests already stacked per shard/coordinate.

        ``feats[shard]`` is the spec's stacked leaf at some rung batch;
        ``codes[coordinate]`` the matching [rung] int32 row-code vector
        for that random-effect coordinate's OWN table. Serial
        dispatch + fetch (the batch-scoring path and the fallback for
        duck-typed program objects without the split API).
        """
        return self.fetch_padded(self.dispatch_padded(feats, codes, n))

    def pack_requests(
        self, requests: list[tuple[dict, dict]]
    ) -> tuple[dict, dict, int]:
        """Stack [(features, entity_ids)] into padded rung operands.

        Returns (feats, codes, rung). Cold entities (and padding rows)
        get code -1 — fixed-effect-only scores.
        """
        n = len(requests)
        rung = self.ladder.rung_for(n)
        feats = {
            s: self.specs[s].stack(
                [r[0][s] for r in requests], rung, self.dtype
            )
            for s in self.shard_order
        }
        codes = {}
        for nm in self._re_names:
            table = self.tables.random[nm]
            rt = table.random_effect_type
            vec = np.full(rung, -1, dtype=np.int32)
            for i, (_, ids) in enumerate(requests):
                vec[i] = table.code_for(ids.get(rt, ""))
            codes[nm] = vec
        return feats, codes, rung

    # -- dataset batch path ----------------------------------------------

    def score_dataset(self, data) -> np.ndarray:
        """Score a whole GameDataset through the ladder (the batch-
        scoring route of ``cli/score.py`` — one scoring implementation
        for online and offline).
        """
        from photon_tpu.data.dataset import DenseFeatures
        from photon_tpu.data.random_effect import scoring_codes

        n = data.num_samples
        plan = self.ladder.chunk_plan(n)
        # Compile only the rungs this dataset's plan dispatches: a
        # 100-row file must not pay the top rung's compile (batch
        # callers construct with compile_now=False for exactly this).
        for rung in sorted({r for _, _, r in plan}):
            self.compile_rung(rung)
        host: dict[str, object] = {}
        for s in self.shard_order:
            feats = data.feature_shards[s]
            if isinstance(feats, DenseFeatures):
                host[s] = np.asarray(feats.x)
            else:
                host[s] = (
                    np.asarray(feats.indices),
                    np.asarray(feats.values),
                )
        full_codes: dict[str, np.ndarray] = {}
        for nm in self._re_names:
            table = self.tables.random[nm]
            full_codes[nm] = scoring_codes(
                data, table.random_effect_type, table.entity_keys
            ).astype(np.int32)
        out = np.zeros(n, dtype=self.dtype)
        for lo, hi, rung in plan:
            feats = {
                s: self.specs[s].slice_rows(
                    host[s], lo, hi, rung, self.dtype
                )
                for s in self.shard_order
            }
            codes = {}
            for nm, fc in full_codes.items():
                vec = np.full(rung, -1, dtype=np.int32)
                vec[: hi - lo] = fc[lo:hi]
                codes[nm] = vec
            out[lo:hi] = self.score_padded(feats, codes, hi - lo)
        return out
