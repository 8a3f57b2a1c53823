"""HBM-resident coefficient tables for online scoring.

A trained ``GameModel`` holds sub-models in training-time layout; serving
needs them as *lookup tables*: one dense weight vector per fixed-effect
coordinate and, per random-effect coordinate, the padded ``[E, S]``
coefficient matrix next to its ``[E, S]`` projector (original feature id
per subspace slot) on device plus a HOST map entity key -> row index.
Scoring is then pure index arithmetic against resident arrays — the same
fused kernels batch scoring uses (``models/game._score_raw_dense`` /
``_score_raw_sparse``), so online and batch scores agree by construction.

Cold entities (keys absent from the map) get code -1, which the kernels
mask to a zero random-effect contribution: the request still scores
through the fixed effect — photon-ml's left-join-with-no-match semantics.

``reload`` swaps a refreshed model into the live tables without a
recompile (coefficient arrays are traced operands, audited by the
tier-2 ``serving`` contract): the default is a reference swap that is
safe against live dispatch (in-flight batches pin the old generation),
``donate=True`` writes the new values into the OLD buffers' HBM via a
donating jitted copy for memory-constrained QUIESCED reloads; a
structure change (new entities, new coordinates) rebuilds the tables
and the caller must rebuild its programs — ``rebuild_from`` does both
in one move (new tables + new AOT ladder off-path, swap under a
caller-supplied quiesce), which is how the pilot's structure-changing
promotions and ``MicroBatchQueue.reload_model`` stay zero-downtime.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

from photon_tpu.data.index_map import IndexMap
from photon_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_tpu.types import TaskType, make_feature_key

# Memory contract (audited by `python -m photon_tpu.analysis --memory`,
# machinery in analysis/memory.py): byte-exact resident formulas for the
# built tables — a fixed coordinate is its [d] weight vector at storage
# width, a random coordinate its [E,S] weights at storage width plus the
# [E,S] int32 projector (the projector never narrows under bf16) — each
# priced against the BUILT device arrays at f32 AND bf16 and against the
# admission oracle (analysis/memory.predict_resident_bytes). A
# structure-changing ``rebuild_from`` builds the next generation
# off-path while the old one serves, so its double-residency window is a
# declared transient allowance, not an accident.
MEMORY_AUDIT = dict(
    name="tables-memory",
    entry="serve.tables.CoefficientTables",
    builder="build_tables_memory",
    resident={
        "table/global": "d * wbytes",
        "table/per-user": "e * s * (wbytes + 4)",
    },
    transients={
        "rebuild_from": "2 * (d * wbytes + e * s * (wbytes + 4))",
    },
    donations={"serve.tables._swap_values": (0,)},
    tolerance=1.5,
)

_swap_cache: dict[tuple, object] = {}


def _swap_values(prev, new):
    """The donating swap body: select the new values INTO the old
    buffer. The select (rather than returning ``new`` outright) keeps
    ``prev`` in the dataflow so the donation can alias the output into
    its buffer — with an identity body jax finds no output to alias the
    donated operand to and drops the donation silently, leaving both
    generations resident (the exact failure analysis/memory.py's
    donation audit exists to catch; it probes THIS function)."""
    import jax.numpy as jnp

    return jnp.where(True, new, prev)


def _device_swap(old, new_host: np.ndarray):
    """Replace ``old``'s values with ``new_host``, donating ``old``.

    The donated input lets XLA alias the output into the old buffer's
    HBM — the reload writes the fresh coefficients into the memory the
    serving programs already read, instead of holding both generations
    resident while the transfer drains. Donation marks ``old`` deleted,
    so this path requires the serving queue QUIESCED (see
    ``CoefficientTables.reload(donate=True)``). CPU backends skip
    donation (the backend would warn on every call)."""
    import jax

    key = (tuple(old.shape), str(old.dtype))
    fn = _swap_cache.get(key)
    if fn is None:
        donate = (0,) if jax.default_backend() not in ("cpu",) else ()
        fn = jax.jit(_swap_values, donate_argnums=donate)
        _swap_cache[key] = fn
    return fn(old, new_host)


@dataclasses.dataclass
class FixedTable:
    """One fixed-effect coordinate: the dense [d] weight vector."""

    name: str
    feature_shard_id: str
    task: TaskType
    weights: object  # jax.Array [d]

    @property
    def num_features(self) -> int:
        return int(self.weights.shape[0])


@dataclasses.dataclass
class RandomTable:
    """One random-effect coordinate: padded per-entity coefficients."""

    name: str
    random_effect_type: str
    feature_shard_id: str
    task: TaskType
    weights: object  # jax.Array [E, S]
    proj: object  # jax.Array [E, S] int32, -1 pad
    entity_keys: tuple  # row i <-> entity_keys[i]
    entity_rows: dict  # str key -> row index (host map)

    @property
    def num_entities(self) -> int:
        return int(self.weights.shape[0])

    @property
    def num_features(self) -> int:
        """Original-space feature dim the projector can address. The
        model alone does not record the shard width, so this is the
        tightest bound the projector implies (features beyond it can
        never contribute — their slots do not exist)."""
        p = np.asarray(self.proj)
        return int(p.max(initial=-1)) + 1 if p.size else 1

    def code_for(self, key) -> int:
        """Row index for an entity key; -1 = cold (fixed-effect-only)."""
        row = self.entity_rows.get(str(key))
        return -1 if row is None else row


@dataclasses.dataclass
class CoefficientTables:
    """Device-resident serving state for one GameModel."""

    fixed: dict[str, FixedTable]
    random: dict[str, RandomTable]
    task: TaskType
    # Monotone model-reload counter: 0 at construction, +1 per reload
    # (in-place swap or rebuild). Surfaced by the serve queue's
    # ``health()`` so an operator can confirm which coefficient
    # generation is live without comparing arrays.
    generation: int = 0
    # Serving precision (ops/precision.py): "bfloat16" stores the
    # coefficient tables at half width — the score programs read bf16
    # and accumulate f32 (models/game.py acc_* helpers). Reloads build
    # the candidate generation at the SAME precision, so a values-only
    # refresh keeps dtypes (and with them the zero-recompile contract).
    precision: str = "float32"

    @property
    def coordinate_order(self) -> tuple[str, ...]:
        """Stable coordinate order (model iteration order) shared with
        the score-program operand layout."""
        return tuple(self.fixed) + tuple(self.random)

    @property
    def retype_order(self) -> tuple[str, ...]:
        """Distinct random-effect types in first-appearance order — one
        REQUEST entity id per type. (Row codes are per COORDINATE, not
        per type: coordinates sharing a type may hold distinct entity
        vocabularies, so each table resolves its own code.)"""
        seen: list[str] = []
        for t in self.random.values():
            if t.random_effect_type not in seen:
                seen.append(t.random_effect_type)
        return tuple(seen)

    def coordinate_stats(self) -> dict:
        """Per-coordinate shape/vocabulary facts for the monitoring and
        readiness surfaces (``cli.serve --monitor-port``'s ``/readyz``
        detail, the bench JSON): which coordinates are live, how many
        entities each random table can resolve, and the generation —
        enough to see a mis-sized vocabulary without pulling arrays."""
        return {
            "generation": self.generation,
            "fixed": {
                n: {"features": t.num_features}
                for n, t in self.fixed.items()
            },
            "random": {
                n: {
                    "entities": t.num_entities,
                    "re_type": t.random_effect_type,
                    "sub_dim": int(t.weights.shape[1]),
                }
                for n, t in self.random.items()
            },
        }

    def codes_for(self, entity_ids: dict) -> dict[str, int]:
        """Per-COORDINATE row codes for one request (-1 = cold); the
        request's entity id is keyed by the coordinate's re_type."""
        return {
            name: t.code_for(entity_ids.get(t.random_effect_type, ""))
            for name, t in self.random.items()
        }

    @staticmethod
    def from_game_model(
        model: GameModel, precision: str = "float32"
    ) -> "CoefficientTables":
        import jax
        import jax.numpy as jnp

        from photon_tpu.ops import precision as precision_mod

        resolved = precision_mod.resolve(precision)

        def put(arr):
            # bf16 table storage (serving mixed precision): half the
            # resident HBM and half the gather width per request; the
            # score kernels accumulate f32 (models/game.py).
            # The table OWNS its buffer (copy=True): a device-resident
            # f32 coefficient array would otherwise be aliased, and a
            # donating reload (``_device_swap``, which donates only off
            # the CPU) would delete the caller's model from under it.
            return precision_mod.in_storage(
                jnp.array(arr, copy=True), resolved
            )

        fixed: dict[str, FixedTable] = {}
        random: dict[str, RandomTable] = {}
        for name, sub in model.items():
            if isinstance(sub, FixedEffectModel):
                fixed[name] = FixedTable(
                    name=name,
                    feature_shard_id=sub.feature_shard_id,
                    task=sub.task,
                    weights=put(sub.model.coefficients.means),
                )
            elif isinstance(sub, RandomEffectModel):
                keys = tuple(str(k) for k in sub.entity_keys)
                random[name] = RandomTable(
                    name=name,
                    random_effect_type=sub.random_effect_type,
                    feature_shard_id=sub.feature_shard_id,
                    task=sub.task,
                    weights=put(sub.coefficients),
                    proj=jax.device_put(
                        jnp.asarray(
                            np.asarray(sub.proj_all).astype(np.int32)
                        )
                    ),
                    entity_keys=keys,
                    entity_rows={k: i for i, k in enumerate(keys)},
                )
            else:
                raise TypeError(f"unknown sub-model type for {name!r}")
        tables = CoefficientTables(
            fixed=fixed, random=random, task=model.task,
            precision=resolved,
        )
        tables.account_resident()
        return tables

    def account_resident(self) -> None:
        """Book every table's device bytes into the cost ledger's HBM
        account (owner ``table/<coordinate>``; obs/ledger.py) — one
        flag check when the ledger is disabled. Called at build and
        after every reload, so the ledger's per-table resident bytes
        and peak watermark track the serving footprint (including the
        transient double-residency of an off-path rebuild)."""
        from photon_tpu.obs import ledger

        if not ledger.enabled():
            return
        for n, t in self.fixed.items():
            ledger.set_resident(
                f"table/{n}", ledger.tree_nbytes(t.weights)
            )
        for n, t in self.random.items():
            ledger.set_resident(
                f"table/{n}", ledger.tree_nbytes((t.weights, t.proj))
            )

    def structure_key(self) -> tuple:
        """Everything a score program specializes on: coordinate names,
        kinds, shard wiring, and array shapes/dtypes. Two models with
        equal keys serve through the SAME compiled ladder."""
        fe = tuple(
            (n, t.feature_shard_id, tuple(t.weights.shape),
             str(t.weights.dtype))
            for n, t in self.fixed.items()
        )
        re = tuple(
            (n, t.random_effect_type, t.feature_shard_id,
             tuple(t.weights.shape), str(t.weights.dtype))
            for n, t in self.random.items()
        )
        return (fe, re)

    def _values_only_delta(self, new: "CoefficientTables") -> bool:
        """True when ``new`` differs from the live tables ONLY in
        coefficient VALUES — same structure, same projectors, same
        entity vocabularies. That is the condition under which a live
        swap cannot tear: row codes stay valid across generations and
        weights are the single changing operand (each reference
        assignment is atomic)."""
        if new.structure_key() != self.structure_key():
            return False
        for name, t in self.random.items():
            src = new.random[name]
            if src.entity_keys != t.entity_keys:
                return False
            if not np.array_equal(
                np.asarray(src.proj), np.asarray(t.proj)
            ):
                return False
        return True

    def reload(self, model: GameModel, *, donate: bool = False) -> bool:
        """Swap a refreshed model's coefficients into the live tables.

        Returns True for a VALUES-ONLY refresh (same coordinates,
        shapes, dtype, projectors, and entity vocabularies — the
        daily-retrain case): each weight reference flips to the new
        generation's device array and every compiled score program
        keeps serving, since coefficients are traced operands. This
        swap is safe AGAINST LIVE DISPATCH: an in-flight batch pins the
        old buffers through its own references, row codes mean the same
        thing in both generations (vocabularies are identical), and a
        batch dispatched mid-swap at worst mixes generations ACROSS
        coordinates for that one batch.

        ``donate=True`` additionally routes each new weights array
        through a donating jitted copy so XLA may write it into the OLD
        buffer's HBM — use it for memory-constrained reloads, and ONLY
        with the queue quiesced (``close()`` or between drives):
        donation marks the old buffer deleted, which would poison a
        concurrently dispatched batch.

        Returns False for anything else — entity vocabulary or
        projector changed, coordinates added/removed, shapes/dtype
        moved: the tables are rebuilt wholesale, which is NOT safe
        under live dispatch (quiesce first), and the caller must
        rebuild its score programs if shapes changed.
        """
        return self._reload_built(
            CoefficientTables.from_game_model(model, self.precision),
            donate=donate,
        )

    def _reload_built(
        self, new: "CoefficientTables", *, donate: bool = False
    ) -> bool:
        """``reload`` against an ALREADY-BUILT new-generation tables
        object — callers that needed the structure answer before
        deciding how to swap (``MicroBatchQueue.reload_model``) avoid a
        second ``from_game_model`` device upload."""
        self.generation += 1
        if not self._values_only_delta(new):
            self.fixed = new.fixed
            self.random = new.random
            self.task = new.task
            self.account_resident()
            return False

        def swap(old, src):
            if donate:
                return _device_swap(old, np.asarray(src))
            return src

        for name, t in self.fixed.items():
            src = new.fixed[name]
            t.weights = swap(t.weights, src.weights)
            t.task = src.task
        for name, t in self.random.items():
            src = new.random[name]
            t.weights = swap(t.weights, src.weights)
            t.task = src.task
        self.task = new.task
        self.account_resident()
        return True

    def rebuild_from(
        self,
        model: GameModel,
        *,
        programs=None,
        quiesce=None,
        adopt=None,
        prebuilt: "CoefficientTables | None" = None,
    ):
        """Structure-changing reload, fully orchestrated.

        ``reload()`` returning False used to leave callers to rebuild
        the score ladder by hand; this does the whole dance: the new
        generation's tables — and, when ``programs`` (the live
        ``ScorePrograms``) is given, a freshly AOT-compiled ladder with
        the same rungs — are built OFF-PATH while the old generation
        keeps serving, then the swap happens inside ``quiesce`` (a
        context-manager factory, e.g. ``MicroBatchQueue.quiesce`` —
        None means the caller guarantees no live dispatch). ``adopt``,
        when given, is called with the new ``ScorePrograms`` INSIDE the
        quiesce window so a dispatch loop can rebind its program
        reference before traffic resumes (``reload_model`` wires it).

        A values-only delta short-circuits to the in-place ``reload``
        swap (no quiesce taken, no programs built) and returns None;
        otherwise returns the new ``ScorePrograms`` (or None when
        ``programs`` was None), rebound to THIS tables object so future
        dispatches read the live generation.
        """
        import contextlib

        new = (
            prebuilt if prebuilt is not None
            else CoefficientTables.from_game_model(model, self.precision)
        )
        if self._values_only_delta(new):
            self._reload_built(new)
            return None
        new_programs = None
        if programs is not None:
            from photon_tpu.serve.programs import ScorePrograms

            # Compile against the new generation's shapes while the old
            # ladder keeps dispatching — the expensive step stays off
            # the serving path.
            new_programs = ScorePrograms(new, ladder=programs.ladder)
        ctx = quiesce() if quiesce is not None else contextlib.nullcontext()
        with ctx:
            self.generation += 1
            self.fixed = new.fixed
            self.random = new.random
            self.task = new.task
            if new_programs is not None:
                # Rebind to the LIVE tables object: the swapped dicts
                # are the very ones the new ladder was compiled
                # against, so operand shapes cannot disagree.
                new_programs.tables = self
            if adopt is not None:
                adopt(new_programs)
        # Outside the quiesce window (host metadata only — the swap
        # pause must stay minimal): re-book the new generation's
        # footprint.
        self.account_resident()
        return new_programs


def build_index_maps_from_model(model_dir: str) -> dict[str, IndexMap]:
    """Per-shard index maps recovered from a saved model's own records.

    A standalone serving process has no training dataset to build index
    maps from; the model directory itself names every feature the model
    can use (each BayesianLinearModelAvro record keys coefficients by
    (name, term)). The union of keys per feature shard, sorted, is a
    complete and deterministic serving-side map — features the model
    never weighted are absent, which is harmless: their coefficient is
    zero either way.
    """
    from photon_tpu.io import avro
    from photon_tpu.io.model_io import COEFFICIENTS, ID_INFO

    shard_keys: dict[str, set] = {}
    for kind in ("fixed-effect", "random-effect"):
        base = os.path.join(model_dir, kind)
        if not os.path.isdir(base):
            continue
        for name in sorted(os.listdir(base)):
            info = os.path.join(base, name, ID_INFO)
            with open(info) as f:
                shard = f.read().strip().splitlines()[-1]
            keys = shard_keys.setdefault(shard, set())
            coef_dir = os.path.join(base, name, COEFFICIENTS)
            if not os.path.isdir(coef_dir):
                continue
            for rec in avro.read_container_dir(coef_dir):
                for ntv in rec["means"]:
                    keys.add(make_feature_key(ntv["name"], ntv["term"]))
                for ntv in rec.get("variances") or ():
                    keys.add(make_feature_key(ntv["name"], ntv["term"]))
    return {
        shard: IndexMap({k: i for i, k in enumerate(sorted(keys))})
        for shard, keys in shard_keys.items()
    }
