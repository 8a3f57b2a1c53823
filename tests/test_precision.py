"""Mixed-precision (ops/precision.py) — policy units and numerical
parity of the bf16 fused fit against the f32 reference on all four GLM
families, plus the serving precision path and the entity-bucket batching
knob that ride the same PR.

Tolerances here are the DOCUMENTED contract (PERFORMANCE.md): bf16
stores ~8 mantissa bits, so coefficient tables agree to ~1e-2 relative
and per-row scores to ~5e-2 absolute at unit scale. The hinge family
upcasts its vmapped solver (no batched-Newton path), so only score/
residual storage rounds there.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu import optim
from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
from photon_tpu.data.dataset import DenseFeatures
from photon_tpu.data.game_data import make_game_dataset
from photon_tpu.data.random_effect import (
    DEFAULT_BUCKET_CAPS,
    RandomEffectDataConfiguration,
    _assign_buckets,
    build_random_effect_dataset,
    predict_plan_shapes,
)
from photon_tpu.estimators.game_estimator import (
    FixedEffectCoordinateConfiguration,
    GameEstimator,
    RandomEffectCoordinateConfiguration,
)
from photon_tpu.ops import precision as px
from photon_tpu.types import TaskType


class TestPolicy:
    def test_resolve_aliases(self):
        assert px.resolve(None) == "float32"
        assert px.resolve("f32") == "float32"
        assert px.resolve("bf16") == "bfloat16"
        assert px.resolve("BFLOAT16") == "bfloat16"
        with pytest.raises(ValueError, match="unknown precision"):
            px.resolve("float16")

    def test_storage_and_cast(self):
        x = jnp.ones(4, jnp.float32)
        assert px.in_storage(x, "float32") is x
        assert px.in_storage(x, "bfloat16").dtype == jnp.bfloat16
        ids = jnp.ones(4, jnp.int32)
        assert px.in_storage(ids, "bfloat16") is ids  # non-float: kept

    def test_acc_einsum_accumulates_f32_on_bf16(self):
        a = jnp.ones((3, 5), jnp.bfloat16)
        b = jnp.ones(5, jnp.bfloat16)
        out = px.acc_einsum("rs,s->r", a, b)
        assert out.dtype == jnp.float32
        # f32 path is the PLAIN einsum (identical program/result dtype)
        out32 = px.acc_einsum(
            "rs,s->r", a.astype(jnp.float32), b.astype(jnp.float32))
        assert out32.dtype == jnp.float32

    def test_acc_sum_bf16_accumulates_f32(self):
        # 4096 ones: a bf16 accumulator stalls once the partial sum
        # outgrows the increment's 8 mantissa bits (backend-dependent —
        # some CPUs upcast reduces internally, TPUs do not, which is
        # exactly why the invariant is spelled explicitly).
        x = jnp.ones(4096, jnp.bfloat16)
        out = px.acc_sum(x)
        assert out.dtype == jnp.float32
        assert float(out) == 4096.0
        # f32 operands take the PLAIN sum (dtype preserved, no convert)
        assert px.acc_sum(jnp.ones(8, jnp.float32)).dtype == jnp.float32

    def test_like_storage(self):
        ref16 = jnp.ones(2, jnp.bfloat16)
        ref32 = jnp.ones(2, jnp.float32)
        x = jnp.ones(2, jnp.float32)
        assert px.like_storage(x, ref16).dtype == jnp.bfloat16
        assert px.like_storage(x, ref32) is x


def _l2(w):
    return GLMOptimizationConfiguration(
        regularization=optim.RegularizationContext(
            optim.RegularizationType.L2
        ),
        regularization_weight=w,
    )


def _workload(task: TaskType, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
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
        id_tags={"userId": uid}, dtype=dtype,
    )


def _fit(task, data, precision):
    est = GameEstimator(
        task,
        {
            "global": FixedEffectCoordinateConfiguration("g", _l2(1e-2)),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "u"), _l2(1.0)
            ),
        },
        num_iterations=2,
        mesh="off",
        precision=precision,
    )
    result = est.fit(data)[0]
    return est, result.model


def _rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(float(np.abs(b).max()), 1e-9)
    return float(np.abs(a - b).max()) / scale


# The documented per-family tolerance table (PERFORMANCE.md): max
# relative coefficient error of the bf16 fused fit vs the f32 reference.
FAMILY_RTOL = {
    TaskType.LINEAR_REGRESSION: 2e-2,
    TaskType.LOGISTIC_REGRESSION: 2e-2,
    TaskType.POISSON_REGRESSION: 3e-2,
    TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM: 2e-2,
}


class TestBf16Parity:
    @pytest.mark.parametrize(
        "task", sorted(FAMILY_RTOL, key=lambda t: t.name),
        ids=lambda t: t.name.lower(),
    )
    def test_fused_fit_parity(self, task):
        data = _workload(task)
        est32, m32 = _fit(task, data, "float32")
        est16, m16 = _fit(task, data, "bfloat16")
        # Both runs rode the FUSED whole-fit path (the parity claim is
        # about the fused programs, not a silent unfused fallback).
        assert est32._fused_cache and est16._fused_cache
        rtol = FAMILY_RTOL[task]
        fe_err = _rel_err(
            m16.models["global"].model.coefficients.means,
            m32.models["global"].model.coefficients.means,
        )
        re_err = _rel_err(
            m16.models["per-user"].coefficients,
            m32.models["per-user"].coefficients,
        )
        assert fe_err <= rtol, (task, fe_err)
        assert re_err <= rtol, (task, re_err)

    def test_score_quantization_is_idempotent_against_storage(self):
        # The residual-drift guard (review finding): the f32 total must
        # accumulate values that round-trip EXACTLY through the bf16
        # carry storage — bf16(f32(bf16(z))) == bf16(z) — so a
        # converged coordinate's `total - read(store(z))` is exactly 0
        # every sweep instead of leaking one rounding per iteration.
        from photon_tpu.algorithm.fused_fit import FusedFit

        rng = np.random.default_rng(0)
        z = jnp.asarray(rng.normal(size=512).astype(np.float32))
        q = FusedFit._quantize_score
        f = type("F", (), {"precision": "bfloat16",
                           "_quantize_score": q})()
        zq = f._quantize_score(z)
        # idempotent: storing the quantized value loses nothing more
        np.testing.assert_array_equal(
            np.asarray(zq),
            np.asarray(zq.astype(jnp.bfloat16).astype(jnp.float32)),
        )
        # and the f32 path is the SAME OBJECT (no trace perturbation)
        f32 = type("F", (), {"precision": "float32",
                             "_quantize_score": q})()
        assert f32._quantize_score(z) is z

    def test_warm_start_reenters_same_program(self):
        # bf16 warm start must reuse the bf16 executables — λ-grid-style
        # re-entry, zero extra fused cache keys.
        data = _workload(TaskType.LOGISTIC_REGRESSION)
        est, model = _fit(TaskType.LOGISTIC_REGRESSION, data, "bf16")
        keys_before = set(est._fused_cache)
        est.fit(data, initial_model=model)
        assert set(est._fused_cache) == keys_before


class TestStaticKey:
    def test_precision_is_a_recompile_key(self):
        from photon_tpu.algorithm.fused_fit import fused_static_key

        data = _workload(TaskType.LINEAR_REGRESSION)
        est, _ = _fit(TaskType.LINEAR_REGRESSION, data, "float32")
        datasets, _ = est.prepare(data)
        coords = est._build_coordinates(
            datasets, {}, {}, logical_rows=data.num_samples)
        k32 = fused_static_key(coords, est.update_sequence, 2, set(),
                               "float32")
        k16 = fused_static_key(coords, est.update_sequence, 2, set(),
                               "bfloat16")
        assert k32 != k16
        # aliases collapse — "bf16" and "bfloat16" must share a key
        k16b = fused_static_key(coords, est.update_sequence, 2, set(),
                                "bf16")
        assert k16 == k16b


class TestServingPrecision:
    def _model(self, seed=0):
        from photon_tpu.models.game import (
            FixedEffectModel, GameModel, RandomEffectModel,
        )
        from photon_tpu.models.glm import (
            Coefficients, GeneralizedLinearModel,
        )

        rng = np.random.default_rng(seed)
        e, s, d = 30, 4, 6
        return GameModel({
            "global": FixedEffectModel(
                GeneralizedLinearModel(
                    Coefficients(means=jnp.asarray(
                        rng.normal(size=d).astype(np.float32))),
                    TaskType.LOGISTIC_REGRESSION,
                ), "g",
            ),
            "per-user": RandomEffectModel(
                coefficients=jnp.asarray(
                    rng.normal(size=(e, s)).astype(np.float32)),
                random_effect_type="userId",
                feature_shard_id="u",
                task=TaskType.LOGISTIC_REGRESSION,
                proj_all=np.tile(np.arange(s), (e, 1)).astype(np.int64),
                entity_keys=tuple(str(i) for i in range(e)),
            ),
        })

    def test_bf16_tables_score_close_to_f32(self):
        from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
        from photon_tpu.serve.tables import CoefficientTables

        model = self._model()
        t32 = CoefficientTables.from_game_model(model)
        t16 = CoefficientTables.from_game_model(model, "bfloat16")
        assert str(
            t16.random["per-user"].weights.dtype) == "bfloat16"
        p32 = ScorePrograms(t32, ladder=ShapeLadder((4,)))
        p16 = ScorePrograms(t16, ladder=ShapeLadder((4,)))
        assert p16.dtype == np.float32  # request payloads stay f32
        rng = np.random.default_rng(1)
        reqs = [
            ({"g": rng.normal(size=6).astype(np.float32),
              "u": rng.normal(size=4).astype(np.float32)},
             {"userId": str(i)})
            for i in range(4)
        ]
        f32_scores = p32.score_padded(*p32.pack_requests(reqs)[:2], 4)
        f16_scores = p16.score_padded(*p16.pack_requests(reqs)[:2], 4)
        np.testing.assert_allclose(
            f16_scores, f32_scores, atol=5e-2, rtol=5e-2)

    def test_values_only_reload_preserves_precision_and_programs(self):
        from photon_tpu.serve.programs import ScorePrograms, ShapeLadder
        from photon_tpu.serve.tables import CoefficientTables
        from photon_tpu.utils import compile_event_count

        t16 = CoefficientTables.from_game_model(self._model(), "bf16")
        programs = ScorePrograms(t16, ladder=ShapeLadder((1, 4)))
        before = compile_event_count()
        # An f32-trained refreshed model reloads into bf16 tables
        # VALUES-ONLY: the candidate is built at the live precision.
        assert t16.reload(self._model(seed=9)) is True
        assert str(
            t16.random["per-user"].weights.dtype) == "bfloat16"
        rng = np.random.default_rng(2)
        reqs = [
            ({"g": rng.normal(size=6).astype(np.float32),
              "u": rng.normal(size=4).astype(np.float32)},
             {"userId": "3"})
        ]
        programs.score_padded(*programs.pack_requests(reqs)[:2], 1)
        assert compile_event_count() - before == 0

    def test_structure_key_separates_precisions(self):
        from photon_tpu.serve.tables import CoefficientTables

        t32 = CoefficientTables.from_game_model(self._model())
        t16 = CoefficientTables.from_game_model(self._model(), "bf16")
        assert t32.structure_key() != t16.structure_key()


class TestBucketBatching:
    def test_merge_off_by_default(self):
        counts = np.asarray([3, 10, 10, 100, 2000])
        active = np.ones(5, bool)
        out = _assign_buckets(counts, active, (16, 64, 256, 1024, 4096))
        assert sorted(out) == [16, 256, 4096]

    def test_tail_buckets_merge_upward(self):
        counts = np.asarray([3, 10, 10, 100, 2000])
        active = np.ones(5, bool)
        out = _assign_buckets(
            counts, active, (16, 64, 256, 1024, 4096),
            min_bucket_entities=4,
        )
        # the 16-cap tail (3 entities) rides into the 256 bucket, which
        # then meets the floor (4); the largest bucket never merges.
        assert sorted(out) == [256, 4096]
        assert sorted(out[256].tolist()) == [0, 1, 2, 3]
        # a floor above every intermediate bucket cascades all the way
        out5 = _assign_buckets(
            counts, active, (16, 64, 256, 1024, 4096),
            min_bucket_entities=5,
        )
        assert sorted(out5) == [4096]
        assert sorted(out5[4096].tolist()) == [0, 1, 2, 3, 4]

    def test_merge_never_drops_and_respects_floor(self):
        rng = np.random.default_rng(0)
        counts = rng.integers(1, 5000, 200)
        active = rng.uniform(size=200) < 0.8
        base = _assign_buckets(counts, active, (16, 64, 256, 1024, 4096))
        merged = _assign_buckets(
            counts, active, (16, 64, 256, 1024, 4096),
            min_bucket_entities=20,
        )
        all_base = np.sort(np.concatenate(list(base.values())))
        all_merged = np.sort(np.concatenate(list(merged.values())))
        np.testing.assert_array_equal(all_base, all_merged)
        assert len(merged) <= len(base)
        # every bucket except possibly the largest meets the floor
        for cap in sorted(merged)[:-1]:
            assert merged[cap].size >= 20
        # members never exceed their bucket's row cap
        for cap, ids in merged.items():
            assert counts[ids].max(initial=0) <= cap

    # (data dtype, the fused fit's row order, rtol, atol). Merging only
    # widens padding, and padded rows carry weight 0: the same optimum.
    #  - float32 in the canonical order, PR 33's parent to the letter:
    #    the fixed effect reads the same rows in the same order under both
    #    plans, so the two fits differ by the slabs' padding alone.
    #  - float64 in home order: the two plans give the fit two row orders
    #    (its rows stand in the home coordinate's entity order), which
    #    changes the order of the fixed effect's sums and nothing else.
    #  - float32 in home order: the L-BFGS stops where a float32 loss no
    #    longer falls (after 4 + 3 and 4 + 2 iterations here), so two
    #    summation orders leave the fixed effect 5.6e-4 apart and the
    #    users' tables 7.4e-4 (max abs; read on the CPU, PR 33). Held
    #    under 2e-3: a bound on float32 rounding through an early stop,
    #    not a precision this test claims.
    PARITY_CASES = {
        "float32_canonical_order": (jnp.float32, False, 1e-4, 1e-5),
        "float64_home_order": (jnp.float64, True, 1e-4, 1e-5),
        "float32_home_order": (jnp.float32, True, 0.0, 2e-3),
    }

    @pytest.mark.parametrize("case", sorted(PARITY_CASES))
    def test_estimator_parity_with_merging(self, case, monkeypatch):
        from photon_tpu.algorithm.fused_fit import FusedFit

        dtype, home_order, rtol, atol = self.PARITY_CASES[case]
        if not home_order:
            monkeypatch.setattr(FusedFit, "_choose_home", lambda *a: None)
        data = _workload(TaskType.LOGISTIC_REGRESSION, dtype=dtype)

        def fit(min_bucket):
            est = GameEstimator(
                TaskType.LOGISTIC_REGRESSION,
                {
                    "global": FixedEffectCoordinateConfiguration(
                        "g", _l2(1e-2)),
                    "per-user": RandomEffectCoordinateConfiguration(
                        RandomEffectDataConfiguration(
                            "userId", "u",
                            min_bucket_entities=min_bucket,
                        ),
                        _l2(1.0),
                    ),
                },
                num_iterations=2,
                mesh="off",
            )
            datasets, _ = est.prepare(data)
            n_blocks = len(datasets["per-user"].blocks)
            model = est.fit(data)[0].model
            (fused,) = est._fused_cache.values()
            assert (fused._home == "per-user") == home_order
            return model, n_blocks

        m_base, blocks_base = fit(0)
        m_merged, blocks_merged = fit(10_000)
        assert blocks_merged <= blocks_base
        assert blocks_merged == 1  # floor above every bucket: one slab
        np.testing.assert_allclose(
            np.asarray(m_merged.models["per-user"].coefficients),
            np.asarray(m_base.models["per-user"].coefficients),
            rtol=rtol, atol=atol,
        )


def _pow2_cap(rows):
    """The ladder's rule, written independently: the next power of two
    that holds the rows, 16 at least."""
    return max(16, 1 << (int(rows) - 1).bit_length())


# case -> (row counts, min_bucket_entities, expected cap per entity; 0: the
# entity is in no bucket). All under DEFAULT_BUCKET_CAPS.
_TAIL = [20] * 3 + [40] * 5 + [100] * 2
_BODY = [50] * 2000 + [100] * 1000  # 256 000 slab rows: 1 / 256 is 1 000
LADDER_CASES = {
    "smallest_rung_is_16": (
        [1, 8, 16, 17], 0, [16, 16, 16, 32]),
    # the configured rungs end at 4096; above it the planner rounds to
    # the next power of two: one rule on both sides
    "same_rule_both_sides_of_4096": (
        [2048, 2049, 4096, 4097, 8192, 8193], 0,
        [2048, 4096, 4096, 8192, 8192, 16384]),
    "inactive_entities_in_no_bucket": (
        [0, 5, 300, 0], 0, [0, 16, 512, 0]),
    # 3 x 32 is under the floor and rides into 64 (8 there: enough);
    # the largest bucket (2 x 128) never merges
    "tail_merges_upward": (_TAIL, 4, [64] * 8 + [128] * 2),
    "tail_cascades_to_the_largest": (_TAIL, 9, [128] * 10),
    # the planner's own rule, whatever the floor: 4 x 32 rides into 64
    # (it pads 128 rows more, under 1 / 256 of the slabs), 40 x 32 stays
    # (1 280 rows more)
    "thin_rung_rides_up": (
        [30] * 4 + _BODY, 0, [64] * 2004 + [128] * 1000),
    "rung_worth_its_padding_stays": (
        [30] * 40 + _BODY, 0, [32] * 40 + [64] * 2000 + [128] * 1000),
}


class TestDefaultLadder:
    @pytest.mark.parametrize("case", sorted(LADDER_CASES))
    def test_default_ladder(self, case):
        counts, floor, expected = LADDER_CASES[case]
        counts = np.asarray(counts)
        members = _assign_buckets(
            counts, counts >= 1, DEFAULT_BUCKET_CAPS,
            min_bucket_entities=floor,
        )
        cap_of = np.zeros(counts.size, np.int64)
        for cap, ids in members.items():
            assert np.all(cap_of[ids] == 0)  # an entity has one bucket
            cap_of[ids] = cap
        np.testing.assert_array_equal(cap_of, np.asarray(expected))
        live = counts >= 1
        assert np.all(cap_of[live] >= counts[live])  # the slab holds it

    @pytest.mark.parametrize("octave", range(3, 15))
    def test_a_slab_is_under_twice_its_entitys_rows(self, octave):
        # Every size of one octave, 2^k < rows <= 2^(k+1), for 8 .. 20 000
        # rows: one rung, so no merge rule engages, and it is the next
        # power of two (16 for the smallest).
        counts = np.arange(8 if octave == 3 else 2 ** octave + 1,
                           min(20_000, 2 ** (octave + 1)) + 1)
        members = _assign_buckets(
            counts, counts >= 1, DEFAULT_BUCKET_CAPS)
        (cap,) = members
        assert cap == _pow2_cap(counts[-1]) == _pow2_cap(counts[0])
        assert members[cap].size == counts.size
        assert counts[-1] <= cap and (cap < 2 * counts[0] or cap == 16)

    def test_the_default_is_the_powers_of_two(self):
        assert DEFAULT_BUCKET_CAPS == tuple(2 ** k for k in range(4, 13))
        assert (RandomEffectDataConfiguration("userId", "u").bucket_caps
                == DEFAULT_BUCKET_CAPS)


RATIO_4_CAPS = (16, 64, 256, 1024, 4096)


def _ladder_workload(task, seed=0, dtype=jnp.float32):
    """A small GLMix whose two random coordinates spread over many rungs:
    users of 9 .. 600 rows (seven rungs of the default ladder, four of
    the ratio-4 one), items of 30 .. 330."""
    rng = np.random.default_rng(seed)
    user_sizes = np.repeat(
        [9, 12, 20, 27, 40, 55, 70, 100, 150, 200, 300, 600], 2)
    n = int(user_sizes.sum())
    uid = rng.permutation(np.repeat(np.arange(user_sizes.size), user_sizes))
    item_sizes = rng.multinomial(n - 30 * 18, np.arange(1, 19) / 171) + 30
    iid = rng.permutation(np.repeat(np.arange(18), item_sizes))
    d, du, di = 8, 5, 3
    x, xu, xi = (rng.normal(size=(n, k)).astype(np.float32)
                 for k in (d, du, di))
    for a in (x, xu, xi):
        a[:, -1] = 1.0
    w = 0.3 * rng.normal(size=d).astype(np.float32)
    wu = 0.3 * rng.normal(size=(user_sizes.size, du)).astype(np.float32)
    wi = 0.2 * rng.normal(size=(18, di)).astype(np.float32)
    z = (x @ w + np.einsum("nd,nd->n", xu, wu[uid])
         + np.einsum("nd,nd->n", xi, wi[iid]))
    if task == TaskType.LOGISTIC_REGRESSION:
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    else:
        y = (z + 0.2 * rng.normal(size=n)).astype(np.float32)
    return make_game_dataset(
        y, {"g": DenseFeatures(x), "u": DenseFeatures(xu),
            "i": DenseFeatures(xi)},
        id_tags={"userId": uid, "itemId": iid},
        dtype=dtype,
    )


def _ladder_fit(task, data, precision, **re_kwargs):
    est = GameEstimator(
        task,
        {
            "global": FixedEffectCoordinateConfiguration("g", _l2(1e-2)),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "u", **re_kwargs),
                _l2(1.0)),
            "per-item": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("itemId", "i", **re_kwargs),
                _l2(1.0)),
        },
        num_iterations=2,
        mesh="off",
        precision=precision,
    )
    datasets, _ = est.prepare(data)
    caps = {
        cid: sorted(int(b.row_ids.shape[1]) for b in datasets[cid].blocks)
        for cid in ("per-user", "per-item")
    }
    model = est.fit(data)[0].model
    assert est._fused_cache  # the fused whole-fit program ran
    return model, caps


class TestLadderParity:
    """The ladder decides how much padding a slab carries, never what is
    fitted: padding rows carry weight 0, so the default ladder and the
    ratio-4 one it replaced give the same model."""

    # (task, precision, data dtype, coefficient atol, score atol). Padding
    # only lengthens sums by zeros: the closed form reads alike to float32
    # rounding, and in float64 so does the Newton route. In float32 a
    # Newton loop ends where its float32 OBJECTIVE stops improving, which
    # leaves a coefficient within about the root of that rounding (some
    # 1e-3 here and under either ladder: the benchmark's limits on
    # ``coef.*`` are of that size); the summation length moves the point.
    @pytest.mark.parametrize(
        "task,precision,dtype,coef_atol,score_atol",
        [(TaskType.LINEAR_REGRESSION, "bfloat16", jnp.float32, 1e-6, 1e-6),
         (TaskType.LOGISTIC_REGRESSION, "float32", jnp.float64, 1e-12,
          1e-12),
         (TaskType.LOGISTIC_REGRESSION, "float32", jnp.float32, 3e-3,
          1e-2)],
        ids=["linear_bf16_direct", "logistic_f64_newton",
             "logistic_f32_newton"],
    )
    def test_same_model_under_both_ladders(
            self, task, precision, dtype, coef_atol, score_atol):
        from photon_tpu.transformers import GameTransformer

        data = _ladder_workload(task, dtype=dtype)
        m2, caps2 = _ladder_fit(task, data, precision)
        m4, caps4 = _ladder_fit(
            task, data, precision, bucket_caps=RATIO_4_CAPS)
        assert caps2["per-user"] == [16, 32, 64, 128, 256, 512, 1024]
        assert caps4["per-user"] == [16, 64, 256, 1024]
        assert len(caps2["per-item"]) > len(caps4["per-item"])
        np.testing.assert_allclose(
            np.asarray(m2.models["global"].model.coefficients.means),
            np.asarray(m4.models["global"].model.coefficients.means),
            rtol=0, atol=coef_atol)
        for cid in ("per-user", "per-item"):
            np.testing.assert_allclose(
                np.asarray(m2.models[cid].coefficients),
                np.asarray(m4.models[cid].coefficients),
                rtol=0, atol=coef_atol, err_msg=cid)
        np.testing.assert_allclose(
            np.asarray(GameTransformer(m2).score(data)),
            np.asarray(GameTransformer(m4).score(data)),
            rtol=0, atol=score_atol)

    @pytest.mark.parametrize("min_bucket_entities", [0, 3])
    def test_shape_oracle_equals_the_built_shapes(self, min_bucket_entities):
        data = _ladder_workload(TaskType.LINEAR_REGRESSION)
        cfg = RandomEffectDataConfiguration(
            "userId", "u", min_bucket_entities=min_bucket_entities)
        pred = predict_plan_shapes(data, cfg)
        ds = build_random_effect_dataset(data, cfg, intercept_index=None)
        built = [(int(b.row_ids.shape[1]), int(b.row_ids.shape[0]))
                 for b in ds.blocks]
        assert [(cap, b) for cap, b, _ in pred["buckets"]] == built
        assert len(built) >= 4  # at least four rungs occupied
        assert pred["packed_shapes"] == ds.packed_view.shapes
        # and the padded slabs hold under twice the rows they pad
        counts = np.bincount(np.asarray(data.id_tags["userId"].host_codes()))
        assert sum(c * b for c, b in built) < 2 * counts.sum()


class TestDonationSafety:
    def test_warmup_thunks_run_with_donation(self):
        # warmup_thunks used to pass w0_full as BOTH the warm-start and
        # the donated output table — with donation live that is an XLA
        # "donated buffer also an input" runtime error. The fix gives
        # each thunk fresh tables; this runs the real thunks.
        data = _workload(TaskType.LOGISTIC_REGRESSION)
        est, _ = _fit(TaskType.LOGISTIC_REGRESSION, data, "float32")
        datasets, _ = est.prepare(data)
        coords = est._build_coordinates(
            datasets, {}, {}, logical_rows=data.num_samples)
        coord = coords["per-user"]
        for thunk in coord.warmup_thunks():
            thunk()

    def test_unfused_train_rebinds_donated_tables(self):
        # The unfused per-bucket loop donates w_all/v_all through
        # _scatter_results; a second train() on the same coordinate must
        # not touch deleted buffers.
        data = _workload(TaskType.LOGISTIC_REGRESSION)
        est, _ = _fit(TaskType.LOGISTIC_REGRESSION, data, "float32")
        datasets, _ = est.prepare(data)
        coords = est._build_coordinates(
            datasets, {}, {}, logical_rows=data.num_samples)
        coord = coords["per-user"]
        m1, _ = coord.train()
        m2, _ = coord.train(initial_model=m1)
        np.asarray(m1.coefficients)  # still alive (never donated)
        np.asarray(m2.coefficients)


class TestSubAddDonation:
    def test_aliased_carry_takes_plain_path(self):
        from photon_tpu.algorithm.coordinate_descent import _sub_add

        t = jnp.ones(16)
        new = jnp.full(16, 2.0)
        # total IS the stored score (single-coordinate descent): must
        # not crash on aliased donation, and must compute correctly.
        out = _sub_add(t, t, new)
        np.testing.assert_allclose(np.asarray(out), 2.0)

    def test_distinct_carry_donates_and_rebinds(self):
        from photon_tpu.algorithm.coordinate_descent import _sub_add

        t = jnp.ones(16)
        old = jnp.full(16, 0.5)
        new = jnp.full(16, 2.0)
        out = _sub_add(t, old, new)
        np.testing.assert_allclose(np.asarray(out), 2.5)
        np.asarray(old), np.asarray(new)  # non-carry operands alive
