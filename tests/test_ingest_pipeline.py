"""Ingest pipeline: determinism, transfer, and overlapped-compile tests.

The pipelined ingest (data/pipeline.py) must be a pure latency
optimization: the parallel planner's output is BYTE-IDENTICAL to the
serial reference path (``PHOTON_TPU_SERIAL_INGEST=1``) — the
deterministic reservoir hash order is the contract — the chunked
double-buffered transfer produces the same packed buffer bytes as the
single-shot path, and the AOT warm compile changes WHICH executable runs
the first fit, never what it computes.

Also pins the round-5 ingest-floor diagnosis: the bisect (PR 1 vs PR 2
prepare timing on identical data) showed ``cache_stats()``'s dir scan
never runs in the prepare path and PR 2 did not slow planning — the real
cost was the plan-buffer build's O(n x buckets) full-table row selection,
fixed by span arithmetic in ``_bucket_rows`` (tested here against the
old full-scan reference, plus a poisoned-plan test proving the full-n
arrays are no longer touched).
"""

from __future__ import annotations

import contextlib
import os

import numpy as np
import pytest

from photon_tpu.data import pipeline
from photon_tpu.data.dataset import DenseFeatures, SparseFeatures
from photon_tpu.data.game_data import make_game_dataset
from photon_tpu.data.random_effect import (
    RandomEffectDataConfiguration,
    _bucket_rows,
    _plan_random_effect,
    build_random_effect_dataset,
    predict_plan_shapes,
)


@contextlib.contextmanager
def ingest_mode(*, serial: bool, threads: int = 2, chunk_min: int = 8):
    """Force the serial or parallel ingest path for one build."""
    saved = {
        k: os.environ.get(k)
        for k in ("PHOTON_TPU_SERIAL_INGEST", "PHOTON_TPU_INGEST_THREADS")
    }
    saved_chunk = pipeline._CHUNK_MIN_ROWS
    os.environ["PHOTON_TPU_SERIAL_INGEST"] = "1" if serial else ""
    os.environ["PHOTON_TPU_INGEST_THREADS"] = str(threads)
    # Tiny fixtures must still exercise the chunked code paths.
    pipeline._CHUNK_MIN_ROWS = chunk_min
    pipeline.reset_executors()
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        pipeline._CHUNK_MIN_ROWS = saved_chunk
        pipeline.reset_executors()


def _fixture(kind: str, n: int = 600, e: int = 41, d: int = 7, seed: int = 3):
    """(GameDataset, config) pairs covering the determinism matrix."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, e, size=n)
    y = rng.normal(size=n).astype(np.float32)
    kw: dict = {}
    if kind == "dense_cap":
        x = rng.normal(size=(n, d)).astype(np.float32)
        feats = DenseFeatures(x)
        kw = dict(active_data_upper_bound=6)
    elif kind == "dense_nocap":
        x = rng.normal(size=(n, d)).astype(np.float32)
        feats = DenseFeatures(x)
    elif kind == "dense_zeros":
        # Exact zeros exercise the presence/segment-OR planner path (and
        # defeat the shape oracle's fully-dense assumption, on purpose).
        x = rng.normal(size=(n, d)).astype(np.float32)
        x[x < 0.3] = 0.0
        feats = DenseFeatures(x)
        kw = dict(active_data_upper_bound=8)
    elif kind == "dense_empty_entities":
        # Lower bound deactivates small entities; entity 0 is made
        # row-free entirely (its code never drawn) — the empty-entity
        # fixture of the determinism contract.
        codes = rng.integers(1, e, size=n)
        head = np.repeat(np.arange(1, e), 3)
        codes[: head.size] = head
        x = rng.normal(size=(n, d)).astype(np.float32)
        feats = DenseFeatures(x)
        kw = dict(active_data_upper_bound=5, active_data_lower_bound=4)
    elif kind == "sparse":
        idx = rng.integers(0, d, size=(n, 3)).astype(np.int32)
        val = rng.normal(size=(n, 3)).astype(np.float32)
        val[val < -1.0] = 0.0
        feats = SparseFeatures(idx, val, d)
        kw = dict(active_data_upper_bound=7)
    else:  # pragma: no cover
        raise KeyError(kind)
    data = make_game_dataset(y, {"s": feats}, id_tags={"g": codes})
    return data, RandomEffectDataConfiguration("g", "s", **kw)


FIXTURES = (
    "dense_cap",
    "dense_nocap",
    "dense_zeros",
    "dense_empty_entities",
    "sparse",
)


def _build(kind: str, *, serial: bool):
    with ingest_mode(serial=serial):
        data, cfg = _fixture(kind)
        return build_random_effect_dataset(
            data, cfg, intercept_index=cfg.feature_shard_id and 6
        )


def _assert_same_packed(a, b):
    """Byte-for-byte packed-buffer + BlockPlan equality — THE diff
    harness shared by the serial-vs-parallel determinism tests and the
    streaming kill-and-resume tests."""
    buf_a = np.asarray(a.packed_view.buffer)
    buf_b = np.asarray(b.packed_view.buffer)
    assert buf_a.dtype == buf_b.dtype == np.int32
    assert buf_a.shape == buf_b.shape
    assert bytes(buf_a) == bytes(buf_b)
    assert a.packed_view.shapes == b.packed_view.shapes
    assert len(a.blocks) == len(b.blocks)
    for ba, bb in zip(a.blocks, b.blocks):
        for f in (
            "entity_codes", "row_ids", "row_counts", "proj",
            "intercept_slots",
        ):
            np.testing.assert_array_equal(
                np.asarray(getattr(ba, f)), np.asarray(getattr(bb, f)), f
            )
    np.testing.assert_array_equal(a.covered_np, b.covered_np)
    np.testing.assert_array_equal(a.proj_all, b.proj_all)
    np.testing.assert_array_equal(a.sub_dims, b.sub_dims)
    assert a.max_sub_dim == b.max_sub_dim


@pytest.mark.parametrize("kind", FIXTURES)
def test_parallel_planner_bit_identical_to_serial(kind):
    """The determinism property: parallel planning produces byte-identical
    packed buffers and identical BlockPlan metadata vs the serial path."""
    a = _build(kind, serial=True)
    b = _build(kind, serial=False)
    _assert_same_packed(a, b)


# ---------------------------------------------------------------------------
# grouping by counting: group_rows, and the whole plan against the sort
# ---------------------------------------------------------------------------


def _codes_for(e: int, law: str, n: int = 20_000) -> np.ndarray:
    rng = np.random.default_rng([e, len(law)])
    if law == "uniform":
        return rng.integers(0, e, size=n)
    if law == "skewed":  # a few entities own most rows, many own none
        return np.minimum(rng.zipf(1.3, size=n) - 1, e - 1)
    # "holes": only every third entity has rows, the last one among them
    codes = rng.integers(0, -(-e // 3), size=n) * 3
    codes[0] = e - 1
    return np.minimum(codes, e - 1)


_GROUPS = (1, 2, 65_535, 65_536, 65_537, 200_000, (1 << 20) + 1)


@pytest.mark.parametrize("serial", [True, False], ids=["serial", "threaded"])
@pytest.mark.parametrize("law", ["uniform", "skewed", "holes"])
@pytest.mark.parametrize("e", _GROUPS)
def test_group_rows_is_the_stable_argsort(e, law, serial):
    codes = _codes_for(e, law).astype(np.int64)
    with ingest_mode(serial=serial):
        perm, how = pipeline.group_rows(codes, e)
    want = np.argsort(codes, kind="stable")
    np.testing.assert_array_equal(perm, want)
    assert perm.dtype == want.dtype
    assert how == ("radix16" if e <= 65_536 else "radix16x2")


@pytest.mark.parametrize(
    "e, how",
    [(1, "radix16"), (65_537, "radix16x2"), ((1 << 20) + 1, "radix16x2"),
     ((1 << 32) + 1, "sort")],
)
def test_group_rows_of_no_rows_and_of_too_many_groups(e, how):
    perm, got = pipeline.group_rows(np.empty(0, np.int64), e)
    assert perm.shape == (0,) and got == how
    few = np.array([3, 0, 3, 1, 0], np.int64)
    perm, got = pipeline.group_rows(few, e)
    np.testing.assert_array_equal(perm, np.argsort(few, kind="stable"))
    assert got == how


def _golden_data(law: str, shard: str, cap_binds: bool):
    """Some 12 000 rows of 200 users and 40 movies, the size of the
    benchmark configurations' ``tiny`` blocks, made here."""
    n, users, movies, d = 12_000, 200, 40, 6
    rng = np.random.default_rng([len(law), len(shard), cap_binds])
    if law == "uniform":
        uid = rng.integers(0, users, size=n)
        mid = rng.integers(0, movies, size=n)
    else:  # power law: entity k's share falls as 1 / (k + 1)
        uid = rng.choice(users, size=n, p=_shares(users))
        mid = rng.choice(movies, size=n, p=_shares(movies))
    y = rng.normal(size=n).astype(np.float32)
    if shard == "sparse":
        idx = rng.integers(0, d, size=(n, 3)).astype(np.int32)
        val = rng.normal(size=(n, 3)).astype(np.float32)
        val[val < -1.0] = 0.0
        feats = SparseFeatures(idx, val, d)
    else:
        x = rng.normal(size=(n, d)).astype(np.float32)
        x[:, -1] = 1.0
        if shard == "dense_few_zeros":
            # Under n / 16 exact zeros, and two entities that lack a
            # feature outright: every row of theirs holds a zero there.
            x[rng.integers(0, n, 40), rng.integers(0, d - 1, 40)] = 0.0
            x[uid == _smallest_active(uid), 2] = 0.0
            x[mid == _smallest_active(mid), 0] = 0.0
        elif shard == "dense_many_zeros":
            x[x < 0.3] = 0.0
        feats = DenseFeatures(x)
    data = make_game_dataset(
        y, {"s": feats}, id_tags={"userId": uid, "movieId": mid})
    # The smallest entity counts set the caps: one that binds for some
    # entities, or one that no entity reaches.
    top = int(max(np.bincount(uid).max(), np.bincount(mid).max()))
    upper = 24 if cap_binds else top
    return data, [
        RandomEffectDataConfiguration(
            tag, "s", active_data_upper_bound=upper,
            active_data_lower_bound=2)
        for tag in ("userId", "movieId")
    ]


def _smallest_active(ids: np.ndarray) -> int:
    """The entity with the fewest rows that still trains (two or more)."""
    counts = np.bincount(ids)
    return int(np.where(counts >= 2, counts, ids.size).argmin())


def _shares(e: int) -> np.ndarray:
    w = 1.0 / np.arange(1, e + 1)
    return w / w.sum()


def _plan_and_flat(data, cfg):
    plan = _plan_random_effect(
        data, cfg, intercept_index=5, extra_features=None)
    pending = build_random_effect_dataset(
        data, cfg, intercept_index=5, lazy=True, defer_transfer=True)
    return plan, pending.flat


@pytest.mark.parametrize("cap_binds", [False, True], ids=["nocap", "cap"])
@pytest.mark.parametrize(
    "shard",
    ["dense", "dense_few_zeros", "dense_many_zeros", "sparse"])
@pytest.mark.parametrize("law", ["uniform", "power"])
def test_plan_by_counting_is_the_plan_by_sorting(
    law, shard, cap_binds, monkeypatch
):
    """The whole plan is the parent's: with ``group_rows`` and the dense
    scan in place, and with them patched to the stable argsort and the
    gather in entity order that stood there before."""
    from photon_tpu.data import random_effect as re_mod

    data, cfgs = _golden_data(law, shard, cap_binds)
    with ingest_mode(serial=True):
        new = [_plan_and_flat(data, cfg) for cfg in cfgs]
        monkeypatch.setattr(
            re_mod, "group_rows",
            lambda codes, e: (np.argsort(codes, kind="stable"), "sort"))
        monkeypatch.setattr(
            re_mod, "_dense_presence_by_scan", lambda *a: None)
        old = [_plan_and_flat(data, cfg) for cfg in cfgs]
    for (plan, flat), (plan0, flat0) in zip(new, old):
        assert plan.grouping == ("sort" if cap_binds else "radix16")
        scans = shard == "dense" or (
            shard == "dense_few_zeros" and not cap_binds)
        assert plan.presence == ("scan" if scans else "gather")
        assert (plan0.grouping, plan0.presence) == ("sort", "gather")
        for f in ("perm", "starts", "sorted_codes", "rank_sorted",
                  "keep_sorted", "counts", "active", "proj_all",
                  "sub_dims", "intercept_slots_all"):
            got, want = getattr(plan, f), getattr(plan0, f)
            np.testing.assert_array_equal(got, want, f)
            assert got.dtype == want.dtype, f
        np.testing.assert_array_equal(plan.table.keys, plan0.table.keys)
        assert sorted(plan.bucket_members) == sorted(plan0.bucket_members)
        assert len(flat) == len(flat0)
        for a, b in zip(flat, flat0):
            np.testing.assert_array_equal(a, b)
            assert a.dtype == b.dtype
    if shard == "dense_few_zeros" and not cap_binds:
        # The two entities that lack a feature: the scan found them.
        assert (new[0][0].sub_dims < 6).sum() == 1
        assert (new[1][0].sub_dims < 6).sum() == 1


# ---------------------------------------------------------------------------
# the round-5 regression pin: _bucket_rows
# ---------------------------------------------------------------------------


def _bucket_rows_full_scan_reference(plan, members):
    """The pre-round-6 implementation: one full-table boolean scan (and a
    re-gather of codes[perm]) PER BUCKET — kept verbatim as the semantic
    reference the span-arithmetic version must match bit for bit."""
    is_member = np.zeros(plan.active.shape[0] + 1, dtype=bool)
    is_member[members] = True
    sorted_codes = plan.codes[plan.perm]
    sel = plan.keep_sorted & is_member[sorted_codes]
    rows_flat = plan.perm[sel]
    owner = sorted_codes[sel]
    member_rank = np.zeros(plan.active.shape[0], dtype=np.int64)
    member_rank[members] = np.arange(members.size)
    t_of = member_rank[owner]
    r_of = plan.rank_sorted[sel]
    return rows_flat, t_of, r_of, plan.counts[members]


@pytest.mark.parametrize("kind", FIXTURES)
def test_bucket_rows_matches_full_scan_reference(kind):
    with ingest_mode(serial=True):
        data, cfg = _fixture(kind)
        plan = _plan_random_effect(
            data, cfg, intercept_index=None, extra_features=None
        )
    for cap, members in sorted(plan.bucket_members.items()):
        got = _bucket_rows(plan, members, cap)
        want = _bucket_rows_full_scan_reference(plan, members)
        for g, w, name in zip(
            got, want, ("rows_flat", "t_of", "r_of", "counts_b")
        ):
            np.testing.assert_array_equal(g, w, f"{name} @ cap {cap}")
            assert g.dtype == w.dtype, (name, g.dtype, w.dtype)


def test_bucket_rows_does_no_full_table_passes():
    """The fix's complexity pin: the selection must touch only
    starts/counts/perm spans, never the full-n codes/keep/rank arrays.
    Poisoning those attributes proves it structurally — the old
    implementation raises immediately on any of them."""
    with ingest_mode(serial=True):
        data, cfg = _fixture("dense_cap")
        plan = _plan_random_effect(
            data, cfg, intercept_index=None, extra_features=None
        )
    reference = {
        cap: _bucket_rows_full_scan_reference(plan, members)
        for cap, members in plan.bucket_members.items()
    }
    plan.codes = None
    plan.keep_sorted = None
    plan.rank_sorted = None
    plan.sorted_codes = None
    for cap, members in sorted(plan.bucket_members.items()):
        got = _bucket_rows(plan, members, cap)
        for g, w in zip(got, reference[cap]):
            np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# chunked transfer
# ---------------------------------------------------------------------------


def test_packed_device_put_chunked_is_byte_identical(monkeypatch):
    """Multi-chunk streaming + donated concat == the single-shot buffer."""
    rng = np.random.default_rng(0)
    arrays = [
        rng.integers(-50, 50, size=s).astype(np.int32)
        for s in ((13,), (7, 5), (3, 4, 2), (1,), (29,))
    ]
    with ingest_mode(serial=False):
        # Shrink the granule so the tiny layout spans several chunks.
        monkeypatch.setattr(pipeline, "_TRANSFER_GRANULE_ELEMS", 16)
        monkeypatch.setattr(pipeline, "transfer_chunk_elems", lambda: 32)
        buf_chunked, shapes_c = pipeline.packed_device_put(arrays)
        monkeypatch.setattr(
            pipeline, "transfer_chunk_elems", lambda: 1 << 20
        )
        buf_single, shapes_s = pipeline.packed_device_put(arrays)
    assert shapes_c == shapes_s
    a = np.asarray(buf_chunked)
    b = np.asarray(buf_single)
    assert a.shape == b.shape
    assert bytes(a) == bytes(b)


def test_padded_len_matches_granule():
    g = pipeline._TRANSFER_GRANULE_ELEMS
    assert pipeline.padded_len(1) == g
    assert pipeline.padded_len(g) == g
    assert pipeline.padded_len(g + 1) == 2 * g


# ---------------------------------------------------------------------------
# shape oracle + overlapped AOT compile
# ---------------------------------------------------------------------------


def test_shape_oracle_predicts_built_layout():
    """On a fully dense shard the predicted packed layout equals the
    built one exactly (the precondition for the warm compile to land)."""
    with ingest_mode(serial=True):
        data, cfg = _fixture("dense_cap")
        pred = predict_plan_shapes(data, cfg)
        ds = build_random_effect_dataset(data, cfg, intercept_index=None)
    assert pred is not None
    assert pred["packed_shapes"] == ds.packed_view.shapes
    assert pred["max_sub_dim"] == ds.max_sub_dim
    assert pred["kept_total"] == int(ds.covered_np.sum())


def test_shape_oracle_declines_unpredictable_layouts():
    with ingest_mode(serial=True):
        data, cfg = _fixture("sparse")
        assert predict_plan_shapes(data, cfg) is None
        data2, cfg2 = _fixture("dense_cap")
        import dataclasses

        capped = dataclasses.replace(cfg2, score_table_width_cap=3)
        assert predict_plan_shapes(data2, capped) is None


def _tiny_estimator_pair():
    from photon_tpu.analysis.program import _tiny_glmix

    return _tiny_glmix()


def _model_tables(result):
    out = {}
    for cid, m in result.model.models.items():
        c = (
            m.coefficients
            if hasattr(m, "coefficients")
            else m.model.coefficients.means
        )
        out[cid] = np.asarray(c)
    return out


def test_aot_warm_compile_first_fit_identical_to_serial():
    """The overlapped compile is a latency optimization ONLY: the fused
    first fit through the AOT executables returns bit-identical
    coefficient tables, and the pipeline reports the compile stages."""
    with ingest_mode(serial=True):
        est_s, data_s = _tiny_estimator_pair()
        want = _model_tables(est_s.fit(data_s)[0])
    with ingest_mode(serial=False):
        est_p, data_p = _tiny_estimator_pair()
        got = _model_tables(est_p.fit(data_p)[0])
        fused = next(reversed(est_p._fused_cache.values()))
        report = pipeline.PIPELINE_STATS.report()
    assert fused._aot is not None, "warm-compile artifacts were not used"
    for cid in want:
        np.testing.assert_array_equal(want[cid], got[cid], cid)
    assert report["compile_seconds"] > 0.0
    assert report["compile_overlap_fraction"] is not None
    assert 0.0 <= report["compile_overlap_fraction"] <= 1.0


def _two_random_effect_pair(seed=5):
    """Two random-effect coordinates: their plan arrays share ONE packed
    buffer (GameEstimator._resolve_pending), the second coordinate's at
    a non-zero offset."""
    from photon_tpu.estimators.game_estimator import (
        GameEstimator,
        FixedEffectCoordinateConfiguration,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(seed)
    n, d = 400, 4
    x = rng.normal(size=(n, d)).astype(np.float32)
    x[:, -1] = 1.0
    users = rng.integers(0, 11, size=n)
    items = rng.integers(0, 7, size=n)
    y = (
        x @ rng.normal(size=d) + rng.normal(size=11)[users]
        + rng.normal(size=7)[items]
    ).astype(np.float32)
    data = make_game_dataset(
        y, {"features": DenseFeatures(x)},
        id_tags={"userId": users, "itemId": items},
    )
    est = GameEstimator(
        TaskType.LINEAR_REGRESSION,
        {
            "global": FixedEffectCoordinateConfiguration("features"),
            "per-user": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "features")),
            "per-item": RandomEffectCoordinateConfiguration(
                RandomEffectDataConfiguration("itemId", "features")),
        },
        intercept_indices={"features": d - 1},
        num_iterations=2,
        mesh="off",
    )
    return est, data


def test_aot_warm_compile_with_two_random_effects_matches_serial():
    """The warm compile's skeletons must model the SHARED packed buffer:
    the materialize program slices it at static offsets, and below one
    transfer granule a standalone skeleton's buffer has the same aval —
    the executable was accepted and the second coordinate read the
    first one's plan arrays (its entities came back untrained)."""
    with ingest_mode(serial=True):
        est_s, data_s = _two_random_effect_pair()
        want = _model_tables(est_s.fit(data_s)[0])
    with ingest_mode(serial=False):
        est_p, data_p = _two_random_effect_pair()
        got = _model_tables(est_p.fit(data_p)[0])
        fused = next(reversed(est_p._fused_cache.values()))
    assert fused._aot is not None, "warm-compile artifacts were not used"
    assert fused._aot["layout"] == fused.packed_layout()
    offsets = [sl[0][0] for sl in fused.packed_layout().values()]
    assert offsets[0] == 0 and offsets[1] > 0
    assert want["per-item"].any(axis=1).all()
    for cid in want:
        np.testing.assert_array_equal(want[cid], got[cid], cid)


def test_aot_materialize_for_another_layout_is_not_dispatched():
    """Slice offsets are static, not part of any operand's aval: the
    layout is compared explicitly, and an executable compiled for
    another one is dropped for the jit path."""
    with ingest_mode(serial=True):
        est, data = _two_random_effect_pair()
        want = _model_tables(est.fit(data)[0])
    with ingest_mode(serial=False):
        est2, data2 = _two_random_effect_pair()
        est2.prepare(data2)
        art = est2._aot_future.result()
        shifted = {
            cid: tuple((off + 1, shape) for off, shape in slices)
            for cid, slices in art["layout"].items()
        }

        def refuse(mat_ops):
            raise AssertionError("dispatched for another layout")

        from concurrent.futures import Future

        fut = Future()
        fut.set_result({**art, "layout": shifted, "mat": refuse})
        est2._aot_future = fut
        got = _model_tables(est2.fit(data2)[0])
    for cid in want:
        np.testing.assert_array_equal(want[cid], got[cid], cid)


def test_stale_shape_prediction_falls_back_to_jit():
    """Exact zeros in a dense shard break the oracle's fully-dense
    assumption: the warm-compiled executable must be discarded and the
    normal jit path produce the same model as the serial run."""
    import jax.numpy as jnp

    from photon_tpu.data.random_effect import (
        skeleton_random_effect_dataset,
    )
    from photon_tpu.estimators.game_estimator import (
        GameEstimator,
        FixedEffectCoordinateConfiguration,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu.types import TaskType

    def build_pair():
        rng = np.random.default_rng(11)
        n, e, d, du = 120, 9, 5, 4
        x = rng.normal(size=(n, d)).astype(np.float32)
        x[:, -1] = 1.0
        xu = rng.normal(size=(n, du)).astype(np.float32)
        # A dead feature column: every real subspace excludes it, so the
        # oracle's fully-dense prediction (sub_dim == du) is wrong for
        # EVERY entity — a deterministic stale-prediction fixture.
        xu[:, 0] = 0.0
        xu[:, -1] = 1.0
        users = rng.integers(0, e, size=n)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        data = make_game_dataset(
            y,
            {"global": DenseFeatures(x), "userShard": DenseFeatures(xu)},
            id_tags={"userId": users},
        )
        est = GameEstimator(
            TaskType.LINEAR_REGRESSION,
            {
                "global": FixedEffectCoordinateConfiguration("global"),
                "per-user": RandomEffectCoordinateConfiguration(
                    RandomEffectDataConfiguration("userId", "userShard")
                ),
            },
            intercept_indices={"global": d - 1, "userShard": du - 1},
            num_iterations=2,
            mesh="off",
        )
        return est, data

    with ingest_mode(serial=True):
        est_s, data_s = build_pair()
        # Confirm the fixture really defeats the oracle.
        skel = skeleton_random_effect_dataset(
            data_s, est_s.coordinate_configs["per-user"].data
        )
        built = est_s.prepare(data_s)[0]["per-user"]
        assert skel is not None
        built_shapes = tuple(
            shape for _, shape in built.packed_view.static_slices()
        )
        assert skel.packed_view.shapes != built_shapes
        want = _model_tables(est_s.fit(data_s)[0])
    with ingest_mode(serial=False):
        est_p, data_p = build_pair()
        got = _model_tables(est_p.fit(data_p)[0])
        fused = next(reversed(est_p._fused_cache.values()))
    assert fused._aot is None, "stale AOT artifacts were not discarded"
    for cid in want:
        np.testing.assert_array_equal(want[cid], got[cid], cid)


def test_declined_warm_compile_records_no_compile_stage():
    """A declined prediction (sparse shard) must leave compile_seconds at
    0 — a truthy near-zero stage would fake an overlap fraction in
    ``PIPELINE_STATS.report()``."""
    from photon_tpu.estimators.game_estimator import (
        GameEstimator,
        FixedEffectCoordinateConfiguration,
        RandomEffectCoordinateConfiguration,
    )
    from photon_tpu.types import TaskType

    with ingest_mode(serial=True):
        data, cfg = _fixture("sparse")
        est = GameEstimator(
            TaskType.LINEAR_REGRESSION,
            {
                "per-g": RandomEffectCoordinateConfiguration(cfg),
            },
            mesh="off",
        )
        pipeline.PIPELINE_STATS.reset()
        assert est._warm_compile(data) is None
        rep = pipeline.PIPELINE_STATS.report()
    assert rep["compile_seconds"] == 0.0
    assert rep["compile_overlap_fraction"] is None


def test_reset_discards_stale_generation_stage():
    """A stage spanning a reset() (an orphaned background compile from a
    previous dataset generation) must not write into the new report."""
    stats = pipeline.PipelineStats()
    with stats.stage("compile"):
        stats.reset()
    assert stats.report()["compile_seconds"] == 0.0
    # ...and the keep list preserves pre-estimator stages.
    stats.add("raw_transfer", 1.5)
    stats.add("plan", 2.0)
    stats.reset(keep=("raw_transfer",))
    rep = stats.report()
    assert rep["stages"].get("raw_transfer") == 1.5
    assert rep["plan_seconds"] == 0.0


def test_stage_reraises_body_exceptions():
    """The generation check lives in a ``finally`` — it must never
    swallow the body's exception."""
    stats = pipeline.PipelineStats()
    with pytest.raises(RuntimeError, match="boom"):
        with stats.stage("compile"):
            raise RuntimeError("boom")
    # The stage still recorded (sub-ms, so assert presence not size).
    assert "compile" in stats.report()["stages"]


def test_pipeline_stats_report_shape():
    stats = pipeline.PipelineStats()
    with stats.stage("plan"):
        pass
    stats.add("compile", 2.0)
    stats.add("compile_wait", 0.5)
    rep = stats.report()
    for key in (
        "plan_seconds", "pack_seconds", "transfer_seconds",
        "compile_seconds", "compile_wait_seconds",
        "compile_overlap_fraction", "stages",
    ):
        assert key in rep
    assert rep["compile_overlap_fraction"] == 0.75
    empty = pipeline.PipelineStats().report()
    assert empty["compile_overlap_fraction"] is None


def test_ingest_pipeline_contract_gates_clean():
    """The tier-2 ingest-pipeline contract on the canonical fixture: the
    warm compile's skeleton-traced programs carry the production
    signatures (census unchanged) and the audit reports zero findings."""
    from photon_tpu.analysis import program

    contracts = [
        c for c in program.collect_contracts()
        if c.name == "ingest-pipeline"
    ]
    assert contracts, "ingest-pipeline contract missing from the registry"
    findings, report = program.audit(contracts, with_cost=False)
    assert [f for f in findings if not f.suppressed] == []
    entry = report["contracts"]["ingest-pipeline"]
    assert set(entry["programs"]) == {"materialize", "fit"}


def test_serial_env_flag_round_trips():
    with ingest_mode(serial=True):
        assert pipeline.serial_ingest()
    with ingest_mode(serial=False):
        assert not pipeline.serial_ingest()


# ---------------------------------------------------------------------------
# streaming kill-and-resume determinism (photon_tpu.data.stream, PR 10)
# ---------------------------------------------------------------------------


STREAM_KINDS = ("cap", "sparse", "empty_entities")


def _write_stream_fixture(kind: str, shard_dir: str):
    """Avro-shard counterparts of the determinism matrix: dense-ish
    rows under an active-data cap, sparse rows with exact zeros, and a
    lower bound deactivating small entities. Returns the RE config."""
    import os

    from photon_tpu.data.random_effect import (
        RandomEffectDataConfiguration,
    )
    from photon_tpu.io.avro_data import write_training_examples
    from photon_tpu.types import DELIMITER

    os.makedirs(shard_dir, exist_ok=True)
    rng = np.random.default_rng(11)
    n_per, shards, d, e = 48, 5, 6, 13
    kw: dict = {}
    if kind == "cap":
        kw = dict(active_data_upper_bound=6)
    elif kind == "sparse":
        kw = dict(active_data_upper_bound=7)
    else:  # empty_entities
        kw = dict(active_data_upper_bound=5, active_data_lower_bound=4)
    base = 0
    for si in range(shards):
        y = rng.normal(size=n_per)
        rows = []
        for _ in range(n_per):
            if kind == "cap":
                feats = range(d)
            else:
                feats = rng.choice(d, size=3, replace=False)
            row = [
                (f"f{j}{DELIMITER}t", float(v))
                for j in feats
                if (v := rng.normal()) > -0.8 or kind == "cap"
            ]
            rows.append(row)
        lo = 1 if kind == "empty_entities" else 0
        meta = [
            {"g": f"e{rng.integers(lo, e)}"} for _ in range(n_per)
        ]
        write_training_examples(
            os.path.join(shard_dir, f"part-{si:05d}.avro"),
            y, rows, metadata=meta, uids=np.arange(base, base + n_per),
        )
        base += n_per
    return RandomEffectDataConfiguration("g", "features", **kw)


@pytest.mark.parametrize("kind", STREAM_KINDS)
def test_streaming_kill_resume_packed_buffers_byte_identical(
    kind, tmp_path
):
    """The acceptance gate's determinism half: kill the streaming
    ingest after shard k (crash-kind fault), resume from the cursor,
    and the resumed dataset's PACKED PLAN BUFFERS are byte-for-byte
    identical to the uninterrupted run's — across the cap / sparse /
    empty-entity fixture matrix, through the same diff harness the
    serial-vs-parallel determinism tests use."""
    from photon_tpu.data.stream import StreamingIngest
    from photon_tpu.io.avro_data import read_training_examples
    from photon_tpu.resilience import FaultPlan, InjectedCrash, faults

    shard_dir = str(tmp_path / "shards")
    cfg = _write_stream_fixture(kind, shard_dir)
    with ingest_mode(serial=True):
        _, imap = read_training_examples(shard_dir)

        def ingest(work, **kw):
            return StreamingIngest(
                shard_dir,
                work_dir=str(tmp_path / work),
                index_maps={"features": imap},
                id_tag_names=["g"],
                **kw,
            )

        full, _ = ingest("full").run()
        with faults.injected(FaultPlan(
            [dict(point="io.shard_read", nth=4, error="crash")]
        )):
            with pytest.raises(InjectedCrash):
                ingest("killed").run()
        resumed, stats = ingest("killed", resume=True).run()
        assert stats["resumed_from_shard"] == 3
        a = build_random_effect_dataset(full, cfg, intercept_index=None)
        b = build_random_effect_dataset(
            resumed, cfg, intercept_index=None
        )
    _assert_same_packed(a, b)
    # The raw streamed columns are byte-identical too.
    assert bytes(np.asarray(full.labels)) == bytes(
        np.asarray(resumed.labels))
    fa = full.feature_shards["features"]
    fb = resumed.feature_shards["features"]
    assert bytes(np.asarray(fa.values)) == bytes(np.asarray(fb.values))
    np.testing.assert_array_equal(
        np.asarray(full.id_tags["g"].codes),
        np.asarray(resumed.id_tags["g"].codes))
