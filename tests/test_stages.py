"""obs.stage: the always-recorded class of span, the records the compile
listener and the model writer leave in the same ring, and the named
scopes of the fused program.

A stage records whether or not telemetry is enabled (an ``obs.span`` does
not), nests by thread like a span, is bounded by the same ring, and is
what ``PIPELINE_STATS.stage`` now is. ``save_game_model`` leaves its split
per coordinate without changing a byte of what it writes.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import threading
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import obs


@pytest.fixture
def ring():
    """A clean ring with telemetry DISABLED; restores flag and bound."""
    was = obs.enabled()
    obs.reset()
    obs.disable()
    yield obs.TRACER
    obs.TRACER.enabled = was
    obs.set_span_retention(4096)
    obs.reset()


def _done(tracer):
    """The ring without what an earlier test's background AOT compile may
    still be landing from its own thread."""
    return [r for r in tracer.completed()
            if not r.thread.startswith("photon-compile")]


def _by_name(tracer):
    out: dict = {}
    for rec in _done(tracer):
        out.setdefault(rec.name, []).append(rec)
    return out


def test_stage_records_with_telemetry_disabled_and_a_span_does_not(ring):
    with obs.stage("outer", coordinate="c") as sp:
        with obs.span("gated") as ghost:
            assert ghost is None
    assert sp is not None
    done = _done(ring)
    assert [(r.kind, r.name) for r in done] == [("stage", "outer")]
    assert done[0].attrs == {"coordinate": "c"}
    assert done[0].seconds == pytest.approx(done[0].t1 - done[0].t0)
    assert done[0].device_wait_seconds is None  # a stage never syncs
    assert done[0].to_json()["kind"] == "stage"


def test_stage_path_thread_and_nesting(ring):
    def work():
        with obs.stage("plan"):
            pass

    worker = threading.Thread(target=work, name="planner-0")
    with obs.stage("prepare"):
        worker.start()
        worker.join()
        with obs.stage("pack"):
            pass
    obs.enable()
    with obs.span("root"):
        with obs.stage("fit"):
            pass
    recs = {r.path: r for r in _done(ring)}
    # A worker's stage roots its own subtree; a stage nests under stages
    # and under enabled spans alike.
    assert set(recs) == {"plan", "prepare", "prepare/pack", "root",
                         "root/fit"}
    assert recs["plan"].thread == "planner-0"
    assert recs["prepare/pack"].thread == recs["prepare"].thread
    assert recs["prepare"].t0 <= recs["prepare/pack"].t0
    assert recs["prepare/pack"].t1 <= recs["prepare"].t1
    assert recs["root"].kind == "span" and recs["root/fit"].kind == "stage"


def test_stage_survives_an_exception_and_pops_its_path(ring):
    with pytest.raises(RuntimeError):
        with obs.stage("broken"):
            raise RuntimeError("boom")
    with obs.stage("after"):
        pass
    assert [r.path for r in _done(ring)] == ["broken", "after"]


@pytest.mark.parametrize("kind", ["stage", "span"])
def test_a_failing_annotation_leaves_no_dead_section_on_the_stack(
        ring, monkeypatch, kind):
    """The annotation is built after the section is on the thread's
    stack; when it cannot be (a jax that fails to import) the section
    goes again, or every later path on the thread would start with it."""
    from photon_tpu.obs import spans

    def broken(path):
        raise ImportError("no jax.profiler")

    obs.enable()
    monkeypatch.setattr(spans, "_annotation", broken)
    with pytest.raises(ImportError):
        with getattr(obs, kind)("dead"):
            pass
    monkeypatch.undo()
    with obs.stage("after"):
        pass
    assert [r.path for r in _done(ring)] == ["after"]


def test_the_ring_stays_bounded_and_counts_what_it_drops(ring):
    from photon_tpu.obs.spans import SpanTracer

    tracer = SpanTracer()  # a ring of its own: nothing else writes to it
    tracer.set_retention(4)
    for k in range(7):
        with tracer.stage(f"s{k}"):
            pass
    assert [r.name for r in tracer.completed()] == ["s3", "s4", "s5", "s6"]
    assert tracer.dropped == 3
    assert obs.REGISTRY.snapshot()["counters"]["spans_dropped_total"] == 3


def test_record_and_stage_sum_leave_finished_events(ring):
    with obs.stage("save"):
        rec = ring.record("compile.lower", 0.25, fun_name="f")
        acc = obs.stage_sum("save.encode", coordinate="per-user")
        for _ in range(3):
            with acc:
                pass
        acc.close()
        obs.stage_sum("save.write").close()  # never entered: no record
    assert rec.kind == "event" and rec.path == "save/compile.lower"
    assert rec.t1 - rec.t0 == pytest.approx(0.25)
    assert rec.thread == threading.current_thread().name
    got = _by_name(ring)
    assert "save.write" not in got
    (enc,) = got["save.encode"]
    assert enc.path == "save/save.encode" and enc.kind == "event"
    assert enc.attrs == {"intervals": 3, "coordinate": "per-user"}
    # The seconds are the sum of the intervals, [t0, t1] their envelope.
    assert 0.0 <= enc.seconds <= enc.t1 - enc.t0
    assert got["save"][0].t0 <= enc.t0 and enc.t1 <= got["save"][0].t1


def test_pipeline_report_is_what_the_recorded_stages_add_up_to(ring):
    from photon_tpu.analysis import program
    from photon_tpu.data.pipeline import PIPELINE_STATS

    with jax.enable_x64(False):
        est, data = program._tiny_glmix()
        est.prepare(data)
    report = PIPELINE_STATS.report()
    assert set(report) >= {
        "plan_seconds", "pack_seconds", "transfer_seconds",
        "compile_seconds", "compile_wait_seconds",
        "compile_overlap_fraction", "stages", "plan_wall_seconds"}
    got = _by_name(ring)
    assert {"dataset", "raw_transfer", "prepare", "plan", "pack",
            "transfer"} <= set(got)
    assert got["raw_transfer"][0].path == "dataset/raw_transfer"
    # prepare() reset the accounting (raw_transfer kept), so each stage
    # the report names is the sum of that prepare's ring records.
    (prep,) = got["prepare"]
    for name in ("plan", "pack", "transfer"):
        mine = [r for r in got[name] if r.t0 >= prep.t0]
        assert report["stages"][name] == pytest.approx(
            sum(r.seconds for r in mine), abs=1e-4)
        assert report[f"{name}_seconds"] == report["stages"][name]
    plans = [r for r in got["plan"] if r.t0 >= prep.t0]
    assert report["plan_wall_seconds"] == pytest.approx(
        max(r.t1 for r in plans) - min(r.t0 for r in plans), abs=1e-4)
    assert all(r.kind == "stage" for r in _done(ring)
               if not r.name.startswith("compile."))


def test_a_plan_stage_says_how_the_bucket_ladder_engaged(ring):
    from photon_tpu.analysis import program

    with jax.enable_x64(False):
        est, data = program._tiny_glmix()
        datasets, _ = est.prepare(data)
    built = {
        cid: [[int(b.row_ids.shape[1]), int(b.row_ids.shape[0])]
              for b in ds.blocks]
        for cid, ds in datasets.items() if hasattr(ds, "blocks")
    }
    plans = _by_name(ring)["plan"]
    assert len(plans) == len(built) >= 1
    for rec in plans:
        assert set(rec.attrs) == {
            "buckets", "slab_rows", "real_rows", "grouping", "presence"}
        assert rec.attrs["buckets"] in built.values()
        assert rec.attrs["slab_rows"] == sum(
            cap * b for cap, b in rec.attrs["buckets"])
        assert 0 < rec.attrs["real_rows"] <= rec.attrs["slab_rows"]
        assert rec.attrs["real_rows"] <= data.num_samples
        json.dumps(rec.to_json())  # attributes an exporter can write


def test_dataset_and_plan_stages_say_how_the_rows_were_grouped(ring):
    """A tiny job's integer ids are coded by counting, its 7 entities
    grouped in one radix pass, and its dense shard (normal draws and an
    intercept: no exact zero) answers the presence test by one scan."""
    from photon_tpu.analysis import program

    with jax.enable_x64(False):
        est, data = program._tiny_glmix()
        est.prepare(data)
    got = _by_name(ring)
    (dataset,) = got["dataset"]
    assert dataset.attrs == {"id_grouping": {"userId": "count"}}
    (plan,) = got["plan"]
    assert plan.attrs["grouping"] == "radix16"
    assert plan.attrs["presence"] == "scan"
    json.dumps(dataset.to_json())


# ---------------------------------------------------------------------------
# save_game_model
# ---------------------------------------------------------------------------

# sha256 of every file the PARENT of this change (5e345a0) wrote for
# _save_model() with os.urandom patched as below: the stages must not
# change a byte.
PARENT_FILES = {
    "fixed-effect/global/coefficients/part-00000.avro":
        "2cdc0640fad92e23b3573a341dbb8d53e28b8710030b595c0dfa77edae8804a4",
    "fixed-effect/global/id-info":
        "c2931e1f0add64579b9e686f7952d647a05c3c93d3b0b28b9ab0008b9d2b4fba",
    "model-metadata.json":
        "1f28bcf6db0e83ffaf8d59690dc380930127811e8bf7ff6ce8ca6e8e5cef41ce",
    "random-effect/per-user/coefficients/part-00000.avro":
        "6f80cc440f7d7dfcf02dbaa6d52233c2c3d219285103e3444344b06317b777ab",
    "random-effect/per-user/id-info":
        "f5558b1e12f8f587bfd9b8e3dc3772c77ecf2c15269a58612c8aded32628867d",
}
PARENT_CONTAINERS = {
    # 8 records at sync_interval 4 (no empty third block), deflate
    "full_blocks":
        "bd13e435d47ca9e3a68885a93ac0770a9f80cf8e45ab48aa639541e2f4ced041",
    # 7 records at sync_interval 4, null codec
    "null_codec":
        "d577ebce977e32ccbc6bd6c3f6dda860442b5fc55b75dceb77221815c198c98e",
    "no_records":
        "54a329f08e09123ceec9819251c9cc8c9ee93123114a66cfca8765a91998db91",
}


def _sha(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _save_model():
    """9001 entities: three blocks of the writer's 4000."""
    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.types import TaskType

    d, e, s = 5, 9001, 2
    fixed = FixedEffectModel(
        GeneralizedLinearModel(
            Coefficients(means=jnp.asarray(np.arange(1, d + 1) / 8.0)),
            TaskType.LINEAR_REGRESSION), "shardA")
    proj = np.stack([np.arange(e) % 3, 3 + np.arange(e) % 2], axis=1)
    w = (np.arange(e * s).reshape(e, s) % 17 - 8) / 16.0
    random = RandomEffectModel(
        coefficients=jnp.asarray(w), random_effect_type="userId",
        feature_shard_id="shardA", task=TaskType.LINEAR_REGRESSION,
        proj_all=proj, entity_keys=tuple(f"u{i}" for i in range(e)))
    return GameModel({"global": fixed, "per-user": random})


def test_save_leaves_its_split_per_coordinate_and_the_parents_bytes(
        ring, tmp_path, monkeypatch):
    from photon_tpu.data.index_map import IndexMap
    from photon_tpu.io import avro
    from photon_tpu.io.model_io import save_game_model

    monkeypatch.setattr(os, "urandom", lambda n: bytes(range(n)))
    out = str(tmp_path / "model")
    save_game_model(_save_model(), out, {"shardA": IndexMap.identity(5)})
    written = {}
    for root, _, files in os.walk(out):
        for name in files:
            path = os.path.join(root, name)
            written[os.path.relpath(path, out)] = _sha(path)
    assert written == PARENT_FILES

    got = _by_name(ring)
    (save,) = got["save"]
    parts = got["save.records"] + got["save.encode"] + got["save.write"]
    for name in ("save.records", "save.encode", "save.write"):
        assert [r.attrs["coordinate"] for r in got[name]] == [
            "global", "per-user"]
        assert all(r.path == "save/" + name for r in got[name])
    assert all(save.t0 <= r.t0 and r.t1 <= save.t1 for r in parts)
    assert sum(r.seconds for r in parts) <= save.seconds
    # Three blocks: the header and each block's encode; open, header,
    # each block's write and the close.
    per_user = {r.name: r for r in parts
                if r.attrs["coordinate"] == "per-user"}
    assert per_user["save.encode"].attrs["intervals"] == 1 + 3 + 1
    assert per_user["save.write"].attrs["intervals"] == 2 + 3 + 1
    # What the one path wrote, on each coordinate's encode record.
    for rec in got["save.encode"]:
        kind = "fixed" if rec.attrs["coordinate"] == "global" else "random"
        raw = [data for _, _, data in avro.iter_container_block_bytes(
            os.path.join(out, f"{kind}-effect", rec.attrs["coordinate"],
                         "coefficients", "part-00000.avro"))]
        assert rec.attrs["bytes_raw"] == sum(map(len, raw))
        assert rec.attrs["bytes_written"] == sum(
            len(zlib.compress(data, wbits=-15)) for data in raw)
    assert [r.attrs["records"] for r in got["save.encode"]] == [1, 9001]


@pytest.mark.parametrize("case", sorted(PARENT_CONTAINERS))
def test_write_container_writes_the_parents_bytes(
        case, ring, tmp_path, monkeypatch):
    from photon_tpu.io import avro

    monkeypatch.setattr(os, "urandom", lambda n: bytes(range(n)))
    schema = {"name": "R", "type": "record", "fields": [
        {"name": "a", "type": "long"}, {"name": "b", "type": "string"}]}
    path = str(tmp_path / "x.avro")
    if case == "full_blocks":
        encoding = obs.stage_sum("save.encode", coordinate="c")
        writing = obs.stage_sum("save.write", coordinate="c")
        avro.write_container(
            path, schema, ({"a": i, "b": f"v{i}"} for i in range(8)),
            sync_interval=4, encoding=encoding, writing=writing)
        encoding.close()
        writing.close()
    elif case == "null_codec":
        avro.write_container(
            path, schema, [{"a": i, "b": f"v{i}"} for i in range(7)],
            sync_interval=4, codec="null")
    else:
        avro.write_container(path, schema, [])
    assert _sha(path) == PARENT_CONTAINERS[case]
    # Only a caller that hands over its stages leaves records.
    names = {r.name for r in _done(ring)}
    assert names == ({"save.encode", "save.write"}
                     if case == "full_blocks" else set())
    assert avro.read_container(path)[1] == [
        {"a": i, "b": f"v{i}"}
        for i in range({"full_blocks": 8, "null_codec": 7,
                        "no_records": 0}[case])]


# ---------------------------------------------------------------------------
# compile durations
# ---------------------------------------------------------------------------


def test_compile_durations_fill_the_stats_and_the_ring_once(ring):
    from photon_tpu.utils import cache_stats, enable_compilation_cache

    enable_compilation_cache()
    before = cache_stats()
    assert {"trace_seconds", "lower_seconds", "backend_compile_seconds",
            "cache_load_seconds"} <= set(before)

    def inner(x):
        return x * 3.0

    fresh = jax.jit(lambda x: jax.jit(inner)(x).sum() + 1.0)
    x = np.arange(7.0, dtype=np.float32)  # no program of its own
    fresh(x).block_until_ready()
    cold = cache_stats()
    me = threading.current_thread().name

    def mine():
        # (Another test's background AOT compile may still be landing
        # records of its own thread.)
        return [r for r in ring.completed() if r.thread == me]

    got: dict = {}
    for r in mine():
        got.setdefault(r.name, []).append(r)
    for key, name in (("trace_seconds", "compile.trace"),
                      ("lower_seconds", "compile.lower"),
                      ("backend_compile_seconds", "compile.backend")):
        spent = sum(r.seconds for r in got[name])
        assert spent > 0.0
        assert cold[key] - before[key] >= spent - 1e-9
        for r in got[name]:
            assert r.kind == "event" and r.attrs["fun_name"]
            assert r.seconds == pytest.approx(r.t1 - r.t0, abs=1e-6)
    # The jit inside the jit is traced inside the outer trace: one record.
    assert len(got["compile.trace"]) == 1
    count = len(mine())
    fresh(x).block_until_ready()  # warm: nothing compiles, nothing lands
    assert len(mine()) == count


def test_a_cache_load_lands_under_its_own_name(ring):
    from photon_tpu.data import pipeline
    from photon_tpu.utils import cache_stats, compile_cache

    # An earlier test's background AOT compile may still load from the
    # cache (the ring filters its records, the counter cannot): let it
    # land before the counter is read.
    pipeline.compile_executor.shutdown()
    before = cache_stats()["cache_load_seconds"]
    compile_cache._on_duration(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    compile_cache._on_duration("/jax/some/other_duration", 9.0)
    assert cache_stats()["cache_load_seconds"] == pytest.approx(before + 0.5)
    assert [(r.name, r.seconds) for r in _done(ring)] == [
        ("compile.cache_load", 0.5)]


# ---------------------------------------------------------------------------
# the fused program: stages around it, scopes inside it
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_fit():
    from photon_tpu.analysis import program

    with jax.enable_x64(False):
        est, data = program._tiny_glmix()
        datasets, _ = est.prepare(data)
        est.fit(data)
        yield est, data, datasets


def test_a_warm_fit_adds_a_handful_of_stages_and_no_compile(ring, tiny_fit):
    est, data, _ = tiny_fit
    with jax.enable_x64(False):
        est.fit(data)
    done = _done(ring)
    assert [r.path for r in done] == [
        "fit/fit.operands", "fit/fit.dispatch", "fit"]
    assert len(done) <= 8 and all(r.kind == "stage" for r in done)
    fit = done[-1]
    assert sum(r.seconds for r in done[:-1]) <= fit.seconds


def test_the_fused_programs_carry_their_scopes(tiny_fit):
    est, data, datasets = tiny_fit
    with jax.enable_x64(False):
        coords = est._build_coordinates(
            datasets, {}, {}, logical_rows=data.num_samples)
        fused = est._fused_for(coords, datasets)
        fit_text = fused.lower(coords).as_text(debug_info=True)
        mat_text = fused.lower_materialize(coords).as_text(debug_info=True)
    # (The lowered text, not the compiled one: the persistent cache's key
    # leaves metadata out, so a cached executable keeps the names it was
    # compiled with.)
    for cid in ("global", "per-user"):
        for phase in ("residual", "score"):
            assert f"coord.{cid}/{phase}/" in fit_text, (cid, phase)
    assert "coord.global/solve.lbfgs/" in fit_text
    # The random effect's solve is a jit of its own inside the program:
    # its call is named by the coordinate, its operations by the route
    # (XLA joins the two when it inlines the call).
    assert "coord.per-user/jit(_solve_block)" in fit_text
    assert re.search(r'"solve\.newton_xla/', fit_text)
    assert "coord.per-user/materialize/" in mat_text
