"""Every pallas_call lowers for the TPU, checked on the CPU.

The parity suites run the kernels through the Pallas INTERPRETER, which
accepts block layouts Mosaic refuses: until this file, two of the three
kernels had block shapes the installed JAX rejected before Mosaic ever
saw them ("the last two dimensions of your block shape are divisible by
8 and 128 ... or be equal to the respective dimensions of the overall
array"), and nothing in tier-1 could notice. Here each wrapper is traced
abstractly and lowered with ``lowering_platforms=("tpu",)`` — no chip,
no execution — at the shapes ``chip_smoke.py`` runs: the 64 / 100 000 x
17 / 20 000 x 9 GLMix, its bucket shapes, and the serve ladder
1/8/64/512. What lowering cannot see (Mosaic's own compile: VMEM
limits, unsupported ops) is what ``chip_smoke.py`` proves on the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.ops import cg_kernel as ck
from photon_tpu.ops import newton_kernel as nk
from photon_tpu.ops import placement
from photon_tpu.ops import segment_reduce as sr
from photon_tpu.ops import serve_kernel as sk
from photon_tpu.types import TaskType

N_ROWS, D = 4_000_000, 64
E_USER, S_USER, E_MOVIE, S_MOVIE = 100_000, 17, 20_000, 9


def sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def lower_tpu(fn, *args, **kwargs) -> str:
    """Trace ``fn`` abstractly and lower it for the TPU; returns the
    module text (a Mosaic kernel is a ``tpu_custom_call``). x64 is OFF,
    as on the chip (the harness turns it on; a Python float in a kernel
    body would trace as f64, which Mosaic has no cast for)."""
    with jax.enable_x64(False):
        traced = (fn if hasattr(fn, "trace") else jax.jit(fn)).trace(
            *args, **kwargs
        )
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the lowering"
    return text


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The state of the gates ON the chip: engaged, never interpreted."""
    for mod, flag in (
        (nk, "PHOTON_NEWTON_KERNEL"),
        (sr, "PHOTON_SEGMENT_KERNEL"),
        (sk, "PHOTON_SERVE_KERNEL"),
    ):
        monkeypatch.setenv(flag, "force")
        monkeypatch.setattr(mod, "interpret_required", lambda: False)


# (rows-per-entity cap, sub_dim) of the smoke's buckets: users hold
# ~40 rows (buckets 64 / 128, cap 512), movies ~200 (256 / 512).
@pytest.mark.parametrize("r,s", [
    (64, S_USER), (128, S_USER), (512, S_USER), (256, S_MOVIE),
    (512, S_MOVIE),
])
@pytest.mark.parametrize("task", [
    TaskType.LOGISTIC_REGRESSION, TaskType.POISSON_REGRESSION,
])
def test_newton_step_lowers(r, s, task):
    assert nk._vmem_estimate_bytes(r, s) <= nk._VMEM_BUDGET_BYTES
    bp = 1024
    lower_tpu(
        nk.newton_step_lanes,
        sds((s, r, bp)), sds((s, bp)), sds((r, bp)), sds((r, bp)),
        sds((r, bp)), sds((s, bp)), sds((s, bp)), sds((s, bp)),
        sds((1, bp)),
        r=r, s=s, task=task,
    )


# [entities, width] of the direct route's CG systems: yahoo.refit's
# per-user blocks (512 / 256 / 128 rows) and linear.refit's buckets.
@pytest.mark.parametrize("b, s", [
    (1213, 197), (4017, 126), (1776, 83), (72380, S_USER), (20000, S_MOVIE),
])
def test_cg_solve_lowers(b, s):
    assert ck._vmem_estimate_bytes(s) <= ck._VMEM_BUDGET_BYTES
    text = lower_tpu(ck.spd_solve_lanes, sds((b, s, s)), sds((b, s)))
    assert "cg_solve_lanes" in text


def test_newton_gate_is_a_vmem_bound():
    """Shapes Mosaic refuses for scoped VMEM (established by AOT
    compiles for a v5e topology) are closed in code; the old r * s <=
    16384 bound admitted all of these."""
    ok = TaskType.LOGISTIC_REGRESSION, jnp.float32
    for r, s in [(1024, 16), (2048, 8), (960, 17), (256, 64), (4096, 2)]:
        assert r * s <= 16_384
        assert nk._vmem_estimate_bytes(r, s) > nk._VMEM_BUDGET_BYTES
    # the Hessian scratch alone bounds s, whatever r is
    assert nk._vmem_estimate_bytes(8, 2048) > nk._VMEM_BUDGET_BYTES
    assert not nk.kernel_supported(*ok, 8, 2048)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_reduce_score_scatter_lowers(compiled_kernels, dtype):
    # models/game._bucket_score_add's scatter: one user bucket.
    b, r = 50_000, 64
    text = lower_tpu(
        lambda z, ids, zb, valid: sr.scatter_add_rows(z, ids, zb, valid),
        sds((N_ROWS,)), sds((b, r), jnp.int32), sds((b, r), dtype),
        sds((b, r), jnp.bool_),
    )
    assert "segment_reduce" in text  # the kernel's stable name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_reduce_score_tail_lowers(compiled_kernels, dtype):
    # the width-capped score table's COO overflow tail
    m = 300_000
    lower_tpu(
        lambda v, ids: sr.sorted_segment_sum(
            v, ids, N_ROWS, multiplicity=3,
            site="segment_reduce/score_tail",
        ),
        sds((m,), dtype), sds((m,), jnp.int32),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_reduce_densify_lowers(compiled_kernels, dtype):
    b, r, k, s = 512, 32, 6, 200
    out = jax.eval_shape(
        lambda i, v: sr.densify_ell_blocks(i, v, s),
        sds((b, r, k), jnp.int32), sds((b, r, k), dtype),
    )
    assert out is not None and out.shape == (b, r, s)
    lower_tpu(
        lambda i, v: sr.densify_ell_blocks(i, v, s),
        sds((b, r, k), jnp.int32), sds((b, r, k), dtype),
    )


def test_segment_reduce_gram_route_lowers(compiled_kernels):
    b, r, k, s = 256, 16, 6, 40
    assert sr.ell_gram_supported(b, r, k, s, grad_mult=2, hess_mult=3)
    lower_tpu(
        lambda i, v, w: (
            sr.ell_gram_blocks(i, v, w, s, multiplicity=3),
            sr.ell_segment_slots(i, v, w, s, multiplicity=2),
        ),
        sds((b, r, k), jnp.int32), sds((b, r, k)), sds((b, r)),
    )


def _score_args(rung, wdtype, kinds, k=8):
    def feat(kind, d):
        if kind == "dense":
            return sds((rung, d))
        return (sds((rung, k), jnp.int32), sds((rung, k)))

    feats = (
        feat(kinds[0], D), feat(kinds[1], S_USER), feat(kinds[2], S_MOVIE)
    )
    return (
        (sds((D,), wdtype),),
        (sds((E_USER, S_USER), wdtype), sds((E_MOVIE, S_MOVIE), wdtype)),
        (sds((E_USER, S_USER), jnp.int32),
         sds((E_MOVIE, S_MOVIE), jnp.int32)),
        feats,
        (sds((rung,), jnp.int32), sds((rung,), jnp.int32)),
    )


@pytest.mark.parametrize("wdtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rung", [1, 8, 64, 512])
def test_fused_score_lowers_at_every_rung(rung, wdtype):
    kinds = ("dense", "dense", "dense")  # the CLI's default request spec
    text = lower_tpu(
        lambda *a: sk.fused_score(
            *a, spec_kinds=kinds, fe_feat=(0,), re_feat=(1, 2),
            interpret=False,
        ),
        *_score_args(rung, wdtype, kinds),
    )
    assert "serve_score" in text


@pytest.mark.parametrize("rung", [12, 200])
def test_fused_score_lowers_off_ladder_and_sparse(rung):
    # a rung that is neither below 8 nor a multiple of the row tile,
    # with sparse request specs on every shard
    kinds = ("sparse", "sparse", "dense")
    lower_tpu(
        lambda *a: sk.fused_score(
            *a, spec_kinds=kinds, fe_feat=(0,), re_feat=(1, 2),
            interpret=False,
        ),
        *_score_args(rung, jnp.float32, kinds),
    )


def test_serve_gate_is_bounded_by_shape(monkeypatch):
    monkeypatch.setenv("PHOTON_SERVE_KERNEL", "force")
    smoke = dict(
        fe_dims=(("dense", D, 0),),
        re_dims=(("dense", S_USER, 0, S_USER),
                 ("dense", S_MOVIE, 0, S_MOVIE)),
    )
    assert sk.kernel_supported("float32", **smoke)
    # a 10M-wide sparse fixed effect: its [1, d] weight row alone is
    # past the VMEM block bound
    assert not sk.kernel_supported(
        "float32", fe_dims=(("sparse", 10_000_000, 32),), re_dims=(),
    )
    # a wide random subspace: the body unrolls one term per slot
    assert not sk.kernel_supported(
        "float32", fe_dims=(("dense", D, 0),),
        re_dims=(("dense", 2048, 0, 1024),),
    )


def test_gates_close_on_operands_that_span_devices(monkeypatch, devices):
    """GSPMD does not partition a Mosaic kernel ("Mosaic kernels cannot
    be automatically partitioned"): the caller that holds the concrete
    arrays observes the placement and the gates close, forced or not."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices[:4]), ("data",))
    sharded = jax.device_put(
        jnp.zeros((8, 4)), NamedSharding(mesh, P("data"))
    )
    replicated = jax.device_put(jnp.zeros(3), NamedSharding(mesh, P()))
    assert placement.spans_devices({"a": sharded})
    assert placement.spans_devices((jnp.zeros(2), replicated))
    assert not placement.spans_devices((jnp.zeros(2), np.zeros(2), None))

    monkeypatch.setenv("PHOTON_NEWTON_KERNEL", "force")
    monkeypatch.setenv("PHOTON_SEGMENT_KERNEL", "force")
    ok = TaskType.LOGISTIC_REGRESSION, jnp.float32, 64, 17
    assert nk.kernel_supported(*ok)
    assert not nk.kernel_supported(*ok, spmd=True)
    assert sr.kernel_supported(1000, 100, jnp.float32)
    assert not sr.kernel_supported(1000, 100, jnp.float32, spmd=True)
    assert not sr.ell_gram_supported(
        8, 4, 2, 5, grad_mult=1, hess_mult=1, spmd=True)
