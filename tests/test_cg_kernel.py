"""The direct route's lane CG kernel (``ops/cg_kernel.py``) against the
vmapped XLA CG it replaces, through the Pallas interpreter; the routed
path through ``_solve_block`` with the gate opened by monkeypatch; and the
gate, by shape and placement, as ``solve_route`` and ``_solve_block``
both read it."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import optim
from photon_tpu.algorithm import random_effect as re_mod
from photon_tpu.algorithm.problems import GLMOptimizationConfiguration
from photon_tpu.algorithm.random_effect import (
    RandomEffectCoordinate,
    _spd_solve_cg,
    solve_route,
)
from photon_tpu.data.random_effect import EntityBlocks
from photon_tpu.ops import cg_kernel as ck
from photon_tpu.ops import placement
from photon_tpu.types import TaskType

from test_wide_subspaces import _estimator, _wide_game


def _systems(rng, b, s, rows):
    """A bucket's normal equations as the direct route forms them: rows
    of a genre bag (the intercept in slot 0 and one to four ones among
    the valid slots) with weights, an L2 diagonal that is a prior's
    (inverse variances over two decades; the intercept unpenalised), and
    two padding slots with a unit diagonal and zero right-hand side. The
    intercept's eigenvalue grows with the rows: ``rows`` sets the
    conditioning: a median of 700-5 000, a few entities to 6e4."""
    valid = s - 2 if s > 3 else s
    x = np.zeros((b, rows, s), np.float32)
    x[:, :, 0] = 1.0
    if valid > 1:
        cols = rng.integers(1, valid, size=(b, rows, 4))
        keep = np.arange(4)[None, None, :] < rng.integers(1, 5, (b, rows, 1))
        np.put_along_axis(x, np.where(keep, cols, 0), 1.0, axis=2)
    wt = rng.uniform(0.5, 2.0, size=(b, rows)).astype(np.float32)
    h = np.matmul(np.transpose(x * wt[:, :, None], (0, 2, 1)), x)
    vm = np.zeros((b, s), np.float32)
    vm[:, :valid] = 1.0
    l2 = (1.0 / np.exp(rng.uniform(0.0, np.log(100.0), (b, s)))) * vm
    l2[:, 0] = 0.0
    h = h + np.eye(s, dtype=np.float32)[None] * (l2 + 1.0 - vm)[:, :, None]
    rhs = np.einsum("brs,br->bs", x, wt * rng.normal(size=(b, rows)))
    rhs = (rhs + l2 * rng.normal(size=(b, s))) * vm
    return h.astype(np.float32), rhs.astype(np.float32)


@pytest.mark.parametrize("b", [5, 130])
@pytest.mark.parametrize("s, rows", [
    (9, 2048), (17, 1024), (83, 512), (126, 256), (197, 512),
])
def test_the_kernel_solves_as_the_xla_cg(rng, b, s, rows):
    h, rhs = _systems(rng, b, s, rows)
    cond = np.linalg.cond(h.astype(np.float64))
    assert 500 < np.median(cond) and cond.max() < 1e5
    got = np.asarray(ck.spd_solve_lanes(
        jnp.asarray(h), jnp.asarray(rhs), interpret=True))
    want = np.asarray(jax.vmap(functools.partial(_spd_solve_cg, sub_dim=s))(
        jnp.asarray(h), jnp.asarray(rhs)))
    assert got.shape == (b, s) and got.dtype == np.float32
    assert np.isfinite(got).all()
    rel = (np.linalg.norm(got - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    assert rel.max() <= 1e-5, (rel.max(), cond.max())
    # padding slots: a unit diagonal and a zero right-hand side solve to 0
    if s > 3:
        assert np.abs(got[:, -2:]).max() <= 1e-6


def test_the_width_the_kernel_serves_is_a_vmem_bound(monkeypatch):
    """Calibrated by AOT compiles for a v5e topology (the module's
    comment): the budget admits every width that compiled, up to 311."""
    monkeypatch.setattr(ck, "interpret_required", lambda: False)
    assert ck.kernel_supported(311, jnp.float32)
    assert not ck.kernel_supported(312, jnp.float32)
    assert ck._vmem_estimate_bytes(314) <= ck._VMEM_LIMIT_BYTES
    assert not ck.kernel_supported(197, jnp.float64)
    assert not ck.kernel_supported(197, jnp.float32, spmd=True)


def test_the_cpu_takes_the_xla_cg():
    assert ck.interpret_required()
    assert not ck.kernel_supported(17, jnp.float32)


# ---- the routed path: _solve_block on a materialized wide ELL bucket


@pytest.fixture
def fresh_solve_block():
    """The gate is read at trace time: drop ``_solve_block``'s traces
    before and after, so that neither a route traced elsewhere nor this
    test's own is served from the jit cache."""
    re_mod._solve_block.clear_cache()
    yield
    re_mod._solve_block.clear_cache()


def _wide_coordinate():
    est = _estimator()
    datasets, _ = est.prepare(_wide_game())
    ds = datasets["per-user"]
    config = GLMOptimizationConfiguration(
        regularization=optim.RegularizationContext(
            optim.RegularizationType.L2),
        regularization_weight=1.0)
    return RandomEffectCoordinate(ds, TaskType.LINEAR_REGRESSION, config)


def _solve_all(coord, seed=0):
    """Every bucket through ``_dispatch_block`` into one table, from a
    nonzero residual vector and warm start."""
    ds = coord.dataset
    rng = np.random.default_rng(seed)
    residuals = jnp.asarray(rng.normal(size=ds.num_rows), jnp.float32)
    w0 = jnp.asarray(
        rng.normal(size=(ds.num_entities, ds.max_sub_dim)), jnp.float32)
    w_all = jnp.zeros((ds.num_entities, ds.max_sub_dim), jnp.float32)
    per_block = []
    for i, block in enumerate(ds.device_blocks()):
        w_all, _, _, _ = coord._dispatch_block(
            block, residuals, w0, w_all, None, block_index=i)
        per_block.append(np.asarray(w_all)[np.asarray(block.entity_codes)])
    return np.asarray(w_all), per_block


def _counting_kernel(monkeypatch):
    calls = []
    real = ck.spd_solve_lanes

    def counted(h, b, **kw):
        calls.append(h.shape)
        return real(h, b, **kw)

    monkeypatch.setattr(ck, "spd_solve_lanes", counted)
    return calls


def _gate_open(s, dtype, *, spmd=False):
    """The gate as it reads on the chip: any width within the budget,
    one device, float32."""
    return (not spmd and jnp.dtype(dtype) == jnp.float32
            and ck._vmem_estimate_bytes(s) <= ck._VMEM_BUDGET_BYTES)


@pytest.mark.parametrize("case", ["kernel", "mesh"])
def test_the_routed_path_gives_the_xla_tables(
        monkeypatch, fresh_solve_block, case):
    """``kernel``: the gate opened, the bucket's CG runs in the kernel
    (interpreted) and the table matches the XLA CG's to float32
    rounding. ``mesh``: the gate as on the chip, but the operands span
    devices (``spmd``): the bucket never takes the kernel, and the table
    is the XLA route's bit for bit."""
    coord = _wide_coordinate()
    (block,) = coord.dataset.device_blocks()
    assert block.x_indices is not None and block.sub_dim > 128
    want, want_blocks = _solve_all(coord)
    re_mod._solve_block.clear_cache()

    calls = _counting_kernel(monkeypatch)
    if case == "kernel":
        monkeypatch.setattr(ck, "kernel_supported", _gate_open)
    else:
        monkeypatch.setattr(ck, "interpret_required", lambda: False)
        monkeypatch.setattr(placement, "spans_devices", lambda tree: True)
    got, got_blocks = _solve_all(coord)
    if case == "mesh":
        assert calls == []
        np.testing.assert_array_equal(got, want)
        return
    assert calls == [(block.num_entities, block.sub_dim, block.sub_dim)]
    assert np.isfinite(got).all() and np.abs(want).max() > 0.1
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)
    for g, w in zip(got_blocks, want_blocks):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-5 * scale)


# ---- the gate on the benchmark's direct rungs, as both readers see it


def _slab(b, r, s):
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    return EntityBlocks(
        entity_codes=sds((b,), i32), x_indices=None,
        x_values=sds((b, r, s), f32), labels=sds((b, r), f32),
        offsets=sds((b, r), f32), weights=sds((b, r), f32),
        row_ids=sds((b, r), i32), proj=sds((b, s), i32),
        penalty_mask=sds((b, s), f32), valid_mask=sds((b, s), f32),
        intercept_slots=sds((b,), i32),
    )


def _traced_scopes(slab, e):
    """The ``solve.<route>`` scopes and whether a ``pallas_call`` is
    among the operations of ``_solve_block``'s trace for ``slab``."""
    sds = jax.ShapeDtypeStruct
    scalar = sds((), jnp.float32)
    with jax.enable_x64(False):
        traced = re_mod._solve_block.trace(
            slab, sds((e,), jnp.float32), None, None,
            sds((e, slab.proj.shape[1]), jnp.float32),
            scalar, scalar, scalar, None,
            sds((e, slab.proj.shape[1]), jnp.float32), None,
            sub_dim=slab.proj.shape[1], task=TaskType.LINEAR_REGRESSION,
            opt_config=optim.OptimizerConfig(),
            use_owlqn=False,
            variance_computation=re_mod.VarianceComputationType.NONE,
            direct=True,
        )
    scopes, prims = set(), set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            prims.add(eqn.primitive.name)
            for part in str(eqn.source_info.name_stack).split("/"):
                if part.startswith("solve."):
                    scopes.add(part)
            for param in eqn.params.values():
                for sub in param if isinstance(param, tuple) else (param,):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)

    walk(traced.jaxpr.jaxpr)
    return scopes, "pallas_call" in prims


# (entities, row cap, width) of the direct rungs the benchmark runs:
# yahoo.refit's per-user blocks (512 / 256 / 128 rows, 197 / 126 / 83
# wide) and a per-item bias rung; linear.refit's users x 17 and movies x 9.
BENCH_RUNGS = [
    (1213, 512, 197), (4017, 256, 126), (1776, 128, 83), (64, 2048, 1),
    (72380, 64, 17), (27620, 128, 17), (20000, 512, 9),
]


@pytest.mark.parametrize("b, r, s, served", [
    rung + (True,) for rung in BENCH_RUNGS
] + [(4, 8, 312, False)])
def test_solve_route_and_solve_block_agree_on_the_benchmarks_rungs(
        monkeypatch, fresh_solve_block, b, r, s, served):
    """On the chip the kernel serves every direct rung the benchmark has;
    a width past the VMEM budget keeps the XLA CG."""
    monkeypatch.setattr(ck, "interpret_required", lambda: False)
    assert ck.kernel_supported(s, jnp.float32) is served
    statics = dict(
        task=TaskType.LINEAR_REGRESSION, opt_config=optim.OptimizerConfig(),
        use_owlqn=False,
        variance_computation=re_mod.VarianceComputationType.NONE,
        direct=True, newton=False,
    )
    route = solve_route(statics, _slab(b, r, s))
    assert route == ("direct_kernel" if served else "direct")
    assert solve_route(statics, _slab(b, r, s), spmd=True) == "direct"
    # the trace needs only the width: a few entities of the rung's shape
    scopes, kernel = _traced_scopes(_slab(3, 4, s), e=5)
    assert scopes == {"solve." + route}
    assert kernel is served
