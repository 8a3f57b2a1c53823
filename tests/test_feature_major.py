"""The fixed effect's solve reads dense features feature-major
(``data.dataset.FeatureMajorFeatures``, taken by ``feature_major`` at the
top of ``algorithm.problems._run_impl`` and in ``FusedFit._fe_score``).

The view computes what ``DenseFeatures`` computes, in the same dtypes;
every other batch is left as it is; every solver route and both variance
computations solve through it; and on a v5e, compiled without a chip, the
program reads the features as stored: no relaid-out copy of them.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu import optim
from photon_tpu.algorithm import problems
from photon_tpu.data import dataset
from photon_tpu.algorithm.problems import (
    GLMOptimizationConfiguration,
    GLMOptimizationProblem,
    VarianceComputationType,
)
from photon_tpu.data.dataset import (
    DenseFeatures,
    FeatureMajorFeatures,
    GLMBatch,
    feature_layout,
    feature_major,
    make_dense_batch,
    make_sparse_batch,
)
from photon_tpu.ops import glm as glm_ops
from photon_tpu.ops import losses
from photon_tpu.ops.normalization import NormalizationContext
from photon_tpu.types import TaskType

N = 300  # no multiple of 128


def _batch(x, rng):
    n = x.shape[0]
    return GLMBatch(
        features=DenseFeatures(x),
        labels=jnp.asarray(rng.uniform(size=n) < 0.4, jnp.float32),
        offsets=jnp.asarray(0.1 * rng.normal(size=n), jnp.float32),
        weights=jnp.asarray(rng.uniform(0.5, 1.5, size=n), jnp.float32),
    )


@pytest.mark.parametrize("d", [9, 64, 130])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_view_computes_what_dense_features_compute(rng, dtype, d):
    """The feature-major products (the TPU's) equal ``DenseFeatures``' to
    float32 rounding, in the same dtypes; on the CPU the view's products
    are ``DenseFeatures``' own, to the bit."""
    x = jnp.asarray(rng.normal(size=(N, d)), dtype)
    w = jnp.asarray(rng.normal(size=d), jnp.float32)
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    batch = _batch(x, rng)
    dense = batch.features
    view = feature_major(batch).features
    assert isinstance(view, FeatureMajorFeatures)
    assert view.xt.shape == (d, N) and view.num_features == d

    def close(got, want):
        assert got.dtype == want.dtype and got.shape == want.shape
        scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=0, atol=1e-5 * scale)

    for name, tpu, v in (
        ("matvec", dataset._fm_matvec, w),
        ("rmatvec", dataset._fm_rmatvec, g),
        ("rmatvec_sq", dataset._fm_rmatvec_sq, g),
        ("gram", dataset._fm_gram, g),
    ):
        # Op by op, as each dtype rounds (a fused bf16 product may keep
        # its squares in float32, and not the same ones both ways).
        close(tpu(view.xt, v), getattr(dense, name)(v))
        product = jax.jit(lambda f, v, name=name: getattr(f, name)(v))
        np.testing.assert_array_equal(product(view, v), product(dense, v))

    loss = losses.get_loss(TaskType.LOGISTIC_REGRESSION)
    viewed = feature_major(batch)
    norm = NormalizationContext(
        factors=jnp.asarray(rng.uniform(0.5, 2.0, size=d), jnp.float32),
        shifts=jnp.asarray(
            np.append(rng.normal(size=d - 1), 0.0), jnp.float32),
        intercept_index=d - 1,
    )
    for n_ctx in (None, norm):
        for hessian in (glm_ops.hessian_diagonal, glm_ops.hessian_matrix):
            np.testing.assert_array_equal(
                jax.jit(hessian, static_argnums=1)(viewed, loss, w, n_ctx),
                jax.jit(hessian, static_argnums=1)(batch, loss, w, n_ctx))


def test_the_helper_leaves_any_other_batch_as_it_is(rng):
    labels = rng.uniform(size=4) < 0.5
    ell = make_sparse_batch(
        [[(0, 1.0), (3, -2.0)], [(1, 0.5)], [], [(2, 1.5), (4, 1.0)]],
        5, labels)
    assert feature_major(ell) is ell
    assert feature_layout(ell) == "row_major"
    stacked = GLMBatch(
        features=DenseFeatures(jnp.zeros((3, 4, 5))),
        labels=jnp.zeros((3, 4)), offsets=jnp.zeros((3, 4)),
        weights=jnp.ones((3, 4)))
    assert feature_major(stacked) is stacked
    assert feature_layout(stacked) == "row_major"
    dense = make_dense_batch(rng.normal(size=(4, 5)), labels)
    assert feature_layout(dense) == "feature_major"
    viewed = feature_major(dense)
    assert feature_layout(viewed) == "feature_major"
    assert feature_major(viewed) is viewed
    for name in ("labels", "offsets", "weights"):
        assert getattr(viewed, name) is getattr(dense, name)


def _config(route):
    reg, weight = optim.RegularizationType.L2, 0.5
    opt = optim.OptimizerConfig()
    variance = VarianceComputationType.SIMPLE
    if route == "owlqn":
        reg, weight = optim.RegularizationType.L1, 30.0
    elif route == "tron":
        opt = optim.OptimizerConfig(optimizer_type=optim.OptimizerType.TRON)
    elif route == "lbfgs_full":
        variance = VarianceComputationType.FULL
    return GLMOptimizationConfiguration(
        optimizer=opt,
        regularization=optim.RegularizationContext(reg),
        regularization_weight=weight,
        variance_computation=variance,
    )


@pytest.mark.parametrize("route", ["owlqn", "tron", "lbfgs_full"])
def test_every_route_and_variance_solves_through_the_view(
        rng, monkeypatch, route):
    """The same problem on the same numbers, once as dense features (read
    through the view) and once as ELL rows (read row by row, as before):
    the solutions and their variances agree to float64 rounding of the
    solver's own stop."""
    n, d = 203, 7
    x = rng.normal(size=(n, d))
    x[:, -1] = 1.0
    z = x @ rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(np.float64)
    dense = make_dense_batch(x, y, dtype=jnp.float64)
    ell = make_sparse_batch(
        [[(j, float(v)) for j, v in enumerate(row)] for row in x], d, y,
        dtype=jnp.float64)
    seen = []

    def spy(batch):
        out = feature_major(batch)
        seen.append(type(out.features).__name__)
        return out

    monkeypatch.setattr(problems, "feature_major", spy)
    problems._run_jit.clear_cache()
    problem = GLMOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION, _config(route), intercept_index=d - 1)
    got = problem.run(dense).model.coefficients
    want = problem.run(ell).model.coefficients
    assert seen == ["FeatureMajorFeatures", "SparseFeatures"]
    # On the CPU the view leaves the solve's arithmetic as it was.
    monkeypatch.setattr(problems, "feature_major", lambda batch: batch)
    problems._run_jit.clear_cache()
    unviewed = problem.run(dense).model.coefficients
    np.testing.assert_array_equal(got.means, unviewed.means)
    np.testing.assert_array_equal(got.variances, unviewed.variances)
    if route == "owlqn":
        assert np.count_nonzero(np.asarray(got.means) == 0.0) > 0
        np.testing.assert_array_equal(
            np.asarray(got.means) == 0.0, np.asarray(want.means) == 0.0)
    np.testing.assert_allclose(got.means, want.means, rtol=1e-6, atol=1e-8)
    assert np.all(np.isfinite(got.variances))
    np.testing.assert_allclose(
        got.variances, want.variances, rtol=1e-6, atol=1e-10)


@pytest.fixture(scope="module")
def one_v5e_chip():
    """One device of a described v5e: a compile for it needs no chip."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # libtpu absent or refusing
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_on_a_v5e_the_solve_reads_the_features_as_stored(
        one_v5e_chip, no_compile_cache):
    """``_run_impl`` on a logistic ``[65 536, 64]`` float32 batch, compiled
    for a v5e without a chip: no copy or transpose yields the features in
    either orientation, and the scratch stays under the features' own
    bytes (the row-major reading padded them to 128 lanes first: a copy
    of twice their bytes)."""
    n, d = 65_536, 64

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    with jax.enable_x64(False):
        batch = GLMBatch(
            features=DenseFeatures(arg((n, d))), labels=arg((n,)),
            offsets=arg((n,)), weights=arg((n,)))
        compiled = problems._run_jit.trace(
            batch, arg((d,)), arg(()), arg(()), NormalizationContext(),
            None, arg(()),
            task=TaskType.LOGISTIC_REGRESSION,
            opt_config=optim.OptimizerConfig(),
            use_owlqn=False, intercept_index=d - 1,
            variance_computation=VarianceComputationType.NONE,
        ).lower().compile()
    text = compiled.as_text()
    shape = re.compile(rf"f32\[({n},{d}|{d},{n})\]")
    moves = [
        line.strip()[:120] for line in text.splitlines()
        if re.search(r"= \S+ (copy|transpose|copy-start)\(", line)
        and shape.search(line.split("=", 1)[1].split("(", 1)[0])
    ]
    assert moves == []
    # The solve's passes read the stored array through its [d, n] bitcast.
    assert re.search(rf"f32\[{d},{n}\]\{{1,0", text)
    features_bytes = n * d * 4
    assert 0 < compiled.memory_analysis().temp_size_in_bytes < features_bytes
