"""Pallas fused Newton-step kernel vs the batch-minor XLA reference.

Runs the kernel in interpret mode (tests execute on the CPU mesh); the
real-TPU path is exercised by the bench and covered by
kernel_supported's backend gate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from photon_tpu.algorithm.random_effect import _NEWTON_LINE_SEARCH_HALVINGS
from photon_tpu.ops import newton_kernel as nk
from photon_tpu.types import TaskType

# The trial count production actually runs (threaded through the kernel
# call in _solve_newton_batched); the reference step below must match.
TRIALS = _NEWTON_LINE_SEARCH_HALVINGS + 1


def _reference_step(task, x, w, y, wt, off, l2, mt, vm, f):
    """Batch-minor XLA Newton step (the _solve_newton_batched body)."""
    s = x.shape[-1]
    z = jnp.einsum("brs,bs->br", x, w) + off
    from photon_tpu.ops import losses as losses_mod

    loss = losses_mod.get_loss(task)
    c = wt * loss.dzz(z, y)
    h = jnp.einsum("brs,brt->bst", x * c[:, :, None], x)
    h = h + (l2 + (1.0 - vm))[:, :, None] * jnp.eye(s, dtype=x.dtype)[None]
    g = (jnp.einsum("brs,br->bs", x, wt * loss.dz(z, y))
         + l2 * (w - mt)) * vm
    h_sb = jnp.transpose(h, (1, 2, 0))

    def cg_step(_, st):
        xx, rr, pp, rs = st
        hp = jnp.sum(h_sb * pp[None, :, :], axis=1)
        alpha = rs / jnp.maximum(jnp.sum(pp * hp, axis=0), 1e-30)
        xx = xx + alpha[None] * pp
        rr = rr - alpha[None] * hp
        rs2 = jnp.sum(rr * rr, axis=0)
        pp = rr + (rs2 / jnp.maximum(rs, 1e-30))[None] * pp
        return xx, rr, pp, rs2

    b0 = -jnp.transpose(g)
    d0, _, _, _ = lax.fori_loop(
        0, s, cg_step,
        (jnp.zeros_like(b0), b0, b0, jnp.sum(b0 * b0, axis=0)))
    d = jnp.transpose(d0) * vm
    gd = jnp.sum(g * d, axis=-1)
    bad = gd >= 0.0
    d = jnp.where(bad[:, None], -g, d)
    gd = jnp.where(bad, -jnp.sum(g * g, axis=-1), gd)
    zd = jnp.einsum("brs,bs->br", x, d)
    ts = 0.5 ** jnp.arange(TRIALS, dtype=x.dtype)
    z_t = z[None] + ts[:, None, None] * zd[None]
    loss_t = loss.loss(z_t, y[None])
    w_t = w[None] + ts[:, None, None] * d[None]
    f_t = jnp.sum(wt[None] * loss_t, axis=-1) + 0.5 * jnp.sum(
        l2[None] * (w_t - mt[None]) ** 2, axis=-1)
    armijo = f_t <= f[None] + 1e-4 * ts[:, None] * gd[None]
    first = jnp.argmax(armijo, axis=0)
    any_ok = jnp.any(armijo, axis=0)
    t_sel = ts[first]
    f_sel = jnp.take_along_axis(f_t, first[None], axis=0)[0]
    improved = any_ok & (f_sel < f)
    w_new = jnp.where(improved[:, None], w + t_sel[:, None] * d, w)
    z2 = jnp.einsum("brs,bs->br", x, w_new) + off
    f_new = jnp.sum(wt * loss.loss(z2, y), axis=-1) + 0.5 * jnp.sum(
        l2 * (w_new - mt) ** 2, axis=-1)
    g_new = (jnp.einsum("brs,br->bs", x, wt * loss.dz(z2, y))
             + l2 * (w_new - mt)) * vm
    return w_new, f_new, g_new, improved


@pytest.mark.parametrize(
    "task,labels",
    [
        (TaskType.LOGISTIC_REGRESSION, "01"),
        # {-1,1} labels: the positive-response threshold must apply
        # inside the kernel exactly as in ops/losses.py (review
        # regression: raw labels silently fit a different model).
        (TaskType.LOGISTIC_REGRESSION, "pm1"),
        (TaskType.POISSON_REGRESSION, "counts"),
    ],
)
def test_kernel_matches_xla_step(rng, task, labels):
    b, r, s = 37, 8, 5
    x = rng.normal(size=(b, r, s)).astype(np.float32)
    w = (rng.normal(size=(b, s)) * 0.1).astype(np.float32)
    if labels == "counts":
        y = rng.poisson(1.0, size=(b, r)).astype(np.float32)
    elif labels == "pm1":
        y = np.where(rng.random((b, r)) > 0.5, 1.0, -1.0).astype(
            np.float32)
    else:
        y = (rng.random((b, r)) > 0.5).astype(np.float32)
    wt = rng.random((b, r)).astype(np.float32) + 0.5
    off = (rng.normal(size=(b, r)) * 0.1).astype(np.float32)
    l2 = np.ones((b, s), np.float32)
    mt = np.zeros((b, s), np.float32)
    vm = np.ones((b, s), np.float32)
    vm[:, -1] = 1.0
    vm[3, -1] = 0.0  # a padded slot
    x[3, :, -1] = 0.0

    from photon_tpu.ops import losses as losses_mod

    loss = losses_mod.get_loss(task)
    z = np.einsum("brs,bs->br", x, w) + off
    f0 = (wt * np.asarray(loss.loss(jnp.asarray(z), jnp.asarray(y)))).sum(
        -1) + 0.5 * (l2 * (w - mt) ** 2).sum(-1)
    f0 = f0.astype(np.float32)

    ref = _reference_step(
        task, *(jnp.asarray(a) for a in (x, w, y, wt, off, l2, mt, vm)),
        jnp.asarray(f0))

    bp = nk.pad_lanes(b)
    pad3 = np.zeros((bp, r, s), np.float32)
    pad3[:b] = x
    x_l = jnp.asarray(np.transpose(pad3, (2, 1, 0)))

    def lanes2(a):
        p = np.zeros((bp,) + a.shape[1:], np.float32)
        p[:b] = a
        return jnp.asarray(p.T)

    out = nk.newton_step_lanes(
        x_l, lanes2(w), lanes2(y), lanes2(wt), lanes2(off), lanes2(l2),
        lanes2(mt), lanes2(vm),
        jnp.asarray(np.pad(f0, (0, bp - b))[None, :]),
        r=r, s=s, task=task, trials=TRIALS, interpret=True,
    )
    w_k = np.asarray(out[0]).T[:b]
    f_k = np.asarray(out[1])[0, :b]
    g_k = np.asarray(out[2]).T[:b]
    imp_k = np.asarray(out[3])[0, :b] > 0

    # fp32 accumulation-order noise through CG (and exp for Poisson)
    # bounds the achievable agreement; improved-flags must match exactly.
    np.testing.assert_allclose(w_k, np.asarray(ref[0]), rtol=2e-3,
                               atol=2e-4)
    np.testing.assert_allclose(f_k, np.asarray(ref[1]), rtol=2e-3,
                               atol=2e-4)
    np.testing.assert_allclose(g_k, np.asarray(ref[2]), rtol=5e-3,
                               atol=5e-4)
    np.testing.assert_array_equal(imp_k, np.asarray(ref[3]))


def test_kernel_supported_gates(rng):
    # CPU backend (the test env) must NOT select the kernel by default...
    assert not nk.kernel_supported(
        TaskType.LOGISTIC_REGRESSION, jnp.float32, 64, 17)
    # ...and never for f64, unsupported losses, or over-budget blocks.
    assert not nk.kernel_supported(
        TaskType.LOGISTIC_REGRESSION, jnp.float64, 64, 17)
    assert not nk.kernel_supported(
        TaskType.LINEAR_REGRESSION, jnp.float32, 64, 17)
    assert not nk.kernel_supported(
        TaskType.LOGISTIC_REGRESSION, jnp.float32, 4096, 17)


# (row cap, sub_dim) of the benchmark's GLMix buckets, engaged as on the
# chip: users x 17 at 32 / 64 / 128 rows and movies x 9 at 256 / 512 under
# the ratio-2 ladder are all served; the movies' old bucket, 1024 x 9
# (17.9 MB estimated against the 14 MiB budget), was the one shape the
# gate refused, and it went to the XLA step.
@pytest.mark.parametrize("r,s,served", [
    (32, 17, True), (64, 17, True), (128, 17, True), (256, 17, True),
    (256, 9, True), (512, 9, True), (1024, 9, False),
])
def test_gate_on_the_benchmarks_bucket_shapes(monkeypatch, r, s, served):
    monkeypatch.setenv("PHOTON_NEWTON_KERNEL", "force")
    assert nk.kernel_supported(
        TaskType.LOGISTIC_REGRESSION, jnp.float32, r, s) is served
    assert (nk._vmem_estimate_bytes(r, s) <= nk._VMEM_BUDGET_BYTES) is served


def test_force_flag_on_cpu_selects_kernel_with_interpret(monkeypatch, rng):
    """A force-flagged CPU run must route through interpret=True rather
    than crashing in Mosaic lowering (TPU-only). kernel_supported says
    yes, interpret_required says 'interpreter', and the forced step
    actually executes and matches the XLA reference."""
    monkeypatch.setenv("PHOTON_NEWTON_KERNEL", "force")
    assert nk.kernel_supported(
        TaskType.LOGISTIC_REGRESSION, jnp.float32, 64, 17)
    assert nk.interpret_required()  # CPU backend in the test env

    b, r, s = 8, 16, 3
    x = rng.normal(size=(b, r, s)).astype(np.float32)
    w = np.zeros((b, s), np.float32)
    y = (rng.uniform(size=(b, r)) > 0.5).astype(np.float32)
    wt = np.ones((b, r), np.float32)
    off = np.zeros((b, r), np.float32)
    l2 = np.full((b, s), 0.5, np.float32)
    mt = np.zeros((b, s), np.float32)
    vm = np.ones((b, s), np.float32)

    from photon_tpu.ops import losses as losses_mod

    loss = losses_mod.get_loss(TaskType.LOGISTIC_REGRESSION)
    z0 = jnp.einsum("brs,bs->br", x, w) + off
    f0 = jnp.sum(wt * loss.loss(z0, y), axis=-1) + 0.5 * jnp.sum(
        l2 * (w - mt) ** 2, axis=-1)

    bp = nk.pad_lanes(b)
    pad = lambda a: np.pad(a, [(0, bp - b)] + [(0, 0)] * (a.ndim - 1))
    x_l = jnp.asarray(np.transpose(pad(x), (2, 1, 0)))
    to_l = lambda a: jnp.asarray(np.transpose(pad(a)))
    w_k, f_k, g_k, imp_k = nk.newton_step_lanes(
        x_l, to_l(w), to_l(y), to_l(wt), to_l(off), to_l(l2), to_l(mt),
        to_l(vm), jnp.asarray(np.pad(np.asarray(f0), (0, bp - b)))[None, :],
        r=r, s=s, task=TaskType.LOGISTIC_REGRESSION, trials=TRIALS,
        interpret=nk.interpret_required(),
    )
    ref = _reference_step(
        TaskType.LOGISTIC_REGRESSION,
        *(jnp.asarray(a) for a in (x, w, y, wt, off, l2, mt, vm)),
        jnp.asarray(f0),
    )
    np.testing.assert_allclose(
        np.transpose(np.asarray(w_k))[:b], np.asarray(ref[0]),
        rtol=2e-3, atol=2e-4)
