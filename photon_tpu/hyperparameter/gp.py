"""Gaussian-process regression for Bayesian hyperparameter search.

TPU-native counterpart of photon-lib
hyperparameter/estimators/GaussianProcessEstimator.scala:36 (slice-sampled
kernel hyperparameters, burn-in + posterior samples) and
GaussianProcessModel.scala:118 (GPML Algorithm 2.1 predictions via Cholesky).

Design notes vs the reference:
- The reference keeps a list of Kernel objects (one per posterior sample) and
  loops; here the posterior samples live in one ``[S, p]`` theta matrix and
  the Cholesky factorizations / predictions are ``vmap``-ped over S.
- Observations are padded to a bucket size with a validity mask so the jitted
  likelihood and predict functions serve a growing observation set without
  recompiling every iteration (the search adds one point per step).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.hyperparameter import kernels
from photon_tpu.hyperparameter.slice_sampler import SliceSampler

Array = jax.Array


@functools.cache
def _gp_device():
    """The GP runs on the host CPU backend when one is registered.

    Slice sampling makes hundreds of sequential tiny (n <= ~100) Cholesky
    calls, each a dispatch plus a host pull; the per-call cost on the
    accelerator is not measured on this chip. The main training path is
    unaffected — only the tuner's GP is pinned here.
    """
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def _put(x):
    dev = _gp_device()
    arr = jnp.asarray(x)
    return arr if dev is None else jax.device_put(arr, dev)


def _pad_to_bucket(n: int, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class _Precomputed:
    chols: Array  # [S, n, n]
    alphas: Array  # [S, n]


@dataclasses.dataclass(frozen=True)
class GaussianProcessModel:
    """Posterior GP over the evaluation function (GaussianProcessModel.scala).

    ``thetas`` holds one kernel-hyperparameter sample per row; predictions
    average over samples (the reference's mean over its kernels list).
    """

    kernel_name: str
    x_train: Array  # [n_pad, d]
    y_train: Array  # [n_pad] (already mean-shifted by y_mean)
    y_mean: float
    valid: Array  # [n_pad]
    thetas: Array  # [S, p]
    _pre: _Precomputed

    @property
    def feature_dimension(self) -> int:
        return int(self.x_train.shape[1])

    def predict(self, x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(means, variances) at query points, averaged over theta samples
        (GaussianProcessModel.predict :58-66)."""
        xq = _put(x)
        means, variances = _predict_all(
            self.kernel_name, self.thetas, self._pre.chols, self._pre.alphas,
            self.x_train, self.valid, xq,
        )
        return (
            np.asarray(jnp.mean(means, axis=0) + self.y_mean),
            np.asarray(jnp.mean(variances, axis=0)),
        )

    def predict_transformed(self, x: np.ndarray, transformation) -> np.ndarray:
        """Mean over samples of transformation(mean_s, var_s)
        (predictTransformed :72-84); the transformation sees *shifted* means,
        matching the reference (yPred + yMean happens per kernel there; the
        EI criterion receives the same shifted values either way because the
        best-eval it compares against is shifted identically)."""
        xq = _put(x)
        means, variances = _predict_all(
            self.kernel_name, self.thetas, self._pre.chols, self._pre.alphas,
            self.x_train, self.valid, xq,
        )
        vals = jax.vmap(transformation)(means + self.y_mean, variances)
        return np.asarray(jnp.mean(vals, axis=0))


def _predict_one(name, theta, chol, alpha, x_train, valid, xq):
    """GPML Alg. 2.1 lines 4-6 for one theta sample
    (GaussianProcessModel.predictWithKernel :92-110)."""
    ktrans = kernels.cross(name, theta, x_train, xq, None)  # [n, m]
    ktrans = ktrans * valid[:, None]
    y_pred = ktrans.T @ alpha
    v = jax.scipy.linalg.solve_triangular(chol, ktrans, lower=True)
    amplitude, noise, _ = kernels.split_theta(theta)
    kx_diag = amplitude + noise  # one-matrix apply: f(0)=1 plus noise
    y_var = jnp.maximum(kx_diag - jnp.sum(v * v, axis=0), 1e-12)
    return y_pred, y_var


def _make_precompute(name: str):
    @jax.jit
    def pre(thetas, x, y, valid):
        def one(theta):
            k = kernels.gram(name, theta, x, valid)
            chol = jnp.linalg.cholesky(k)
            alpha = jax.scipy.linalg.cho_solve((chol, True), y * valid)
            return chol, alpha

        chols, alphas = jax.vmap(one)(thetas)
        return _Precomputed(chols=chols, alphas=alphas)

    return pre


_PRECOMPUTE = {n: _make_precompute(n) for n in kernels.KERNEL_NAMES}


def _make_predict(name: str):
    @jax.jit
    def predict(thetas, chols, alphas, x_train, valid, xq):
        return jax.vmap(
            lambda t, c, a: _predict_one(name, t, c, a, x_train, valid, xq)
        )(thetas, chols, alphas)

    return predict


_PREDICT = {n: _make_predict(n) for n in kernels.KERNEL_NAMES}


def _predict_all(name, thetas, chols, alphas, x_train, valid, xq):
    return _PREDICT[name](thetas, chols, alphas, x_train, valid, xq)


class GaussianProcessEstimator:
    """Slice-sample kernel hyperparameters, return a posterior-averaged model.

    Reference: GaussianProcessEstimator.scala:36 — burn-in
    (monteCarloNumBurnInSamples=100) then monteCarloNumSamples=10 posterior
    draws; amplitude/noise sampled jointly (or amplitude alone with fixed
    noise when ``noisy_target`` is False), length scales dimension-wise
    (sampleNext :94-137).
    """

    def __init__(
        self,
        kernel: str = "matern52",
        normalize_labels: bool = False,
        noisy_target: bool = False,
        num_burn_in_samples: int = 100,
        num_samples: int = 10,
        seed: int = 0,
    ):
        if kernel not in kernels.KERNEL_NAMES:
            raise ValueError(f"unknown kernel {kernel!r}")
        self.kernel = kernel
        self.normalize_labels = normalize_labels
        self.noisy_target = noisy_target
        self.num_burn_in_samples = num_burn_in_samples
        self.num_samples = num_samples
        self.seed = seed

    def fit(self, x: np.ndarray, y: np.ndarray) -> GaussianProcessModel:
        x = np.asarray(x, dtype=float)
        y = np.asarray(y, dtype=float)
        if x.ndim != 2 or x.shape[0] == 0:
            raise ValueError("empty input")
        if x.shape[0] != y.shape[0]:
            raise ValueError("x and y must have the same number of rows")
        y_mean = float(np.mean(y)) if self.normalize_labels else 0.0
        y = y - y_mean

        n, d = x.shape
        n_pad = _pad_to_bucket(n)
        x_pad = np.zeros((n_pad, d))
        x_pad[:n] = x
        y_pad = np.zeros(n_pad)
        y_pad[:n] = y
        valid = np.zeros(n_pad)
        valid[:n] = 1.0

        xj = _put(x_pad)
        yj = _put(y_pad)
        vj = _put(valid)

        # The sampler's logp runs host-side: step-out makes O(100) tiny
        # sequential likelihood calls per draw (see log_likelihood_np).
        def logp(theta_np: np.ndarray) -> float:
            return kernels.log_likelihood_np(self.kernel, theta_np, x, y)

        theta = np.asarray(kernels.initial_theta(jnp.asarray(y), d))
        sampler = SliceSampler(rng=np.random.default_rng(self.seed))
        for _ in range(self.num_burn_in_samples):
            theta = self._sample_next(theta, logp, sampler)
        samples = []
        for _ in range(self.num_samples):
            theta = self._sample_next(theta, logp, sampler)
            samples.append(theta.copy())

        thetas = _put(np.stack(samples))
        pre = _PRECOMPUTE[self.kernel](thetas, xj, yj, vj)
        return GaussianProcessModel(
            kernel_name=self.kernel,
            x_train=xj,
            y_train=yj,
            y_mean=y_mean,
            valid=vj,
            thetas=thetas,
            _pre=pre,
        )

    def _sample_next(self, theta, logp, sampler) -> np.ndarray:
        """One sweep: amplitude(+noise), then length scales
        (GaussianProcessEstimator.sampleNext :94-137)."""
        amp_noise = theta[:2]
        ls = theta[2:]

        if self.noisy_target:
            amp_noise = sampler.draw(
                amp_noise,
                lambda an: logp(np.concatenate([an, ls])),
            )
        else:
            amp = sampler.draw(
                amp_noise[:1],
                lambda a: logp(np.concatenate(
                    [a, [kernels.DEFAULT_NOISE], ls])),
            )
            amp_noise = np.concatenate([amp, [kernels.DEFAULT_NOISE]])

        ls = sampler.draw_dimension_wise(
            ls,
            lambda l: logp(np.concatenate([amp_noise, l])),
        )
        return np.concatenate([amp_noise, ls])
