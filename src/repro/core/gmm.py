"""Gaussian Mixture Model over the key domain (Section 3.4).

UpLIF learns the incoming-update distribution D_update online with a 1-D GMM
and uses its CDF to size Nullifier gaps (Eq. 6). EM is fully vectorized in
JAX (fixed iteration count so it jits once); the E-step also exists as a
Pallas kernel (repro/kernels/gmm_estep.py) with this module as its oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.types import GMMState

_SQRT2 = float(np.sqrt(2.0))
_LOG_SQRT_2PI = float(0.5 * np.log(2.0 * np.pi))
_MIN_STD = 1e-9


def init_gmm_uniform(lo: float, hi: float, n_components: int = 4) -> GMMState:
    """Uniform prior over [lo, hi] — the Phase-2 assumption before any update
    has been observed (Section 3.2, Phase 2)."""
    lo, hi = float(lo), float(hi)
    span = max(hi - lo, 1.0)
    centers = lo + (np.arange(n_components) + 0.5) / n_components * span
    stds = np.full(n_components, span / n_components)  # flat-ish mixture
    return GMMState(
        weights=jnp.full((n_components,), 1.0 / n_components, dtype=jnp.float64),
        means=jnp.asarray(centers, dtype=jnp.float64),
        stds=jnp.asarray(stds, dtype=jnp.float64),
    )


def _log_prob(state: GMMState, x: jnp.ndarray) -> jnp.ndarray:
    """(N, K) component log densities."""
    z = (x[:, None] - state.means[None, :]) / state.stds[None, :]
    return (
        jnp.log(state.weights[None, :])
        - 0.5 * z * z
        - jnp.log(state.stds[None, :])
        - _LOG_SQRT_2PI
    )


def e_step(state: GMMState, x: jnp.ndarray):
    """Responsibilities (N, K) and per-point log-likelihood (N,)."""
    lp = _log_prob(state, x)
    norm = jax.scipy.special.logsumexp(lp, axis=1, keepdims=True)
    return jnp.exp(lp - norm), norm[:, 0]


@functools.partial(jax.jit, static_argnames=("n_iters",))
def _em(state: GMMState, x: jnp.ndarray, n_iters: int) -> GMMState:
    def step(state, _):
        resp, _ = e_step(state, x)
        nk = resp.sum(axis=0) + 1e-12
        means = (resp * x[:, None]).sum(axis=0) / nk
        var = (resp * (x[:, None] - means[None, :]) ** 2).sum(axis=0) / nk
        stds = jnp.sqrt(jnp.maximum(var, _MIN_STD))
        weights = nk / x.shape[0]
        return GMMState(weights=weights, means=means, stds=stds), None

    state, _ = jax.lax.scan(step, state, None, length=n_iters)
    return state


def fit_gmm(
    keys: jnp.ndarray,
    n_components: int = 4,
    n_iters: int = 25,
    seed: int = 0,
) -> GMMState:
    """Fit D_update from an observed update-key sample (float64 positions in
    key space). k-quantile init keeps EM deterministic and restart-safe."""
    x = jnp.asarray(keys, dtype=jnp.float64)
    qs = jnp.quantile(x, jnp.linspace(0.0, 1.0, n_components + 2)[1:-1])
    span = jnp.maximum(x.max() - x.min(), 1.0)
    init = GMMState(
        weights=jnp.full((n_components,), 1.0 / n_components, dtype=jnp.float64),
        means=qs.astype(jnp.float64),
        stds=jnp.full((n_components,), span / (2.0 * n_components), dtype=jnp.float64),
    )
    return _em(init, x, n_iters)


@jax.jit
def gmm_pdf(state: GMMState, x: jnp.ndarray) -> jnp.ndarray:
    lp = _log_prob(state, jnp.asarray(x, dtype=jnp.float64))
    return jnp.exp(jax.scipy.special.logsumexp(lp, axis=1))


@jax.jit
def gmm_cdf(state: GMMState, x: jnp.ndarray) -> jnp.ndarray:
    """Mixture CDF — the integral in Eq. 6 between two keys is a CDF diff."""
    x = jnp.asarray(x, dtype=jnp.float64)
    z = (x[:, None] - state.means[None, :]) / (state.stds[None, :] * _SQRT2)
    comp = 0.5 * (1.0 + jax.scipy.special.erf(z))
    return (state.weights[None, :] * comp).sum(axis=1)


def gmm_cdf_np(state: GMMState, x: np.ndarray) -> np.ndarray:
    """Host-side mixture CDF (numpy/scipy). The jitted ``gmm_cdf`` pays a
    fresh XLA compile for every distinct input length, which turns the
    variable-length host callers (nullifier gap sizing at retrain, the
    tuning forecaster) into compile mills; a K-component erf over numpy is
    microseconds at any length."""
    from scipy.special import erf  # scipy ships with jax

    x = np.asarray(x, dtype=np.float64)
    w = obs.fetch("gmm.cdf", state.weights)
    mu = obs.fetch("gmm.cdf", state.means)
    sd = obs.fetch("gmm.cdf", state.stds)
    z = (x[:, None] - mu[None, :]) / (sd[None, :] * _SQRT2)
    return (w[None, :] * 0.5 * (1.0 + erf(z))).sum(axis=1)


def gmm_memory_bytes(state: GMMState) -> int:
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in state)
