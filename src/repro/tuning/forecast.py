"""Streaming D_update forecasting (Section 3.4, online; DESIGN.md §7.2).

The paper estimates the incoming-update distribution D_update with a GMM and
sizes Nullifier gaps from its CDF (Eq. 6). After PR 1 that estimate was an
offline artifact: fit once from a reservoir at retrain time, never consulted
while serving. This module turns it into a *forecaster* that tracks the
insert stream live and drives three proactive decisions:

  * per-shard insert mass  -> delta-buffer presizing (no mid-wave realloc /
    recompile) and shard split / rebalance triggers;
  * the current GMM        -> Eq. 6 gap sizing whenever the controller
    schedules a (shard) retrain, so gaps open where inserts are *predicted*;
  * mass drift             -> a cheap distribution-shift signal.

Estimation is stepwise EM over decayed sufficient statistics (Cappé &
Moulines 2009): each observed batch contributes one E-step — the dense
(N, K) responsibility kernel, run through the Pallas E-step
(repro/kernels/gmm_estep.py) with the pure-JAX ``core.gmm.e_step`` as
fallback — followed by a closed-form M-step on the decayed stats. Old
batches decay geometrically, so the mixture tracks shift at a rate set by
``decay`` instead of averaging over the whole history. Keys are mapped to
the unit interval before the f32 kernel so 52-bit magnitudes don't eat the
mantissa; responsibilities are scale-invariant, the stats are accumulated
in f64 on the raw keys.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.gmm import gmm_cdf_np, init_gmm_uniform
from repro.core.nullifier import gap_sizes
from repro.core.types import GMMState

_MIN_STD_FRAC = 1e-6   # std floor as a fraction of the key-domain span


@dataclasses.dataclass
class ForecastConfig:
    n_components: int = 4
    decay: float = 0.65       # per-batch geometric decay of the EM stats
    min_obs: int = 256        # observations before the forecast is trusted
    max_batch: int = 8192     # subsample cap per observed batch
    # dense E-step via the Pallas kernel; None = auto (TPU only — interpret
    # mode on CPU is an emulation, far slower than numpy). A kernel that
    # fails raises: nothing downgrades to the host path behind the caller.
    use_pallas: Optional[bool] = None
    seed: int = 0


class UpdateForecaster:
    """Streaming-EM GMM over observed insert keys."""

    def __init__(
        self,
        lo: float,
        hi: float,
        config: ForecastConfig = ForecastConfig(),
    ):
        self.cfg = config
        if config.use_pallas is None:
            from repro.kernels.ops import on_tpu

            config = dataclasses.replace(config, use_pallas=on_tpu())
            self.cfg = config
        self.lo = float(lo)
        self.hi = float(hi)
        self.span = max(self.hi - self.lo, 1.0)
        K = config.n_components
        self.gmm: GMMState = init_gmm_uniform(lo, hi, K)
        # decayed sufficient statistics (responsibility-weighted moments)
        self._s0 = np.zeros(K)   # sum of responsibilities
        self._s1 = np.zeros(K)   # sum of resp * x
        self._s2 = np.zeros(K)   # sum of resp * x^2
        self.n_obs = 0
        self.n_batches = 0
        # distribution-shift signal: EWMA of the per-step component-mean
        # movement (span-normalized). Near 0 under a stationary stream,
        # spikes when the insert distribution moves — the "shift" axis of
        # the workload signature the Q-table store keys on.
        self.drift_ewma = 0.0
        self._rng = np.random.default_rng(config.seed)

    # -- estimation ---------------------------------------------------------
    def _responsibilities(self, x: np.ndarray) -> np.ndarray:
        """(N, K) responsibilities under the current mixture."""
        if self.cfg.use_pallas:
            from repro.kernels.ops import gmm_estep

            # unit-domain scaling keeps the f32 kernel conditioned on
            # 52-bit keys; the shared -log(span) shifts every component
            # equally and cancels in the softmax
            xs = jnp.asarray((x - self.lo) / self.span)
            ms = (self.gmm.means - self.lo) / self.span
            ss = jnp.maximum(self.gmm.stds / self.span, _MIN_STD_FRAC)
            return np.asarray(
                gmm_estep(xs, self.gmm.weights, ms, ss), dtype=np.float64
            )
        # host path: a K-component E-step over numpy is microseconds per
        # batch and — unlike a jitted path — indifferent to the batch
        # length, so the per-wave observe never compiles anything
        w = np.asarray(self.gmm.weights)
        mu = np.asarray(self.gmm.means)
        sd = np.maximum(np.asarray(self.gmm.stds), 1e-300)
        z = (x[:, None] - mu[None, :]) / sd[None, :]
        logp = np.log(w[None, :]) - 0.5 * z * z - np.log(sd[None, :])
        m = logp.max(axis=1, keepdims=True)
        e = np.exp(logp - m)
        return e / e.sum(axis=1, keepdims=True)

    def observe(self, keys: np.ndarray):
        """One streaming-EM step on a batch of observed insert keys."""
        x = np.asarray(keys, dtype=np.float64)
        if len(x) == 0:
            return
        if len(x) > self.cfg.max_batch:
            x = self._rng.choice(x, self.cfg.max_batch, replace=False)
        resp = self._responsibilities(x)
        d = self.cfg.decay
        self._s0 = d * self._s0 + resp.sum(axis=0)
        self._s1 = d * self._s1 + resp.T @ x
        self._s2 = d * self._s2 + resp.T @ (x * x)
        self.n_obs += len(x)
        self.n_batches += 1
        if self.n_obs < self.cfg.min_obs:
            return
        # closed-form M-step on the decayed stats
        s0 = np.maximum(self._s0, 1e-12)
        w = s0 / s0.sum()
        mu = self._s1 / s0
        var = np.maximum(self._s2 / s0 - mu * mu, 0.0)
        std = np.maximum(np.sqrt(var), _MIN_STD_FRAC * self.span)
        drift = float(
            np.mean(np.abs(mu - np.asarray(self.gmm.means)))
        ) / self.span
        self.drift_ewma = 0.8 * self.drift_ewma + 0.2 * drift
        self.gmm = GMMState(
            weights=jnp.asarray(w, dtype=jnp.float64),
            means=jnp.asarray(mu, dtype=jnp.float64),
            stds=jnp.asarray(std, dtype=jnp.float64),
        )

    @property
    def ready(self) -> bool:
        """Enough mass observed for the forecast to outrank the prior."""
        return self.n_obs >= self.cfg.min_obs

    # -- forecast consumers ---------------------------------------------------
    def shard_mass(self, boundaries: np.ndarray) -> np.ndarray:
        """Predicted insert-mass per shard of a range partition: CDF diffs at
        the S-1 boundaries, normalized to sum to 1 over the S shards."""
        b = np.asarray(boundaries, dtype=np.float64)
        if len(b) == 0:
            return np.ones(1)
        cdf = gmm_cdf_np(self.gmm, b)
        mass = np.diff(np.concatenate([[0.0], cdf, [1.0]]))
        mass = np.maximum(mass, 0.0)
        t = mass.sum()
        return mass / t if t > 0 else np.full(len(b) + 1, 1.0 / (len(b) + 1))

    def bmat_presize(
        self, boundaries: np.ndarray, horizon_inserts: int
    ) -> int:
        """Per-shard delta-buffer capacity that absorbs the next
        ``horizon_inserts`` inserts if they land as forecast (hottest shard
        sets the size — capacities are shared across the stacked shards)."""
        mass = self.shard_mass(boundaries)
        return int(np.ceil(float(mass.max()) * horizon_inserts))

    def hottest_shard(self, boundaries: np.ndarray) -> int:
        return int(np.argmax(self.shard_mass(boundaries)))

    def imbalance(self, boundaries: np.ndarray) -> float:
        """max/mean predicted shard mass — ≥ ~2 means the partition no longer
        matches where inserts are going (split/rebalance trigger)."""
        mass = self.shard_mass(boundaries)
        return float(mass.max() * len(mass))

    def gap_sizes(
        self, keys: np.ndarray, *, alpha_target: float, d_max: int
    ) -> np.ndarray:
        """Eq. 6 Nullifier gap counts under the *forecast* D_update."""
        return gap_sizes(
            keys, self.gmm, alpha_target=alpha_target, d_max=d_max
        )
