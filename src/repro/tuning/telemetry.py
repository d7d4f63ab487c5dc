"""Live per-shard telemetry for the online tuning loop (DESIGN.md §7.1).

The Section 4.1 performance measures were computed on demand by host-side
``measures()`` calls; online tuning needs them *per shard*, *cheaply* and
*between every request wave*. Everything structural already lives in the
device-resident ``UpLIFState`` pytree (counters, BMAT sizes, array shapes),
so one tiny jitted program reduces the stacked state to [S] signal vectors —
a single small transfer per snapshot, no per-field host round-trips and no
recomputation of anything the hot path already maintains.

Workload-side signals (throughput, memory) cannot come from the pytree; the
``Telemetry`` aggregator maintains EWMAs of them from the wave timings the
serving loop reports, normalizing the reward terms of Algorithm 1.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.bmat import bmat_height
from repro.core.sharded import ShardedUpLIF
from repro.core.state import UpLIFState


class ShardSignals(NamedTuple):
    """Per-shard [S] signal vectors reduced on-device from the stacked state."""

    n_keys: jnp.ndarray          # int64[S] — live in-place keys
    n_bmat_live: jnp.ndarray     # int64[S] — live delta-buffer entries
    bmat_size: jnp.ndarray       # int32[S] — delta-buffer rows incl. tombstones
    bmat_fill: jnp.ndarray       # float64[S] — size / capacity
    occupancy: jnp.ndarray       # float64[S] — live keys / slot capacity
    n_overflow: jnp.ndarray      # int64[S] — lifetime BMAT-routed inserts
    min_granularity: jnp.ndarray  # int64[S] — smallest failed-window span


@jax.jit
def shard_signals(state: UpLIFState) -> ShardSignals:
    """ONE device program: stacked pytree -> [S] signals (S*7 scalars out)."""
    c = state.counters
    cap = state.slots.keys.shape[-1]
    bcap = state.bmat.keys.shape[-1]
    size = state.bmat.size
    return ShardSignals(
        n_keys=c.n_keys,
        n_bmat_live=c.n_bmat_live,
        bmat_size=size,
        bmat_fill=size.astype(jnp.float64) / float(max(bcap, 1)),
        occupancy=c.n_keys.astype(jnp.float64) / float(max(cap, 1)),
        n_overflow=c.n_overflow,
        min_granularity=c.min_granularity,
    )


@dataclasses.dataclass
class TelemetrySnapshot:
    """Host view of one telemetry read: per-shard arrays + global measures."""

    n_shards: int
    n_keys: np.ndarray           # [S]
    n_bmat_live: np.ndarray      # [S]
    bmat_size: np.ndarray        # [S]
    bmat_fill: np.ndarray        # [S]
    occupancy: np.ndarray        # [S]
    n_overflow: np.ndarray       # [S]
    min_granularity: np.ndarray  # [S]
    bmat_height: np.ndarray      # [S] — dependent gathers per rank query (S1)
    alpha: np.ndarray            # [S] — error scaling Γ̄-1 per shard (S3)
    n_models: np.ndarray         # [S] — spline knots per shard (S4)
    bmat_type: str               # S5
    throughput_ewma: float       # ops/s over recent waves
    memory_ewma: float           # index bytes
    range_lat_ewma: float        # seconds per range query (0 = none seen)

    def shard_measures(self, s: int) -> dict:
        """Section 4.1 measure dict for shard ``s`` (controller state input)."""
        return {
            "bmat_height": int(self.bmat_height[s]),
            "bmat_fill": float(self.bmat_fill[s]),
            "granularity": int(self.min_granularity[s]),
            "error_scaling": float(self.alpha[s]),
            "n_models": int(self.n_models[s]),
            "bmat_type": self.bmat_type,
            "bmat_size": int(self.bmat_size[s]),
            "n_keys": int(self.n_keys[s]),
            "occupancy": float(self.occupancy[s]),
            "n_shards": self.n_shards,
        }


@dataclasses.dataclass
class TelemetryConfig:
    ewma_alpha: float = 0.25     # weight of the newest wave observation
    memory_every: int = 4        # snapshot-to-snapshot memory re-read cadence


class Telemetry:
    """EWMA aggregator + snapshot reader for a ``ShardedUpLIF`` router."""

    def __init__(self, config: TelemetryConfig = TelemetryConfig()):
        self.cfg = config
        self.throughput_ewma = 0.0
        self.memory_ewma = 0.0
        self.range_lat_ewma = 0.0
        self.n_waves = 0
        self.n_range_obs = 0
        self._snap_count = 0

    def observe_wave(self, n_ops: int, seconds: float):
        """Feed one request wave's measured throughput into the EWMA."""
        if seconds <= 0 or n_ops <= 0:
            return
        tput = n_ops / seconds
        a = self.cfg.ewma_alpha
        self.throughput_ewma = (
            tput if self.n_waves == 0
            else (1 - a) * self.throughput_ewma + a * tput
        )
        self.n_waves += 1

    def observe_range(self, n_queries: int, seconds: float):
        """Feed measured range-scan latency (per query) into its EWMA —
        the signal that folds scan cost into the controller reward, making
        scan-favoring BMAT-type switches learnable (Fig. 4 crossover)."""
        if seconds < 0 or n_queries <= 0:
            return
        lat = seconds / n_queries
        a = self.cfg.ewma_alpha
        self.range_lat_ewma = (
            lat if self.n_range_obs == 0
            else (1 - a) * self.range_lat_ewma + a * lat
        )
        self.n_range_obs += 1

    @obs.traced("tuner.telemetry")
    def snapshot(self, index: ShardedUpLIF) -> TelemetrySnapshot:
        """Read the per-shard signals (one device reduce + one transfer)."""
        sig = obs.fetch("tuner.telemetry", shard_signals(index.state))
        bsz = np.asarray(sig.bmat_size)
        heights = np.asarray(
            [
                bmat_height(int(b), index.bmat_kind, index.cfg.bmat_fanout)
                for b in bsz
            ]
        )
        if self._snap_count % self.cfg.memory_every == 0 or self.memory_ewma == 0:
            self.memory_ewma = float(index.index_bytes())
        self._snap_count += 1
        return TelemetrySnapshot(
            n_shards=index.n_shards,
            n_keys=np.asarray(sig.n_keys),
            n_bmat_live=np.asarray(sig.n_bmat_live),
            bmat_size=bsz,
            bmat_fill=np.asarray(sig.bmat_fill),
            occupancy=np.asarray(sig.occupancy),
            n_overflow=np.asarray(sig.n_overflow),
            min_granularity=np.asarray(sig.min_granularity),
            bmat_height=heights,
            alpha=np.asarray([m.alpha for m in index._meta]),
            n_models=np.asarray(
                [m.rs_static.n_spline for m in index._meta]
            ),
            bmat_type=index.bmat_kind,
            throughput_ewma=self.throughput_ewma,
            memory_ewma=self.memory_ewma,
            range_lat_ewma=self.range_lat_ewma,
        )
