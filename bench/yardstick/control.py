"""The control of the correctness check: the plain reference put in the
program's place, with one guarantee of the configuration broken.

The configurations state that lookups match keys exactly, all 64 bits.
The step that would tempt a later change is to compare keys in the chip's
native 32-bit floating point. ``Float32KeyIndex`` serves the gateway's
waves from the sorted bootstrap arrays on the device, with keys rounded to
float32: distinct keys that round alike answer for each other. The
benchmark's comparison has to find it not correct.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _slot(k32, q):
    q32 = q.astype(jnp.float32)
    i = jnp.minimum(jnp.searchsorted(k32, q32), k32.shape[0] - 1)
    return i, k32[i] == q32


@jax.jit
def _lookup(k32, v, q):
    i, found = _slot(k32, q)
    return found, jnp.where(found, v[i], 0)


@jax.jit
def _upsert(k32, v, q, val, n):
    i, found = _slot(k32, q)
    live = found & (jnp.arange(q.shape[0]) < n)
    return v.at[jnp.where(live, i, k32.shape[0])].set(val, mode="drop")


def _pad(a: np.ndarray, width) -> np.ndarray:
    out = np.zeros(width or len(a), np.int64)
    out[: len(a)] = a
    return out


class Float32KeyIndex:
    """Answers ``apply_wave`` like ``ShardedUpLIF``, keys compared as float32."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        self._k32 = jnp.asarray(np.asarray(keys).astype(np.float32))
        self._v = jnp.asarray(np.asarray(vals, np.int64))
        self.size = len(keys)

    def apply_wave(self, wave):
        from repro.core.sharded import MixedWaveResult

        res = MixedWaveResult()
        if wave.insert_keys is not None and len(wave.insert_keys):
            n = len(wave.insert_keys)
            self._v = _upsert(self._k32, self._v,
                             _pad(wave.insert_keys, wave.pad_insert),
                             _pad(wave.insert_vals, wave.pad_insert), n)
        if wave.lookup_keys is not None and len(wave.lookup_keys):
            n = len(wave.lookup_keys)
            f, v = _lookup(self._k32, self._v, _pad(wave.lookup_keys, wave.pad_lookup))
            res.lookup_found = np.asarray(f)[:n]
            res.lookup_vals = np.asarray(v)[:n]
        return res


def build_control(config: dict, keys: np.ndarray, vals: np.ndarray):
    """The cell's gateway over ``Float32KeyIndex``, with no tuner."""
    from repro.serve import GatewayConfig, RequestGateway

    from yardstick.cell import Deployment

    index = Float32KeyIndex(keys, vals)
    gw = RequestGateway(index, config=GatewayConfig(**config["gateway"]))
    return Deployment(gw=gw, index=index, close=gw.close)
