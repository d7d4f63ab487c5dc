"""GMM E-step kernel (Section 3.4 D_update estimation).

Dense responsibility computation with a numerically-stable component
softmax — the EM inner loop that dominates GMM refits on large update
reservoirs. Samples run along the lanes ([1, N_BLK] blocks) and the K
components along the sublanes, so the softmax reduces over sublanes; the
tiny [K, 1] parameter columns stay resident. The wrapper returns the
conventional (N, K) layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

N_BLK = 2048


def _kernel(x_ref, w_ref, mu_ref, sd_ref, out_ref):
    x = x_ref[...]
    w = w_ref[...]
    mu = mu_ref[...]
    sd = sd_ref[...]
    z = (x - mu) / sd
    logp = jnp.log(w) - np.float32(0.5) * z * z - jnp.log(sd)
    m = jnp.max(logp, axis=0, keepdims=True)
    e = jnp.exp(logp - m)
    out_ref[...] = e / jnp.sum(e, axis=0, keepdims=True)


def gmm_estep_pallas(x, weights, means, stds, *, interpret: bool):
    """(N, K) float32 responsibilities; ``x`` is float32 [N], N a multiple
    of N_BLK; the parameters are float32 [K]."""
    n = x.shape[0]
    k = weights.shape[0]
    assert n % N_BLK == 0, "pad samples to N_BLK (ops.py does this)"
    zero = np.int32(0)
    col = pl.BlockSpec((k, 1), lambda i: (zero, zero))
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        grid=(n // N_BLK,),
        in_specs=[pl.BlockSpec((1, N_BLK), lambda i: (zero, i)), col, col, col],
        out_specs=pl.BlockSpec((k, N_BLK), lambda i: (zero, i)),
        interpret=interpret,
        name="gmm_estep",
    )(x.reshape(1, n), weights.reshape(k, 1), means.reshape(k, 1),
      stds.reshape(k, 1))
    return out.T
