"""Pallas TPU kernels for UpLIF's lookup hot path + GMM E-step.

Layout note (TPU adaptation): TPU vector units have no native int64, so all
kernels take keys decomposed into (hi: int32 = key >> 32, lo: uint32) — exact
for the 52-bit key domain. ``ops.py`` performs the decomposition, jit-wraps
each kernel and decides in one place (``ops.interpret_mode``) whether they
compile for the TPU or run in Pallas interpret mode (CPU tests);
``ref.py`` holds the pure-jnp oracles operating on the same decomposed
representation. The main-path kernels (fused locate, BMAT rank, GMM
E-step) compile for a TPU v5e (``tests/test_tpu_compile.py``); ``rows.py``
holds the row layout and scalar probes the two search kernels share.
"""
from repro.kernels import ops, ref  # noqa: F401
