"""The main-path Pallas kernels compile for a TPU v5e.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached: these tests lower each kernel with
``interpret=False`` at the largest shapes the ``ops`` guards admit and ask
Mosaic for the executable. A kernel that interpret mode accepts but the
chip's compiler refuses (a gather form Mosaic cannot lower, an int64 that
x64 slipped into the body, a block that breaks the (8, 128) tiling, more
VMEM than the kernel may use) fails here, on the CPU.

The topology is described inside a module fixture — never at import —
because only one process at a time may load the TPU library; under
pytest-xdist every worker imports this file, and only the worker that runs
it loads the library. The persistent compilation cache is off around the
compiles: an executable for a described chip cannot be read back.
"""
import functools
import os

import pytest

import repro.core  # noqa: F401 — x64: the kernels run under it
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import compile_cache
from repro.kernels import ops
from repro.kernels.bmat_rank import bmat_rank_offset_pallas
from repro.kernels.gmm_estep import gmm_estep_pallas
from repro.kernels.spline_lookup import fused_locate_pallas

I32, U32, F32 = jnp.int32, jnp.uint32, jnp.float32
N_TABLE = (1 << 16) + 2  # radix table of the default radix_bits=16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with compile_cache.disabled():
        yield SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n_shards,n_queries", [(1, 4096), (2, 256)])
def test_fused_locate_compiles(one_chip, n_shards, n_queries):
    """Fused locate at the slot guard: S * cap = MAX_VMEM_SLOTS, the
    largest knot and radix arrays the guard admits for S shards."""
    cap = ops.MAX_VMEM_SLOTS // n_shards
    n_table = min(N_TABLE, ops.MAX_VMEM_KEYS // n_shards)
    n_knots = ops.MAX_VMEM_KEYS // n_shards
    assert ops.locate_fusable(cap, n_knots, n_table, n_shards)
    fn = functools.partial(
        fused_locate_pallas, n_table=n_table, n_knots=n_knots, cap=cap,
        window=64, rs_iters=12, interpret=False,
    )
    flat = lambda n, d: ((n_shards * n,), d)
    q = lambda d: ((n_queries,), d)
    c = _compile(
        fn, one_chip,
        ((n_shards * n_table,), I32), flat(n_knots, I32), flat(n_knots, U32),
        flat(n_knots, F32), flat(cap, I32), flat(cap, U32),
        q(I32), q(U32), q(I32), q(I32), q(I32), q(I32),
    )
    # the slot halves alone are 8 MiB: they must be the kernel's operands
    assert c.memory_analysis().argument_size_in_bytes >= 8 << 20


@pytest.mark.parametrize("n_shards", [1, 8])
def test_bmat_rank_compiles(one_chip, n_shards):
    """Offset rank kernel at the key guard: S * cap = MAX_VMEM_KEYS."""
    fanout = 16
    cap = ops.MAX_VMEM_KEYS // n_shards
    nf = cap // fanout + 1
    assert ops.rank_fusable(n_shards * cap, n_shards * nf)
    fn = functools.partial(
        bmat_rank_offset_pallas, cap=cap, nf=nf, fanout=fanout,
        interpret=False,
    )
    S = n_shards
    _compile(
        fn, one_chip,
        ((S * cap,), I32), ((S * cap,), U32), ((S * nf,), I32),
        ((S * nf,), U32), ((4096,), I32), ((4096,), U32), ((4096,), I32),
        ((4096,), I32),
    )


@pytest.mark.parametrize("k", [4, 8])
def test_gmm_estep_compiles(one_chip, k):
    """E-step at the forecaster's 8192-sample subsample cap."""
    from repro.tuning.forecast import ForecastConfig

    n = ForecastConfig().max_batch
    assert n % 2048 == 0
    fn = functools.partial(gmm_estep_pallas, interpret=False)
    c = _compile(fn, one_chip, ((n,), F32), ((k,), F32), ((k,), F32),
                 ((k,), F32))
    assert c.memory_analysis().output_size_in_bytes == n * k * 4
