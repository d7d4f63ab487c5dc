"""The trace reduction, on a small fixed trace."""
import numpy as np
import pytest

import bench_tiny  # noqa: F401 -- import paths
from yardstick import trace as tr

MS = 1_000_000  # ns


def fixed_trace():
    # window 0..100 ms; device busy 10-30 (two overlapping ops) and 50-60;
    # programs: sinsert 10-30, slookup 50-60; router span 5-35, tuner 35-45
    ops = [("fusion.1", 10 * MS, 25 * MS), ("copy.2", 20 * MS, 30 * MS),
           ("fusion.1", 50 * MS, 60 * MS), ("fusion.1", 120 * MS, 130 * MS)]
    programs = [("jit_sinsert(7)", 10 * MS, 30 * MS),
                ("jit_slookup(8)", 50 * MS, 60 * MS),
                ("jit_slookup(8)", 120 * MS, 130 * MS)]
    spans = {"window": [(0, 100 * MS)], "router": [(5 * MS, 35 * MS)],
             "tuner": [(35 * MS, 45 * MS)]}
    return tr.Trace(ops=ops, programs=programs, spans=spans)


def test_program_name():
    assert tr.program_name("jit_sinsert(42)") == "sinsert"
    assert tr.program_name("jit__vrange(3)") == "_vrange"
    assert tr.program_name("fusion") == "fusion"


def test_busy_and_window():
    t = fixed_trace()
    assert tr.busy(t) == [(10 * MS, 30 * MS), (50 * MS, 60 * MS)]
    assert tr.busy_seconds(t) == pytest.approx(0.030)
    assert tr.window_seconds(t) == pytest.approx(0.100)


def test_program_times_count_runs_in_window_only():
    t = fixed_trace()
    assert tr.program_times(t) == {"sinsert": (1, pytest.approx(0.020)),
                                   "slookup": (1, pytest.approx(0.010))}
    assert tr.ms_per_run(t, "sinsert") == pytest.approx(20.0)
    assert tr.ms_per_run(t, "sdelete") is None


def test_idle_gaps_labelled_by_open_span():
    gaps = tr.idle_gaps(fixed_trace())
    assert gaps[0] == ("gateway_or_generator", pytest.approx(0.040))  # 60-100
    assert sorted((n, round(s, 6)) for n, s in gaps) == [
        ("gateway_or_generator", 0.04), ("router", 0.01), ("tuner", 0.02)]


def test_top_ops_named_by_program():
    top = dict(tr.top_ops(fixed_trace()))
    assert top == {"sinsert/fusion.1": pytest.approx(0.015),
                   "sinsert/copy.2": pytest.approx(0.010),
                   "slookup/fusion.1": pytest.approx(0.010)}


def test_from_xspace_reads_benchmark_spans(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones(128)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.router"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    t = tr.from_xspace(pb)
    assert len(t.spans["window"]) == 1 and len(t.spans["router"]) == 2
    lo, hi = t.window
    assert all(lo <= a < b <= hi for a, b in t.spans["router"])
    assert np.isfinite(tr.window_seconds(t))
