"""The in-program recorder (``repro.obs``): span nesting and self time,
exceptions, counters, the ring's bound, threads, compile attribution and
host-sync counting."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs


def _by_name(rec, t0=0):
    return {s.name: s for s in rec.spans(t0, time.perf_counter_ns() + 1)}


def test_nested_spans_link_parents_and_subtract_children():
    rec = obs.Recorder()
    with rec.span("outer", wave=rec.new_wave()):
        time.sleep(0.01)
        with rec.span("inner"):
            time.sleep(0.02)
            with rec.span("leaf", arg=7):
                time.sleep(0.005)
    s = _by_name(rec)
    assert s["outer"].parent == -1
    assert s["inner"].parent == s["outer"].id
    assert s["leaf"].parent == s["inner"].id and s["leaf"].arg == 7
    assert s["outer"].wave == s["inner"].wave == s["leaf"].wave == 0
    assert {sp.thread for sp in s.values()} == {threading.get_ident()}
    agg = rec.snapshot()["spans"]
    for name in ("outer", "inner", "leaf"):
        sp = s[name]
        assert agg[name]["count"] == 1
        assert agg[name]["total_s"] == pytest.approx((sp.end_ns - sp.start_ns) * 1e-9)
    dur = {n: (sp.end_ns - sp.start_ns) * 1e-9 for n, sp in s.items()}
    assert agg["leaf"]["self_s"] == pytest.approx(dur["leaf"])
    assert agg["inner"]["self_s"] == pytest.approx(dur["inner"] - dur["leaf"])
    assert agg["outer"]["self_s"] == pytest.approx(dur["outer"] - dur["inner"])
    assert 0.008 < agg["outer"]["self_s"] < dur["outer"] - 0.02
    assert rec.current_wave() == -1


def test_span_closes_on_exception():
    rec = obs.Recorder()
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("failing"):
                raise ValueError("boom")
    s = _by_name(rec)
    assert s["failing"].parent == s["outer"].id
    assert s["outer"].end_ns >= s["failing"].end_ns
    assert rec._stack() == []           # nothing left open on the thread
    with rec.span("after"):
        pass
    assert _by_name(rec)["after"].parent == -1


def test_counters_add_up():
    rec = obs.Recorder()
    rec.count("a")
    rec.count("a", 4)
    rec.count("b", 2)
    assert rec.counter("a") == 5 and rec.counter("missing") == 0
    assert rec.snapshot()["counters"] == {"a": 5, "b": 2}


def test_ring_is_bounded_and_reports_what_it_dropped():
    rec = obs.Recorder(ring=4)
    t0 = time.perf_counter_ns()
    for i in range(3):
        with rec.span("s", arg=i):
            pass
    assert [s.arg for s in rec.spans(t0, time.perf_counter_ns())] == [0, 1, 2]
    t_mid = time.perf_counter_ns()
    for i in range(3, 10):
        with rec.span("s", arg=i):
            pass
    assert rec.dropped == 6 and rec.snapshot()["dropped"] == 6
    assert rec.snapshot()["spans"]["s"]["count"] == 10  # aggregates keep all
    # a window the ring no longer holds in full reads as unknown ...
    assert rec.spans(t0, time.perf_counter_ns()) is None
    # ... one that begins after the last evicted span does not
    last_evicted = 5
    t_after = rec._evicted_end_ns + 1
    kept = rec.spans(t_after, time.perf_counter_ns())
    assert [s.arg for s in kept] == list(range(last_evicted + 1, 10))
    assert t_after > t_mid


def test_two_threads_record_their_own_stacks():
    rec = obs.Recorder()
    n = 200
    barrier = threading.Barrier(2)

    def work(tag):
        barrier.wait()
        for _ in range(n):
            with rec.span(tag + ".outer", wave=rec.new_wave()):
                with rec.span(tag + ".inner"):
                    pass

    ts = [threading.Thread(target=work, args=(t,)) for t in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in ts)
    spans = rec.spans(0, time.perf_counter_ns() + 1)
    by_id = {s.id: s for s in spans}
    agg = rec.snapshot()["spans"]
    for tag in ("a", "b"):
        assert agg[tag + ".outer"]["count"] == agg[tag + ".inner"]["count"] == n
        inner = [s for s in spans if s.name == tag + ".inner"]
        threads = {s.thread for s in spans if s.name.startswith(tag)}
        assert len(threads) == 1
        for s in inner:
            p = by_id[s.parent]
            assert p.name == tag + ".outer" and p.thread == s.thread
            assert p.wave == s.wave and p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns
    waves = [s.wave for s in spans if s.name.endswith(".outer")]
    assert len(set(waves)) == 2 * n     # every wave id handed out once


def test_compile_inside_a_span_is_charged_to_it():
    """A jit of a shape never seen before compiles inside the span: the
    process-wide listener charges it to the innermost open span."""
    rec = obs.RECORDER
    name = "test_obs.fresh_jit"
    before = rec.counter("compiles." + name)
    f = jax.jit(lambda x: x * 3 + 1)
    x = np.ones(977, np.float32)
    outer = rec.counter("compiles.test_obs.outer")
    with obs.span("test_obs.outer"):
        with obs.span(name):
            f(x).block_until_ready()
    assert rec.counter("compiles." + name) == before + 1
    assert rec.counter("compiles.test_obs.outer") == outer
    with obs.span(name):
        f(x).block_until_ready()  # compiled already
    assert rec.counter("compiles." + name) == before + 1


def test_fetch_counts_device_reads_by_site():
    rec = obs.Recorder()
    x = jnp.arange(5)
    out = rec.fetch("site.a", x)
    assert isinstance(out, np.ndarray) and out.tolist() == [0, 1, 2, 3, 4]
    a, b = rec.fetch("site.b", (x, jnp.asarray(3)))
    assert int(b) == 3
    rec.fetch("site.a", np.arange(3))      # already on the host: no sync
    c = rec.snapshot()["counters"]
    assert c == {"host_syncs": 2, "host_syncs.site.a": 1,
                 "host_syncs.site.b": 1}


def test_spans_appear_in_a_profiler_trace(tmp_path):
    """Inside a profiler session each span is an ``uplif.<name>`` event;
    outside one it opens no annotation."""
    import glob

    from jax.profiler import ProfileData

    rec = obs.Recorder()
    with rec.span("untraced") as sp:
        assert sp.ann is None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with rec.span("outer"):
        with rec.span("inner"):
            pass
    jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [e.name for p in ProfileData.from_file(pb).planes
             for line in p.lines for e in line.events]
    assert names.count("uplif.outer") == names.count("uplif.inner") == 1
    assert "uplif.untraced" not in names
