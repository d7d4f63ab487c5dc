"""In-program spans and counters of the served index path.

One ``Recorder`` per process (``RECORDER``) sees the layers of a wave as
they nest on the gateway's flusher thread::

    gateway.wave ─┬─ gateway.drain
                  ├─ router.apply_wave ── router.<op> ─┬─ router.prepare
                  │                                    ├─ router.view
                  │                                    ├─ router.capacity
                  │                                    ├─ router.launch
                  │                                    └─ router.wait
                  ├─ gateway.complete
                  ├─ tuner.observe_inserts ── tuner.forecast
                  └─ tuner.after_wave ─┬─ tuner.commit, tuner.drain,
                                       └─ tuner.telemetry, tuner.decide,
                                          tuner.act

and the maintenance workers' ``executor.build``. Each span records its
name, start and end on ``time.perf_counter_ns()``, the span it nests in on
its thread, the thread, the wave that caused it and one integer argument
(the build id of a build). While a profiler session is open it also opens
an annotation ``uplif.<name>``, so the trace shows the spans beside the
device operations; with none open that costs a flag check.

Besides the spans the recorder keeps per-name aggregates (count, total and
self seconds: a span's duration less what its children cover), named
counters, and a bounded ring of the last ``RING`` spans with a count of
those it dropped. A ``jax.monitoring`` listener counts every backend
compile and persistent-cache load as ``compiles.<innermost open span>`` of
the compiling thread (``compiles.none`` outside any span). ``fetch`` is
the one way the served path reads a device array back to the host; it
counts ``host_syncs`` and ``host_syncs.<site>``.

Spans are per wave, never per request, so the recorder is always on. Its
state is process-wide because the compile listener and the router it
observes are: counters of two gateways in one process add up.
"""
from __future__ import annotations

import collections
import functools
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax
from jax.profiler import TraceAnnotation

PREFIX = "uplif."
#: raw spans kept: a 51 s window of ~22 waves/s at ~25 spans a wave is
#: ~28k; twice that, and the waves that follow the window, still fit
RING = 1 << 16
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_now = time.perf_counter_ns
_thread = threading.get_ident
_profiling = TraceAnnotation.is_enabled    # a profiler session is open
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Span(NamedTuple):
    id: int
    name: str
    start_ns: int           # time.perf_counter_ns()
    end_ns: int
    parent: int             # id of the enclosing span on its thread, or -1
    thread: int             # threading.get_ident()
    wave: int               # id of the wave that caused it, or -1
    arg: int                # e.g. executor.build's build id, or -1


class _Open:
    """One span while it is open (the context manager ``span`` returns)."""

    __slots__ = ("rec", "name", "wave", "arg", "id", "parent", "start",
                 "child_ns", "ann", "stack")

    def __init__(self, rec: "Recorder", name: str, wave: int, arg: int):
        self.rec, self.name, self.wave, self.arg = rec, name, wave, arg

    def __enter__(self) -> "_Open":
        rec = self.rec
        stack = rec._stack()
        if stack:
            top = stack[-1]
            self.parent = top.id
            if self.wave < 0:
                self.wave = top.wave
        else:
            self.parent = -1
        self.id = next(rec._ids)
        self.child_ns = 0
        self.stack = stack
        if _profiling():
            self.ann = TraceAnnotation(PREFIX + self.name)
            self.ann.__enter__()
        else:
            self.ann = None
        stack.append(self)
        self.start = _now()
        return self

    def __exit__(self, *exc) -> bool:
        end = _now()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        stack = self.stack
        stack.pop()
        dur = end - self.start
        if stack:
            stack[-1].child_ns += dur
        rec = self.rec
        row = (self.id, self.name, self.start, end, self.parent, _thread(),
               self.wave, self.arg)
        with rec._lock:
            a = rec._agg.get(self.name)
            if a is None:
                a = rec._agg[self.name] = [0, 0, 0]
            a[0] += 1
            a[1] += dur
            a[2] += dur - self.child_ns
            ring = rec._ring
            if len(ring) == ring.maxlen:
                rec.dropped += 1
                rec._evicted_end_ns = max(rec._evicted_end_ns, ring[0][3])
            ring.append(row)
        return False


class Recorder:
    """Spans, their per-name aggregates, counters and a ring of raw spans."""

    def __init__(self, ring: int = RING):
        self._local = threading.local()
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self._waves = itertools.count()
        self._agg: Dict[str, List[int]] = {}      # name -> [n, total, self] ns
        self._counters: Dict[str, int] = {}
        self._ring: collections.deque = collections.deque(maxlen=ring)
        self.dropped = 0
        self._evicted_end_ns = -1                  # latest end dropped

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    # -- recording ------------------------------------------------------------
    def span(self, name: str, wave: int = -1, arg: int = -1) -> _Open:
        """Context manager timing ``name``; ``wave`` defaults to the
        enclosing span's on this thread."""
        return _Open(self, name, wave, arg)

    def new_wave(self) -> int:
        return next(self._waves)

    def current_wave(self) -> int:
        """Wave of the innermost open span on this thread, or -1."""
        stack = self._stack()
        return stack[-1].wave if stack else -1

    def count(self, name: str, n: int = 1):
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def counter(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def fetch(self, site: str, x):
        """``jax.device_get(x)``, counted as one host sync at ``site`` when
        ``x`` (an array or a pytree of arrays) holds a device array."""
        leaves = ((x,) if isinstance(x, jax.Array)
                  else jax.tree_util.tree_leaves(x))
        if any(isinstance(a, jax.Array) for a in leaves):
            with self._lock:
                c = self._counters
                c["host_syncs"] = c.get("host_syncs", 0) + 1
                key = "host_syncs." + site
                c[key] = c.get(key, 0) + 1
        return jax.device_get(x)

    def compiled(self):
        """One program compiled or loaded on this thread, charged to the
        innermost open span."""
        stack = self._stack()
        self.count("compiles." + (stack[-1].name if stack else "none"))

    # -- reading --------------------------------------------------------------
    def snapshot(self) -> dict:
        """Aggregates (seconds) and counters, as ``RequestGateway.stats()``
        reports them under ``"obs"``."""
        with self._lock:
            return {
                "spans": {n: {"count": c, "total_s": t * 1e-9,
                              "self_s": s * 1e-9}
                          for n, (c, t, s) in self._agg.items()},
                "counters": dict(self._counters),
                "dropped": self.dropped,
            }

    def spans(self, t0_ns: int, t1_ns: int) -> Optional[List[Span]]:
        """The closed spans that overlap ``[t0_ns, t1_ns)``, or None when
        the ring dropped one that may have."""
        with self._lock:
            if self._evicted_end_ns >= t0_ns:
                return None
            return [Span._make(s) for s in self._ring
                    if s[3] > t0_ns and s[2] < t1_ns]


RECORDER = Recorder()


def span(name: str, wave: int = -1, arg: int = -1) -> _Open:
    return _Open(RECORDER, name, wave, arg)


def traced(name: str):
    """Decorator: the call is one span ``name`` of ``RECORDER``."""
    def deco(fn):
        @functools.wraps(fn)
        def inner(*a, **kw):
            with _Open(RECORDER, name, -1, -1):
                return fn(*a, **kw)
        return inner
    return deco


def count(name: str, n: int = 1):
    RECORDER.count(name, n)


def fetch(site: str, x):
    return RECORDER.fetch(site, x)


def _on_duration(event: str, _secs: float, **_):
    if event == _COMPILE_EVENT:
        RECORDER.compiled()


def _on_event(event: str, **_):
    if event == _CACHE_HIT_EVENT:
        RECORDER.compiled()


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
