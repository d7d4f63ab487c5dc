"""Counts the programs a process compiles, and when (a copy of the smoke
test's ``CompileClock``, with a time stamp per event)."""
from __future__ import annotations

import threading
import time


class CompileClock:
    """Every XLA backend compile and every load from the persistent
    compilation cache, with the host time it was reported at. A load from
    the cache still traces and lowers the program, so both count as a
    program the process had to make."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.per_fun = {}
        self.stamps = []          # (start, end, "compile" | "cache_hit", fun)
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, name, secs, fun_name="?", **_):
        if name == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += secs
                n, s = self.per_fun.get(fun_name, (0, 0.0))
                self.per_fun[fun_name] = (n + 1, s + secs)
                now = time.perf_counter()
                self.stamps.append((now - secs, now, "compile", fun_name))

    def _evt(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            with self._lock:
                now = time.perf_counter()
                self.stamps.append((now, now, "cache_hit", "?"))

    @property
    def count(self) -> int:
        return len(self.stamps)

    def between(self, t0: float, t1: float):
        """The events that overlap [t0, t1) (a compile ends when reported)."""
        with self._lock:
            return [s for s in self.stamps if s[0] < t1 and s[1] >= t0]
