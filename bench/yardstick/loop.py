"""YCSB's client model against the request gateway: a closed loop.

``clients`` virtual clients each keep one request in flight and send the
next one as soon as the answer comes, with no think time. One generator
thread (the caller's) sends every request; the gateway's completion
callbacks only hand the finished request back to it. Requests are taken
from the seeded stream in the order they are sent, so the n-th request of
a run is the same in every run of a seed. Each request goes out, and its
answer comes back, through its op kind (``bench/ops/<op>.py``).

Every request sent gets one row, in the order sent, with its answer and
the gateway's timestamps (``t_submit``, ``t_dispatch``, ``t_done``), plus
the generator's lag: from the client's previous answer to this submit.
"""
from __future__ import annotations

import functools
import threading
import time

import numpy as np

from yardstick.reference import ERROR, MISSING, OK, Records
from yardstick.traffic import Stream


class ClosedLoop:
    def __init__(self, gw, stream: Stream, ops: tuple, clients: int,
                 capacity: int = 1 << 20):
        from repro.serve import RetryAfter

        self._retry_after = RetryAfter
        self.gw = gw
        self.stream = stream
        self.ops = ops
        self.clients = int(clients)
        self.n = 0                    # requests sent (rows)
        self.in_flight = 0
        self.rejected = 0
        self._cv = threading.Condition()
        self._ready = []              # (cid, row, future), from callbacks
        self._parked = []             # clients waiting for the next pump
        self._delayed = []            # (due, cid) after a RetryAfter
        self._last_done = np.full(self.clients, np.nan)
        self._alloc(capacity)

    # -- rows ------------------------------------------------------------------
    def _alloc(self, cap: int):
        old = getattr(self, "_c", None)
        c = {
            "op": np.zeros(cap, np.int8),
            "key": np.zeros(cap, np.int64),
            "arg": np.zeros(cap, np.int64),
            "t_submit": np.zeros(cap),
            "t_dispatch": np.zeros(cap),
            "t_done": np.zeros(cap),
            "lag": np.full(cap, np.nan),
            "status": np.full(cap, MISSING, np.int8),
            "found": np.zeros(cap, bool),
            "got": np.zeros(cap, np.int64),
        }
        if old is not None:
            for k, a in old.items():
                c[k][: len(a)] = a
        self._c = c

    def column(self, name: str) -> np.ndarray:
        return self._c[name][: self.n]

    def columns(self) -> dict:
        return {k: self.column(k) for k in self._c}

    def records(self) -> Records:
        c = self.columns()
        return Records(op=c["op"], key=c["key"], arg=c["arg"],
                       wave=c["t_dispatch"], status=c["status"],
                       found=c["found"], got=c["got"])

    # -- sending (generator thread) ---------------------------------------------
    def _send(self, cid: int, op: int, key: int, arg: int) -> bool:
        try:
            fut = self.ops[op].send(self.gw, key, arg)
        except self._retry_after as e:
            self.rejected += 1
            self._delayed.append((time.perf_counter() + e.retry_after_s, cid))
            return False
        row = self.n
        if row == len(self._c["op"]):
            self._alloc(2 * row)
        c = self._c
        c["op"][row] = op
        c["key"][row] = key
        c["arg"][row] = arg
        c["t_submit"][row] = fut.t_submit
        if cid >= 0:
            c["lag"][row] = fut.t_submit - self._last_done[cid]
        self.n += 1
        self.in_flight += 1
        fut.add_done_callback(functools.partial(self._done, cid, row))
        return True

    def _send_next(self, cid: int):
        i = self.n % len(self.stream)
        self._send(cid, int(self.stream.op[i]), int(self.stream.key[i]),
                   int(self.stream.arg[i]))

    def _done(self, cid: int, row: int, fut):
        """Completion callback (gateway thread): hand the request back."""
        with self._cv:
            self._ready.append((cid, row, fut))
            self._cv.notify()

    def _record(self, row: int, fut):
        c = self._c
        c["t_dispatch"][row] = fut.t_dispatch
        c["t_done"][row] = fut.t_done
        try:
            v = fut.result(0)
        except Exception:  # noqa: BLE001 -- the answer is an error: counted
            c["status"][row] = ERROR
            return
        c["status"][row] = OK
        c["found"][row], c["got"][row] = self.ops[c["op"][row]].encode(v)

    def _take(self, until: float):
        """Wait (at most until ``until``) for answers; returns them."""
        with self._cv:
            while not self._ready:
                now = time.perf_counter()
                wait = until - now
                if self._delayed:
                    wait = min(wait, min(d for d, _ in self._delayed) - now)
                if wait <= 0:
                    break
                self._cv.wait(min(wait, 0.005))
            ready, self._ready = self._ready, []
        return ready

    # -- driving ---------------------------------------------------------------
    def start(self):
        self._parked = list(range(self.clients))

    def pump(self, t_end: float):
        """Run the loop until ``t_end``; clients answered after it park."""
        for cid in self._parked:
            self._send_next(cid)
        self._parked = []
        while True:
            now = time.perf_counter()
            if self._delayed:
                due = [cid for d, cid in self._delayed if d <= now]
                self._delayed = [(d, c) for d, c in self._delayed if d > now]
                for cid in due:
                    self._send_next(cid)
            if now >= t_end:
                return
            ready = self._take(t_end)
            now = time.perf_counter()
            for cid, row, fut in ready:
                self.in_flight -= 1
                self._record(row, fut)
                if cid < 0:
                    continue
                self._last_done[cid] = fut.t_done
                if now < t_end:
                    self._send_next(cid)
                else:
                    self._parked.append(cid)

    def finish(self, timeout: float) -> int:
        """Send nothing more; wait for every answer still due, at most
        ``timeout`` seconds. Returns how many never came."""
        t_end = time.perf_counter() + timeout
        self._delayed = []
        while self.in_flight and time.perf_counter() < t_end:
            for cid, row, fut in self._take(t_end):
                self.in_flight -= 1
                self._record(row, fut)
                if cid >= 0:
                    self._last_done[cid] = fut.t_done
                    self._parked.append(cid)
        return self.in_flight

    def read(self, op: int, keys: np.ndarray, timeout: float) -> int:
        """Send op kind ``op`` (a read) for every key once, with no client
        behind it, and wait for the answers; returns how many never came."""
        for k in keys:
            while not self._send(-1, op, int(k), 0):
                due, _ = self._delayed.pop()
                time.sleep(max(due - time.perf_counter(), 0.0))
        return self.finish(timeout)
