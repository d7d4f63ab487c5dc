"""The plain reference and the comparison that decides ``correct``.

``Reference`` is a sorted key array with values (copied from the smoke
test's reference, independent of ``repro``). ``check_answers`` replays a
run's requests against it in the order the gateway applied them: the
gateway drains each wave at one instant and stamps every request of that
wave with it (``t_dispatch``), waves run one at a time, and within a wave
its queues run in ``WAVE_ORDER``, writes before reads. Each op kind
(``bench/ops/<op>.py``) replays its own requests of a wave, in submission
order (an update: duplicate writes of one key, the last one wins). So the
reference knows the one right answer of every request, including reads of
keys whose updates were still in flight.
"""
from __future__ import annotations

import dataclasses

import numpy as np

OK, ERROR, MISSING = 0, 1, 2

# the order in which ``ShardedUpLIF.apply_wave`` runs a wave's queues
WAVE_ORDER = ("insert", "delete", "lookup", "range")


class Reference:
    """Plain model of the index's semantics: a sorted key array with values."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        self.keys = np.array(keys, dtype=np.int64)
        self.vals = np.array(vals, dtype=np.int64)

    def _find(self, q: np.ndarray):
        i = np.searchsorted(self.keys, q)
        ic = np.minimum(i, len(self.keys) - 1)
        return i, ic, (i < len(self.keys)) & (self.keys[ic] == q)

    def lookup(self, q: np.ndarray):
        _, ic, hit = self._find(np.asarray(q, dtype=np.int64))
        return hit, np.where(hit, self.vals[ic], 0)

    def upsert(self, k: np.ndarray, v: np.ndarray):
        """Write distinct keys ``k``: existing ones get ``v``, new ones are
        inserted in order."""
        k = np.asarray(k, dtype=np.int64)
        v = np.asarray(v, dtype=np.int64)
        _, ic, hit = self._find(k)
        self.vals[ic[hit]] = v[hit]
        if hit.all():
            return
        order = np.argsort(k[~hit])
        fresh, fv = k[~hit][order], v[~hit][order]
        pos = np.searchsorted(self.keys, fresh)
        self.keys = np.insert(self.keys, pos, fresh)
        self.vals = np.insert(self.vals, pos, fv)


@dataclasses.dataclass
class Records:
    """One row per request a run sent, in submission order."""

    op: np.ndarray        # int8: index into the run's op kinds
    key: np.ndarray       # int64
    arg: np.ndarray       # int64: the op's argument (an update's value)
    wave: np.ndarray      # float64: the request's t_dispatch (its wave)
    status: np.ndarray    # int8: OK, ERROR (the future raised) or MISSING
    found: np.ndarray     # bool: the answer, as the op kind encodes it
    got: np.ndarray       # int64: (a read's value; an update's ack, 1)

    def __len__(self) -> int:
        return len(self.op)


def last_wins(keys: np.ndarray, vals: np.ndarray):
    """Distinct keys with the value of their last occurrence."""
    _, first_from_end = np.unique(keys[::-1], return_index=True)
    sel = len(keys) - 1 - first_from_end
    return keys[sel], vals[sel]


def check_answers(ref: Reference, rec: Records, ops: tuple) -> dict:
    """Counts of requests whose answer says the wrong thing (or raised) and
    of requests that never got one. Advances ``ref`` through every wave;
    ``ops`` are the op kinds ``rec.op`` indexes."""
    order = [i for q in WAVE_ORDER for i, op in enumerate(ops) if op.QUEUE == q]
    wrong = rec.status == ERROR
    done = np.nonzero(rec.status == OK)[0]
    waves, inv = np.unique(rec.wave[done], return_inverse=True)
    done = done[np.argsort(inv, kind="stable")]   # by wave, then submission
    cuts = np.cumsum(np.bincount(inv, minlength=len(waves)))[:-1]
    for g in np.split(done, cuts):
        for i in order:
            r = g[rec.op[g] == i]
            if len(r):
                wrong[r] |= ops[i].replay(ref, rec.key[r], rec.arg[r],
                                          rec.found[r], rec.got[r])
    return {
        "wrong_answers": int(wrong.sum()),
        "missing_answers": int((rec.status == MISSING).sum()),
    }
