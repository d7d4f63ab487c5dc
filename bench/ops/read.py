"""Op kind ``read``: YCSB's read, a point lookup of a bootstrap key drawn
from YCSB's scrambled zipfian. Rides the gateway's lookup queue."""
import numpy as np

from yardstick.traffic import scrambled_zipfian

QUEUE = "lookup"


def draw(keys, rng, size):
    """The keys and arguments of ``size`` requests."""
    return keys[scrambled_zipfian(rng, size, len(keys))], np.zeros(size, np.int64)


def send(gw, key, arg):
    return gw.submit_lookup(key)


def encode(answer):
    """The gateway's answer as ``(found, got)``."""
    found, val = answer
    return bool(found), int(val)


def replay(ref, key, arg, found, got):
    """This op's requests of one wave, in submission order, against the
    reference; returns which answers are wrong."""
    f, v = ref.lookup(key)
    return (found != f) | (got != v)


def prime(key, val, width):
    """A wave at pad width ``width`` that runs this op on a stored key and
    leaves the state as it was."""
    return {"lookup_keys": np.asarray([key], np.int64), "pad_lookup": width}
