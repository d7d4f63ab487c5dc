"""Op kind ``update``: YCSB's update, a new value for a bootstrap key drawn
from YCSB's scrambled zipfian. Rides the gateway's insert queue (an
upsert); the acknowledgement is ``True``."""
import numpy as np

from yardstick.reference import last_wins
from yardstick.traffic import VALUE_LIMIT, scrambled_zipfian

QUEUE = "insert"
WRITES = True   # the keys it wrote are read back after the window


def draw(keys, rng, size):
    idx = scrambled_zipfian(rng, size, len(keys))
    return keys[idx], rng.integers(0, VALUE_LIMIT, size, dtype=np.int64)


def send(gw, key, arg):
    return gw.submit_insert(key, arg)


def encode(answer):
    return False, 1 if answer is True else 0


def replay(ref, key, arg, found, got):
    """Duplicate writes of one key in a wave: the last submitted wins."""
    ref.upsert(*last_wins(key, arg))
    return got != 1


def prime(key, val, width):
    """Writes a stored key's own value back: the state does not change."""
    return {"insert_keys": np.asarray([key], np.int64),
            "insert_vals": np.asarray([val], np.int64), "pad_insert": width}
