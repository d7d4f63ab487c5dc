"""The replay that decides ``correct``: wave order, writes before reads,
the last duplicate write wins; and a perturbed reference is caught."""
import numpy as np

from bench_tiny import BENCH
from yardstick import spec
from yardstick.reference import (ERROR, MISSING, OK, Records, Reference,
                                 check_answers)

OPS = spec.load_ops(BENCH, {"ops": {"read": 0.5, "update": 0.5}})
READ, UPDATE = 0, 1

KEYS = np.array([10, 20, 30], np.int64)
VALS = np.array([1, 2, 3], np.int64)


def records(rows):
    """rows: (op, key, val, wave, status, found, got), in submission order."""
    cols = list(zip(*rows))
    return Records(
        op=np.array(cols[0], np.int8), key=np.array(cols[1], np.int64),
        arg=np.array(cols[2], np.int64), wave=np.array(cols[3], float),
        status=np.array(cols[4], np.int8), found=np.array(cols[5], bool),
        got=np.array(cols[6], np.int64))


# wave 1.0: a read of 20 before any write; wave 2.0: two writes of 20 (the
# later one wins) sent after a read of 20 in the same wave, which still sees
# the write; a read of an absent key; wave 3.0: 20 read back
GOOD = [
    (READ, 20, 0, 1.0, OK, True, 2),
    (READ, 20, 0, 2.0, OK, True, 6),
    (UPDATE, 20, 5, 2.0, OK, False, 1),
    (UPDATE, 20, 6, 2.0, OK, False, 1),
    (READ, 40, 0, 2.0, OK, False, 0),
    (READ, 20, 0, 3.0, OK, True, 6),
    (READ, 30, 0, 3.0, OK, True, 3),
]


def test_sound_answers_pass():
    assert check_answers(Reference(KEYS, VALS), records(GOOD), OPS) == {
        "wrong_answers": 0, "missing_answers": 0}


def test_perturbed_reference_fails():
    vals = VALS.copy()
    vals[2] += 1                                   # 30's value
    out = check_answers(Reference(KEYS, vals), records(GOOD), OPS)
    assert out["wrong_answers"] == 1


def test_first_write_winning_is_wrong():
    rows = list(GOOD)
    rows[1] = (READ, 20, 0, 2.0, OK, True, 5)
    rows[5] = (READ, 20, 0, 3.0, OK, True, 5)
    assert check_answers(Reference(KEYS, VALS), records(rows), OPS)["wrong_answers"] == 2


def test_errors_missing_and_refused_acks_count():
    rows = list(GOOD)
    rows[6] = (READ, 30, 0, 0.0, MISSING, False, 0)
    rows[4] = (READ, 40, 0, 2.0, ERROR, False, 0)
    rows.append((UPDATE, 10, 9, 4.0, OK, False, 0))     # acked False
    out = check_answers(Reference(KEYS, VALS), records(rows), OPS)
    assert out == {"wrong_answers": 2, "missing_answers": 1}


def test_reference_upsert_inserts_new_keys_in_order():
    ref = Reference(KEYS, VALS)
    ref.upsert(np.array([25, 5, 30]), np.array([7, 8, 9]))
    assert ref.keys.tolist() == [5, 10, 20, 25, 30]
    assert ref.vals.tolist() == [8, 1, 2, 7, 9]
    found, got = ref.lookup(np.array([25, 26]))
    assert found.tolist() == [True, False] and got.tolist() == [7, 0]
