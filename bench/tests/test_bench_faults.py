"""The comparison that decides ``correct`` catches a broken timed path.

Each fault is planted under a tiny run of the whole harness (all but the
look for a chip): a write wave that leaves the state unchanged, half of a
wave's lookups left out, one answer altered where it is produced. The
exchange between chips does not exist in these one-chip cells. The
control, the plain reference with keys compared as float32 in the
program's place, must fail too."""
import time

import numpy as np
import pytest

from bench_tiny import SEED, quick, tiny_cell


def broken(fault: str):
    def build(config, keys, vals):
        from yardstick.cell import build_program

        dep = build_program(config, keys, vals)
        index = dep.index
        if fault == "state_unchanged":
            index.insert = lambda keys, vals=None, pad_to=None: 0
            return dep
        inner = index.apply_wave

        def apply_wave(wave):
            res = inner(wave)
            if res.lookup_found is not None:
                found = np.array(res.lookup_found)
                got = np.array(res.lookup_vals)
                if fault == "half_batch":
                    half = len(found) // 2
                    found[half:], got[half:] = False, 0
                else:
                    got[0] += 1
                res.lookup_found, res.lookup_vals = found, got
            return res

        index.apply_wave = apply_wave
        return dep

    return build


@pytest.mark.parametrize("name,fault", [
    ("wikits24m_s8.ycsb_a", "state_unchanged"),
    ("wikits24m_s8.ycsb_a", "half_batch"),
    ("wikits24m_s8.ycsb_a", "altered_answer"),
    ("fb24m_s8.ycsb_c", "half_batch"),
    ("fb24m_s8.ycsb_c", "altered_answer"),
])
def test_fault_in_timed_path_is_not_correct(name, fault, monkeypatch):
    cellmod = quick(monkeypatch)
    res = cellmod.run_cell(tiny_cell(name), SEED, 1.0, False,
                           time.perf_counter(), build=broken(fault))
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0
    assert res["failed"] > 0


@pytest.mark.parametrize("name", ["wikits24m_s8.ycsb_a", "fb24m_s8.ycsb_c"])
def test_control_float32_keys_is_not_correct(name, monkeypatch):
    from yardstick.control import build_control

    cellmod = quick(monkeypatch)
    res = cellmod.run_cell(tiny_cell(name), SEED, 1.0, False,
                           time.perf_counter(), build=build_control)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0
