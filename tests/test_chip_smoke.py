"""chip_smoke.py rehearsed on the CPU: both phases at a tiny size, with the
kernels in Pallas interpret mode, and the refusal to report a result
anywhere but on a TPU."""
import json

import chip_smoke


def test_phase_kernels_on_cpu():
    med = chip_smoke.phase_kernels(n_keys=20_000, rounds=2, batch=1024,
                                   reps=2)
    assert set(med) == {"fused", "spline", "binsearch"}
    assert all(t > 0 for m in med.values() for t in m.values())


def test_phase_serve_on_cpu():
    res = chip_smoke.phase_serve(
        n_keys=120_000, n_shards=4, n_clients=3, n_reads=512, n_writes=384,
        n_hot=12_288, timeouts=300.0,
    )
    assert res["setup_s"] > 0 and res["warmup_s"] > 0


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    for line in out.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        assert "ok" not in doc
