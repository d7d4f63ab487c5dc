"""Chip benchmark of the served index: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` at the checkout's root, its
configuration in ``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` and, with ``--trace 1``, each per-layer
metric's reader in ``bench/metrics/<metric>.py``. It runs on the TPU it is
started on: with no TPU, or fewer chips than the cell asks for, it exits
with code 2 and prints no result.

Progress goes to standard error and ends with each number that decides
``correct`` beside its limit. The last line of standard output is the
result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last the ``checks``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the persistent compilation cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from yardstick.spec import load_cell

    cell = load_cell(ROOT, args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s). Nothing was "
              "run.", file=sys.stderr)
        return 2
    from repro import compile_cache
    from yardstick.cell import run_cell

    compile_cache.enable()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START,
                      trace_dir=str(ROOT / ".bench_trace"))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
