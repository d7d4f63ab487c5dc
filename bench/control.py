"""Runs the control of the correctness check on the chip, at a cell's own
size and load, for several seeds in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

Each seed is one run of the cell with ``Float32KeyIndex`` (the reference,
keys compared as float32) in the program's place. It prints, per seed, the
numbers the benchmark compares; the control must come out not correct. The
benchmark's own runs never run this.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from yardstick.spec import load_cell

    cell = load_cell(ROOT, args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"control: {args.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    from repro import compile_cache
    from yardstick.cell import run_cell
    from yardstick.control import build_control

    compile_cache.enable()
    for seed in args.seeds:
        res = run_cell(cell, seed, args.seconds, False, time.perf_counter(),
                       build=build_control)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
