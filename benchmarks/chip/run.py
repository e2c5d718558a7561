#!/usr/bin/env python3
"""Chip benchmark of the sparse Cholesky solver: one run of one cell.

    python3 benchmarks/chip/run.py --workload elast3d_g32.refactor \
        --seed 12345 --seconds 20 --trace 0

Run from the root of a checkout, on a machine that holds the chips the cell
asks for.  The run sets up (plan, programs, value ring), warms up one step,
measures for ``--seconds`` and prints one JSON object as the last line of
standard output: with ``--trace 0`` the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window.  The numbers compared with the plain reference go last, beside
their limits, in that line and on standard error.

It exits nonzero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for, when the solver is missing (a directory that holds
only the benchmark), when anything compiles inside the window, when a
dispatch fell back, or when the cell expects the fused kernel and no group
ran it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import harness

    try:
        spec = harness.load_spec(args.workload)
        result = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START)
    except harness.RunFailed as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for line in harness.summary_lines(result):
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:  # noqa: BLE001 — any failure prints no result
        traceback.print_exc()
        sys.exit(1)
