#!/usr/bin/env python3
"""Readings that set the limits of a cell's check: the program on many
seeds and the control (``chipbench.control``) on a few, in one process so
that set-up and compilation are paid once.

    python3 benchmarks/chip/readings.py --workload lap2d5_g512.refactor \
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 3

Each seed gets its own value ring and a short window at the cell's own
load, checked as a run checks it; one JSON line per seed.  One warm-up step,
on the first seed's warm-up values, compiles every program before the first
window.  The benchmark's runs never run this.
"""
import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def _seeds(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def readings(spec, solver, seeds, seconds, control: bool) -> list:
    from chipbench import control as ctl, harness

    undo = ctl.bf16_storage(solver.engine) if control else None
    out = []
    try:
        for seed in seeds:
            ring = harness.make_ring(spec, seed)
            win = harness.run_window(
                solver, ring, seconds,
                keep=int(spec.traffic["check_factors"]))
            steps, secs = win.steps, win.seconds
            rec = {"seed": seed, "control": control, "steps": steps,
                   "step_s": secs / steps, "compiles": win.compiles,
                   "refine_steps": win.refine_steps,
                   **harness.check(spec, solver, ring, win)}
            print(json.dumps(rec), flush=True)
            out.append(rec)
    finally:
        if undo is not None:
            undo()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    from chipbench import harness

    t0 = time.perf_counter()
    spec = harness.load_spec(args.workload)
    harness.device_info(spec.chips, require_tpu=True)
    harness.enable_caches()
    solver = harness.prepare(spec)
    seeds = _seeds(args.seeds) + _seeds(args.control_seeds)
    F, _x = harness.step(solver, *harness.make_ring(spec, seeds[0]).warm)
    del F, _x
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    readings(spec, solver, _seeds(args.seeds), args.seconds, False)
    readings(spec, solver, _seeds(args.control_seeds), args.seconds, True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
