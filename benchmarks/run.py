"""Benchmark harness — one entry per paper table/figure plus kernel and
roofline summaries.  Prints ``name,us_per_call,derived`` CSV sections.

    PYTHONPATH=src python -m benchmarks.run            # moderate suite
    PYTHONPATH=src python -m benchmarks.run --quick    # tiny suite (CI)
    PYTHONPATH=src python -m benchmarks.run --full     # everything
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

RESULTS = pathlib.Path(__file__).resolve().parent / "results"

QUICK_SUITE = ["elast3d_12", "kkt_192", "lap3d_24", "lap2d_256"]
DEFAULT_SUITE = ["lap2d_256", "lap2d_384", "lap2d9_256", "lap3d_24",
                 "lap3d_32", "lap3d27_24", "elast3d_12", "elast3d_16",
                 "kkt_192"]


def _max_resid(rows) -> float | None:
    """Largest *_resid across rows; None when no suite emitted residuals
    (e.g. verify=False runs or an empty/killed suite)."""
    resids = [v for r in rows for k, v in r.items() if k.endswith("_resid")]
    return max(resids) if resids else None


def bench_cholesky(suite) -> dict:
    import time
    from benchmarks import cholesky_tables as ct
    RESULTS.mkdir(parents=True, exist_ok=True)
    rows = []
    for name in suite:  # one matrix at a time: partial results survive kills
        t0 = time.time()
        rows.extend(ct.run_suite([name]))
        print(f"# done {name} in {time.time() - t0:.0f}s", flush=True)
        (RESULTS / "cholesky_suite.json").write_text(json.dumps(rows, indent=2))
    print("\n# Table I — GPU-accelerated RL (speedup vs best CPU-only)")
    print(ct.table1(rows))
    print("\n# Table II — GPU-accelerated RLB (speedup vs best CPU-only)")
    print(ct.table2(rows))
    print("\n# Figure 3 — performance profile (fraction within tau of best)")
    print(ct.fig3_profile(rows))
    resid = _max_resid(rows)
    if resid is None:
        print("\n# residual sanity: no residuals recorded")
    else:
        print(f"\n# residual sanity: max {resid:.3e}")
    return {"rows": rows, "max_resid": resid}


def bench_schedule(suite) -> dict:
    """Sequential vs level-scheduled batched offload (see core/schedule.py)."""
    import time
    from benchmarks import cholesky_tables as ct
    RESULTS.mkdir(parents=True, exist_ok=True)
    rows = []
    for name in suite:
        t0 = time.time()
        rows.extend(ct.run_schedule_compare([name]))
        print(f"# done schedule {name} in {time.time() - t0:.0f}s", flush=True)
    print("\n# Schedule — seq vs level-scheduled batched offload (full offload)")
    print(ct.table_schedule(rows))
    resid = _max_resid(rows)
    if resid is not None:
        print(f"# schedule residual sanity: max {resid:.3e}")
    return {"rows": rows, "max_resid": resid}


def bench_solve(suite) -> dict:
    """Host per-supernode solve loop vs device level-scheduled batched solve
    (RHS blocks of 1 and 64; see core/device_store.py).  Emits
    results/BENCH_solve.json alongside BENCH_cholesky.json."""
    import time
    from benchmarks import cholesky_tables as ct
    RESULTS.mkdir(parents=True, exist_ok=True)
    rows = []
    for name in suite:
        t0 = time.time()
        rows.extend(ct.run_solve_compare([name]))
        print(f"# done solve {name} in {time.time() - t0:.0f}s", flush=True)
    print("\n# Solve — host loop vs device level-scheduled batched (RHS 1 / 64)")
    print(ct.table_solve(rows))
    resid = _max_resid(rows)
    if resid is not None:
        print(f"# solve residual sanity: max {resid:.3e}")
    bench = {"rows": rows, "max_resid": resid}
    out = RESULTS / "BENCH_solve.json"
    out.write_text(json.dumps(bench, indent=2))
    print(f"# machine-readable solve results -> {out}")
    return bench


def bench_serve() -> dict:
    """Serving-path throughput: CholeskyServer synthetic request stream
    (plan-cache hit/miss, factorizations/sec, solves/sec) plus the M=8
    batched-vs-independent factorization speedup.  Emits
    results/BENCH_serve.json."""
    from benchmarks import serve_bench
    RESULTS.mkdir(parents=True, exist_ok=True)
    bench = serve_bench.run()
    print("\n# Serve — plan-cache stream + M=8 batched factorization")
    print(serve_bench.table(bench))
    out = RESULTS / "BENCH_serve.json"
    out.write_text(json.dumps(bench, indent=2))
    print(f"# machine-readable serve results -> {out}")
    return bench


def bench_analyze(suite) -> dict:
    """Static-analysis metrics (no numeric phase): per-bucket VMEM headroom
    vs the 16 MiB reference and padded/masked flop-waste ratios, both bucket
    families.  Emits results/BENCH_analyze.json."""
    from benchmarks import analyze_bench
    RESULTS.mkdir(parents=True, exist_ok=True)
    bench = analyze_bench.run(suite)
    print("\n# Analyze — VMEM headroom + waste ratios per bucket family")
    print(analyze_bench.table(bench))
    n_err = bench["report"]["errors"]
    print(f"# analyze findings: {n_err} error(s), "
          f"{bench['report']['warnings']} warning(s)")
    out = RESULTS / "BENCH_analyze.json"
    out.write_text(json.dumps(bench, indent=2))
    print(f"# machine-readable analyze results -> {out}")
    return bench


def bench_guard() -> dict:
    """Breakdown-guard detection overhead (guard="off" vs guard="raise",
    interleaved best-of-3) plus recovery outcomes on the BREAKDOWN_SUITE.
    Emits results/BENCH_guard.json."""
    from benchmarks import guard_bench
    RESULTS.mkdir(parents=True, exist_ok=True)
    bench = guard_bench.run()
    print("\n# Guard — detection overhead + breakdown recovery")
    print(guard_bench.table(bench))
    worst = max(r["overhead"] for r in bench["detection"])
    print(f"# worst detection overhead: {worst * 100:.1f}%")
    out = RESULTS / "BENCH_guard.json"
    out.write_text(json.dumps(bench, indent=2))
    print(f"# machine-readable guard results -> {out}")
    return bench


def bench_kernels() -> None:
    from benchmarks import kernel_bench
    print("\n# Kernels — name,us_per_call,derived")
    for line in kernel_bench.run():
        print(line)


def bench_roofline() -> None:
    """Summarize cached dry-run roofline records (produced by
    repro.launch.dryrun; see EXPERIMENTS.md §Roofline)."""
    d = RESULTS / "dryrun"
    if not d.exists():
        print("\n# Roofline — no dryrun results cached (run repro.launch.dryrun)")
        return
    print("\n# Roofline — arch,shape,mesh,bound,t_compute,t_memory,t_collective,"
          "model_vs_hlo_flops,mfu_at_roofline")
    for p in sorted(d.glob("*.json")):
        r = json.loads(p.read_text())
        if r.get("skipped"):
            print(f"{r['arch']},{r['shape']},{r['mesh']},SKIPPED,,,,,")
            continue
        if not r.get("ok"):
            print(f"{r['arch']},{r['shape']},{r['mesh']},FAILED,,,,,")
            continue
        rf = r["roofline"]
        print(f"{r['arch']},{r['shape']},{r['mesh']},{rf['bound']},"
              f"{rf['t_compute_s']:.3e},{rf['t_memory_s']:.3e},"
              f"{rf['t_collective_s']:.3e},"
              f"{rf.get('model_vs_hlo_flops', 0):.3f},"
              f"{rf.get('mfu_at_roofline', 0):.4f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    choices=[None, "cholesky", "schedule", "solve", "serve",
                             "analyze", "guard", "kernels", "roofline"])
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.quick:
        suite = QUICK_SUITE
    elif args.full:
        from repro.sparse import MATRIX_SUITE
        suite = list(MATRIX_SUITE)
    else:
        suite = DEFAULT_SUITE

    bench = {}
    if args.only in (None, "cholesky"):
        bench["cholesky"] = bench_cholesky(suite)
    if args.only in (None, "schedule"):
        # the schedule comparison offloads everything, so stick to the quick
        # suite unless a full run was explicitly requested
        bench["schedule"] = bench_schedule(suite if args.full else QUICK_SUITE)
    if args.only in (None, "solve"):
        # same full-offload rationale as the schedule comparison
        bench_solve(suite if args.full else QUICK_SUITE)
    if args.only in (None, "serve"):
        bench_serve()
    if args.only in (None, "analyze"):
        # static passes only — cheap enough to run the quick suite always
        bench_analyze(suite if args.full else QUICK_SUITE)
    if args.only in (None, "guard"):
        bench_guard()
    if bench:
        RESULTS.mkdir(parents=True, exist_ok=True)
        out = RESULTS / "BENCH_cholesky.json"
        out.write_text(json.dumps(bench, indent=2))
        print(f"\n# machine-readable results -> {out}")
    if args.only in (None, "kernels"):
        bench_kernels()
    if args.only in (None, "roofline"):
        bench_roofline()


if __name__ == "__main__":
    main()
