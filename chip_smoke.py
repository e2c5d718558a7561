#!/usr/bin/env python3
"""Smoke test of the solver's main path on one TPU chip.

    python3 chip_smoke.py              # elasticity_3d(32) + a served stream
    python3 chip_smoke.py --grid 24 --serve-grid 32   # a quicker run

Phase 1 factors one structural finite-element system (3-D elasticity with 3
dof per vertex, the audikw_1 / Fault_639 family of ``repro.sparse.gen``)
through ``cholesky(A, device_engine=DeviceEngine())``, solves it on the
device with 1 and 64 right-hand sides, and checks the float64 residual with
scipy on the host.  A small 3-D Laplacian is checked against
``scipy.sparse.linalg.spsolve``.  Phase 2 serves a short request stream
through ``CholeskyServer()``: a new pattern, a repeat that must hit the plan
cache with no rebuild, one batched factorization of 8 matrices, and solves.

Each phase prints one JSON line of measurements.  The last line is
``{"ok": true, "device": {...}}``, printed only when every check passed.
The script exits nonzero without a result line when JAX finds no TPU, when
no group ran the compiled fused kernel, when any fallback fired, when a
check fails, or when anything raises.  Everything runs in this one process,
the only one that touches the chip.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import resource
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

RESID_TOL = 1e-10


class CheckFailed(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def rel_resid(A, x, b) -> float:
    """Largest per-column relative residual ||A x - b|| / ||b||, float64."""
    import numpy as np

    X = x.reshape(x.shape[0], -1)
    B = b.reshape(b.shape[0], -1)
    R = A @ X - B
    return float(np.max(np.linalg.norm(R, axis=0)
                        / np.linalg.norm(B, axis=0)))


class CompileClock:
    """Sums XLA backend-compile time and counts compiles (JAX monitoring)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_structural(grid: int, seed: int, clock: CompileClock, dev) -> dict:
    """Phase 1: one elasticity system, device factor + refined solves."""
    import numpy as np
    import scipy.sparse.linalg as spl

    from repro.core import DeviceEngine, cholesky
    from repro.sparse import elasticity_3d, laplacian_3d

    t0 = time.perf_counter()
    A = elasticity_3d(grid)
    n = A.shape[0]
    t_gen = time.perf_counter() - t0
    eng = DeviceEngine()
    check(eng.backend == "pallas", f"default backend is {eng.backend!r}")
    c0 = clock.seconds
    t0 = time.perf_counter()
    F = cholesky(A, device_engine=eng)
    t_factor = time.perf_counter() - t0
    c_factor = clock.seconds - c0
    sched = F.stats["schedule"]
    nnz_l = int(F.sym.factor_nnz())
    rng = np.random.default_rng(seed)
    solves = {}
    for k in (1, 64):
        b = rng.standard_normal(n) if k == 1 else rng.standard_normal((n, k))
        c0 = clock.seconds
        t0 = time.perf_counter()
        x = F.solve(b, backend="device")
        dt = time.perf_counter() - t0
        r = rel_resid(A, x, b)
        hist = F.ir_history[-1]
        solves[k] = {"wall_s": dt, "compile_s": clock.seconds - c0,
                     "resid": r, "refine_steps": len(hist) - 1,
                     "refine_hist": hist}
        check(np.all(np.isfinite(x)), f"nonfinite solution ({k} rhs)")
        check(r <= RESID_TOL, f"elasticity residual {r:.3e} ({k} rhs)")
    rec = {
        "phase": "structural", "matrix": f"elasticity_3d({grid})",
        "n": n, "nnz_A": int(A.nnz), "nnz_L": nnz_l,
        "factor_bytes": nnz_l * eng.dtype.itemsize,
        "dtype": str(eng.dtype), "backend": eng.backend,
        "levels": sched["levels"], "groups": sched["batches"],
        "group_programs": dict(eng.group_programs),
        "fallbacks": dict(eng.fallbacks),
        "gen_s": t_gen, "factor_wall_s": t_factor,
        "factor_compile_s": c_factor,
        "solve_1": solves[1], "solve_64": solves[64],
        "peak_bytes_in_use": peak_bytes(dev),
    }
    check(eng.group_programs["pallas"] > 0,
          "no group of the elasticity factor ran the compiled kernel")
    check(sum(eng.fallbacks.values()) == 0, f"fallbacks {eng.fallbacks}")
    del F

    # small Laplacian against scipy's sparse direct solver
    L = laplacian_3d(16)
    eng2 = DeviceEngine()
    t0 = time.perf_counter()
    FL = cholesky(L, device_engine=eng2)
    b = rng.standard_normal(L.shape[0])
    x = FL.solve(b, backend="device")
    x_ref = spl.spsolve(L.tocsc(), b)
    err = float(np.linalg.norm(x - x_ref) / np.linalg.norm(x_ref))
    rl = rel_resid(L, x, b)
    rec["laplacian_3d(16)"] = {
        "n": L.shape[0], "wall_s": time.perf_counter() - t0, "resid": rl,
        "rel_err_vs_spsolve": err, "group_programs": dict(eng2.group_programs),
        "fallbacks": dict(eng2.fallbacks),
    }
    check(rl <= RESID_TOL, f"laplacian residual {rl:.3e}")
    check(err <= 1e-9, f"laplacian error vs spsolve {err:.3e}")
    check(sum(eng2.fallbacks.values()) == 0, f"fallbacks {eng2.fallbacks}")
    return rec


def phase_served(grid: int, seed: int, clock: CompileClock, dev) -> dict:
    """Phase 2: a served stream over one resident engine."""
    from repro.launch.serve import CholeskyServer, run_stream, synthetic_stream

    # the first seed whose stream has a repeat factor, a batch of 8 and a
    # solve after the first (new-pattern) request
    for s in range(seed, seed + 64):
        reqs = synthetic_stream(requests=6, patterns=1, grid=grid, many=8,
                                nrhs=4, seed=s)
        kinds = [k for k, _p, _m in reqs[1:]]
        if {"factor", "factor_many", "solve"} <= set(kinds):
            break
    srv = CholeskyServer()
    c0 = clock.seconds
    t0 = time.perf_counter()
    rep = run_stream(srv, reqs, grid=grid, seed=s)
    wall = time.perf_counter() - t0
    rec = {
        "phase": "served", "grid": grid, "n": grid * grid,
        "requests": rep["requests"], "wall_s": wall,
        "compile_s": clock.seconds - c0,
        "factorizations": rep["factorizations"], "solves": rep["solves"],
        "cache": rep["cache"], "repeat_rebuilds": rep["repeat_rebuilds"],
        "max_solve_resid": rep["max_solve_resid"],
        "rejected": rep["rejected"], "degraded": rep["degraded"],
        "group_programs": dict(srv.engine.group_programs),
        "fallbacks": rep["fallbacks"],
        "peak_bytes_in_use": peak_bytes(dev),
    }
    check(rep["rejected"] == 0 and rep["degraded"]["failures"] == 0,
          f"rejected requests: {rep['degraded']}")
    check(rep["cache"]["hits"] >= 1, f"no plan-cache hit: {rep['cache']}")
    check(rep["repeat_rebuilds"] == 0,
          f"repeat_rebuilds = {rep['repeat_rebuilds']}")
    check(rep["max_solve_resid"] <= RESID_TOL,
          f"served residual {rep['max_solve_resid']:.3e}")
    check(srv.engine.group_programs["pallas"] > 0,
          "no served group ran the compiled kernel")
    check(sum(rep["fallbacks"].values()) == 0, f"fallbacks {rep['fallbacks']}")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    # grid 32 (n = 98,304): a cold run compiles one program per (level x
    # bucket) group, level solve and panel gather, about 520 s of compile
    # for v5e at this grid; at grid 44 the compiles alone exceed the
    # 1200 s a smoke run may take
    ap.add_argument("--grid", type=int, default=32,
                    help="elasticity_3d grid side (n = 3 * grid^3)")
    ap.add_argument("--serve-grid", type=int, default=48,
                    help="2-D Laplacian grid side of the served stream")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    clock = CompileClock()
    emit({"phase": "setup", "platform": dev.platform,
          "kind": dev.device_kind, "count": len(devs),
          "compile_cache": str(cache)})
    t0 = time.perf_counter()
    emit(phase_structural(args.grid, args.seed, clock, dev))
    emit(phase_served(args.serve_grid, args.seed, clock, dev))
    emit({"phase": "total", "wall_s": time.perf_counter() - t0,
          "compile_s": clock.seconds, "compiles": clock.count,
          "host_peak_rss_bytes":
              resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024})
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devs)}})
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:  # noqa: BLE001 — any failure is a failed smoke
        traceback.print_exc()
        sys.exit(1)
