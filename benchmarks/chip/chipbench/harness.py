"""One run of one cell: set-up, warm-up, the measured window, the check.

A cell is ``<config>.<traffic>``.  ``BENCHMARK.json`` names the cell's
configuration file and its metrics; everything else is found by name under
the benchmark's directory:

    configs/<config>.json    sizes, source, cuts, the guarantees stated,
                             and the matrix class by ``generator``
    generators/<generator>.py  the matrix class (``chipbench.matrices``)
    traffic/<traffic>.json   the parameters of the step loop
    limits/<cell>.json       the limit of each number compared, with the
                             readings it was set from
    metrics/<metric>.py      a reader ``read(run) -> float | None``

A step refactors the configuration's fixed pattern with new values and
then solves with refinement to float64 accuracy: the loop of implicit time
stepping, Newton and interior-point methods.  Values and right-hand sides
come from ``--seed`` through a ring made in set-up, so the window times the
solver and not the generator; the warm-up step factors a value set of its
own, which no step of the window repeats.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import pathlib
import shutil
import time
from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np

from chipbench import matrices, reference, trace as tracemod, work

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
REPO_ROOT = BENCH_DIR.parents[1]
#: compile and plan caches live at fixed paths inside the checkout: the
#: path is part of the compile cache's key, so a moving one never hits
CACHE_DIR = BENCH_DIR / ".cache"
OUT_DIR = BENCH_DIR / ".out"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
#: random probe columns of the factor check
PROBES = 4


class RunFailed(RuntimeError):
    """The run cannot be measured: no result line is printed."""


@dataclass
class Spec:
    """Everything one cell's run reads from files."""
    cell: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    bench_dir: pathlib.Path

    def reader(self, metric: str):
        path = self.bench_dir / "metrics" / f"{metric}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read


def _for_cell(entries: list, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def load_spec(cell: str, manifest=None, bench_dir=None) -> Spec:
    """Read the cell's entries from ``BENCHMARK.json`` and its files."""
    manifest = pathlib.Path(manifest or REPO_ROOT / "BENCHMARK.json")
    bench_dir = pathlib.Path(bench_dir or BENCH_DIR)
    bench = json.loads(manifest.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell not in cells:
        raise RunFailed(f"no workload {cell!r} in {manifest}")
    w = cells[cell]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_file = manifest.parent / configs[w["config"]]["file"]
    return Spec(
        cell=cell, chips=int(w["chips"]),
        config=json.loads(cfg_file.read_text()),
        traffic=json.loads(
            (bench_dir / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads(
            (bench_dir / "limits" / f"{cell}.json").read_text())["limits"],
        end_to_end=_for_cell(bench["end_to_end"], cell),
        per_layer=_for_cell(bench["per_layer"], cell),
        bench_dir=bench_dir,
    )


def enable_caches() -> None:
    """JAX's persistent compile cache at the benchmark's fixed path, or in
    ``JAX_COMPILATION_CACHE_DIR`` where the environment sets one; every
    program cached (a small program loads faster than it compiles)."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(CACHE_DIR / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCount:
    """Counts tracing, lowering and compiling events (JAX monitoring)."""

    def __init__(self):
        import jax

        self.count = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _duration, **_kw):
        if event in COMPILE_EVENTS:
            self.count += 1

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on)


@dataclass
class Ring:
    """The values and right-hand sides a run cycles through, and the value
    set of the warm-up step, which the ring does not hold."""
    seed: int
    mats: list
    rhs: list
    warm: tuple

    def __len__(self) -> int:
        return len(self.mats)

    def __getitem__(self, k: int) -> tuple:
        return self.mats[k % len(self)], self.rhs[k % len(self)]


def make_ring(spec: Spec, seed: int) -> Ring:
    cfg, tr = spec.config, spec.traffic
    rng = np.random.default_rng(seed)
    sets = []
    for _ in range(int(tr["ring"]) + 1):
        coef = matrices.coefficients(rng, cfg, float(tr["coef_spread"]),
                                     spec.bench_dir)
        A = matrices.matrix(cfg, coef, spec.bench_dir)
        b = rng.standard_normal((A.shape[0], int(tr["nrhs"])))
        sets.append((A, b[:, 0] if tr["nrhs"] == 1 else b))
    warm, *ring = sets
    return Ring(seed, [A for A, _ in ring], [b for _, b in ring], warm)


@dataclass
class Solver:
    """The system under test, set up once per process: the plan of the
    configuration's pattern and one device engine, as a user who refactors
    one pattern every step holds them."""
    plan: object
    engine: object
    core: object
    n: int


def prepare(spec: Spec) -> Solver:
    from repro import core

    A0 = matrices.matrix(spec.config, bench_dir=spec.bench_dir)
    eng = core.DeviceEngine()
    # the plan on disk holds the schedule family this engine will ask for,
    # so that only a checkout's first run builds it (no metric reads it)
    bucket = ("fused" if eng.fused_groups and eng.backend == "pallas"
              else "batch")
    cache = core.PlanCache(cache_dir=CACHE_DIR / "plans",
                           warm_buckets=(bucket,))
    plan = cache.get(A0)
    return Solver(plan, eng, core, int(A0.shape[0]))


def _annotate(name: str, on: bool):
    if not on:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(tracemod.SPAN_PREFIX + name)


@dataclass
class Window:
    steps: int = 0
    seconds: float = 0.0
    xs: list = field(default_factory=list)         # refined answer per step
    refine_steps: list = field(default_factory=list)
    kept: list = field(default_factory=list)       # (step, sym, panels)
    last: object = None                            # the last step's factor
    counters: dict = field(default_factory=dict)
    compiles: int = 0
    #: (ran the compiled kernel, true rows per lane, true width per lane)
    #: of every group dispatch, as the engine was handed it
    dispatches: list = field(default_factory=list)


def step(solver: Solver, A, b, traced: bool = False):
    """One refactor-and-solve step on the values ``A`` and the right-hand
    side ``b``."""
    with _annotate("factor", traced):
        F = solver.core.cholesky(A, plan=solver.plan,
                                 device_engine=solver.engine)
    with _annotate("solve", traced):
        x = F.solve(b, backend="device")
    return F, x


def _counters(eng) -> dict:
    return {"bytes_in": eng.stats["bytes_in"],
            "device_calls": eng.stats["device_calls"],
            "kernel_groups": eng.group_programs["pallas"],
            "fallbacks": sum(eng.fallbacks.values())}


class DispatchLog:
    """Records each group dispatch the engine makes while open: whether the
    compiled kernel ran it (the engine's own count of kernel dispatches
    moved) and the group's true extents as the engine holds them on the
    device, read only once the window has closed."""

    def __init__(self, eng, log: list):
        self.eng = eng
        self.own = "fused_group" in vars(eng)
        self.real = real = eng.fused_group

        def fused_group(chunk, pool, g, lvl=-1, **kw):
            before = eng.group_programs["pallas"]
            out = real(chunk, pool, g, lvl, **kw)
            log.append((eng.group_programs["pallas"] > before, g.rows, g.ws))
            return out

        eng.fused_group = fused_group

    def close(self) -> None:
        if self.own:
            self.eng.fused_group = self.real
        else:
            del self.eng.fused_group


def kernel_work(dispatches: list, itemsize: int) -> dict:
    """Flops and least HBM bytes of the supernodes that the compiled
    kernel factored, counted from their true extents."""
    flops = nbytes = 0.0
    for kernel, rows, ws in dispatches:
        if not kernel:
            continue
        rows, ws = np.asarray(rows), np.asarray(ws)
        for r, w in zip(rows[ws > 0].tolist(), ws[ws > 0].tolist()):
            flops += work.supernode_flops(w, r - w)
            nbytes += work.supernode_bytes(w, r - w, itemsize)
    return {"flops": flops, "bytes": nbytes}


def run_window(solver: Solver, ring: Ring, seconds: float, *,
               keep: int, traced: bool = False) -> Window:
    """Refactor and solve until ``seconds`` have passed; the window ends on
    a completed step.  ``keep`` earlier factors, drawn from the seed by
    reservoir sampling, stay on the host for the check."""
    eng = solver.engine
    rng = np.random.default_rng([ring.seed, 1])
    win = Window()
    clock = CompileCount()
    before = _counters(eng)
    log = DispatchLog(eng, win.dispatches)
    F = None
    t0 = time.perf_counter()
    with _annotate("window", traced):
        while True:
            if F is not None:
                k = win.steps - 1
                item = (k, F.sym, F.panels)
                if len(win.kept) < keep:
                    win.kept.append(item)
                else:
                    j = int(rng.integers(0, k + 1))
                    if j < keep:
                        win.kept[j] = item
            F = None  # the previous factor's device state goes first
            F, x = step(solver, *ring[win.steps], traced)
            win.xs.append(x)
            win.refine_steps.append(
                len(F.ir_history[-1]) - 1 if F.ir_history else 0)
            win.steps += 1
            if time.perf_counter() - t0 >= seconds:
                break
    win.seconds = time.perf_counter() - t0
    log.close()
    clock.close()
    win.compiles = clock.count
    after = _counters(eng)
    win.counters = {k: after[k] - before[k] for k in after}
    win.last = F
    return win


def check(spec: Spec, solver: Solver, ring: Ring, win: Window) -> dict:
    """Compare what the window produced with the plain reference, once the
    window has closed: every refined answer, the last step's factor and a
    sample of earlier ones, and the last factor's unrefined device solve.
    Frees the last factor's device state on the way."""
    lim = spec.limits
    F = win.last
    k_last = win.steps - 1
    A_last, b_last = ring[k_last]
    x0 = F.solve(b_last, backend="device", refine=False)
    raw = reference.rel_residual(A_last, x0, b_last)
    kept = win.kept + [(k_last, F.sym, F.panels)]
    F.dstore = None
    win.last = None
    del F
    failed = set()
    if not raw <= lim["raw_resid"]:
        failed.add(k_last)
    zrng = np.random.default_rng([ring.seed, 2])
    factor_err = 0.0
    for k, sym, panels in kept:
        Z = zrng.standard_normal((solver.n, PROBES))
        it = ((int(sym.super_ptr[s]), sym.rows[s], panels[s])
              for s in range(sym.nsuper))
        e = reference.factor_error(ring[k][0], sym.perm, it, Z)
        if not e <= lim["factor_err"]:
            failed.add(k)
        factor_err = max(factor_err, e) if e == e else math.inf
    refined = 0.0
    for k, x in enumerate(win.xs):
        r = reference.rel_residual(ring[k][0], x, ring[k][1])
        if not r <= lim["refined_resid"]:
            failed.add(k)
        refined = max(refined, r) if r == r else math.inf
    checks = {
        "factor_err": {"value": factor_err, "limit": lim["factor_err"]},
        "raw_resid": {"value": raw, "limit": lim["raw_resid"]},
        "refined_resid": {"value": refined, "limit": lim["refined_resid"]},
    }
    ok = not failed and all(c["value"] <= c["limit"]
                            for c in checks.values())
    return {"correct": ok, "attempted": win.steps, "failed": len(failed),
            "checks": checks}


@dataclass
class RunRecord:
    """What a metric reader may read."""
    steps: int
    window_s: float
    counters: dict
    refine_steps: list
    peak: dict
    kernel_work: dict = None    # over the window; read in traced runs only
    trace: object = None


def device_info(chips_needed: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < chips_needed):
        raise RunFailed(
            f"need {chips_needed} TPU chip(s); JAX finds {len(devs)} "
            f"{devs[0].platform!r} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_of(kind: str, bench_dir: pathlib.Path) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise RunFailed(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def run(spec: Spec, seed: int, seconds: float, traced: bool, *,
        t_start: float, require_tpu: bool = True) -> dict:
    """One whole run; returns the result object the last line prints."""
    device = device_info(spec.chips, require_tpu)
    peak = (peak_of(device["kind"], spec.bench_dir) if require_tpu
            else None)
    enable_caches()
    solver = prepare(spec)
    ring = make_ring(spec, seed)
    F, _x = step(solver, *ring.warm)        # warm-up: every program once
    del F, _x
    trace_dir = OUT_DIR / "trace" / spec.cell
    if traced:
        import jax

        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    t_window = time.perf_counter()
    win = run_window(solver, ring, seconds,
                     keep=int(spec.traffic["check_factors"]), traced=traced)
    if traced:
        jax.profiler.stop_trace()
    setup_s = t_window - t_start
    if win.compiles:
        raise RunFailed(f"{win.compiles} compile events inside the window")
    if win.counters["fallbacks"]:
        raise RunFailed(f"fallbacks inside the window: "
                        f"{solver.engine.fallbacks}")
    if (require_tpu and spec.config["expects_kernel"]
            and not win.counters["kernel_groups"]):
        raise RunFailed("no group ran the compiled kernel in the window")
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    rec = RunRecord(win.steps, win.seconds, win.counters, win.refine_steps,
                    peak)
    out = {"correct": False, "attempted": win.steps, "failed": 0,
           "metrics": {}, "device": device}
    if traced:
        itemsize = np.dtype(spec.config["guarantees"]["device_values"])
        rec.kernel_work = kernel_work(win.dispatches, itemsize.itemsize)
        rec.trace = tracemod.load(trace_dir)
        device["busy_s"] = tracemod.busy_seconds(rec.trace)
        device["window_s"] = tracemod.window_seconds(rec.trace)
        out["breakdown"] = {"device_ops": tracemod.top_ops(rec.trace),
                            "idle_gaps": tracemod.idle_gaps(rec.trace)}
        _write_trace_summary(spec.cell, rec.trace)
        for m in spec.per_layer:
            v = spec.reader(m["name"])(rec)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"step_s": win.seconds / win.steps, "setup_s": setup_s}
        for m in spec.end_to_end:
            out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
    win.dispatches.clear()
    verdict = check(spec, solver, ring, win)
    out.update(correct=verdict["correct"], attempted=verdict["attempted"],
               failed=verdict["failed"])
    out["checks"] = verdict["checks"]        # last key, by contract
    return out


def _write_trace_summary(cell: str, tr) -> None:
    """The device operations of the trace with their labels, for a reader
    who looks for a kernel's name."""
    seen: dict = {}
    for o in tr.ops:
        s = seen.setdefault(o.name, {"seconds": 0.0, "events": 0,
                                     "label": o.label[:300]})
        s["seconds"] += o.dur * 1e-9
        s["events"] += 1
    top = sorted(seen.items(), key=lambda kv: -kv[1]["seconds"])
    top = top[:60] + [kv for kv in top[60:] if "custom-call" in kv[1]["label"]]
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    (OUT_DIR / f"{cell}.trace_summary.json").write_text(json.dumps(
        {"devices": tr.devices, "spans": len(tr.spans), "ops": dict(top)},
        indent=1))


def summary_lines(result: dict) -> list:
    """The numbers compared, each beside its limit, for standard error."""
    return [f"check {name} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}"
            for name, c in result["checks"].items()]
