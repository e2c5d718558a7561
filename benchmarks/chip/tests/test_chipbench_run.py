"""Whole runs of the harness on the CPU at tiny sizes, with the look for a
chip skipped and the chip's float32 device values steered in; and each cell
as committed, which must refuse to run without a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import harness, matrices  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    """Float32 device values as on the chip; caches and traces in a
    temporary directory; a spec maker that shrinks the cell's matrix to
    its generator's ``TINY`` size."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    monkeypatch.setattr(kops, "device_dtype", lambda: jnp.float32)
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "cache")
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path / "out")
    monkeypatch.setattr(harness, "enable_caches", lambda: None)

    def make(cell, **kw):
        spec = harness.load_spec(cell, **kw)
        spec.config.update(matrices.generator(spec.config["generator"],
                                              spec.bench_dir).TINY)
        return spec

    return make


def _run(spec, traced=False, seed=2 ** 31 + 7):
    return harness.run(spec, seed, 0.5, traced, t_start=0.0,
                       require_tpu=False)


@pytest.mark.parametrize("traced", [False, True])
def test_last_line_has_the_contract_keys(tiny, traced):
    spec = tiny("lap2d5_g512.refactor")
    out = _run(spec, traced)
    assert list(out) == KEYS[:-1] + (["breakdown"] if traced else []) + [
        "checks"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    names = spec.per_layer if traced else spec.end_to_end
    want = {m["name"] for m in names}
    assert set(out["metrics"]) <= want
    if not traced:
        assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(out)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_exits_nonzero_without_a_tpu(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", cell,
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_new_config_traffic_and_metric_found_by_name(tiny, tmp_path):
    """A later PR adds a configuration, a mix and a metric as new files and
    new BENCHMARK.json entries, and edits nothing that is there."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".cache", ".out", "tests", "__pycache__"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = json.loads((BENCH / "configs" / "lap2d5_g512.json").read_text())
    cfg = dict(base, name="lap2d5_g16",
               **matrices.generator(base["generator"]).TINY)
    (bench / "configs" / "lap2d5_g16.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "wide.json").write_text(json.dumps(
        {"loop": "closed", "ring": 2, "nrhs": 1, "coef_spread": 2.0,
         "check_factors": 1}))
    (bench / "limits" / "lap2d5_g16.wide.json").write_text(json.dumps(
        {"limits": {"factor_err": 1e-4, "raw_resid": 1e-2,
                    "refined_resid": 1e-10}}))
    (bench / "metrics" / "steps_seen.wide.py").write_text(
        "def read(run):\n    return float(run.steps)\n")
    cell = "lap2d5_g16.wide"
    man["configs"].append({"name": "lap2d5_g16", "source": "x",
                           "file": "bench/configs/lap2d5_g16.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": cell, "config": "lap2d5_g16",
                             "traffic": "wide", "chips": 1, "why": "test"})
    man["per_layer"].append({"name": "steps_seen.wide", "unit": "1",
                             "better": "higher", "source": "host_clock",
                             "layer": "test", "moves": "step_s",
                             "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    spec = harness.load_spec(cell, manifest=tmp_path / "BENCHMARK.json",
                             bench_dir=bench)
    assert spec.config == cfg and spec.traffic["ring"] == 2
    out = _run(spec, traced=True)
    assert out["correct"] is True
    assert out["metrics"]["steps_seen.wide"]["value"] == out["attempted"]
    # the committed cells' metrics do not name the new cell
    assert "idle_share.refactor" not in out["metrics"]


def test_compile_inside_the_window_fails(tiny, monkeypatch):
    import jax
    import jax.numpy as jnp

    spec = tiny("elast3d_hex_g24.refactor")
    real = harness.run_window

    def compiling(solver, ring, seconds, **kw):
        real_step = harness.step

        def step(*a, **k):
            jax.jit(lambda v: v * 2.0)(jnp.arange(3.0 + len(a)))
            return real_step(*a, **k)

        monkeypatch.setattr(harness, "step", step)
        return real(solver, ring, seconds, **kw)

    monkeypatch.setattr(harness, "run_window", compiling)
    with pytest.raises(harness.RunFailed, match="compile"):
        _run(spec)


def test_fallback_inside_the_window_fails(tiny, monkeypatch):
    from repro.faults import FaultPlan

    spec = tiny("elast3d_hex_g24.refactor")
    real = harness.run_window

    def faulty(solver, ring, seconds, **kw):
        solver.engine.faults = FaultPlan(fail_dispatch=1)
        return real(solver, ring, seconds, **kw)

    monkeypatch.setattr(harness, "run_window", faulty)
    with pytest.raises(harness.RunFailed, match="fallback"):
        _run(spec)


def test_warm_up_values_are_not_in_the_ring(tiny):
    spec = tiny("elast3d_hex_g24.refactor")
    ring = harness.make_ring(spec, 2 ** 31 + 17)
    A, b = ring.warm
    assert len(ring) == spec.traffic["ring"]
    for k in range(len(ring)):
        Ak, bk = ring[k]
        assert (abs(A - Ak).max() > 0) and not (b == bk).all()
    again = harness.make_ring(spec, 2 ** 31 + 17)
    assert abs(again.warm[0] - A).max() == 0


def test_kernel_work_counts_kernel_dispatches_by_true_extents():
    import numpy as np

    from chipbench import work

    # lanes (rows, width): a padded lane has width 0 and is not counted
    kern = (True, np.array([10, 6, 0]), np.array([4, 6, 0]))
    xla = (False, np.array([50]), np.array([20]))
    got = harness.kernel_work([kern, xla, kern], 4)
    want_f = 2 * (work.supernode_flops(4, 6) + work.supernode_flops(6, 0))
    want_b = 2 * (work.supernode_bytes(4, 6, 4)
                  + work.supernode_bytes(6, 0, 4))
    assert got == {"flops": want_f, "bytes": want_b}


def test_dispatch_log_reads_what_the_engine_ran(tiny):
    """The window logs every group dispatch with the engine's own report
    of the program that ran it, and leaves the engine as it found it."""
    from repro.core import DeviceEngine

    spec = tiny("lap2d5_g512.refactor")
    solver = harness.prepare(spec)
    # the compiled kernel's path, in interpret mode on the CPU
    solver.engine = eng = DeviceEngine(backend="pallas")
    ring = harness.make_ring(spec, 2 ** 31 + 23)
    win = harness.run_window(solver, ring, 0.0, keep=1)
    assert "fused_group" not in vars(eng)
    assert win.steps == 1
    assert len(win.dispatches) == sum(eng.group_programs.values())
    ran_kernel = sum(k for k, _r, _w in win.dispatches)
    assert ran_kernel == eng.group_programs["pallas"] > 0
    # every column of the matrix is factored once, by one dispatch
    assert sum(int(w.sum()) for _k, _r, w in win.dispatches) == solver.n
