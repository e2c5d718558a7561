"""Matrix classes found by name: each cell's inputs are pinned bit for bit,
and a matrix class that a later change adds as new files alone (generator,
configuration, limits, manifest entries) runs through the harness."""
import hashlib
import json
import pathlib
import shutil
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import harness, matrices  # noqa: E402

PIN_SEED = 2 ** 31 + 41
#: sha256 over ``make_ring``'s value sets, the warm-up set first: each
#: matrix's indptr and indices as int64, its data and the right-hand side as
#: float64, at the cell's ``TINY`` size for ``PIN_SEED``.  Recorded from the
#: generators as they were before they moved into ``generators/``.
PINNED = {
    "elast3d_hex_g24.refactor":
        "1300aa48d134da517da8695b032abf803df9d25aaf00b1a51dd846c4f465d5a8",
    "lap2d5_g512.refactor":
        "8e590db8d5ae4bd81726f737ddc01f96a3e0cda7f9e275583ced9d6d290772fe",
}


def _tiny(spec):
    spec.config.update(matrices.generator(spec.config["generator"],
                                          spec.bench_dir).TINY)
    return spec


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_ring_is_pinned_bit_for_bit(cell):
    ring = harness.make_ring(_tiny(harness.load_spec(cell)), PIN_SEED)
    h = hashlib.sha256()
    for A, b in [ring.warm] + [ring[k] for k in range(len(ring))]:
        for a in (A.indptr.astype(np.int64), A.indices.astype(np.int64),
                  A.data.astype(np.float64), np.asarray(b, np.float64)):
            h.update(np.ascontiguousarray(a).tobytes())
    assert h.hexdigest() == PINNED[cell]


TOY_GENERATOR = '''"""1-D Dirichlet Laplacian with one coefficient per edge: tridiagonal
and SPD for positive coefficients."""
import numpy as np
import scipy.sparse as sp

from chipbench.matrices import _canonical

TINY = {"n": 64}


def n_coefficients(cfg):
    return int(cfg["n"]) + 1


def matrix(cfg, coef):
    n = int(cfg["n"])
    c = np.ones(n + 1) if coef is None else coef
    off = -c[1:n]
    return _canonical(sp.diags([off, c[:n] + c[1:], off], [-1, 0, 1]))
'''


def _snapshot() -> dict:
    """Size and time of change of the committed benchmark's files."""
    skip = {"__pycache__", ".cache", ".out", ".pytest_cache"}
    files = [ROOT / "BENCHMARK.json"] + [
        p for p in BENCH.rglob("*")
        if p.is_file() and not skip & set(p.relative_to(BENCH).parts)]
    return {str(p): (p.stat().st_size, p.stat().st_mtime_ns) for p in files}


@pytest.mark.parametrize("traced", [False, True])
def test_planted_matrix_class_runs_from_new_files_alone(
        traced, tmp_path, monkeypatch):
    """A toy generator, its configuration, limits and a one-cell manifest
    in a directory of their own, beside copies of the committed traffic mix
    and one metric reader, run correct; no file of the committed benchmark
    changes."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    monkeypatch.setattr(kops, "device_dtype", lambda: jnp.float32)
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "cache")
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path / "out")
    monkeypatch.setattr(harness, "enable_caches", lambda: None)
    before = _snapshot()

    bench = tmp_path / "bench"
    for d in ("generators", "configs", "traffic", "limits", "metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "generators" / "tridiag_1d.py").write_text(TOY_GENERATOR)
    cfg = {"name": "tridiag_n64", "generator": "tridiag_1d", "n": 64,
           "guarantees": {"device_values": "float32"},
           "expects_kernel": True}
    (bench / "configs" / "tridiag_n64.json").write_text(json.dumps(cfg))
    shutil.copy(BENCH / "traffic" / "refactor.json", bench / "traffic")
    cell = "tridiag_n64.refactor"
    (bench / "limits" / f"{cell}.json").write_text(json.dumps(
        {"limits": {"factor_err": 1e-4, "raw_resid": 1e-2,
                    "refined_resid": 1e-10}}))
    metric = "refine_steps.refactor"
    shutil.copy(BENCH / "metrics" / f"{metric}.py", bench / "metrics")
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    man = {
        "configs": [{"name": cfg["name"], "source": "test",
                     "file": "bench/configs/tridiag_n64.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": cell, "config": cfg["name"],
                       "traffic": "refactor", "chips": 1, "why": "test"}],
        "end_to_end": real["end_to_end"],
        "per_layer": [dict(m, workloads=[cell]) for m in real["per_layer"]
                      if m["name"] == metric],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    spec = harness.load_spec(cell, manifest=tmp_path / "BENCHMARK.json",
                             bench_dir=bench)
    assert spec.config == cfg
    out = harness.run(spec, 2 ** 31 + 61, 0.5, traced, t_start=0.0,
                      require_tpu=False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    want = [metric] if traced else ["step_s", "setup_s"]
    assert sorted(out["metrics"]) == sorted(want)
    assert _snapshot() == before
