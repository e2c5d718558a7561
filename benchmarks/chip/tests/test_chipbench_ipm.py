"""The interior-point class (``generators/ipm_normal_3d.py``) and the
refinement metrics of its cell: the ring pinned bit for bit, the normal
equations against the KKT system they condense, their pattern, and the
readers of ``gmres_ms.newton`` and ``refine_solves.newton`` on a recorded
traced run at the generator's ``TINY`` size."""
import hashlib
import pathlib
import sys

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg as spla

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import harness, matrices  # noqa: E402

CELL = "ipm_ocp3d_g32.newton"
PIN_SEED = 2 ** 31 + 41
#: sha256 over ``make_ring``'s value sets at ``TINY`` size for ``PIN_SEED``,
#: hashed as ``test_chipbench_generators.py`` hashes the other cells';
#: recorded from the generator as it was added
PINNED = "59b633f072d39c735d28748d501b151be3af256aaf36b0f8eb2e0c5702b77de1"


def _gen():
    return matrices.generator("ipm_normal_3d")


def _tiny_spec():
    spec = harness.load_spec(CELL)
    spec.config.update(_gen().TINY)
    return spec


def test_ring_is_pinned_bit_for_bit():
    ring = harness.make_ring(_tiny_spec(), PIN_SEED)
    h = hashlib.sha256()
    for A, b in [ring.warm] + [ring[k] for k in range(len(ring))]:
        for a in (A.indptr.astype(np.int64), A.indices.astype(np.int64),
                  A.data.astype(np.float64), np.asarray(b, np.float64)):
            h.update(np.ascontiguousarray(a).tobytes())
    assert h.hexdigest() == PINNED


def _laplacian_7pt(N: int) -> sp.csr_matrix:
    """K = (N+1)^2 times the 7-point Dirichlet stencil, built from 1-D
    second differences (not the generator's grid edges)."""
    e = np.ones(N)
    t = sp.diags([-e[:-1], 2 * e, -e[:-1]], [-1, 0, 1])
    eye = sp.eye(N)
    K = (sp.kron(eye, sp.kron(eye, t)) + sp.kron(eye, sp.kron(t, eye))
         + sp.kron(t, sp.kron(eye, eye)))
    return sp.csr_matrix((N + 1) ** 2 * K)


def test_normal_equations_give_the_kkt_systems_multiplier_step():
    """The float64 KKT system [[H_y, 0, K^T], [0, H_u, -I], [K, -I, 0]]
    solved whole gives the same dl as S dl = K H_y^-1 r_y - H_u^-1 r_u - r_l
    with the generator's h^4 S (both sides times h^4), at a spread where
    both are well conditioned."""
    cfg = dict(_tiny_spec().config)
    N = int(cfg["grid"])
    n = N ** 3
    rng = np.random.default_rng(2 ** 31 + 5)
    coef = matrices.coefficients(rng, cfg, 1.25)
    r = rng.standard_normal(3 * n)
    hy, hu = coef[:n], coef[n:]
    K, eye = _laplacian_7pt(N), sp.eye(n)
    kkt = sp.bmat([[sp.diags(hy), None, K.T], [None, sp.diags(hu), -eye],
                   [K, -eye, None]], format="csc")
    dl = spla.spsolve(kkt, r)[2 * n:]
    ry, ru, rl = r[:n], r[n:2 * n], r[2 * n:]
    S = _gen().matrix(cfg, coef)
    dl_s = spla.spsolve(S, (K @ (ry / hy) - ru / hu - rl) / (N + 1) ** 4)
    assert np.linalg.norm(dl_s - dl) <= 1e-10 * np.linalg.norm(dl)


def test_matrix_is_spd_with_25_nonzeros_in_an_interior_row():
    cfg = dict(_tiny_spec().config)
    N = int(cfg["grid"])
    coef = matrices.coefficients(np.random.default_rng(7), cfg, 1000.0)
    S = _gen().matrix(cfg, coef)
    assert (S != S.T).nnz == 0
    assert np.linalg.eigvalsh(S.toarray()).min() > 0
    per_row = np.diff(S.indptr).reshape(N, N, N)
    assert per_row.max() == 25
    assert (per_row[2:-2, 2:-2, 2:-2] == 25).all()
    # the pattern is the unit matrix's, whatever the coefficients
    S1 = _gen().matrix(cfg, None)
    assert np.array_equal(S.indptr, S1.indptr)
    assert np.array_equal(S.indices, S1.indices)


def _traced_run(tmp_path, monkeypatch, float32: bool):
    """One traced run of the cell at ``TINY`` size; returns the result and
    each step's ``CholeskyFactor.refine_solves``, the warm-up's first."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    if float32:
        monkeypatch.setattr(kops, "device_dtype", lambda: jnp.float32)
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "cache")
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path / "out")
    monkeypatch.setattr(harness, "enable_caches", lambda: None)
    solves, step = [], harness.step

    def counted(solver, A, b, traced=False):
        F, x = step(solver, A, b, traced)
        solves.append(list(F.refine_solves))
        return F, x

    monkeypatch.setattr(harness, "step", counted)
    out = harness.run(_tiny_spec(), 2 ** 31 + 61, 0.5, True, t_start=0.0,
                      require_tpu=False)
    return out, solves


def test_refinement_readers_read_a_recorded_run(tmp_path, monkeypatch):
    """Float32 device values, as on the chip: the steps refine through
    GMRES, and the count read from the trace is the factors' own."""
    out, solves = _traced_run(tmp_path, monkeypatch, float32=True)
    assert out["correct"] is True
    window = solves[1:]
    assert len(window) == out["attempted"] and all(len(s) == 1
                                                   for s in window)
    got = out["metrics"]
    assert got["refine_solves.newton"]["value"] == pytest.approx(
        sum(s[0] for s in window) / len(window))
    assert got["refine_solves.newton"]["unit"] == "1/step"
    # the cycles' host work is theirs: chol.refine's self time leaves it out
    assert got["gmres_ms.newton"]["value"] > 0
    assert "refine_ms.refactor" in got


def test_refinement_readers_are_silent_without_refinement(tmp_path,
                                                          monkeypatch):
    """Float64 device values: nothing refines, no span carries a count, and
    both readers return None (the line leaves them out)."""
    out, solves = _traced_run(tmp_path, monkeypatch, float32=False)
    assert all(s == [] for s in solves)
    assert "refine_solves.newton" not in out["metrics"]
    assert "gmres_ms.newton" not in out["metrics"]
    spec = harness.Spec("x", 1, {}, {}, {}, [], [], BENCH)
    run = harness.RunRecord(steps=1, window_s=1.0, counters={},
                            refine_steps=[], peak=None)
    assert spec.reader("refine_solves.newton")(run) is None
    assert spec.reader("gmres_ms.newton")(run) is None
