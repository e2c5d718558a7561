"""The plain reference and the matrix generators."""
import pathlib
import sys

import numpy as np
import pytest
import scipy.sparse as sp

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import matrices, reference  # noqa: E402

#: every generator file, at the size its ``TINY`` gives
TINY = [{"generator": p.stem, **matrices.generator(p.stem).TINY}
        for p in sorted((BENCH / "generators").glob("*.py"))]
HEX = matrices.generator("elasticity_hex")


@pytest.mark.parametrize("gen,grid", [("laplacian_2d", 7),
                                      ("laplacian_2d", 12)])
def test_unit_coefficients_give_the_solvers_matrix(gen, grid):
    from repro.sparse import gen as original

    ref = sp.csc_matrix(getattr(original, gen)(grid))
    ref.sum_duplicates()
    ref.sort_indices()
    A = matrices.matrix({"generator": gen, "grid": grid})
    assert np.array_equal(A.indptr, ref.indptr)
    assert np.array_equal(A.indices, ref.indices)
    assert np.allclose(A.data, ref.data, rtol=0, atol=1e-15)


@pytest.mark.parametrize("cfg", TINY, ids=lambda c: c["generator"])
def test_seeded_values_keep_pattern_and_stay_spd(cfg):
    rng = np.random.default_rng(2 ** 31 + 11)
    A0 = matrices.matrix(cfg)
    A = matrices.matrix(cfg, matrices.coefficients(rng, cfg, 1.25))
    assert np.array_equal(A.indices, A0.indices)
    assert not np.allclose(A.data, A0.data)
    assert abs(A - A.T).max() == 0
    assert np.linalg.eigvalsh(A.toarray()).min() > 0


def test_hex_element_moves_rigidly_without_strain():
    K = HEX.hex_stiffness()
    assert np.array_equal(K, K.T)
    ev = np.linalg.eigvalsh(K)
    assert (np.abs(ev) < 1e-12).sum() == 6 and ev[6] > 1e-2
    # the three translations are among the null modes
    for c in range(3):
        t = np.zeros(24)
        t[c::3] = 1.0
        assert np.abs(K @ t).max() < 1e-12


def _hex_by_elements(grid, coef):
    """Assemble the clamped cube element by element, the plain way."""
    K = HEX.hex_stiffness()
    n = 3 * grid ** 3
    A = np.zeros((n, n))
    e = 0
    for k in range(grid + 1):
        for j in range(grid + 1):
            for i in range(grid + 1):
                dofs = []
                for c in (0, 1):
                    for b in (0, 1):
                        for a in (0, 1):
                            v = (i + a, j + b, k + c)
                            inside = all(1 <= x <= grid for x in v)
                            vert = ((v[0] - 1) + grid * (v[1] - 1)
                                    + grid * grid * (v[2] - 1))
                            dofs += [3 * vert + d if inside else -1
                                     for d in range(3)]
                for p, dp in enumerate(dofs):
                    for q, dq in enumerate(dofs):
                        if dp >= 0 and dq >= 0:
                            A[dp, dq] += coef[e] * K[p, q]
                e += 1
    return A


@pytest.mark.parametrize("grid", [2, 3])
def test_hex_assembly_equals_the_element_sum(grid):
    rng = np.random.default_rng(2 ** 31 + 5)
    cfg = {"generator": "elasticity_hex", "grid": grid}
    coef = matrices.coefficients(rng, cfg, 1.25)
    A = matrices.matrix(cfg, coef)
    assert np.allclose(A.toarray(), _hex_by_elements(grid, coef),
                       rtol=0, atol=1e-14)
    if grid == 3:
        # the middle vertex couples with its 27 vertex neighbours
        assert np.diff(A.indptr).max() == 81


def _dense_panels(L):
    """One supernode per column: (first, rows, P) of a dense factor."""
    n = L.shape[0]
    for j in range(n):
        rows = np.flatnonzero(L[:, j])
        rows = rows[rows >= j]
        yield j, rows, L[rows, j][:, None]


@pytest.mark.parametrize("cfg", TINY, ids=lambda c: c["generator"])
def test_factor_error_sees_exact_and_wrong_factors(cfg):
    A = matrices.matrix(cfg)
    rng = np.random.default_rng(0)
    perm = rng.permutation(A.shape[0])
    Ap = A.toarray()[perm][:, perm]
    L = np.linalg.cholesky(Ap)
    Z = rng.standard_normal((A.shape[0], 4))
    assert reference.factor_error(A, perm, _dense_panels(L), Z) < 1e-14
    L[20, 3] += 1e-4
    assert reference.factor_error(A, perm, _dense_panels(L), Z) > 1e-6


def test_rel_residual_of_nonfinite_answer_is_inf():
    T = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(4, 4))
    A = sp.csc_matrix(sp.kron(T, sp.eye(4)) + sp.kron(sp.eye(4), T))
    b = np.ones(16)
    x = np.linalg.solve(A.toarray(), b)
    assert reference.rel_residual(A, x, b) < 1e-14
    x[3] = np.nan
    assert reference.rel_residual(A, x, b) == float("inf")
