# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real CPU device.  Only launch/dryrun.py (its own process) forces 512
# placeholder devices.
import numpy as np
import pytest
import scipy.sparse as sp


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def make_spd(n: int, density: float, seed: int) -> sp.csc_matrix:
    """Random sparse SPD with symmetric pattern + diagonal dominance."""
    r = np.random.default_rng(seed)
    nnz = max(int(density * n * n), n)
    rows = r.integers(0, n, nnz)
    cols = r.integers(0, n, nnz)
    vals = r.standard_normal(nnz)
    A = sp.csr_matrix((vals, (rows, cols)), shape=(n, n))
    A = (A + A.T) * 0.5
    d = np.abs(A).sum(axis=1)
    A = A + sp.diags(np.asarray(d).ravel() + 1.0)
    A = sp.csc_matrix(A)
    A.sort_indices()
    return A


def ipm_normal_3d(N: int, spread: float, seed: int):
    """Normal equations of an interior-point Newton step of 3-D optimal
    control and a right-hand side: ``S = K H_y^-1 K^T + H_u^-1`` with ``K``
    the 7-point Dirichlet -Laplacian on the N^3 interior grid scaled by
    h^-2 = (N+1)^2, and the diagonals of ``H_y`` then ``H_u`` drawn
    log-uniform in [1/spread, spread] from ``seed``, then ``b``.  The
    spread of the barrier terms sets the conditioning: kappa(S) grows
    about as N^4 spread^2."""
    def t(m):
        e = np.ones(m)
        return sp.diags([-e[:-1], 2 * e, -e[:-1]], [-1, 0, 1])

    eye = sp.eye(N)
    K = (N + 1) ** 2 * (sp.kron(eye, sp.kron(eye, t(N)))
                        + sp.kron(eye, sp.kron(t(N), eye))
                        + sp.kron(t(N), sp.kron(eye, eye)))
    n = N ** 3
    r = np.random.default_rng(seed)
    c = np.exp(r.uniform(-np.log(spread), np.log(spread), size=2 * n))
    S = K @ sp.diags(1.0 / c[:n]) @ K.T + sp.diags(1.0 / c[n:])
    S = sp.csc_matrix(0.5 * (S + S.T))
    S.sort_indices()
    return S, r.standard_normal(n)
