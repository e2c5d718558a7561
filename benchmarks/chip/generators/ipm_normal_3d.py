"""Normal equations of the interior-point Newton systems of 3-D
PDE-constrained optimal control, the problem class of SuiteSparse's
nlpkkt family (Schenk, Waechter, Weiser, SIAM J. Sci. Comput. 2008).

The problem: minimise 1/2 ||y - y_hat||^2 + alpha/2 ||u||^2 subject to
K y = u and bounds on y and u, with K the 7-point Dirichlet -Laplacian on
an N^3 interior grid, h = 1/(N+1), K = h^-2 * stencil.  A primal-dual
interior-point method solves, every Newton step,

    [ H_y   0    K^T ] [dy]           H_y = I + Sigma_y
    [ 0     H_u  -I  ] [du]  =  r,    H_u = alpha I + Sigma_u
    [ K     -I   0   ] [dl]

with Sigma = Z X^-1 the diagonal barrier terms.  nlpkkt holds that
indefinite matrix; a code that factors L L^T eliminates dy and du and
factors what is left,

    S = K H_y^-1 K^T + H_u^-1,

which is SPD with 25 nonzeros in an interior row (the 7-point stencil
squared).  This file builds S in the stencil's units: with T = h^2 K the
integer stencil it returns h^4 S = T H_y^-1 T^T + h^4 H_u^-1, the same
system divided through by (N+1)^4, which leaves kappa, every relative
residual and a factor's relative error as they are and keeps the entries
of order one.  The 2 N^3 coefficients are the diagonals of H_y (first N^3)
and H_u (last N^3), taken directly as the per-step values: their spread
stands for the barrier terms' as mu falls.

Size key: ``grid``, N, the interior points on each side (n = N^3).
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from chipbench.matrices import _canonical

#: a grid the CPU tests can factor in seconds
TINY = {"grid": 6}


def grid_edges(dims: tuple) -> np.ndarray:
    """(E, 2) vertex pairs of the axis-aligned grid edges of a grid with
    ``dims`` = (nx, ny[, nz]) points, x fastest (copied from
    ``laplacian_2d.py`` so that each class stands alone)."""
    idx = np.arange(int(np.prod(dims))).reshape(dims[::-1])
    pairs = []
    for axis in range(idx.ndim):
        a = np.take(idx, np.arange(idx.shape[axis] - 1), axis=axis)
        b = np.take(idx, np.arange(1, idx.shape[axis]), axis=axis)
        pairs.append(np.stack([a.ravel(), b.ravel()], axis=1))
    return np.concatenate(pairs)


def _grid(cfg: dict) -> int:
    return int(cfg["grid"])


def n_coefficients(cfg: dict) -> int:
    return 2 * _grid(cfg) ** 3


def stencil(N: int) -> sp.csr_matrix:
    """T = h^2 K: the 7-point Dirichlet -Laplacian stencil on the N^3
    interior grid (6 on the diagonal whatever the neighbours)."""
    n = N ** 3
    e = grid_edges((N, N, N))
    rows = np.concatenate([e[:, 0], e[:, 1], np.arange(n)])
    cols = np.concatenate([e[:, 1], e[:, 0], np.arange(n)])
    vals = np.concatenate([-np.ones(2 * e.shape[0]), np.full(n, 6.0)])
    return sp.coo_matrix((vals, (rows, cols)), shape=(n, n)).tocsr()


def matrix(cfg: dict, coef: np.ndarray | None) -> sp.csc_matrix:
    """h^4 S = T H_y^-1 T^T + h^4 H_u^-1; ``coef=None`` gives
    h^4 (K K^T + I)."""
    N = _grid(cfg)
    n = N ** 3
    if coef is None:
        coef = np.ones(2 * n)
    hy, hu = coef[:n], coef[n:2 * n]
    T = stencil(N)
    S = T @ sp.diags(1.0 / hy) @ T.T + sp.diags(1.0 / (hu * (N + 1) ** 4))
    # the two triangles of a float product may differ in the last bit
    return _canonical(0.5 * (S + S.T))
