"""2-D five-point grid Laplacian with one coefficient per grid edge.

Copied from the solver's generator (``repro.sparse.gen``, 5-point stencil)
so the benchmark's inputs cannot change with the program.  The copy adds
one thing: every grid edge carries a coefficient, which scales its
conductance.  With all coefficients 1 the matrix equals the original entry
for entry (a test checks this).

Size key: ``grid``, the points on each side.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from chipbench.matrices import _canonical

#: the shift the original adds (``+ 1e-3 * I``)
SHIFT = 1e-3
#: a grid the CPU tests can factor in seconds
TINY = {"grid": 20}


def grid_edges(dims: tuple) -> np.ndarray:
    """(E, 2) vertex pairs of the axis-aligned grid edges of a grid with
    ``dims`` = (nx, ny[, nz]) points, x fastest (the original's ``kron``
    order)."""
    idx = np.arange(int(np.prod(dims))).reshape(dims[::-1])
    pairs = []
    for axis in range(idx.ndim):
        a = np.take(idx, np.arange(idx.shape[axis] - 1), axis=axis)
        b = np.take(idx, np.arange(1, idx.shape[axis]), axis=axis)
        pairs.append(np.stack([a.ravel(), b.ravel()], axis=1))
    return np.concatenate(pairs)


def _dims(cfg: dict) -> tuple:
    return (int(cfg["grid"]),) * 2


def n_coefficients(cfg: dict) -> int:
    dims = _dims(cfg)
    return int(grid_edges(dims).shape[0]) + int(np.prod(dims))


def weighted_laplacian(dims: tuple, coef: np.ndarray) -> sp.csc_matrix:
    """Dirichlet grid Laplacian with one coefficient per edge, plus the
    original's shift.  ``coef`` holds the E interior edges' coefficients,
    then one per vertex for its edges to the boundary (a vertex with k
    missing neighbours gets k times its boundary coefficient), which is how
    the original's ``2`` on the diagonal of every 1-D factor arises."""
    nv = int(np.prod(dims))
    e = grid_edges(dims)
    ne = e.shape[0]
    c, cb = coef[:ne], coef[ne:ne + nv]
    deg = np.zeros(nv)
    np.add.at(deg, e[:, 0], c)
    np.add.at(deg, e[:, 1], c)
    # boundary edges: 2 per axis, minus the interior edges that vertex has
    inner = np.zeros(nv)
    np.add.at(inner, e[:, 0], 1.0)
    np.add.at(inner, e[:, 1], 1.0)
    missing = 2 * len(dims) - inner
    diag = deg + missing * cb + SHIFT
    rows = np.concatenate([e[:, 0], e[:, 1], np.arange(nv)])
    cols = np.concatenate([e[:, 1], e[:, 0], np.arange(nv)])
    vals = np.concatenate([-c, -c, diag])
    return _canonical(sp.coo_matrix((vals, (rows, cols)), shape=(nv, nv)))


def matrix(cfg: dict, coef: np.ndarray | None) -> sp.csc_matrix:
    if coef is None:
        coef = np.ones(n_coefficients(cfg))
    return weighted_laplacian(_dims(cfg), coef)
