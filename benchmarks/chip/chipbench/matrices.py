"""Seeded SPD matrices on a fixed sparsity pattern.

``laplacian_2d`` is copied from the solver's generator (``repro.sparse.gen``,
5-point stencil) so the benchmark's inputs cannot change with the program.
The copy adds one thing: every grid edge carries a coefficient, drawn
log-uniformly from ``[1/spread, spread]`` by the caller's generator.  With
all coefficients 1 the matrix equals the original entry for entry (a test
checks this).

``elasticity_hex`` is the benchmark's own: 3-D linear elasticity on a cube
of trilinear hexahedral (Q1) elements, 3 displacement dof per vertex, every
boundary vertex clamped.  Each interior vertex couples with its 27 vertex
neighbours, 81 nonzeros per row, as in the hexahedral structural models of
SuiteSparse's ``GHS_psdef`` set (audikw_1: 82 per row).  Every element
carries a coefficient that scales its Young's modulus.

A coefficient scales one edge's conductance or one element's stiffness, as
a heterogeneous or nonlinear material does from one Newton or time step to
the next.  Every coefficient is positive, so each matrix is SPD and the
pattern never changes.
"""
from __future__ import annotations

import functools

import numpy as np
import scipy.sparse as sp

#: the shift the Laplacian original adds (``+ 1e-3 * I``)
SHIFT = 1e-3
#: Poisson's ratio of the elastic material (steel)
POISSON = 0.3


def _canonical(A) -> sp.csc_matrix:
    A = sp.csc_matrix(A)
    A.sum_duplicates()
    A.sort_indices()
    return A


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


def n_coefficients(generator: str, grid: int) -> int:
    """How many coefficients one matrix of the generator takes."""
    if generator == "laplacian_2d":
        dims = (grid, grid)
        return int(grid_edges(dims).shape[0]) + int(np.prod(dims))
    if generator == "elasticity_hex":
        return (grid + 1) ** 3
    raise ValueError(f"unknown generator {generator!r}")


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


def hex_stiffness(nu: float = POISSON) -> np.ndarray:
    """(24, 24) stiffness of a unit-cube trilinear element with Young's
    modulus 1, by 2 x 2 x 2 Gauss quadrature.  Local dof ``3 a + c`` is
    component ``c`` of corner ``a = i + 2 j + 4 k``."""
    lam = nu / ((1 + nu) * (1 - 2 * nu))
    mu = 1.0 / (2 * (1 + nu))
    D = np.zeros((6, 6))
    D[:3, :3] = lam
    D[np.arange(3), np.arange(3)] += 2 * mu
    D[np.arange(3, 6), np.arange(3, 6)] = mu
    corners = np.array([(i, j, k) for k in (0, 1) for j in (0, 1)
                        for i in (0, 1)], dtype=float) * 2 - 1
    gauss = np.array([-1.0, 1.0]) / np.sqrt(3.0)
    K = np.zeros((24, 24))
    for gz in gauss:
        for gy in gauss:
            for gx in gauss:
                p = np.array([gx, gy, gz])
                # dN/dx on the unit cube: x = (xi + 1) / 2, so dN/dx = 2 dN/dxi
                f = 1 + corners * p                     # (8, 3)
                dN = np.empty((8, 3))
                for d in range(3):
                    o = [e for e in range(3) if e != d]
                    dN[:, d] = 2 * corners[:, d] * f[:, o[0]] * f[:, o[1]] / 8
                B = np.zeros((6, 24))
                for a in range(8):
                    x, y, z = dN[a]
                    c = 3 * a
                    B[0, c], B[1, c + 1], B[2, c + 2] = x, y, z
                    B[3, c], B[3, c + 1] = y, x
                    B[4, c + 1], B[4, c + 2] = z, y
                    B[5, c], B[5, c + 2] = z, x
                K += B.T @ D @ B / 8.0                  # det J = 1/8
    return (K + K.T) / 2


@functools.lru_cache(maxsize=2)
def _hex_assembly(grid: int):
    """Pattern and scatter of the clamped cube with ``grid``^3 free
    vertices: (indptr, indices, position of each element entry in the CSC
    data, which entries of the 24 x 24 element matrix are kept, n)."""
    g, ne = grid, grid + 1
    nv = g ** 3
    n = 3 * nv
    # vertex (i, j, k), 0..g+1 on each axis; 0 and g+1 are clamped
    i, j, k = np.meshgrid(np.arange(ne), np.arange(ne), np.arange(ne),
                          indexing="ij")
    i, j, k = (a.transpose(2, 1, 0).ravel() for a in (i, j, k))  # x fastest
    corner = np.array([(a, b, c) for c in (0, 1) for b in (0, 1)
                       for a in (0, 1)])
    vi = i[:, None] + corner[:, 0]
    vj = j[:, None] + corner[:, 1]
    vk = k[:, None] + corner[:, 2]
    free = ((vi >= 1) & (vi <= g) & (vj >= 1) & (vj <= g)
            & (vk >= 1) & (vk <= g))
    vert = np.where(free, (vi - 1) + g * (vj - 1) + g * g * (vk - 1), -1)
    dof = (3 * vert[:, :, None] + np.arange(3)).reshape(-1, 24)   # (E, 24)
    dof[np.repeat(vert < 0, 3, axis=1)] = -1
    r = np.repeat(dof, 24, axis=1)                                 # (E, 576)
    c = np.tile(dof, (1, 24))
    keep = (r >= 0) & (c >= 0)
    elem, local = np.nonzero(keep)
    key = c[keep].astype(np.int64) * n + r[keep]
    uniq, pos = np.unique(key, return_inverse=True)
    indices = (uniq % n).astype(np.int32)
    indptr = np.searchsorted(uniq // n, np.arange(n + 1)).astype(np.int32)
    return indptr, indices, pos, elem, local, n


def elasticity_hex(grid: int, coef: np.ndarray) -> sp.csc_matrix:
    """Clamped Q1 elasticity on ``(grid + 1)^3`` elements, element ``e``'s
    Young's modulus ``coef[e]`` (elements x fastest)."""
    indptr, indices, pos, elem, local, n = _hex_assembly(grid)
    Ke = hex_stiffness().ravel()
    data = np.bincount(pos, weights=coef[elem] * Ke[local],
                       minlength=indices.shape[0])
    return sp.csc_matrix((data, indices.copy(), indptr.copy()), shape=(n, n))


def matrix(generator: str, grid: int, coef: np.ndarray | None = None
           ) -> sp.csc_matrix:
    """One matrix of the configuration; ``coef=None`` gives the unit
    coefficients (for ``laplacian_2d``, the original generator's matrix)."""
    if coef is None:
        coef = np.ones(n_coefficients(generator, grid))
    if generator == "laplacian_2d":
        return weighted_laplacian((grid, grid), coef)
    if generator == "elasticity_hex":
        return elasticity_hex(grid, coef)
    raise ValueError(f"unknown generator {generator!r}")


def coefficients(rng: np.random.Generator, generator: str, grid: int,
                 spread: float) -> np.ndarray:
    """Coefficients drawn log-uniformly from [1/spread, spread]."""
    k = n_coefficients(generator, grid)
    return np.exp(rng.uniform(-np.log(spread), np.log(spread), size=k))
