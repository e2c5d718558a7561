"""3-D linear elasticity on a cube of trilinear hexahedral (Q1) elements.

The benchmark's own: 3 displacement dof per vertex, every boundary vertex
clamped.  Each interior vertex couples with its 27 vertex neighbours, 81
nonzeros per row, as in the hexahedral structural models of SuiteSparse's
``GHS_psdef`` set (audikw_1: 82 per row).  Every element carries a
coefficient that scales its Young's modulus.

Size key: ``grid``, the free vertices on each side of the cube.
"""
from __future__ import annotations

import functools

import numpy as np
import scipy.sparse as sp

#: Poisson's ratio of the elastic material (steel)
POISSON = 0.3
#: a grid the CPU tests can factor in seconds
TINY = {"grid": 4}


def n_coefficients(cfg: dict) -> int:
    return (int(cfg["grid"]) + 1) ** 3


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
    data, which entries of the 24 x 24 element matrix are kept, n).  Cached:
    set-up builds every matrix of a ring on this one pattern."""
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


def matrix(cfg: dict, coef: np.ndarray | None) -> sp.csc_matrix:
    if coef is None:
        coef = np.ones(n_coefficients(cfg))
    return elasticity_hex(int(cfg["grid"]), coef)
