"""The plain reference: what a correct answer satisfies, in float64.

Nothing here imports the solver or takes anything it made besides the
answers under test.  A factorization answers with a permutation ``perm``
and a lower-triangular ``L`` such that ``A[perm][:, perm] = L L^T``; a solve
answers with ``x`` such that ``A x = b``.

``factor_error`` probes the whole factor at once: with ``Z`` a few random
columns, ``||L (L^T Z) - A_p Z|| / ||A_p Z||`` sees an error in any entry of
``L`` at a cost of two passes over the factor, where an entrywise product
``L L^T`` would cost as much as the factorization.  It measures the factor's
backward error, which depends on the precision the factor was computed in
and not on the matrix's condition number.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def factor_error(A: sp.spmatrix, perm: np.ndarray, panels, Z: np.ndarray
                 ) -> float:
    """Relative probe error of ``L L^T`` against ``A[perm][:, perm]``.

    ``panels`` yields ``(first, rows, P)`` per supernode: its first column,
    the row index of each panel row (the first ``w`` are the supernode's own
    columns), and the dense ``(len(rows), w)`` panel whose top ``w x w``
    block is lower triangular (its strict upper part is ignored)."""
    A = sp.csr_matrix(A)
    n = A.shape[0]
    Z = np.asarray(Z, dtype=np.float64)
    u = np.zeros_like(Z)                  # L^T Z
    parts = []
    for first, rows, P in panels:
        w = P.shape[1]
        P = np.array(P, dtype=np.float64)
        P[:w] = np.tril(P[:w])
        u[first:first + w] = P.T @ Z[rows]
        parts.append((first, rows, P))
    v = np.zeros_like(Z)                  # L (L^T Z)
    for first, rows, P in parts:
        v[rows] += P @ u[first:first + P.shape[1]]
    zn = np.empty_like(Z)
    zn[perm] = Z                          # A_p Z = (A (P^T Z))[perm]
    ref = (A @ zn)[perm]
    if v.shape[0] != n:
        raise ValueError("factor and matrix sizes differ")
    return float(np.linalg.norm(v - ref) / np.linalg.norm(ref))


def rel_residual(A: sp.spmatrix, x: np.ndarray, b: np.ndarray) -> float:
    """Largest per-column ``||A x - b|| / ||b||``; inf for a nonfinite x."""
    x = np.asarray(x, dtype=np.float64)
    if not np.all(np.isfinite(x)):
        return float("inf")
    X = x.reshape(x.shape[0], -1)
    B = np.asarray(b, dtype=np.float64).reshape(b.shape[0], -1)
    R = sp.csr_matrix(A) @ X - B
    return float(np.max(np.linalg.norm(R, axis=0)
                        / np.linalg.norm(B, axis=0)))
