"""Partition-refinement reordering of columns within supernodes
(Jacquelin–Ng–Peyton [11], Karsavuran–Ng–Peyton [12]).

RLB issues one DSYRK/DGEMM per block pair, so its performance is governed by
the number of blocks.  Reordering the columns *within* each supernode never
changes the fill, but it can make the update footprints of descendant
supernodes contiguous, collapsing many small blocks into few large ones.

For each supernode ``a`` we collect the restriction sets
``R_d = tail(d) ∩ cols(a)`` of every descendant ``d`` that updates ``a`` and
run ordered partition refinement: cells are split by each ``R_d`` with the
touched part placed toward the previously-touched region, which drives each
``R_d`` toward a contiguous column range.
"""
from __future__ import annotations

import numpy as np

from repro.core import counters
from repro.core.symbolic import SymbolicFactor


def refine_cell_order(width: int, restrictions: list[np.ndarray]) -> np.ndarray:
    """Ordered partition refinement on ``range(width)``.

    restrictions: list of int arrays (column offsets in [0, width)).
    Returns a permutation ``g`` of range(width): new position k holds old
    column ``g[k]``.
    """
    if width == 1 or not restrictions:
        return np.arange(width, dtype=np.int64)
    cells: list[np.ndarray] = [np.arange(width, dtype=np.int64)]
    # bigger restriction sets first: they establish the coarse layout
    for R in sorted(restrictions, key=lambda r: -r.shape[0]):
        if R.shape[0] in (0, width):
            continue
        inR = np.zeros(width, dtype=bool)
        inR[R] = True
        new_cells: list[np.ndarray] = []
        seen_touched = False
        for C in cells:
            m = inR[C]
            hit = C[m]
            miss = C[~m]
            if hit.size == 0 or miss.size == 0:
                new_cells.append(C)
                if hit.size:
                    seen_touched = True
                continue
            if not seen_touched:
                # first touched cell: put hits last so they abut the next one
                new_cells.append(miss)
                new_cells.append(hit)
                seen_touched = True
            else:
                new_cells.append(hit)
                new_cells.append(miss)
        cells = new_cells
    return np.concatenate(cells)


def collect_restrictions(sym: SymbolicFactor) -> list[list[np.ndarray]]:
    """restrictions[a] = list of col-offset arrays from descendants updating a."""
    out: list[list[np.ndarray]] = [[] for _ in range(sym.nsuper)]
    for s in range(sym.nsuper):
        w = sym.width(s)
        t = sym.rows[s][w:]
        m = t.shape[0]
        k = 0
        while k < m:
            a = int(sym.snode[t[k]])
            fa, la = int(sym.super_ptr[a]), int(sym.super_ptr[a + 1])
            k1 = int(np.searchsorted(t, la))
            out[a].append((t[k:k1] - fa).astype(np.int64))
            k = k1
    return out


def refine_partition(sym: SymbolicFactor) -> tuple[SymbolicFactor, np.ndarray]:
    """Compute the within-supernode reordering and apply it to the symbolic
    factor.  Returns (new_sym, g) where g is the global permutation to apply
    to the already-permuted matrix: ``A2 = A[g][:, g]``."""
    n = sym.n
    restrictions = collect_restrictions(sym)
    g = np.arange(n, dtype=np.int64)
    for a in range(sym.nsuper):
        fa, la = int(sym.super_ptr[a]), int(sym.super_ptr[a + 1])
        w = la - fa
        if w > 1 and restrictions[a]:
            local = refine_cell_order(w, restrictions[a])
            g[fa:la] = fa + local

    # relabel: old label r -> new label gmap[r]
    gmap = np.empty(n, dtype=np.int64)
    gmap[g] = np.arange(n, dtype=np.int64)

    rows = []
    for s in range(sym.nsuper):
        w = sym.width(s)
        tail = np.sort(gmap[sym.rows[s][w:]])
        rows.append(np.concatenate([sym.rows[s][:w], tail]))

    # rebuild the column etree consistent with the relabeling
    parent = np.full(n, -1, dtype=np.int64)
    for s in range(sym.nsuper):
        f, l = int(sym.super_ptr[s]), int(sym.super_ptr[s + 1])
        parent[f:l - 1] = np.arange(f + 1, l, dtype=np.int64)
        t = rows[s][l - f:]
        parent[l - 1] = t[0] if t.shape[0] else -1

    new_sym = SymbolicFactor(
        n=n, perm=sym.perm[g], parent=parent, super_ptr=sym.super_ptr.copy(),
        rows=rows, snode=sym.snode.copy(), sparent=sym.sparent.copy(),
        colcount=None,
    )
    return new_sym, g


# ---------------------------------------------------------------------------
# residual-driven solve refinement (breakdown recovery)
# ---------------------------------------------------------------------------
def block_refine(matvec, psolve, B, X=None, *, tol=1e-12, max_steps=10):
    """Stationary iterative refinement of a whole block of right-hand sides
    at once: ``X += psolve(B - matvec(X))``, one block solve per step.

    ``B`` is (..., n, k); residuals are taken per column over axis -2.  A
    step is kept, column by column, only where it at least halves that
    column's residual, and the loop stops when every column is at ``tol``
    or no column contracted.  A float32 device factor of a well-conditioned
    SPD matrix needs nothing more (each step gains about
    -log10(kappa * eps32) digits); an ill-conditioned SPD matrix, whose
    steps contract too little, and a perturbed factor of an indefinite
    matrix stall here and finish in GMRES (``refine_solve``).  Returns
    ``(X, res, hist)``: per-column relative residuals and the
    max-over-columns trajectory."""
    B = np.asarray(B, dtype=np.float64)
    X = np.asarray(psolve(B) if X is None else X, dtype=np.float64)
    bn = np.maximum(np.linalg.norm(B, axis=-2), np.finfo(np.float64).tiny)
    R = B - matvec(X)
    res = np.linalg.norm(R, axis=-2) / bn
    hist = [float(res.max())]
    for _ in range(max_steps):
        if hist[-1] <= tol:
            break
        Xt = X + np.asarray(psolve(R), dtype=np.float64)
        Rt = B - matvec(Xt)
        rt = np.linalg.norm(Rt, axis=-2) / bn
        better = rt < 0.5 * res
        if not better.any():
            break
        X = np.where(better[..., None, :], Xt, X)
        R = np.where(better[..., None, :], Rt, R)
        res = np.where(better, rt, res)
        hist.append(float(res.max()))
    return X, res, hist


def refine_solve(F, A, b, *, x0=None, tol=1e-12, max_iter=None,
                 backend: str = "host", engine=None):
    """Refine ``F.solve`` toward the solution of the ORIGINAL system A x = b.

    Used whenever the factor is inexact for A: a float32 device factor, or
    ``guard="perturb"`` / ``guard="shift"`` recovery, where ``F`` is an
    exact factorization of a *perturbed* matrix A + E and its raw solve is
    only a preconditioner for A.  Block iterative refinement over all RHS
    columns comes first (``block_refine``; it alone converges when A is SPD
    and well conditioned next to the factor's precision), then each column
    still above ``tol`` gets one more guarded IR step and flexible GMRES
    with ``M^{-1} = F.solve`` (``_refine_one``): stationary IR provably
    stalls when A is indefinite — a pivot perturbed from d <= 0 up to t > 0
    contributes an iteration factor |t - d| / t >= 1 — while GMRES with a
    rank-p perturbation preconditioner terminates in at most p + 1
    iterations; and it stalls too when kappa(A) * eps32 nears 1, where
    GMRES still converges.

    Returns ``(x, hist, solves)``: ``hist`` is the relative-residual
    trajectory (max over RHS columns for multi-RHS ``b``), ``solves`` the
    number of times the factor was applied, the raw solve included.  The
    ``chol.refine`` span carries ``solves`` as its argument.
    """
    with counters.span("refine") as span:
        b = np.asarray(b, dtype=np.float64)
        squeeze = b.ndim == 1
        B = b[:, None] if squeeze else b
        if max_iter is None:
            # full-basis GMRES terminates exactly within n steps; rank-p
            # perturbations (guard='perturb') need only p + 1 — budget 2p
            # plus slack for finite-precision drag — while full-rank shifts
            # (guard='shift') may need the spectrum-driven worst case
            rep = getattr(F, "guard_report", None)
            p = sum(q["n_clamped"] for q in rep.perturbations) if rep else 0
            if rep is not None and p and not rep.shift:
                max_iter = int(min(B.shape[0], max(2 * p + 30, 100)))
            else:
                max_iter = int(min(B.shape[0], 300))
        solves = 0

        def psolve(v):
            nonlocal solves
            solves += 1
            return np.asarray(
                F.solve(v, backend=backend, engine=engine, refine=False)
            )

        X0 = (None if x0 is None
              else np.asarray(x0, dtype=np.float64).reshape(B.shape))
        X, res, hist = block_refine(lambda V: A @ V, psolve, B, X0, tol=tol)
        hists = []
        for j in np.flatnonzero(res > tol):
            X[:, j], hj = _refine_one(A, B[:, j], X[:, j], psolve, tol,
                                      max_iter)
            res[j] = hj[-1]
            hists.append(hj)
        if hists:
            # entry i = worst GMRES column at stage i, floored at the block's
            # converged columns; the last entry is the final max residual
            done = float(np.max(res, initial=0.0))
            depth = max(len(h) for h in hists)
            hist += [max(max(h[min(i, len(h) - 1)] for h in hists),
                         done if i == depth - 1 else 0.0)
                     for i in range(depth)]
        span.set_metadata(solves=solves)
        return (X[:, 0] if squeeze else X), hist, solves


def _refine_one(A, b, x0, psolve, tol, max_iter):
    """Single-RHS refinement: 1 guarded IR step, then restarted flexible
    GMRES cycles (Saad, SIAM J. Sci. Comput. 14, 1993), each in a
    ``chol.gmres`` span.  Returns the iterate with the smallest true
    residual seen and ``hist``, whose last entry is that residual."""
    bnorm = float(np.linalg.norm(b))
    if bnorm == 0.0:
        return np.zeros_like(b), [0.0]
    x = psolve(b) if x0 is None else x0.astype(np.float64).copy()
    r = b - A @ x
    rnorm = float(np.linalg.norm(r))
    hist = [rnorm / bnorm]
    if hist[-1] <= tol:
        return x, hist
    # one stationary IR step — free when E is tiny relative to an SPD A, but
    # DIVERGENT when A is indefinite (iteration factor |t - d|/t >= 1 for a
    # flipped pivot), so accept it only if it actually reduced the residual:
    # GMRES can only recover ~machine precision RELATIVE to its starting
    # residual, so letting IR blow r up by 1e5 costs 1e5 in final accuracy
    xt = x + psolve(r)
    rt = b - A @ xt
    rtnorm = float(np.linalg.norm(rt))
    if rtnorm < rnorm:
        x, r, rnorm = xt, rt, rtnorm
    hist.append(rnorm / bnorm)
    if hist[-1] <= tol:
        return x, hist
    # restarted GMRES on the residual equation: each cycle's attainable
    # accuracy is ~eps * kappa relative to ITS OWN r0, so restarting from
    # the corrected iterate compounds the reduction past the single-cycle
    # floating-point floor.  Flexible: the cycle keeps Z[j] = M^-1 V[j] and
    # updates x += Z y, exact for any preconditioner — a float32 solve of an
    # ill-conditioned matrix is too far from linear for x += M^-1 (V y)
    for _cycle in range(4):
        start = rnorm
        with counters.span("gmres"):
            x, r, rnorm = _fgmres_cycle(A, b, x, r, rnorm, psolve,
                                        tol * bnorm, max_iter, hist)
        hist[-1] = rnorm / bnorm  # true, not Arnoldi, residual
        if hist[-1] <= tol or not rnorm < 0.5 * start:
            break
    return x, hist


def _fgmres_cycle(A, b, x, r, rnorm, psolve, atol, max_iter, hist):
    """One flexible GMRES cycle from ``x`` (true residual ``r``, of norm
    ``rnorm``); appends each iteration's Arnoldi residual to ``hist``.

    The Arnoldi residual tracks the true one until float64 rounding floors
    the latter (about eps * ||A|| ||Z y||, relative to the cycle's own
    start).  Once the Arnoldi residual claims a fourfold fall since the
    last true reading, the true residual is read, and the cycle stops
    where it has not halved: further iterations would be solves spent past
    that floor.  Returns ``(x, r, rnorm)`` for the iterate with the
    smallest true residual read, never worse than the input."""
    bnorm = float(np.linalg.norm(b))
    x0, best = x, (x, r, rnorm)
    V = [r / rnorm]
    Z = []
    H = np.zeros((max_iter + 1, max_iter))
    e1 = np.zeros(max_iter + 1)
    e1[0] = rnorm
    last = rnorm
    for j in range(max_iter):
        Z.append(psolve(V[j]))
        w = A @ Z[j]
        for i in range(j + 1):
            H[i, j] = float(V[i] @ w)
            w = w - H[i, j] * V[i]
        # one reorthogonalization pass: single-pass MGS loses
        # orthogonality over ~100 iterations and breaks the
        # exact-termination property the rank-p argument relies on
        for i in range(j + 1):
            c = float(V[i] @ w)
            H[i, j] += c
            w = w - c * V[i]
        H[j + 1, j] = float(np.linalg.norm(w))
        y = np.linalg.lstsq(H[:j + 2, :j + 1], e1[:j + 2], rcond=None)[0]
        est = float(np.linalg.norm(e1[:j + 2] - H[:j + 2, :j + 1] @ y))
        hist.append(est / bnorm)
        claim = est <= 0.25 * last
        end = est <= atol or H[j + 1, j] <= 1e-300 or j == max_iter - 1
        if claim or end:
            xt = x0 + np.stack(Z, axis=1) @ y
            rt = b - A @ xt
            tn = float(np.linalg.norm(rt))
            if tn < best[2]:
                best = (xt, rt, tn)
            if claim and not tn < 0.5 * last:
                return best
            last = tn
        if end:
            break
        V.append(w / H[j + 1, j])
    return best
