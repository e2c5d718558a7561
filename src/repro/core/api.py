"""Public API for the sparse Cholesky library.

    from repro.core import cholesky
    F = cholesky(A, method="rl", offload_threshold=600_000)
    x = F.solve(b)

Repeat-pattern streams skip the symbolic phase entirely through the plan
cache (repro.core.plan_cache):

    cache = PlanCache()
    plan = cache.get(A)                      # analyzed + warmed once
    F = cholesky(A2, plan=plan, device_engine=eng)     # numeric only
    Fs = cholesky_many([A3, A4], plan=plan, device_engine=eng)
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from repro.core.merge import merge_supernodes
from repro.core.numeric import (
    BatchCholeskyFactor,
    CholeskyFactor,
    HostEngine,
    OffloadPolicy,
    PanelStore,
    factorize_levels,
    factorize_levels_device_many,
    factorize_rl,
    factorize_rlb,
)
from repro.core.refine import refine_partition
from repro.core.symbolic import SymbolicFactor, symbolic_analyze
from repro.sparse.ordering import fill_reducing_ordering


def symbolic_pipeline(
    A: sp.spmatrix,
    *,
    ordering: str = "nd",
    merge: bool = True,
    refine: bool = True,
    max_growth: float = 0.25,
) -> tuple[SymbolicFactor, sp.csc_matrix]:
    """The paper's full preprocessing pipeline: fill-reducing ordering ->
    symbolic factorization -> supernode amalgamation (25% storage cap) ->
    partition refinement.  Returns (sym, permuted matrix)."""
    A = sp.csc_matrix(A)
    order = fill_reducing_ordering(A, ordering)
    sym, Aperm = symbolic_analyze(A, order=order)
    if merge:
        sym = merge_supernodes(sym, max_growth=max_growth)
    if refine:
        sym, g = refine_partition(sym)
        Aperm = Aperm[g][:, g].tocsc()
        Aperm.sort_indices()
    return sym, Aperm


def cholesky(
    A: sp.spmatrix,
    *,
    method: str = "rl",
    ordering: str = "nd",
    merge: bool = True,
    refine: bool = True,
    max_growth: float = 0.25,
    device_engine=None,
    offload_threshold: int | None = None,
    batch_transfers: bool = False,
    schedule: str | None = None,
    max_batch: int = 256,
    assembly: str = "auto",
    staging: str | None = None,
    sym: SymbolicFactor | None = None,
    Aperm: sp.csc_matrix | None = None,
    plan=None,
    guard: str = "off",
) -> CholeskyFactor:
    """Factor a sparse SPD matrix.

    method            'rl' or 'rlb' (the two paper variants)
    device_engine     accelerator engine (repro.core.engines.DeviceEngine);
                      None = CPU-only baseline
    offload_threshold supernode size (rows*width) above which work moves to
                      the device (paper: 600k for RL, 750k for RLB); None
                      with a device engine = offload everything ("GPU only")
    batch_transfers   RLB only: paper's version 1 (single bulk transfer per
                      supernode) instead of version 2 (per-block transfers)
    schedule          'seq' (paper-faithful one-supernode-at-a-time loop) or
                      'levels' (level-scheduled batched execution: etree
                      levels x engine buckets run as single vmapped
                      dispatches — see repro.core.schedule).  'levels' uses
                      the RL update-matrix formulation for either method.
                      Default (None): 'levels' whenever a device engine is
                      passed, 'seq' otherwise.  NOTE: with a device engine,
                      method='rlb' therefore also runs the RL formulation
                      (and its update-matrix storage) unless schedule='seq'
                      is pinned; batch_transfers with schedule='levels' is
                      rejected rather than silently ignored.
    max_batch         'levels' only: max supernodes stacked per dispatch
    assembly          'levels' only: 'auto' (device-resident assembly on full
                      offload — one fused dispatch per (level x bucket)
                      group, and the factor stays on the device for
                      solve(backend='device')), 'host' (always assemble on
                      the host), or 'device' (force device residency; see
                      repro.core.device_store)
    staging           device-resident path only: 'async' (default with fused
                      groups — per-level packed-storage chunks whose uploads
                      overlap earlier levels' compute, double-buffered) or
                      'sync' (one up-front staging transfer)
    sym / Aperm       reuse a precomputed symbolic factorization.  ``sym``
                      alone is enough: the permuted matrix is recomputed
                      from ``sym.perm`` without re-analysis.
    plan              a CachedPlan (repro.core.plan_cache) — opts out of
                      the symbolic phase entirely: zero analysis/schedule/
                      plan builds, and with a fully-offloading device
                      engine the panel fill runs as one vectorized gather
                      through the plan's fill indices.
    guard             breakdown policy (repro.core.guard):
                      'off'     no detection; bit-identical to the unguarded
                                program (same compiled program cache entry)
                      'raise'   validate the input, detect non-positive/
                                nonfinite pivots in-kernel, raise a
                                BreakdownError naming the first broken
                                supernode
                      'perturb' clamp pivots below eps*4096*max|diag(A)|
                                during elimination (CHOLMOD-style dynamic
                                perturbation, recorded in the GuardReport);
                                subsequent solves auto-refine against the
                                original matrix
                      'shift'   retry with a growing global diagonal shift
                                until the factorization is clean; solves
                                auto-refine against the original matrix.
                      In-kernel detection ('raise'/'perturb') needs the
                      fully-offloaded device-resident levels path; the
                      host paths detect through numpy's LinAlgError.
    """
    if method not in ("rl", "rlb"):
        raise ValueError(f"unknown method {method!r} (want 'rl' or 'rlb')")
    if schedule is None:
        schedule = "levels" if device_engine is not None else "seq"
    if schedule not in ("seq", "levels"):
        raise ValueError(f"unknown schedule {schedule!r} (want 'seq' or 'levels')")
    if assembly not in ("auto", "host", "device"):
        raise ValueError(
            f"unknown assembly {assembly!r} (want 'auto', 'host', or 'device')"
        )
    if assembly == "device" and device_engine is None:
        raise ValueError("assembly='device' requires a device engine")
    if assembly != "auto" and schedule == "seq":
        raise ValueError(
            f"assembly={assembly!r} only applies to schedule='levels' "
            "(the sequential paths always assemble on the host)"
        )
    if batch_transfers and schedule == "levels":
        # loud, not silent: batch_transfers tunes the sequential RLB loop,
        # which the levels schedule (RL formulation) never runs.  This also
        # catches rlb+engine callers relying on the old 'seq' default.
        raise ValueError(
            "batch_transfers applies only to the sequential RLB path; "
            "pass schedule='seq' (with a device engine the default is "
            "now 'levels')"
        )
    if plan is not None and sym is None:
        sym = plan.sym
    policy = None
    if device_engine is not None:
        policy = OffloadPolicy(threshold=offload_threshold if offload_threshold is not None else 0)
    if staging is not None and schedule != "levels":
        raise ValueError(
            "staging applies only to the device-resident levels schedule"
        )
    if guard not in ("off", "raise", "perturb", "shift"):
        raise ValueError(
            f"unknown guard {guard!r} (want 'off', 'raise', 'perturb', or "
            "'shift')"
        )
    gval, gkw = None, {}
    if guard != "off":
        from repro.core.guard import perturb_threshold, validate_matrix

        gval = validate_matrix(A)  # raises BadMatrixError on NaN/Inf/asym
        if guard == "shift":
            # retry loop over guard='raise' with growing diagonal shifts
            return _cholesky_shift(
                A, gval,
                dict(method=method, device_engine=device_engine,
                     offload_threshold=offload_threshold, schedule=schedule,
                     max_batch=max_batch, assembly=assembly, staging=staging,
                     ordering=ordering, merge=merge, refine=refine,
                     max_growth=max_growth, sym=sym, plan=plan),
            )
        device_resident = (
            schedule == "levels" and device_engine is not None
            and assembly != "host"
            and (assembly == "device" or policy.threshold == 0)
        )
        if device_resident:
            # in-kernel detection: status lanes ride the existing readback
            if guard == "raise":
                gkw = dict(guard="raise", guard_thr=0.0, guard_clamp=False)
            else:
                gkw = dict(guard="perturb", guard_clamp=True,
                           guard_thr=perturb_threshold(gval["max_abs_diag"]))
        elif guard == "perturb":
            raise ValueError(
                "guard='perturb' needs in-kernel pivot clamps, i.e. the "
                "fully-offloaded device-resident levels path (device engine "
                "+ full offload); use guard='shift' on host paths"
            )
    if (plan is not None and schedule == "levels" and assembly != "host"
            and device_engine is not None
            and (assembly == "device" or policy.threshold == 0)):
        # plan fast path: device-resident factorization with the panel fill
        # as ONE vectorized gather — no permuted matrix is ever built
        from repro.core.numeric import _factorize_levels_device

        store = PanelStore(sym, storage=plan.fill_storage(A))
        F = _factorize_levels_device(
            sym, None, device_engine, max_batch=max_batch, staging=staging,
            store=store, **gkw,
        )
        if guard != "off":
            F = _attach_guard(F, A, guard, gval)
        return _refine_low_precision(F, A)
    if sym is None:
        sym, Aperm = symbolic_pipeline(
            A, ordering=ordering, merge=merge, refine=refine, max_growth=max_growth
        )
    elif Aperm is None:
        # precomputed symbolic factorization, fresh values: permute without
        # re-analysis (sym.perm already folds in any refinement reordering)
        p = sym.perm
        Aperm = sp.csc_matrix(A)[p][:, p].tocsc()
        Aperm.sort_indices()
    try:
        if schedule == "levels":
            F = factorize_levels(
                sym, Aperm, engine=HostEngine(), device_engine=device_engine,
                policy=policy, max_batch=max_batch, assembly=assembly,
                staging=staging, **gkw,
            )
        elif method == "rl":
            F = factorize_rl(
                sym, Aperm, engine=HostEngine(), device_engine=device_engine,
                policy=policy,
            )
        else:
            F = factorize_rlb(
                sym, Aperm, engine=HostEngine(), device_engine=device_engine,
                policy=policy, batch_transfers=batch_transfers,
            )
    except np.linalg.LinAlgError as e:
        # host-path breakdown detection: numpy's potrf failure, upgraded to
        # the same structured error the in-kernel guards raise
        if guard == "off":
            raise
        from repro.core.guard import BreakdownError, GuardReport

        rep = GuardReport(guard=guard, n_supernodes=int(sym.nsuper),
                          min_pivot=float("nan"), validation=gval)
        rep.broken.append({"supernode": None, "level": None,
                           "min_pivot": float("nan"), "nonfinite": False})
        raise BreakdownError(rep, f"Cholesky breakdown: {e}") from e
    if guard != "off":
        F = _attach_guard(F, A, guard, gval)
    return _refine_low_precision(F, A)


def _low_precision(dstore) -> bool:
    return dstore is not None and np.dtype(dstore.dtype).itemsize < 8


def _refine_low_precision(F: CholeskyFactor, A) -> CholeskyFactor:
    """A float32 device factor is inexact for ``A``: record ``A`` as the
    refinement target, so solves refine to float64 accuracy as they do for
    a perturbed factor."""
    if F.guard_A is None and _low_precision(F.dstore):
        F.guard_A = sp.csc_matrix(A)
    return F


def _attach_guard(F: CholeskyFactor, A, guard: str, val) -> CholeskyFactor:
    """Finish a guarded factorization: attach validation info, raise on
    unrecovered breakdown, and record the original matrix whenever solves
    must refine against it (perturbed or shifted factors)."""
    from repro.core.guard import BreakdownError, GuardReport

    rep = F.guard_report
    if rep is None:
        # host path factored cleanly (potrf would have raised otherwise):
        # synthesize a clean report with the true min pivot from the panels
        rep = GuardReport(guard=guard, n_supernodes=int(F.sym.nsuper))
        m = float("inf")
        for s in range(F.sym.nsuper):
            w = F.sym.width(s)
            d = np.diagonal(F.panels[s][:w, :w])
            if w:
                m = min(m, float(np.min(d * d)))
        rep.min_pivot = m
        F.guard_report = rep
    rep.guard = guard
    rep.validation = val
    if not rep.ok:
        raise BreakdownError(rep)
    if rep.needs_refine:
        F.guard_A = sp.csc_matrix(A)
    return F


def _cholesky_shift(A, val, kw):
    """guard='shift' recovery: refactor with a growing global diagonal shift
    A + tau*I until the guarded factorization comes back clean.  Works on
    every execution path (detection via guard='raise').  Solves against the
    returned factor auto-refine toward the ORIGINAL unshifted system."""
    from repro.core.guard import BreakdownError, perturb_threshold

    A = sp.csc_matrix(A)
    n = int(A.shape[0])
    tau0 = max(perturb_threshold(val["max_abs_diag"]),
               float(np.finfo(np.float64).tiny))
    tau, shifts, last = 0.0, 0, None
    for _ in range(30):  # 10x per step: overshoots the minimal shift by <10x
        Ak = A if tau == 0.0 else (A + tau * sp.eye(n, format="csc")).tocsc()
        try:
            kwk = kw if tau == 0.0 else dict(kw, plan=None)  # pattern may gain diag
            if kwk.get("plan") is None and kw.get("plan") is not None:
                kwk["sym"] = kw["plan"].sym if kw.get("sym") is None else kw["sym"]
            F = cholesky(Ak, guard="raise", **kwk)
        except BreakdownError as e:
            last = e
            shifts += 1
            tau = tau0 * (10.0 ** (shifts - 1))
            continue
        rep = F.guard_report
        rep.guard = "shift"
        rep.shift = float(tau)
        rep.shifts = shifts
        rep.validation = val
        if tau > 0.0:
            F.guard_A = A  # refine solves back to the unshifted system
        return F
    rep = last.report
    rep.guard = "shift"
    rep.shift = float(tau)
    rep.shifts = shifts
    raise BreakdownError(
        rep, f"shift recovery failed after {shifts} shifts "
        f"(last tau = {tau:.3g}): {last}"
    ) from last


def cholesky_many(
    As,
    *,
    device_engine=None,
    plan=None,
    sym: SymbolicFactor | None = None,
    ordering: str = "nd",
    merge: bool = True,
    refine: bool = True,
    max_batch: int = 256,
    staging: str | None = None,
    guard: str = "off",
) -> BatchCholeskyFactor:
    """Factor M sparse SPD matrices sharing ONE sparsity pattern with a
    single set of device dispatches.

    The matrices' value arrays are stacked behind a leading matrix axis
    through the whole device-resident pipeline — staged chunks, update pool,
    packed factor — so each (level x bucket) group factors all M matrices in
    ONE fused dispatch of M*batch lanes.  Per-request overheads (panel fill,
    staging transfers, per-group dispatch latency) are paid once per group
    instead of once per (matrix, group): at quick-suite sizes this is >3x
    the factorizations/sec of M independent ``cholesky`` calls.

    As             sequence of matrices with identical sparsity patterns
                   (values may differ arbitrarily; each must be SPD)
    device_engine  DeviceEngine with fused groups (default: a fresh one)
    plan           CachedPlan for the shared pattern (repro.core.plan_cache);
                   None analyzes As[0] once and builds a plan in-process
    sym            alternative to ``plan``: a bare SymbolicFactor (the fill
                   then goes through a plan built here)

    Returns a BatchCholeskyFactor: per-matrix zero-copy factors via
    ``.factor(i)``, all-matrix resident solves via ``.solve(b)``.
    """
    from repro.core.plan_cache import CachedPlan, build_fill_plan, canonical_csc
    from repro.core.plan_cache import pattern_fingerprint

    As = list(As)
    if not As:
        raise ValueError("cholesky_many needs at least one matrix")
    if guard not in ("off", "raise", "perturb"):
        raise ValueError(
            f"unknown guard {guard!r} for cholesky_many (want 'off', "
            "'raise', or 'perturb'; 'shift' is single-matrix only)"
        )
    gvals, gkw = None, {}
    if guard != "off":
        from repro.core.guard import perturb_threshold, validate_matrix

        gvals = [validate_matrix(Ai) for Ai in As]
        if guard == "raise":
            gkw = dict(guard="raise")
        else:
            # one thr per fused dispatch covers all M lanes: use the most
            # conservative (largest-diagonal) matrix's threshold
            gkw = dict(
                guard="perturb", guard_clamp=True,
                guard_thr=max(perturb_threshold(v["max_abs_diag"])
                              for v in gvals),
            )
    if plan is None:
        if sym is None:
            sym, _Aperm = symbolic_pipeline(
                As[0], ordering=ordering, merge=merge, refine=refine
            )
        A0 = canonical_csc(As[0])
        fill_src, fill_dst = build_fill_plan(sym, A0)
        plan = CachedPlan(
            key=pattern_fingerprint(A0), sym=sym, fill_src=fill_src,
            fill_dst=fill_dst, n=A0.shape[0], nnz=int(A0.nnz),
        )
    if device_engine is None:
        from repro.core.engines import DeviceEngine
        device_engine = DeviceEngine()
    from repro.core.relind import scatter_plan

    M = len(As)
    cells = int(scatter_plan(plan.sym).storage_cells)
    storage = np.zeros((M, cells), dtype=np.float64)
    for i, A in enumerate(As):
        plan.fill_storage(A, row=storage[i])
    BF = factorize_levels_device_many(
        plan.sym, storage, device_engine, max_batch=max_batch,
        staging=staging, **gkw,
    )
    if guard != "off":
        from repro.core.guard import BreakdownError

        for rep, v in zip(BF.guard_reports, gvals):
            rep.validation = v
        bad = [r for r in BF.guard_reports if not r.ok]
        if bad:
            raise BreakdownError(bad[0])
        if guard == "perturb":
            BF.guard_As = [
                sp.csc_matrix(Ai) if rep.needs_refine else None
                for Ai, rep in zip(As, BF.guard_reports)
            ]
    if _low_precision(BF.dstore):
        BF.guard_As = [sp.csc_matrix(Ai) for Ai in As]
    return BF


def solve(A: sp.spmatrix, b: np.ndarray, *, solve_backend: str = "host",
          **kw) -> np.ndarray:
    """Factor-and-solve convenience wrapper.  ``solve_backend`` picks the
    substitution path ('host' loop or 'device' level-scheduled batched —
    see CholeskyFactor.solve); every other kwarg goes to ``cholesky``."""
    return cholesky(A, **kw).solve(b, backend=solve_backend)
