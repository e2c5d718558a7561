"""Numeric supernodal right-looking Cholesky: the RL and RLB variants.

Both variants factor the current supernode with POTRF (dense Cholesky of the
diagonal block) + TRSM (triangular solve of the rectangular part), then push
its updates right:

  * RL    computes the whole update matrix U = L_tail @ L_tail^T with one
          SYRK into preallocated working storage and scatters ("assembles")
          it into every ancestor using generalized relative indices.
  * RLB   walks the block pairs (B, B') of the supernode and applies each
          update directly into ancestor storage with one SYRK (diagonal
          target) or GEMM (off-diagonal target) per pair — no update matrix.

The dense math is routed through an *engine* (see repro.core.engines) so the
same control flow runs either entirely on the host (the paper's CPU-only
baseline) or with large supernodes offloaded to the accelerator (the paper's
GPU version).  The engine API makes the transfers explicit:

    h = eng.stage(P, w)          # CPU -> device transfer of the supernode
    eng.factor(h)                # POTRF + TRSM on the device
    P = eng.read_panel(h)        # device -> CPU
    U = eng.syrk_tail(h)         # RL: update matrix on device, then transfer
    eng.syrk_block/gemm_block    # RLB: one call per block (pair)

Assembly (the scatter into ancestor panels) goes through a *scatter plan*
precomputed in the symbolic phase (repro.core.relind.ScatterPlan): all panels
live in one flat array (PanelStore) and each supernode's whole update matrix
is applied with a single fancy-indexed subtraction.  In the sequential paths
and the mixed host/device level-scheduled path that scatter runs on the host,
as in the paper (OpenMP there, vectorized numpy here).

Beyond the paper, ``factorize_levels`` replaces the one-supernode-at-a-time
offload loop with *level-scheduled batched* execution: supernodes on the same
supernodal-etree level are independent, so each (level x engine bucket) group
is staged as one stacked buffer and factored by one vmapped fused
POTRF+TRSM+SYRK dispatch (see repro.core.schedule and the engines' batched
protocol: stage_batch / factor_batch / read_panels_batch / syrk_tail_batch).

When every supernode is offloaded, the numeric phase goes fully
*device-resident* (repro.core.device_store): the flat PanelStore storage is
staged once, each (level x bucket) group gathers its panels, applies pending
updates scatter-free (a pool of packed update entries + prefix-sum segment
sums), factors, and packs its results — all on the device — and the
finished factor is read back once: O(1) host<->device transfers total
instead of one round trip per group.  The device-resident factor also
serves ``CholeskyFactor.solve(b, backend="device")``: level-scheduled
batched forward/backward substitution with the RHS block resident on the
device and the triangular diagonal blocks pre-inverted into batched GEMMs.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np
import scipy.linalg as sla
import scipy.sparse as sp

from repro.core import counters
from repro.core.relind import ancestor_updates, scatter_plan, supernode_blocks
from repro.core.schedule import cached_schedule
from repro.core.symbolic import SymbolicFactor


# ---------------------------------------------------------------------------
# host engine: the paper's CPU-only baseline (BLAS/LAPACK via numpy/scipy)
# ---------------------------------------------------------------------------
class HostEngine:
    name = "host"

    def stage(self, P: np.ndarray, w: int):
        return (P, w)

    def factor(self, h) -> None:
        P, w = h
        Ld = np.linalg.cholesky(P[:w, :w])
        P[:w, :w] = Ld
        if P.shape[0] > w:
            # TRSM: X = B L^{-T}  <=>  L Y = B^T, X = Y^T
            P[w:] = sla.solve_triangular(Ld, P[w:].T, lower=True).T

    def read_panel(self, h) -> np.ndarray:
        return h[0]

    def syrk_tail(self, h) -> np.ndarray:
        P, w = h
        B = P[w:]
        return B @ B.T

    def syrk_block(self, h, k0: int, k1: int) -> np.ndarray:
        P, w = h
        B = P[w + k0:w + k1]
        return B @ B.T

    def gemm_block(self, h, kr0: int, kr1: int, kc0: int, kc1: int) -> np.ndarray:
        P, w = h
        return P[w + kr0:w + kr1] @ P[w + kc0:w + kc1].T

    def gather(self, xs) -> list:
        return [np.asarray(x) for x in xs]

    def fetch(self, x) -> np.ndarray:
        return np.asarray(x)

    def release(self, h) -> None:
        pass

    def flush(self) -> None:
        pass

    # -- batched protocol (level-scheduled path) ---------------------------
    # Host batches are plain per-item loops over the scalar ops: numerically
    # identical to the sequential path, and the protocol symmetry lets
    # factorize_levels treat host and device engines uniformly.
    def stage_batch(self, Ps: list, ws: list) -> list:
        return [self.stage(P, w) for P, w in zip(Ps, ws)]

    def factor_batch(self, hs: list) -> None:
        for h in hs:
            self.factor(h)

    def read_panels_batch(self, hs: list) -> list:
        return [self.read_panel(h) for h in hs]

    def syrk_tail_batch(self, hs: list) -> list:
        return [self.syrk_tail(h) if h[0].shape[0] > h[1] else None for h in hs]

    def release_batch(self, hs: list) -> None:
        pass


@dataclass
class OffloadPolicy:
    """The paper's size threshold: supernodes with rows*width >= threshold run
    on the accelerator, everything smaller stays on the host.
    (Paper: 600,000 for RL, 750,000 for RLB on an A100.)"""
    threshold: int = 600_000

    def on_device(self, sym: SymbolicFactor, s: int) -> bool:
        return sym.size(s) >= self.threshold


# ---------------------------------------------------------------------------
# factor container
# ---------------------------------------------------------------------------
@dataclass
class CholeskyFactor:
    sym: SymbolicFactor
    panels: list  # list of (rows_s, w_s) float64 arrays; cols are factor cols
    stats: dict | None = None
    # flat-storage backing of ``panels`` (PanelStore) and, after a
    # device-resident factorization or device solve, the device mirror
    # (repro.core.device_store.DevicePanelStore) holding the factor on the
    # accelerator for transfer-free solves
    store: object | None = None
    dstore: object | None = None
    # breakdown-safety extras: the reduced per-factorization GuardReport
    # (guarded factorizations only), and the original matrix solves refine
    # against whenever the factor is inexact for it — recorded
    # perturbations, a shift, or a float32 device factor
    guard_report: object | None = None
    guard_A: object | None = None
    # relative-residual trajectory of every refined solve on this factor,
    # and the number of times each applied the factor (raw solve included)
    ir_history: list = field(default_factory=list)
    refine_solves: list = field(default_factory=list)

    def L_dense(self) -> np.ndarray:
        """Assemble the full dense L (for small-n validation only)."""
        n = self.sym.n
        L = np.zeros((n, n))
        for s in range(self.sym.nsuper):
            f = int(self.sym.super_ptr[s])
            w = self.sym.width(s)
            r = self.sym.rows[s]
            P = self.panels[s]
            for c in range(w):
                L[r[c:], f + c] = P[c:, c]
        return L

    def factor_nnz(self) -> int:
        return self.sym.factor_nnz()

    def logdet(self) -> float:
        acc = 0.0
        for s in range(self.sym.nsuper):
            w = self.sym.width(s)
            d = np.diagonal(self.panels[s][:w, :w])
            acc += float(np.sum(np.log(d)))
        return 2.0 * acc

    def solve(self, b: np.ndarray, *, backend: str = "host",
              engine=None, refine: bool | None = None) -> np.ndarray:
        """Solve A x = b using P A P^T = L L^T.

        backend  'host' (per-supernode scipy loop, the paper's solve) or
                 'device' (level-scheduled batched substitution against the
                 device-resident factor; see repro.core.device_store).  The
                 device path reuses the factor a device-resident
                 ``factorize_levels`` left on the accelerator; otherwise it
                 stages the factor once and keeps it resident for later
                 solves.
        engine   device backend only: DeviceEngine to stage with when no
                 device-resident factor exists yet (default: a fresh one).
        refine   run residual-driven refinement against the original matrix
                 ``guard_A``.  Default ``None`` enables it whenever the
                 factor records one — recorded perturbations, a diagonal
                 shift, or a float32 device factor — so such factors still
                 solve the *original* system to float64 precision.
        """
        if refine is None:
            refine = self.guard_A is not None
        if refine:
            if self.guard_A is None:
                raise ValueError(
                    "refined solve needs the original matrix; this factor "
                    "carries no guard_A (factor with guard= to record it)"
                )
            from repro.core.refine import refine_solve
            x, hist, solves = refine_solve(self, self.guard_A, b,
                                           backend=backend, engine=engine)
            self.ir_history.append(hist)
            self.refine_solves.append(solves)
            if self.guard_report is not None:
                self.guard_report.ir_history.append(hist)
            return x
        if backend == "device":
            return self.solve_device(b, engine=engine)
        if backend != "host":
            raise ValueError(f"unknown backend {backend!r} (want 'host' or 'device')")
        sym = self.sym
        y = np.asarray(b, dtype=np.float64)[sym.perm].copy()
        squeeze = y.ndim == 1
        if squeeze:
            y = y[:, None]
        # forward: L z = Pb
        for s in range(sym.nsuper):
            f = int(sym.super_ptr[s])
            w = sym.width(s)
            P = self.panels[s]
            y[f:f + w] = sla.solve_triangular(P[:w, :w], y[f:f + w], lower=True)
            t = sym.rows[s][w:]
            if t.shape[0]:
                y[t] -= P[w:] @ y[f:f + w]
        # backward: L^T x = z
        for s in range(sym.nsuper - 1, -1, -1):
            f = int(sym.super_ptr[s])
            w = sym.width(s)
            P = self.panels[s]
            t = sym.rows[s][w:]
            rhs = y[f:f + w]
            if t.shape[0]:
                rhs = rhs - P[w:].T @ y[t]
            y[f:f + w] = sla.solve_triangular(P[:w, :w].T, rhs, lower=False)
        x = np.empty_like(y)
        x[sym.perm] = y
        return x[:, 0] if squeeze else x

    def solve_device(self, b: np.ndarray, *, engine=None) -> np.ndarray:
        """Level-scheduled batched solve on the device (see
        repro.core.device_store.device_solve).  Stages the factor on first
        use when it is not already device-resident."""
        from repro.core.device_store import DevicePanelStore, device_solve

        if self.dstore is None:
            if self.store is None:
                raise ValueError(
                    "device solve needs PanelStore-backed panels; this factor "
                    "was built without flat storage"
                )
            if engine is None:
                from repro.core.engines import DeviceEngine
                engine = DeviceEngine()
            sched = cached_schedule(self.sym, bucket="batch")
            self.dstore = DevicePanelStore(
                engine, self.sym, sched, self.store.storage, factored=True
            )
        return device_solve(self.dstore, b)


def _fill_panels(sym: SymbolicFactor, Aperm: sp.csc_matrix, panels: list) -> None:
    """Scatter the (permuted) matrix into zeroed supernode panels (lower part)."""
    Ap, Ai, Ax = Aperm.indptr, Aperm.indices, Aperm.data
    for s in range(sym.nsuper):
        f = int(sym.super_ptr[s])
        w = sym.width(s)
        r = sym.rows[s]
        P = panels[s]
        for c in range(w):
            j = f + c
            lo, hi = Ap[j], Ap[j + 1]
            rows_j = Ai[lo:hi]
            keep = rows_j >= j
            pos = np.searchsorted(r, rows_j[keep])
            P[pos, c] = Ax[lo:hi][keep]


def init_panels(sym: SymbolicFactor, Aperm: sp.csc_matrix) -> list:
    panels = [
        np.zeros((sym.rows[s].shape[0], sym.width(s)), dtype=np.float64)
        for s in range(sym.nsuper)
    ]
    _fill_panels(sym, Aperm, panels)
    return panels


class PanelStore:
    """All supernode panels in ONE flat float64 array, plus the precomputed
    scatter plan (repro.core.relind.ScatterPlan).

    ``panels[s]`` is a C-contiguous *view* into ``storage`` — panel code
    reads/writes it like an ordinary (rows, w) array, while ``scatter``
    assembles a whole update matrix with a single vectorized fancy-indexed
    subtraction against the flat storage.  Callers must never rebind a
    panel, only write into it (``panels[s][...] = ...``).
    """

    def __init__(self, sym: SymbolicFactor, storage: np.ndarray | None = None):
        self.plan = scatter_plan(sym)
        # one trailing trash cell absorbs the plan's upper-triangle entries;
        # ``storage`` lets callers wrap an existing flat array (the plan
        # cache's vectorized fill, one row of a multi-matrix batch) in panel
        # views without copying
        if storage is None:
            storage = np.zeros(self.plan.storage_cells, dtype=np.float64)
        self.storage = storage
        offs = self.plan.offs
        self.panels = [
            self.storage[offs[s]:offs[s + 1]].reshape(
                sym.rows[s].shape[0], sym.width(s)
            )
            for s in range(sym.nsuper)
        ]

    def scatter(self, s: int, U: np.ndarray) -> None:
        """Apply supernode s's update matrix to every ancestor at once.
        Destinations are unique (plus the don't-care trash cell), so plain
        fancy indexing is exact."""
        dst = self.plan.dst[s]
        if dst.shape[0]:
            self.storage[dst] -= U.ravel()


def init_panel_store(sym: SymbolicFactor, Aperm: sp.csc_matrix) -> PanelStore:
    store = PanelStore(sym)
    _fill_panels(sym, Aperm, store.panels)
    return store


def _reset_events(engine) -> None:
    if hasattr(engine, "reset_events"):
        engine.reset_events()


def _pick_engine(engine, device_engine, policy, sym, s, stats):
    if device_engine is not None and policy is not None and policy.on_device(sym, s):
        stats["supernodes_on_device"] += 1
        return device_engine
    return engine


# ---------------------------------------------------------------------------
# RL
# ---------------------------------------------------------------------------
def factorize_rl(
    sym: SymbolicFactor,
    Aperm: sp.csc_matrix,
    *,
    engine=None,
    device_engine=None,
    policy: OffloadPolicy | None = None,
) -> CholeskyFactor:
    engine = engine or HostEngine()
    store = init_panel_store(sym, Aperm)
    panels = store.panels
    stats = {"method": "rl", "supernodes_on_device": 0, "supernodes_total": sym.nsuper}

    for s in range(sym.nsuper):
        w = sym.width(s)
        eng = _pick_engine(engine, device_engine, policy, sym, s, stats)
        h = eng.stage(panels[s], w)          # transfer 1: CPU -> device
        eng.factor(h)                        # POTRF + TRSM
        out = eng.read_panel(h)              # transfer 2 (synchronous; the
        # device-resident path overlaps staging with compute instead)
        if out is not panels[s]:             # HostEngine factors in place
            panels[s][...] = out
        if sym.rows[s].shape[0] == w:
            eng.release(h)
            continue
        U = np.asarray(eng.syrk_tail(h))     # SYRK; transfer 3: U back to CPU
        eng.release(h)
        # assembly on the host, as in the paper — one vectorized scatter per
        # supernode through the precomputed plan (no per-ancestor loop)
        store.scatter(s, U)
    if device_engine is not None:
        device_engine.flush()
    return CholeskyFactor(sym=sym, panels=panels, stats=stats, store=store)


# ---------------------------------------------------------------------------
# level-scheduled batched execution (see repro.core.schedule)
# ---------------------------------------------------------------------------
def factorize_levels(
    sym: SymbolicFactor,
    Aperm: sp.csc_matrix,
    *,
    engine=None,
    device_engine=None,
    policy: OffloadPolicy | None = None,
    max_batch: int = 256,
    assembly: str = "auto",
    staging: str | None = None,
    guard: str | None = None,
    guard_thr: float = 0.0,
    guard_clamp: bool = False,
) -> CholeskyFactor:
    """Level-scheduled batched right-looking factorization.

    Supernodes are processed level by level up the supernodal etree (each
    level is an antichain — see repro.core.schedule), and each level's
    same-bucket supernodes go through the engines' batched protocol:

        hb = eng.stage_batch(panels, ws)   # ONE transfer per (level, bucket)
        eng.factor_batch(hb)               # ONE vmapped POTRF+TRSM+SYRK
        eng.read_panels_batch(hb)          # ONE bulk read-back
        eng.syrk_tail_batch(hb)            # ONE bulk read-back of updates

    Assembly applies each supernode's precomputed scatter plan (one fancy-
    indexed subtraction), so host work per supernode is O(1) numpy calls.
    Uses the RL update-matrix formulation for every supernode; with a device
    engine this collapses the sequential path's O(nsuper) transfers and
    dispatches to O(levels x buckets).  Per-level batch statistics are
    recorded in ``stats["level_stats"]``.

    assembly  'auto'   — go fully device-resident (see below) whenever a
                         device engine takes every supernode (full offload,
                         i.e. a zero offload threshold); host assembly
                         otherwise.
              'host'   — always assemble on the host (the pre-device-resident
                         behaviour, kept for mixed-offload and comparison).
              'device' — force the device-resident path (requires a device
                         engine; the offload policy is ignored — everything
                         runs on the device).

    The device-resident path (repro.core.device_store) runs ONE
    zero-transfer dispatch per (level x bucket) group (gather +
    apply-updates + fused factor + pack in a single program; the
    three-dispatch PR 2 pipeline remains as the ``fused_groups=False``
    oracle), stages the packed storage in per-level chunks whose uploads
    overlap earlier levels' compute (``staging='async'``, the default — see
    below), and reads the factor back once.  The returned factor keeps the
    device storage attached (``CholeskyFactor.dstore``) so
    ``solve(b, backend="device")`` reuses it without re-staging.

    staging   device-resident path only: 'async' (default with fused
              groups) uploads level k+1's packed-storage chunk before
              dispatching level k, double-buffered; 'sync' stages
              everything up front in one transfer (PR 2 behaviour).
    """
    if assembly not in ("auto", "host", "device"):
        raise ValueError(
            f"unknown assembly {assembly!r} (want 'auto', 'host', or 'device')"
        )
    if assembly == "device" and device_engine is None:
        raise ValueError("assembly='device' requires a device engine")
    if device_engine is not None and assembly != "host" and (
        assembly == "device"
        or (policy is not None and policy.threshold == 0)
    ):
        return _factorize_levels_device(
            sym, Aperm, device_engine, max_batch=max_batch, staging=staging,
            guard=guard, guard_thr=guard_thr, guard_clamp=guard_clamp,
        )
    if guard is not None:
        raise ValueError(
            "guarded factorization requires the fully-offloaded "
            "device-resident path (device engine + full offload, or "
            "assembly='device'); the host/mixed paths detect breakdown "
            "through numpy's LinAlgError instead"
        )
    if staging is not None:
        raise ValueError(
            "staging applies only to the device-resident path (full offload "
            "or assembly='device')"
        )
    engine = engine or HostEngine()
    store = init_panel_store(sym, Aperm)
    panels = store.panels
    sched = cached_schedule(sym, max_batch=max_batch)
    stats = {
        "method": "levels",
        "assembly": "host",
        "supernodes_on_device": 0,
        "supernodes_total": sym.nsuper,
        "schedule": sched.batch_stats(),
        "level_stats": [],
    }

    for lvl, lgroups in enumerate(sched.groups):
        lrec = {"level": lvl, "supernodes": 0, "batches": 0, "max_batch": 0,
                "on_device": 0}
        for bg in lgroups:
            if device_engine is not None and policy is not None:
                on_dev = np.array([policy.on_device(sym, int(s)) for s in bg.ids])
            else:
                on_dev = np.zeros(bg.ids.shape[0], dtype=bool)
            for eng, ids in ((device_engine, bg.ids[on_dev]),
                             (engine, bg.ids[~on_dev])):
                if ids.shape[0] == 0:
                    continue
                if eng is device_engine:
                    stats["supernodes_on_device"] += int(ids.shape[0])
                    lrec["on_device"] += int(ids.shape[0])
                hb = eng.stage_batch(
                    [panels[int(s)] for s in ids],
                    [sym.width(int(s)) for s in ids],
                )
                eng.factor_batch(hb)
                outs = eng.read_panels_batch(hb)
                us = eng.syrk_tail_batch(hb)
                eng.release_batch(hb)
                for s, out, U in zip(ids, outs, us):
                    s = int(s)
                    if out is not panels[s]:
                        panels[s][...] = out
                    if U is not None:
                        store.scatter(s, U)
                lrec["batches"] += 1
                lrec["max_batch"] = max(lrec["max_batch"], int(ids.shape[0]))
                lrec["supernodes"] += int(ids.shape[0])
        stats["level_stats"].append(lrec)
    if device_engine is not None:
        device_engine.flush()
    return CholeskyFactor(sym=sym, panels=panels, stats=stats, store=store)


def _reduce_guard(sym, sched, status_groups, *, mode: str, thr: float):
    """Reduce the per-lane kernel status rows of one factorization into a
    GuardReport: zip each group's (Bp, 4) status block — (min d^2, n_clamped,
    nonfinite, clamp magnitude) per lane, pad lanes (inf, 0, 0, 0) — with the
    schedule's supernode ids, in (level, group, lane) = elimination order, so
    ``first_broken`` names the first supernode that actually broke."""
    from repro.core.guard import GuardReport

    rep = GuardReport(guard=mode, n_supernodes=int(sym.nsuper),
                      perturb_thr=float(thr))
    it = iter(status_groups)
    mins: list = []
    for lvl, lgroups in enumerate(sched.groups):
        lvl_min = None
        for bg in lgroups:
            st = np.asarray(next(it), dtype=np.float64)
            ids = np.asarray(bg.ids)
            for j in range(int(ids.shape[0])):
                mind2, ncl, nf, mag = st[j]
                snode = int(ids[j])
                mins.append(mind2)
                if np.isfinite(mind2):
                    lvl_min = mind2 if lvl_min is None else min(lvl_min, mind2)
                clamped = ncl > 0
                if clamped:
                    rep.perturbations.append({
                        "supernode": snode, "level": lvl,
                        "min_pivot": float(mind2), "n_clamped": int(ncl),
                        "magnitude": float(mag),
                    })
                # broken = nonfinite panel, or a nonpositive/NaN pivot that no
                # clamp rescued (NaN fails the ``> 0`` comparison on purpose)
                if (nf > 0) or (not clamped and not (mind2 > 0)):
                    rep.broken.append({
                        "supernode": snode, "level": lvl,
                        "min_pivot": float(mind2),
                        "nonfinite": bool(nf > 0),
                    })
                    if rep.first_broken is None:
                        rep.first_broken = snode
                        rep.first_broken_level = lvl
        rep.level_min_pivots.append(
            (lvl, None if lvl_min is None else float(lvl_min))
        )
    arr = np.asarray(mins, dtype=np.float64)
    fin = arr[np.isfinite(arr)]
    if fin.size:
        rep.min_pivot = float(np.min(fin))
    elif arr.size and np.any(np.isnan(arr)):
        rep.min_pivot = float("nan")
    return rep


def _factorize_levels_device(
    sym: SymbolicFactor,
    Aperm: sp.csc_matrix | None,
    device_engine,
    *,
    max_batch: int = 256,
    staging: str | None = None,
    store: PanelStore | None = None,
    guard: str | None = None,
    guard_thr: float = 0.0,
    guard_clamp: bool = False,
) -> CholeskyFactor:
    """Fully device-resident level-scheduled factorization: assembly runs on
    the device through precomputed index plans (scatter-free fan-in — see
    repro.core.device_store), each (level x bucket) group is ONE fused
    dispatch, and with ``staging='async'`` (the default) level k+1's packed
    storage chunk is uploaded before level k is dispatched, so transfers
    overlap compute (``jax.device_put`` is asynchronous) — the within-device
    analogue of the fan-both formulation's communication/compute overlap.

    Bucket family: the pallas fused kernel masks pad lanes, identity slabs,
    and beyond-tail SYRK tiles outright, so it uses the coarse power-of-two
    ``bucket="fused"`` family (fewer compiles, bigger batches, near-zero
    flop waste).  The xla inner math has no masking — padded cells burn real
    flops — so it keeps the fine ``bucket="batch"`` family.

    ``store`` lets the plan-cache path hand in a pre-filled PanelStore
    (vectorized fill through CachedPlan.fill_storage) so ``Aperm`` may be
    None; otherwise the store is filled from ``Aperm`` as usual."""
    from repro.core.device_store import DevicePanelStore

    with counters.span("factor"):
        _reset_events(device_engine)  # one event log per factorization
        if store is None:
            store = init_panel_store(sym, Aperm)
        fused = bool(getattr(device_engine, "fused_groups", False))
        pallas = getattr(device_engine, "backend", "") == "pallas"
        bucket = "fused" if fused and pallas else "batch"
        sched = cached_schedule(sym, max_batch=max_batch, bucket=bucket)
        dstore = DevicePanelStore(device_engine, sym, sched, store.storage,
                                  staging=staging, guard=guard is not None,
                                  guard_thr=guard_thr,
                                  guard_clamp=guard_clamp)
        stats = {
            "method": "levels",
            "assembly": "device",
            "staging": dstore.staging,
            "bucket": bucket,
            "dispatches_per_group": 1 if dstore.fused else 3,
            "supernodes_on_device": sym.nsuper,
            "supernodes_total": sym.nsuper,
            "schedule": sched.batch_stats(),
            "level_stats": [],
        }
        for lvl, lgroups in enumerate(sched.groups):
            # double buffering: issue the next level's chunk upload BEFORE
            # this level's dispatches block on compute
            dstore.prefetch_level(lvl + 1)
            lrec = {"level": lvl, "supernodes": 0, "batches": 0,
                    "max_batch": 0, "on_device": 0}
            for gi, bg in enumerate(lgroups):
                dstore.assemble_group(lvl, gi)
                nb = int(bg.ids.shape[0])
                lrec["batches"] += 1
                lrec["supernodes"] += nb
                lrec["on_device"] += nb
                lrec["max_batch"] = max(lrec["max_batch"], nb)
            stats["level_stats"].append(lrec)
        dstore.read_into(store.storage)  # ONE bulk factor read-back
        device_engine.flush()
        report = None
        if guard is not None:
            report = _reduce_guard(sym, sched, dstore.guard_status(),
                                   mode=guard, thr=guard_thr)
            stats["guard"] = guard
        return CholeskyFactor(
            sym=sym, panels=store.panels, stats=stats, store=store,
            dstore=dstore, guard_report=report,
        )


# ---------------------------------------------------------------------------
# multi-matrix batched factorization (one pattern, M value streams)
# ---------------------------------------------------------------------------
@dataclass
class BatchCholeskyFactor:
    """M factors of matrices sharing ONE sparsity pattern, produced by a
    single set of fused multi-matrix dispatches (see ``cholesky_many``).

    ``storage`` is the (M, cells) flat factor block; ``factor(i)`` wraps row
    i in panel views (a zero-copy CholeskyFactor, usable anywhere a
    single-matrix factor is).  ``solve`` runs all M right-hand sides through
    the same level-scheduled device dispatches, against the still-resident
    device factor."""
    sym: SymbolicFactor
    nmat: int
    storage: np.ndarray       # (M, storage_cells)
    stats: dict | None = None
    dstore: object | None = None
    guard_reports: list | None = None  # per-matrix GuardReport (guarded runs)
    # per-matrix original A where the factor is inexact for it (perturbed,
    # or every matrix of a float32 device factor); None entries are exact
    guard_As: list | None = None
    _factors: list | None = None

    def factor(self, i: int) -> CholeskyFactor:
        """Zero-copy single-matrix view of factor ``i``."""
        if self._factors is None:
            self._factors = [None] * self.nmat
        f = self._factors[i]
        if f is None:
            store = PanelStore(self.sym, storage=self.storage[i])
            f = self._factors[i] = CholeskyFactor(
                sym=self.sym, panels=store.panels, stats=self.stats,
                store=store,
                guard_report=(self.guard_reports[i]
                              if self.guard_reports else None),
                guard_A=self.guard_As[i] if self.guard_As else None,
            )
        return f

    def solve(self, b: np.ndarray) -> np.ndarray:
        """Solve A_i x_i = b_i for all M systems at once: ``b`` is
        (M, n) or (M, n, nrhs) — every substitution level is ONE dispatch
        covering all matrices.  A resident (jax) ``b`` stays resident:
        zero transfers, resident result — unless the factor is float32
        (``guard_As`` set for every matrix), when block refinement against
        the original matrices runs on the host and returns float64."""
        from repro.core.device_store import device_solve

        if self.guard_As is None or any(A is None for A in self.guard_As):
            return device_solve(self.dstore, b)
        from repro.core.refine import block_refine

        B = np.asarray(b, dtype=np.float64)
        squeeze = B.ndim == 2
        if squeeze:
            B = B[..., None]
        with counters.span("refine"):
            X, _res, _hist = block_refine(
                lambda X: np.stack([A @ x for A, x in zip(self.guard_As, X)]),
                lambda R: device_solve(self.dstore, R), B,
            )
        return X[..., 0] if squeeze else X


def factorize_levels_device_many(
    sym: SymbolicFactor,
    storage: np.ndarray,
    device_engine,
    *,
    max_batch: int = 256,
    staging: str | None = None,
    guard: str | None = None,
    guard_thr: float = 0.0,
    guard_clamp: bool = False,
) -> BatchCholeskyFactor:
    """Factor M matrices sharing one pattern with ONE set of level-scheduled
    dispatches: ``storage`` is the (M, cells) pre-filled flat PanelStore
    block (CachedPlan.fill_storage per row), and every (level x bucket)
    group runs as a single ``fused_group_many`` dispatch whose batch stacks
    all M matrices' lanes.  Per-group dispatch/driver overhead — the
    dominant cost at quick-suite sizes — is paid once per group instead of
    once per (matrix, group)."""
    from repro.core.device_store import DevicePanelStore

    with counters.span("factor"):
        _reset_events(device_engine)
        M = int(storage.shape[0])
        fused = bool(getattr(device_engine, "fused_groups", False))
        if not fused:
            raise ValueError(
                "multi-matrix factorization requires fused groups")
        bucket = ("fused"
                  if getattr(device_engine, "backend", "") == "pallas"
                  else "batch")
        sched = cached_schedule(sym, max_batch=max_batch, bucket=bucket)
        dstore = DevicePanelStore(device_engine, sym, sched, storage,
                                  staging=staging, nmat=M,
                                  guard=guard is not None, guard_thr=guard_thr,
                                  guard_clamp=guard_clamp)
        stats = {
            "method": "levels_many",
            "assembly": "device",
            "staging": dstore.staging,
            "bucket": bucket,
            "nmat": M,
            "supernodes_on_device": sym.nsuper,
            "supernodes_total": sym.nsuper,
            "schedule": sched.batch_stats(),
        }
        for lvl, lgroups in enumerate(sched.groups):
            dstore.prefetch_level(lvl + 1)
            for gi in range(len(lgroups)):
                dstore.assemble_group(lvl, gi)
        dstore.read_into(storage)  # ONE bulk read-back of all M factors
        device_engine.flush()
        reports = None
        if guard is not None:
            stat = dstore.guard_status()
            reports = [
                _reduce_guard(sym, sched, [st[m] for st in stat],
                              mode=guard, thr=guard_thr)
                for m in range(M)
            ]
            stats["guard"] = guard
        return BatchCholeskyFactor(
            sym=sym, nmat=M, storage=storage, stats=stats, dstore=dstore,
            guard_reports=reports,
        )


# ---------------------------------------------------------------------------
# RLB
# ---------------------------------------------------------------------------
def factorize_rlb(
    sym: SymbolicFactor,
    Aperm: sp.csc_matrix,
    *,
    engine=None,
    device_engine=None,
    policy: OffloadPolicy | None = None,
    batch_transfers: bool = False,
) -> CholeskyFactor:
    """RLB.  With a device engine, ``batch_transfers=False`` is the paper's
    second version (one transfer + assembly per block update — low memory);
    ``batch_transfers=True`` is the first version (keep every block update on
    the device until the supernode is done, then transfer them all at once)."""
    engine = engine or HostEngine()
    store = init_panel_store(sym, Aperm)
    panels = store.panels
    stats = {
        "method": "rlb", "supernodes_on_device": 0,
        "supernodes_total": sym.nsuper, "blas_calls": 0,
    }

    for s in range(sym.nsuper):
        w = sym.width(s)
        eng = _pick_engine(engine, device_engine, policy, sym, s, stats)
        h = eng.stage(panels[s], w)
        eng.factor(h)
        out = eng.read_panel(h)
        if out is not panels[s]:  # in-place: panels are PanelStore views
            panels[s][...] = out
        t = sym.rows[s][w:]
        if not t.shape[0]:
            eng.release(h)
            continue
        blocks = supernode_blocks(sym, s)
        relmap = {u.anc: u for u in ancestor_updates(sym, s)}
        defer = batch_transfers and eng is not engine
        pending: list = []
        for bi, B in enumerate(blocks):
            a = B.anc
            nb = B.k1 - B.k0
            r0, c0 = B.row_pos0, B.col_off0
            S = eng.syrk_block(h, B.k0, B.k1)
            stats["blas_calls"] += 1
            if defer:
                pending.append(((a, r0, None, c0, nb, True), S))
            else:
                panels[a][r0:r0 + nb, c0:c0 + nb] -= np.tril(eng.fetch(S))
            for B2 in blocks[bi + 1:]:
                G = eng.gemm_block(h, B2.k0, B2.k1, B.k0, B.k1)
                stats["blas_calls"] += 1
                u = relmap[a]
                rpos = u.rel_rows[B2.k0 - u.k0: B2.k1 - u.k0]
                if defer:
                    pending.append(((a, None, rpos, c0, nb, False), G))
                else:
                    panels[a][rpos[:, None], np.arange(c0, c0 + nb)[None, :]] -= eng.fetch(G)
        eng.release(h)
        if pending:
            # paper's RLB version 1: one big transfer, then host assembly
            results = eng.gather(x for _, x in pending)
            for (tgt, _), R in zip(pending, results):
                a, r0, rpos, c0, nb, diag = tgt
                if diag:
                    panels[a][r0:r0 + nb, c0:c0 + nb] -= np.tril(R)
                else:
                    panels[a][rpos[:, None], np.arange(c0, c0 + nb)[None, :]] -= R
    if device_engine is not None:
        device_engine.flush()
    return CholeskyFactor(sym=sym, panels=panels, stats=stats, store=store)
