"""Device-resident factorization state: DevicePanelStore + batch index plans.

The host PanelStore keeps the whole factor in ONE flat float64 array and
assembles update matrices with precomputed flat-index scatters
(repro.core.relind.ScatterPlan).  This module moves the numeric phase onto
the accelerator: the initial storage is staged once, every per-batch
operation of the level-scheduled factorization — panel gather, update
application, fused POTRF+TRSM+SYRK, result packing — runs as jitted device
programs, and the finished factor comes back in one transfer.  A
factorization costs O(1) host<->device transfers total instead of one round
trip per (level x bucket) group, and the device-resident factor serves
``CholeskyFactor.solve(b, backend="device")`` without re-staging.

Scatter-free assembly (fan-in)
------------------------------
XLA lowers element scatter to a serial loop (slow on CPU, poor on TPU), so
the device path never scatters into the flat factor.  Instead it exploits
two structural facts of level-scheduled right-looking factorization:

  * a panel's storage cells are READ exactly once — when its own group is
    gathered for factoring (updates target only strictly later levels, and
    factored panels are only consumed by the final read-back / solve);
  * every update entry's destination is known symbolically.

So update matrices go to a preallocated device *pool* (packed real entries,
one contiguous ``dynamic_update_slice`` per group), and when a group is
gathered its pending contributions are applied as per-cell sums: with the
incoming pool entries gathered in destination order, one sorted segment sum
(``engines.segment_sums``) adds each cell's entries on their own, so each
sum's rounding error is relative to that cell's contributions.  (The
difference of two prefixes of one running sum, ``C[hi] - C[lo]``, would
carry error relative to the whole group's total, which cancels in
float32.)  Factored panels are packed per group (a gather) and concatenated
once into the contiguous device factor the solve programs index.

Values are stored on the device as the engine's dtype (``eng.dtype``,
float32 on TPU); the host copies stay float64.

Index plans
-----------
For each schedule BatchGroup (level, bucket (Lp, Wp), supernode ids, B lanes
padded to Bp) the plan precomputes, all host-side and cached on the
LevelSchedule:

    cells (r,)        flat-storage index of each real panel cell, packed in
                      (lane, row, col) order (ascending, one contiguous run
                      per lane)
    src (n,)          pool position of every incoming update entry, sorted
                      by destination packed cell
    lo / hi (r,)      segment bounds of each packed cell's contributions (only
                      ``hi`` is staged: ``lo`` is ``hi`` shifted by one)
    gidx (Bp,Lp,Wp)   index into the zero/one-extended packed vector that
                      reproduces the stacked padded panel buffer (pad cells
                      -> the zero cell r, identity diagonals -> the one cell
                      r+1); host-side only — the device programs rebuild it
                      from ``pstart``/extents (``engines.panel_index``)
    pstart (Bp,)      packed start of each lane's cells within the group
    ppack (r,)        position in the factored (Bp,Lp,Wp) buffer of each
                      real cell (packs the factored panels)
    upack (n_out,)    position in the (Bp,mp,mp) update buffer of each real
                      lower-triangle update entry, in pool order
    cols (Bp,Wp)      solve: global RHS row of each supernode column
                      (pad -> the RHS trash row at index n)
    tails (Bp,mp)     solve: global RHS row of each tail row (pad -> trash)
    base              offset of this group's packed cells in the
                      concatenated device factor

Correctness of whole-batch application rests on the schedule: levels are
antichains of the supernodal etree, so every contribution to a group is in
the pool before the group runs, and the same argument makes the
level-scheduled triangular solves exact (forward writes each supernode's
RHS rows once and pushes updates only to later levels; backward reads only
rows finalized by earlier, higher-level steps).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import counters
from repro.core.engines import _bucket_batch, panel_index
from repro.core.relind import scatter_plan
from repro.core.schedule import LevelSchedule
from repro.core.symbolic import SymbolicFactor


@dataclass
class GroupIndices:
    """Host-side index arrays for one schedule BatchGroup (see module doc)."""
    level: int
    Lp: int
    Wp: int
    B: int
    Bp: int
    base: int
    off: int               # this group's slice start in the update pool
    lb: int                # slice start within the level's packed chunk
    cells: np.ndarray      # (r,)
    src: np.ndarray        # (n,)
    hi: np.ndarray         # (r,)
    ppack: np.ndarray      # (r,)
    upack: np.ndarray      # (n_out,)
    rows_arr: np.ndarray   # (Bp,) true row count per lane (pad lanes 0)
    ws_arr: np.ndarray     # (Bp,) true width per lane (pad lanes 0)
    pstart: np.ndarray     # (Bp,) packed start of each lane (pad lanes 0)
    cols: np.ndarray       # (Bp, Wp)
    tails: np.ndarray      # (Bp, Lp-Wp)

    @property
    def lo(self) -> np.ndarray:
        """(r,) segment starts: ``hi`` shifted right by one."""
        return np.concatenate([np.zeros(1, self.hi.dtype), self.hi[:-1]])

    @property
    def gidx(self) -> np.ndarray:
        """(Bp, Lp, Wp) padded-layout gather index, built on demand (the
        device programs build the same from ``pstart`` and the extents)."""
        return panel_index(np, self.pstart, self.rows_arr, self.ws_arr,
                           self.Lp, self.Wp, self.cells.shape[0])


@dataclass
class DeviceGroupPlan:
    """All GroupIndices of a schedule plus the global layouts."""
    groups: list            # list[list[GroupIndices]], same shape as sched.groups
    cells_concat: np.ndarray  # (packed_total,) factor cell of every packed slot
    level_base: np.ndarray  # (n_levels+1,) packed-slot start of each level
    packed_total: int       # == total real factor cells
    pool_size: int          # total real update entries


def build_device_plan(sym: SymbolicFactor, sched: LevelSchedule) -> DeviceGroupPlan:
    """Precompute every group's index arrays (symbolic phase; O(padded factor
    cells + update entries))."""
    counters.bump("device_plan")
    plan = scatter_plan(sym)
    offs = plan.offs
    n = sym.n
    packed_total = int(offs[-1])
    # src entries index the update pool, which is usually LARGER than the
    # packed factor — size the index dtype for both
    pool_total = sum(
        m * (m + 1) // 2
        for m in (sym.rows[s].shape[0] - sym.width(s) for s in range(sym.nsuper))
    )
    idx_t = (np.int32
             if max(packed_total, pool_total) < np.iinfo(np.int32).max
             else np.int64)

    # pass 1: per-supernode placement (group id, packed base of its lane)
    flat_groups = [bg for lg in sched.groups for bg in lg]
    packed_start = np.empty(sym.nsuper, dtype=np.int64)  # global packed base
    group_base = np.zeros(len(flat_groups) + 1, dtype=np.int64)
    pos = 0
    for gi, bg in enumerate(flat_groups):
        group_base[gi] = pos
        for s in bg.ids:
            s = int(s)
            packed_start[s] = pos
            pos += sym.rows[s].shape[0] * sym.width(s)
    group_base[-1] = pos
    assert pos == packed_total

    # pass 2: pool layout + every update entry's destination (packed slot)
    pool_off = np.zeros(len(flat_groups) + 1, dtype=np.int64)
    dest_pos: list = []
    for gi, bg in enumerate(flat_groups):
        cnt = 0
        for s in bg.ids:
            s = int(s)
            w = sym.width(s)
            m = sym.rows[s].shape[0] - w
            if m == 0:
                continue
            il, jl = np.tril_indices(m)
            dcell = plan.dst[s].reshape(m, m)[il, jl].astype(np.int64)
            # destination supernode of each entry -> its packed slot
            a = np.searchsorted(offs, dcell, side="right") - 1
            dest_pos.append((packed_start[a] + (dcell - offs[a])).astype(idx_t))
            cnt += il.shape[0]
        pool_off[gi + 1] = pool_off[gi] + cnt
    pool_size = int(pool_off[-1])
    dest_pos = np.concatenate(dest_pos) if dest_pos else np.empty(0, idx_t)
    # incoming entries of each group, sorted by destination packed slot:
    # each group's slots are one contiguous range, in group order, so the
    # slot order is also the group order (stable: ties keep pool order)
    order = np.argsort(dest_pos, kind="stable")
    sorted_pos = dest_pos[order]
    del dest_pos
    grp_lo = np.searchsorted(sorted_pos, group_base[:-1])
    grp_hi = np.searchsorted(sorted_pos, group_base[1:])

    # pass 3: per-group index arrays
    out: list = []
    gi = 0
    cells_concat = np.empty(packed_total, dtype=np.int64)
    level_base = np.zeros(len(sched.groups) + 1, dtype=np.int64)
    for lvl_i, lgroups in enumerate(sched.groups):
        level_base[lvl_i] = group_base[gi]
        lvl_out = []
        for bg in lgroups:
            Lp, Wp = bg.Lp, bg.Wp
            mp = Lp - Wp
            B = int(bg.ids.shape[0])
            Bp = _bucket_batch(B)
            base = int(group_base[gi])
            r = int(group_base[gi + 1] - base)
            cols = np.full((Bp, Wp), n, dtype=idx_t)
            tails = np.full((Bp, mp), n, dtype=idx_t)
            cells = np.empty(r, dtype=idx_t)
            ppack = np.empty(r, dtype=idx_t)
            rows_arr = np.zeros(Bp, dtype=np.int32)  # pad lanes stay (0, 0):
            ws_arr = np.zeros(Bp, dtype=np.int32)    # the masked kernel skips them
            pstart = np.zeros(Bp, dtype=idx_t)
            upacks = []
            p = 0
            for i, s in enumerate(bg.ids):
                s = int(s)
                w = sym.width(s)
                f = int(sym.super_ptr[s])
                rows = sym.rows[s]
                m = rows.shape[0] - w
                rows_arr[i] = rows.shape[0]
                ws_arr[i] = w
                pstart[i] = p
                sz = rows.shape[0] * w
                cells[p:p + sz] = offs[s] + np.arange(sz)
                # padded row of each real row: diag rows stay, tail rows jump
                # past the identity extension
                prow = np.concatenate(
                    [np.arange(w), np.arange(Wp, Wp + m)]
                )
                cgrid = np.arange(w)
                pp = ((i * Lp + prow)[:, None] * Wp + cgrid).ravel()
                ppack[p:p + sz] = pp
                cols[i, :w] = f + np.arange(w)
                if m:
                    tails[i, :m] = rows[w:]
                    il, jl = np.tril_indices(m)
                    upacks.append(i * mp * mp + il * mp + jl)
                p += sz
            cells_concat[base:base + r] = cells
            upack = (np.concatenate(upacks).astype(idx_t)
                     if upacks else np.empty(0, dtype=idx_t))
            src = order[grp_lo[gi]:grp_hi[gi]].astype(idx_t)
            pp_in = sorted_pos[grp_lo[gi]:grp_hi[gi]] - base
            hi = np.cumsum(np.bincount(pp_in, minlength=r)).astype(idx_t)
            lvl_out.append(GroupIndices(
                level=bg.level, Lp=Lp, Wp=Wp, B=B, Bp=Bp,
                base=base, off=int(pool_off[gi]),
                lb=int(base - level_base[bg.level]),
                cells=cells, src=src, hi=hi,
                ppack=ppack, upack=upack,
                rows_arr=rows_arr, ws_arr=ws_arr, pstart=pstart,
                cols=cols, tails=tails,
            ))
            gi += 1
        out.append(lvl_out)
    level_base[-1] = packed_total
    return DeviceGroupPlan(
        groups=out, cells_concat=cells_concat, level_base=level_base,
        packed_total=packed_total, pool_size=pool_size,
    )


@partial(jax.jit, static_argnums=1)
def _split(flat, pieces: tuple):
    """Slice the one staged index upload into its per-group arrays in ONE
    program (``pieces`` = ((offset, shape), ...), static, so a repeat
    pattern reuses the compiled split): eager slices would compile one tiny
    program per distinct (offset, size)."""
    out = []
    for off, shape in pieces:
        n = int(np.prod(shape))
        out.append(flat[off:off + n].reshape(shape))
    return out


@jax.jit
def _concat_flat(xs):
    """Concatenate along the last axis, each array flattened past the
    leading (matrix) axes of the first (one program per input structure)."""
    lead = xs[0].shape[:-1]
    return jnp.concatenate([x.reshape(lead + (-1,)) for x in xs], axis=-1)


def device_plan(sym: SymbolicFactor, sched: LevelSchedule) -> DeviceGroupPlan:
    """Cached accessor mirroring ``relind.scatter_plan``: built once per
    LevelSchedule (itself cached per SymbolicFactor), reused across
    factorizations and solves."""
    if sched.device_plan is None:
        sched.device_plan = build_device_plan(sym, sched)
    return sched.device_plan


class _DevGroup:
    """One group's index arrays as device-resident buffers, plus its static
    bucket shape (Bp, Lp, Wp) and packed cell count r."""
    __slots__ = ("cells", "src", "hi", "pstart", "ppack", "upack",
                 "rows", "ws", "cols", "tails", "off", "base", "lb",
                 "Bp", "Lp", "Wp", "r", "P", "Dinv")

    def __init__(self, cells, src, hi, pstart, ppack, upack, rows, ws,
                 cols, tails, off, base, lb, Bp, Lp, Wp, r):
        self.cells, self.src, self.hi = cells, src, hi
        self.pstart, self.ppack, self.upack = pstart, ppack, upack
        self.rows, self.ws = rows, ws
        self.cols, self.tails = cols, tails
        self.off, self.base, self.lb = off, base, lb
        self.Bp, self.Lp, self.Wp, self.r = Bp, Lp, Wp, r
        self.P = None     # stacked padded factored panels (built at finalize)
        self.Dinv = None  # inverted diagonal blocks (built at finalize)

    def drop_assembly(self) -> None:
        """Free the factorization-only index buffers (the solve keeps
        extents, cols and tails)."""
        self.cells = self.src = self.hi = self.ppack = self.upack = None


class DevicePanelStore:
    """The flat PanelStore factorization state, resident on the device.

    Construction performs a fixed number of host->device transfers
    regardless of schedule size: the index plan (one concatenated staged
    upload, sliced/reshaped on the device) plus either the filled initial
    storage (``factored=False``; ``assemble_group`` then advances the
    factorization one (level, bucket) dispatch at a time with zero
    transfers) or the already-factored packed panels (``factored=True`` —
    staging an existing host factor for device solves).  ``read_into``
    brings the finished factor back in one transfer; the packed factor
    (``factor_ext``) stays resident so ``device_solve`` reuses it without
    re-staging.
    """

    def __init__(self, eng, sym: SymbolicFactor, sched: LevelSchedule,
                 host_storage: np.ndarray, *, factored: bool = False,
                 staging: str | None = None, nmat: int = 1,
                 guard: bool = False, guard_thr: float = 0.0,
                 guard_clamp: bool = False):
        """``nmat`` > 1 selects the MULTI-MATRIX layout: ``host_storage`` is
        (nmat, cells) — nmat value streams over ONE sparsity pattern — and
        every value buffer (chunks, pool, factor_ext) carries a leading
        matrix axis while the index plan is shared verbatim.  Each group
        then factors all nmat matrices in one ``fused_group_many`` dispatch.

        ``staging`` (non-factored only) picks how the raw packed storage
        reaches the device:

            'async'  — per-level chunks, each ``jax.device_put`` issued
                       BEFORE the previous level's dispatches (device_put is
                       asynchronous, so uploads overlap compute: the first
                       levels factor while later panels are still in
                       flight).  Double-buffered by the driver via
                       ``prefetch_level``.  Default with fused groups.
            'sync'   — one monolithic upload at construction (the PR 2
                       behaviour; also what the three-dispatch fallback
                       requires, since its gather reads the full storage).
        """
        self.eng, self.sym, self.sched = eng, sym, sched
        gp = device_plan(sym, sched)
        self.plan = gp
        self.nmat = int(nmat)
        self.fused = (not factored) and bool(getattr(eng, "fused_groups", False))
        # breakdown detection: every guarded dispatch also returns per-lane
        # status rows, accumulated device-side and piggybacked onto the ONE
        # read_into transfer (zero extra transfers)
        self.guard = bool(guard)
        self.guard_thr = float(guard_thr)
        self.guard_clamp = bool(guard_clamp)
        self._status: list = []
        self._status_host = None
        if self.guard and not (factored or self.fused):
            raise ValueError(
                "guarded factorization needs fused groups (the "
                "three-dispatch fallback emits no status lanes)"
            )
        if self.nmat > 1 and not (factored or self.fused):
            raise ValueError(
                "multi-matrix factorization needs fused groups (the "
                "three-dispatch fallback has no multi-matrix programs)"
            )
        if staging is None:
            staging = "async" if self.fused else "sync"
        if staging not in ("async", "sync"):
            raise ValueError(f"unknown staging {staging!r} (want 'async' or 'sync')")
        if staging == "async" and not self.fused:
            raise ValueError(
                "staging='async' needs fused groups (the three-dispatch "
                "path gathers from the full staged storage)"
            )
        self.staging = staging
        # one staged upload of every group's index arrays, device-side slicing
        if factored:
            kinds = ("pstart", "rows_arr", "ws_arr", "cols", "tails")
        elif self.fused:  # the fused program never indexes raw storage cells
            kinds = ("src", "hi", "pstart", "ppack", "upack",
                     "rows_arr", "ws_arr", "cols", "tails")
        else:
            kinds = ("cells", "src", "hi", "pstart", "ppack", "upack",
                     "rows_arr", "ws_arr", "cols", "tails")
        parts = [getattr(g, k).ravel()
                 for lvl in gp.groups for g in lvl for k in kinds]
        flat = np.concatenate(parts) if parts else np.zeros(0, dtype=np.int32)
        pieces, pos = [], 0
        for lvl in gp.groups:
            for g in lvl:
                for k in kinds:
                    a = getattr(g, k)
                    pieces.append((pos, a.shape))
                    pos += a.size
        dflat = eng.put(flat)
        split = iter(_split(dflat, tuple(pieces)))
        empty = jnp.zeros(0, dflat.dtype)
        self.groups: list = []
        for lvl in gp.groups:
            row = []
            for g in lvl:
                devs = {k: next(split) for k in kinds}
                row.append(_DevGroup(
                    cells=devs.get("cells", empty),
                    src=devs.get("src", empty),
                    hi=devs.get("hi", empty),
                    pstart=devs["pstart"],
                    ppack=devs.get("ppack", empty),
                    upack=devs.get("upack", empty),
                    rows=devs["rows_arr"], ws=devs["ws_arr"],
                    cols=devs["cols"], tails=devs["tails"],
                    off=g.off, base=g.base, lb=g.lb,
                    Bp=g.Bp, Lp=g.Lp, Wp=g.Wp, r=int(g.cells.shape[0]),
                ))
            self.groups.append(row)
        self.factor_ext = None
        self.storage0 = None
        self._packed: list = []
        self._solve_ready = False
        self._chunks: list = []
        self._host_storage = None
        # resident solve-layout index buffers, uploaded lazily at
        # ensure_solve_ready (factor-only usage never pays for them)
        self.trash = None
        self._iperm = None
        self._operm = None
        dt = self.dtype = np.dtype(getattr(eng, "dtype", np.float64))
        if factored:
            # stage the already-factored panels, packed (one transfer)
            if self.nmat > 1:
                packed = np.empty((self.nmat, gp.packed_total + 2), dtype=dt)
                packed[:, :-2] = host_storage[:, gp.cells_concat]
                packed[:, -2:] = (0.0, 1.0)
            else:
                packed = np.empty(gp.packed_total + 2, dtype=dt)
                packed[:-2] = host_storage[gp.cells_concat]
                packed[-2:] = (0.0, 1.0)
            self.factor_ext = eng.put(packed)
            return
        pool_shape = ((self.nmat, gp.pool_size) if self.nmat > 1
                      else (gp.pool_size,))
        self.pool = jnp.zeros(pool_shape, dtype=dt)
        if not self.fused:
            self.storage0 = eng.put(host_storage.astype(dt))
            return
        # fused staging: the raw storage packed in group (= level) order, so
        # each level's cells are one contiguous chunk and a group's slice is
        # [lb, lb + r) — the device never gathers through ``cells`` at all
        lb = gp.level_base
        nlev = len(gp.groups)
        if staging == "sync":
            whole = eng.put(host_storage[..., gp.cells_concat].astype(dt))
            self._chunks = [whole[..., lb[l]:lb[l + 1]] for l in range(nlev)]
        else:
            # keep the raw storage and gather each level's cells lazily at
            # prefetch time: by then earlier levels' dispatches are already
            # in flight, so the host-side gather (and the device_put it
            # feeds) both overlap compute instead of serializing up front
            self._host_storage = host_storage
            self._chunks = [None] * nlev
            self.prefetch_level(0)

    def prefetch_level(self, lvl: int) -> None:
        """Gather one level's packed-storage chunk and issue its
        (asynchronous) upload.  The driver calls this for level k+1 before
        dispatching level k, so the transfer overlaps the factor compute
        (double buffering); the issue order is logged to the engine's event
        list."""
        if (self.staging != "async" or lvl >= len(self._chunks)
                or self._chunks[lvl] is not None):
            return
        eng = self.eng
        gp = self.plan
        cells = gp.cells_concat[gp.level_base[lvl]:gp.level_base[lvl + 1]]
        self._chunks[lvl] = eng.put(
            self._host_storage[..., cells].astype(self.dtype)
        )
        if hasattr(eng, "_event"):
            eng._event("upload", lvl)

    def assemble_group(self, lvl: int, gi: int) -> None:
        """Factor one (level, bucket) group on the device: gather+apply
        pending updates, fused POTRF+TRSM+SYRK, pack the results — ONE
        dispatch with fused groups, three on the fallback path."""
        g = self.groups[lvl][gi]
        eng = self.eng
        if self.fused:
            if self.staging == "async" and self._chunks[lvl] is None:
                self.prefetch_level(lvl)  # direct callers without a driver
            run = eng.fused_group_many if self.nmat > 1 else eng.fused_group
            if self.guard:
                packed, self.pool, st = run(
                    self._chunks[lvl], self.pool, g, lvl, guard=True,
                    thr=self.guard_thr, clamp=self.guard_clamp
                )
                self._status.append(st)
            else:
                packed, self.pool = run(self._chunks[lvl], self.pool, g, lvl)
        else:
            buf = eng.gather_group(self.storage0, self.pool, g)
            fp, u = eng.factor_group(buf, g.rows, g.ws)
            packed, self.pool = eng.pack_group(fp, u, self.pool, g)
        self._packed.append(packed)

    def finalize(self) -> None:
        """Concatenate the per-group packed factors into the device-resident
        factor the solve programs read (device op, no transfer)."""
        if self.factor_ext is not None:
            return
        tail = jnp.array([0.0, 1.0], dtype=self.dtype)
        if self.nmat > 1:
            tail = jnp.tile(tail, (self.nmat, 1))
            self.factor_ext = jnp.concatenate(self._packed + [tail], axis=1)
        else:
            self.factor_ext = jnp.concatenate(self._packed + [tail])
        self._packed = []
        self.storage0 = None
        self.pool = None
        self._chunks = []
        self._host_storage = None
        for row in self.groups:
            for dg in row:
                dg.drop_assembly()

    def ensure_solve_ready(self) -> None:
        """Lazy solve preparation (first device solve only — factor-only
        usage never pays for it): build P/Dinv for every group and upload
        the solve-layout index buffers (trash rows + the permutations that
        stage/unstage a resident RHS) in ONE transfer."""
        if self._solve_ready:
            return
        self.finalize()
        self._materialize_panels()
        n, M = self.sym.n, self.nmat
        perm = self.sym.perm
        iperm_nat = np.empty(n, dtype=np.int64)
        iperm_nat[perm] = np.arange(n)
        stride = np.arange(M, dtype=np.int64) * (n + 1)
        # padded row (mi, i) sources natural row (mi, perm[i]); trash rows
        # source row 0 and are zeroed right after the staging gather
        iperm = (np.concatenate([perm, [0]])[None, :]
                 + (np.arange(M, dtype=np.int64) * n)[:, None]).ravel()
        iperm[(n + 1) * np.arange(M) + n] = 0
        operm = (iperm_nat[None, :] + stride[:, None]).ravel()
        trash = stride + n
        aux = self.eng.put(np.concatenate([trash, iperm, operm]))
        self.trash = aux[:M]
        self._iperm = aux[M:M + M * (n + 1)]
        self._operm = aux[M + M * (n + 1):]
        self._solve_ready = True

    def _materialize_panels(self) -> None:
        """Materialize each group's stacked padded factored-panel buffer P
        and its inverted diagonal blocks Dinv: rebase gidx onto the
        concatenated factor (real cells shift by the group base, the
        zero/one cells map to the shared pair at the end of factor_ext),
        gather ONCE, and run one batched triangular inversion per group
        (device ops, executed once).  Solves then index no factor storage
        and solve no triangular systems — they read the resident P/Dinv
        buffers and run batched GEMMs, at the cost of one extra padded copy
        of the factor on the device."""
        eng = self.eng
        total = self.plan.packed_total
        n, M = self.sym.n, self.nmat
        # M factors stack into one (M*Bp, ...) panel batch; each matrix's
        # RHS rows live in its own (n+1) block, so the per-lane column/tail
        # targets shift by mi*(n+1) (the shared pad target n lands on each
        # matrix's OWN trash row)
        for dg in (dg for row in self.groups for dg in row):
            dg.P, dg.cols, dg.tails = eng._panels_fn(
                M, dg.Bp, dg.Lp, dg.Wp, dg.r
            )(self.factor_ext, dg.pstart, dg.rows, dg.ws, dg.cols, dg.tails,
              dg.base, total, n + 1)
            dg.Dinv = eng.invert_diag(dg.P)

    def read_into(self, host_storage: np.ndarray) -> None:
        """One bulk device->host transfer of the (factored) packed panels.
        Guarded factorizations concatenate the per-group status rows onto
        the same transfer, so detection costs zero extra transfers."""
        self.finalize()
        nf = self.factor_ext.shape[-1]
        if self._status:
            blob = self.eng.get(
                _concat_flat([self.factor_ext] + self._status)
            )
            packed, self._status_host = blob[..., :nf], blob[..., nf:]
            self._status = []
        else:
            packed = self.eng.get(self.factor_ext)
        host_storage[..., self.plan.cells_concat] = packed[..., :-2]

    def guard_status(self):
        """Per-group host status arrays in (level, group) dispatch order —
        (Bp, 4) each, or (nmat, Bp, 4) for the multi-matrix layout; see
        kernels/fused.py STATUS_COLS for the column layout.  Available
        after ``read_into``; None when not guarded."""
        if self._status_host is None:
            return None
        out = []
        pos = 0
        for row in self.groups:
            for dg in row:
                Bp = dg.Bp
                k = Bp * 4
                if self.nmat > 1:
                    out.append(
                        self._status_host[:, pos:pos + k].reshape(
                            self.nmat, Bp, 4
                        )
                    )
                else:
                    out.append(self._status_host[pos:pos + k].reshape(Bp, 4))
                pos += k
        return out


def _solve_levels(dstore: DevicePanelStore, dy):
    """Run the forward then backward substitution levels on a staged RHS."""
    eng, groups, trash = dstore.eng, dstore.groups, dstore.trash
    for lvl in range(len(groups)):                 # forward: L z = P b
        row = groups[lvl]
        dy = eng.solve_fwd_level(dy, trash,
                                 [g.P for g in row], [g.Dinv for g in row],
                                 [g.cols for g in row], [g.tails for g in row])
    for lvl in range(len(groups) - 1, -1, -1):     # backward: L^T x = z
        row = groups[lvl]
        dy = eng.solve_bwd_level(dy, trash,
                                 [g.P for g in row], [g.Dinv for g in row],
                                 [g.cols for g in row], [g.tails for g in row])
    return dy


def device_solve(dstore: DevicePanelStore, b) -> np.ndarray:
    """Solve A x = b with the device-resident factor: level-scheduled batched
    forward/backward substitution.

    A HOST RHS (np.ndarray) costs ONE upload and ONE download; a RESIDENT
    RHS (a jax array already on the device) costs ZERO transfers — it is
    permuted into the padded solve layout by a device program
    (``eng.stage_rhs``) and the solution comes back as a resident array, so
    iterative callers (Newton steps, multi-RHS streams) chain solves without
    touching the host.  The staged RHS is (nmat*(n+1), nrhs) — one trash row
    per matrix; each LEVEL runs as one jitted dispatch chaining its groups'
    batched Dinv-GEMM diagonal steps (triangular blocks are inverted once at
    finalize — through kernels/trsm.py on the pallas backend) and gathered
    tail GEMM updates, forward up the levels then backward down them.  With
    ``nmat`` > 1, ``b`` is (nmat, n, nrhs) (or (nmat, n)) and all matrices
    solve in the same dispatches.
    """
    dstore.ensure_solve_ready()
    sym, eng, M = dstore.sym, dstore.eng, dstore.nmat
    n = sym.n
    if not isinstance(b, np.ndarray):
        # resident path: permute on the device, return a resident array
        squeeze = b.ndim == (1 if M == 1 else 2)
        y = b[..., None] if squeeze else b
        flat = y.reshape(M * n, y.shape[-1])
        dy = eng.stage_rhs(flat, dstore._iperm, dstore.trash)
        dy = _solve_levels(dstore, dy)
        x = eng.unstage_rhs(dy, dstore._operm).reshape(y.shape)
        return x[..., 0] if squeeze else x
    y = np.asarray(b, dtype=np.float64)
    squeeze = y.ndim == (1 if M == 1 else 2)
    if squeeze:
        y = y[..., None]
    k = y.shape[-1]
    if M > 1:
        yp = np.zeros((M, n + 1, k), dtype=dstore.dtype)
        yp[:, :n] = y[:, sym.perm]
        dy = eng.put(yp.reshape(M * (n + 1), k))
        z = eng.get(_solve_levels(dstore, dy)).astype(np.float64)
        z = z.reshape(M, n + 1, k)[:, :n]
        x = np.empty_like(z)
        x[:, sym.perm] = z
    else:
        yp = np.zeros((n + 1, k), dtype=dstore.dtype)
        yp[:n] = y[sym.perm]
        dy = eng.put(yp)
        z = eng.get(_solve_levels(dstore, dy))[:n].astype(np.float64)
        x = np.empty_like(z)
        x[sym.perm] = z
    return x[..., 0] if squeeze else x
