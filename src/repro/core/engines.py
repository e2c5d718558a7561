"""Accelerator engine: the paper's GPU offload, adapted to the JAX/TPU model.

A supernode panel is *staged* (host -> device transfer) into a padded,
bucket-shaped device buffer; POTRF/TRSM/SYRK/GEMM run on the device through
jitted functions (pure-XLA by default — the MAGMA-BLAS analogue — or the
Pallas kernels on a real TPU); results are read back explicitly.  In the
scalar and batched protocols assembly stays on the host, as in the paper;
the *device-resident* protocol (put/get, gather_group/factor_group/
pack_group, invert_diag, solve_fwd_level/solve_bwd_level — driven by
repro.core.device_store) goes beyond it and performs assembly and the
triangular solves entirely on the device, scatter-free.

Shape bucketing: supernode shapes vary per matrix, but jit specializes on
static shapes, so panels are padded into a small geometric family of bucket
shapes (identity-extended diagonal blocks keep the math exact).  This is the
TPU-native replacement for MAGMA's variable-size BLAS — the compile cache
warms once per bucket, after which every supernode reuses a compiled kernel.

Layout of a staged panel (rows r, width w, buckets Wp >= w, Lp >= Wp + r - w):

    [0   : w )   diagonal block D (lower triangle valid)
    [w   : Wp)   identity extension (keeps chol/trsm exact)
    [Wp  : Wp + r - w)  tail rows (the rectangular part)
    [... : Lp)   zero padding

Beyond the scalar protocol (stage/factor/read_panel/syrk_tail), the engine
speaks a *batched* protocol used by the level-scheduled path
(repro.core.schedule): ``stage_batch`` stacks same-bucket panels into one
(batch, Lp, Wp) buffer with identity-padded lanes, ``factor_batch`` runs a
single vmapped fused POTRF+TRSM+SYRK dispatch, and ``read_panels_batch`` /
``syrk_tail_batch`` bulk-transfer the results back.
"""
from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.guard import GFLOOR_MULT
from repro.kernels import ops as kops
from repro.kernels.fused import fits_vmem, fused_factor_syrk

_HI = jax.lax.Precision.HIGHEST


class DispatchFault(RuntimeError):
    """A runtime fault of one group dispatch that a re-run on the next tier
    of the fallback chain may get past.  It is the only error the chain
    absorbs: a lowering, compile or out-of-memory error propagates.
    Nothing in the engine raises it; ``repro.faults`` injects it."""


def _mm(a, b):
    """Device matmul at full float32 precision (the TPU default for float32
    operands is a single bfloat16 pass)."""
    return jnp.matmul(a, b, precision=_HI)


#: most rows one scatter writes in the level-solve programs: the TPU
#: compiler takes 3-16 s on a row scatter of 16384 or more indices, and
#: well under 1 s on each piece of 8192 (compiling for v5e)
SCATTER_ROWS = 8192


def scatter_rows(y, idx, vals, add: bool = False):
    """``y.at[idx].set(vals)`` (or ``.add``) over rows of ``y``, issued in
    pieces of at most ``SCATTER_ROWS`` indices, in order."""
    for i in range(0, idx.shape[0], SCATTER_ROWS):
        ref = y.at[idx[i:i + SCATTER_ROWS]]
        piece = vals[i:i + SCATTER_ROWS]
        y = ref.add(piece) if add else ref.set(piece)
    return y


def trsm_rlt(L, B, nb: int = 128):
    """X with ``X @ L.T = B``: L lower triangular (W, W), B (m, W) — the
    TRSM of a panel's tail below its factored diagonal block.

    XLA's ``triangular_solve`` unrolls over W/128 blocks on TPU, so its
    compile time grows fast with W (about 7 s at W = 512 and 22 s at 1024,
    compiling for v5e).  Past one block this loops over ``nb``-column
    blocks instead: each step subtracts the solved columns' contribution
    (unsolved columns of X are still zero) and solves one (nb, nb) block,
    so the compiled program is the same size whatever W."""
    W = L.shape[0]
    if W <= nb or W % nb:
        return jax.lax.linalg.triangular_solve(
            L, B, left_side=False, lower=True, transpose_a=True
        )

    def step(k, X):
        k0 = k * nb
        Lrow = jax.lax.dynamic_slice_in_dim(L, k0, nb, axis=0)      # (nb, W)
        Lkk = jax.lax.dynamic_slice_in_dim(Lrow, k0, nb, axis=1)    # (nb, nb)
        rhs = jax.lax.dynamic_slice_in_dim(B, k0, nb, axis=1) - _mm(X, Lrow.T)
        xk = jax.lax.linalg.triangular_solve(
            Lkk, rhs, left_side=False, lower=True, transpose_a=True
        )
        return jax.lax.dynamic_update_slice_in_dim(X, xk, k0, axis=1)

    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(W // nb), step,
                             jnp.zeros_like(B))


def panel_index(xp, pstart, rows, ws, Lp: int, Wp: int, r: int):
    """(Bp, Lp, Wp) gather index of a group's stacked padded panels into its
    zero/one-extended packed cell vector (``r`` = the zero cell, ``r + 1``
    = the one cell), computed from the per-lane packed start, row count
    and width (pad lanes: rows = w = 0, an identity panel).  ``xp`` is
    numpy or jax.numpy: the device programs build it on the fly instead of
    staging a padded-size index array."""
    it = pstart.dtype
    ri = xp.arange(Lp, dtype=it)[None, :, None]
    ci = xp.arange(Wp, dtype=it)[None, None, :]
    w = ws.astype(it)[:, None, None]
    m = rows.astype(it)[:, None, None] - w
    p = pstart[:, None, None]
    diag = (ri < w) & (ci < w)
    tail = (ri >= Wp) & (ri < Wp + m) & (ci < w)
    prow = xp.where(ri < Wp, ri, ri - Wp + w)   # row within the true panel
    idx = xp.where(diag | tail, p + prow * w + ci, r)
    return xp.where((ri == ci) & (ri >= w), r + 1, idx).astype(it)


def segment_sums(vals, hi):
    """Per-cell sums of destination-sorted update entries along the last
    axis: cell c owns ``vals[..., lo[c]:hi[c]]`` with ``lo`` = ``hi``
    shifted right by one.  Each cell's entries are added on their own (a
    sorted segment sum), so a sum's rounding error is relative to that
    cell's contributions.  (A difference of two prefixes of one running
    sum, ``C[hi] - C[lo]``, is relative to the group's whole running total
    instead: it costs about a digit in float64 and cancels outright in
    float32.)"""
    n = vals.shape[-1]
    r = hi.shape[0]
    # segment id of every entry: the first cell whose end lies past it
    # (empty cells are skipped).  A binary search, not a cumsum of segment
    # starts: the TPU compiler takes 2-26 s on a cumsum of 1e5-1e7
    # entries, against about 1 s on this search.
    seg = jnp.searchsorted(hi, jnp.arange(n, dtype=hi.dtype), side="right",
                           method="scan")

    def one(v):
        return jax.ops.segment_sum(v, seg, num_segments=r,
                                   indices_are_sorted=True)

    for _ in range(vals.ndim - 1):
        one = jax.vmap(one)
    return one(vals)


def _bucket(x: int, base: int = 128) -> int:
    """Geometric bucket family: 128, 256, 384, 512, 768, 1024, 1536, 2048, ..."""
    if x <= base:
        return base
    b = base
    while b < x:
        b *= 2
    return b


def _bucket_w(w: int) -> int:
    for c in (64, 128, 256, 512):
        if w <= c:
            return c
    return -(-w // 512) * 512


def _bucket_nb(nb: int) -> int:
    # coarse on purpose: every distinct (Lp, Wp, nrp, ncp) combination is a
    # separate XLA compile; masks make padding exact, so fewer/larger buckets
    # trade a little padded compute for a bounded compile cache
    for c in (64, 256, 1024, 4096):
        if nb <= c:
            return c
    return -(-nb // 4096) * 4096


def bucket_shape(rows: int, w: int) -> tuple[int, int]:
    """Padded (Lp, Wp) bucket for a supernode panel of ``rows`` x ``w``.

    This is THE bucket function: ``stage``/``stage_batch`` pad to it and the
    level scheduler (repro.core.schedule) groups supernodes by it, so one
    compiled program per bucket serves both the sequential and batched paths.
    """
    Wp = _bucket_w(w)
    m = rows - w
    # Lp must also cover the largest padded RLB block (see _slice_rows)
    Lp = _bucket(max(Wp + m, _bucket_nb(m) if m else 0))
    return Lp, Wp


def _bucket_batch(b: int) -> int:
    """Pad a batch count to the next power of two: at most ~log2(max batch)
    distinct compiled batch programs per bucket, never one per group size."""
    p = 1
    while p < b:
        p *= 2
    return p


def _bucket_w_fine(w: int) -> int:
    for c in (8, 16, 32, 64, 128, 256, 512):
        if w <= c:
            return c
    return -(-w // 512) * 512


def _bucket_qoct(x: int, base: int = 16) -> int:
    """Quarter-octave bucket family: 2^k * {1, 1.25, 1.5, 1.75} — padding
    overhead <= 25% per dimension at ~4x the bucket count of powers of two."""
    if x <= base:
        return base
    b = base
    while True:
        for f in (1.0, 1.25, 1.5, 1.75):
            v = int(b * f)
            if x <= v:
                return v
        b *= 2


def bucket_shape_batch(rows: int, w: int) -> tuple[int, int]:
    """Padded (Lp, Wp) bucket for the DEVICE-RESIDENT level-scheduled path.

    Much finer than ``bucket_shape``: that family is coarse because the
    sequential staging path pays one XLA program AND one host pack loop per
    bucket, and RLB's block slicing forces Lp up to the padded block size.
    The device-resident path (repro.core.device_store) has neither
    constraint — panels are gathered through precomputed index maps, so the
    only cost of more buckets is compile count — and padded cells are pure
    wasted flops.  Fine buckets cut the padded panel volume ~8x and the
    padded SYRK flops ~15x on the benchmark matrices.
    """
    Wp = _bucket_w_fine(w)
    return _bucket_qoct(Wp + rows - w), Wp


def _bucket_pow2(x: int, base: int) -> int:
    b = base
    while b < x:
        b *= 2
    return b


def bucket_shape_fused(rows: int, w: int) -> tuple[int, int]:
    """Padded (Lp, Wp) bucket for the FUSED masked-kernel path.

    The fused Pallas kernel (repro.kernels.fused) takes the true per-lane
    extents and skips pad lanes, identity-extension slabs, and
    beyond-the-tail SYRK tiles outright, so padding costs memory but not
    flops.  That inverts ``bucket_shape_batch``'s trade: COARSER buckets are
    strictly better — fewer program shapes to compile, bigger batches per
    dispatch.  The width ``Wp`` and the tail ``mp = Lp - Wp`` are each a
    power of two (base 8): a tail of 128 rows or more is then a multiple
    of 128, so the kernel's SYRK tile is 128 wide and its u block stays
    ``(mp, 128)``; a shorter tail is one full-width tile.
    """
    Wp = _bucket_pow2(w, 8)
    m = rows - w
    return Wp + (_bucket_pow2(m, 8) if m else 0), Wp


def _host_lane_factor(buf: np.ndarray, rows: int, w: int, Wp: int,
                      thr: float):
    """Numpy re-factor of one staged lane (the engine's host fallback tier).

    ``buf`` is the lane's identity-extended (Lp, Wp) panel; returns
    (factored panel, (mp, mp) update matrix, 4-wide status lane) with the
    same semantics — including the sign-flipping clamp rule at ``thr`` —
    as the device programs."""
    Lp = buf.shape[0]
    mp = Lp - Wp
    m = rows - w
    fp = np.zeros_like(buf)
    u = np.zeros((mp, mp))
    idx = np.arange(w, Wp)
    fp[idx, idx] = 1.0
    st = np.array([np.inf, 0.0, 0.0, 0.0])
    if w == 0:
        return fp, u, st
    A = buf[:w, :w]
    W = np.vstack([np.tril(A) + np.tril(A, -1).T, buf[Wp:Wp + m, :w]])
    with np.errstate(invalid="ignore", divide="ignore", over="ignore"):
        for k in range(w):
            d2 = W[k, k]
            st[0] = np.fmin(st[0], d2)  # NaN-ignoring: keep the real pivot
            if thr > 0:
                # GMW81-style growth floor (see kernels/fused.py col_body):
                # thr = GFLOOR_MULT * max|diag(A)|, so theta^2 * GFLOOR_MULT
                # / thr = theta^2 / max|diag| and the scaled column stays
                # below sqrt(max|diag|)
                col = W[k + 1:, k]
                theta = float(np.max(np.abs(col))) if col.size else 0.0
                gfloor = theta * theta * (GFLOOR_MULT / thr)
                if (not d2 >= thr) or (not d2 >= gfloor):
                    d2c = max(thr, abs(d2), gfloor)
                    if not np.isfinite(d2c):
                        d2c = thr
                    st[1] += 1.0
                    st[3] += (d2c - d2) if np.isfinite(d2) else d2c
                    d2 = d2c
            dk = np.sqrt(d2)
            W[k, k] = dk
            W[k + 1:, k] /= dk
            W[k + 1:, k + 1:] -= np.outer(W[k + 1:, k], W[k + 1:w, k])
    fp[:w, :w] = np.tril(W[:w])
    fp[Wp:Wp + m, :w] = W[w:]
    if m:
        u[:m, :m] = W[w:] @ W[w:].T
    if not np.all(np.isfinite(fp)):
        st[2] = 1.0
    return fp, u, st


class _Handle:
    __slots__ = ("dev", "rows", "w", "Lp", "Wp", "_u")

    def __init__(self, dev, rows, w, Lp, Wp):
        self.dev, self.rows, self.w, self.Lp, self.Wp = dev, rows, w, Lp, Wp
        self._u = None


class _BatchHandle:
    """A staged batch of same-bucket panels: dev is (Bp, Lp, Wp) with the
    first ``B`` lanes real and the rest identity padding."""
    __slots__ = ("dev", "rows", "ws", "Lp", "Wp", "B", "_u")

    def __init__(self, dev, rows, ws, Lp, Wp, B):
        self.dev, self.rows, self.ws = dev, rows, ws
        self.Lp, self.Wp, self.B = Lp, Wp, B
        self._u = None


class DeviceEngine:
    """Engine that offloads the dense supernode math to the accelerator.

    backend      'xla' (jnp ops — MAGMA-analogue device BLAS), 'pallas'
                 (the fused Pallas supernode kernel + per-op kernels;
                 interpret on CPU), or None (default) — resolve like the
                 kernel ops do (REPRO_KERNEL_BACKEND, else 'pallas' on TPU,
                 'xla' elsewhere).  Under 'pallas', a group whose bucket
                 exceeds the kernel's VMEM bound runs the XLA group program
                 (``fits_vmem``), decided from the shape before dispatch.
    fused        factor the panel in ONE device call (beyond-paper: the
                 paper issues DPOTRF and DTRSM separately)
    fused_groups device-resident path: run each (level x bucket) group as
                 ONE dispatch (gather + apply updates + factor + pack fused
                 into a single program) instead of three; False keeps the
                 three-dispatch PR 2 pipeline as the oracle

    Values live on the device as ``kops.device_dtype()`` (``self.dtype``):
    float32 on TPU, float64 elsewhere.
    """

    name = "device"

    def __init__(self, backend: str | None = None, fused: bool = True,
                 fused_groups: bool = True, events_cap: int = 4096):
        self.backend = backend if backend is not None else kops.default_backend()
        self.dtype = np.dtype(kops.device_dtype())
        self.fused = fused
        self.fused_groups = fused_groups
        self.stats = {"transfers_in": 0, "transfers_out": 0,
                      "bytes_in": 0, "bytes_out": 0, "device_calls": 0}
        # ordered issue log of (tag, level) staging/dispatch events — the
        # async double-buffering evidence (repro.core.device_store issues
        # the level-(k+1) chunk upload before dispatching level k; tests
        # and benchmarks assert the order here).  Deliberately NOT in
        # ``stats``: callers zero that dict wholesale between runs.  A
        # long-lived serving engine factors thousands of times, so the log
        # is (a) reset at the start of every device-resident factorization
        # (``reset_events``) and (b) ring-buffered at ``events_cap`` as a
        # backstop for drivers that never reset — it must not grow without
        # bound.
        self.events: deque = deque(maxlen=events_cap)
        # set when the ring buffer drops an event: a truncated trace cannot
        # PROVE the upload-before-dispatch order, so the hazard checker
        # (repro.analyze.hazards) reports INCONCLUSIVE instead of PASS
        self.events_overflowed = False
        # donated device buffers (the update pool, solve RHS) most recently
        # consumed by donating programs: passing one to a program again is
        # an aliasing bug that only *manifests* on hardware that honours
        # donation (CPU jax silently ignores it), so it is detected here and
        # logged as a ``donation_reuse`` event for the hazard checker.
        # Short on purpose: the realistic bug re-passes a *recent* buffer,
        # and on backends that ignore donation (CPU) the deque would
        # otherwise keep large dead pools alive.
        self._donated: deque = deque(maxlen=4)
        # compiled programs keyed by (kind, *bucket shape).  A plain dict on
        # the instance (NOT functools.lru_cache on bound methods, which pins
        # ``self`` in the global cache forever) so the jit cache dies with
        # the engine.
        self._programs: dict = {}
        # optional fault-injection hooks (repro.faults.FaultPlan): exercised
        # by the chaos tests, None in production.  Kept as a plain attribute
        # so wiring a plan costs nothing when absent.
        self.faults = None
        # degraded-mode counters for the fused-group fallback chain
        # (primary backend -> xla -> host re-factor of the failing group).
        # Deliberately NOT in ``stats``: callers assert exact equality on
        # that dict, and these only move when a dispatch tier fails.
        self.fallbacks = {"xla": 0, "host": 0, "failed": 0}
        # fused-group dispatches by the program that ran them ('pallas' =
        # the compiled kernel, 'xla' = the vmapped group program)
        self.group_programs = {"pallas": 0, "xla": 0}

    def uses_kernel(self, Lp: int, Wp: int, *, guard: bool = False) -> bool:
        """Shape routing: True when a (Lp, Wp) bucket runs the fused Pallas
        kernel, False when it runs the XLA program (xla backend, or a
        bucket past the kernel's VMEM bound)."""
        return self.backend == "pallas" and fits_vmem(
            Lp, Wp, self.dtype.itemsize, guard=guard
        )

    def _event(self, tag: str, lvl: int) -> None:
        if (self.events.maxlen is not None
                and len(self.events) == self.events.maxlen):
            self.events_overflowed = True
        self.events.append((tag, lvl))

    def _note_donation(self, buf, lvl: int = -1) -> None:
        """Record that ``buf`` was donated to a device program; log a
        ``donation_reuse`` event if it had ALREADY been donated (the caller
        is re-reading a buffer whose storage the runtime may have reused)."""
        if any(buf is b for b in self._donated):
            self._event("donation_reuse", lvl)
        else:
            self._donated.append(buf)

    def reset_events(self) -> None:
        """Start a fresh event log (called at the top of each device-resident
        factorization so the async-order assertions always see exactly one
        run, and serving engines don't accumulate logs across requests)."""
        self.events.clear()
        self.events_overflowed = False
        self._donated.clear()

    def _program(self, key, build):
        fn = self._programs.get(key)
        if fn is None:
            fn = self._programs[key] = build()
        return fn

    # -- jitted device programs, cached per bucket shape -------------------
    def _factor_fn(self, Lp: int, Wp: int):
        backend = self.backend

        def f(p):
            if backend == "pallas":
                return kops.factor_panel(p, Wp, backend="pallas")
            # panels carry only the lower triangle -> do NOT symmetrize
            ld = jax.lax.linalg.cholesky(p[:Wp, :Wp], symmetrize_input=False)
            if Lp > Wp:
                x = jax.lax.linalg.triangular_solve(
                    ld, p[Wp:], left_side=False, lower=True, transpose_a=True
                )
                return jnp.concatenate([ld, x], axis=0)
            return ld

        return self._program(("factor", Lp, Wp), lambda: jax.jit(f))

    def _syrk_tail_fn(self, Lp: int, Wp: int):
        backend = self.backend

        def f(p):
            b = p[Wp:]
            if backend == "pallas":
                return kops.syrk_ln(b, backend="pallas")
            return b @ b.T

        return self._program(("syrk_tail", Lp, Wp), lambda: jax.jit(f))

    def _factor_syrk_fn(self, Lp: int, Wp: int):
        """Fused factor + update-matrix program: one round trip per supernode.

        A bucket that ``uses_kernel`` runs the single fused Pallas kernel
        (repro.kernels.fused) with the panel's true extents; any other runs
        the XLA factor + SYRK chain (one jit).  Both take (p, rows, w)."""
        if self.uses_kernel(Lp, Wp):

            def fp_(p, rows, w):
                fp, u = fused_factor_syrk(
                    p[None],
                    jnp.reshape(rows, (1,)).astype(jnp.int32),
                    jnp.reshape(w, (1,)).astype(jnp.int32),
                    interpret=kops._interpret(),
                )
                return fp[0], u[0]

            return self._program(("factor_syrk", Lp, Wp), lambda: jax.jit(fp_))
        one = self._one_factor_syrk(Lp, Wp)
        return self._program(("factor_syrk", Lp, Wp),
                             lambda: jax.jit(lambda p, rows, w: one(p)))

    @staticmethod
    def _slice_rows(p, start, npad, n):
        """Rows [start, start+n) of p, zero-padded to npad rows.
        dynamic_slice clamps starts near the end; compensate with a roll."""
        Lp = p.shape[0]
        s = jnp.minimum(start, Lp - npad)
        blk = jax.lax.dynamic_slice(p, (s, 0), (npad, p.shape[1]))
        blk = jnp.roll(blk, -(start - s), axis=0)
        return jnp.where(jnp.arange(npad)[:, None] < n, blk, 0)

    def _syrk_block_fn(self, Lp: int, Wp: int, nbp: int):
        backend = self.backend

        def f(p, k0, nb):
            blk = self._slice_rows(p, Wp + k0, nbp, nb)
            if backend == "pallas":
                return kops.syrk_ln(blk, backend="pallas")
            return blk @ blk.T

        return self._program(("syrk_block", Lp, Wp, nbp), lambda: jax.jit(f))

    def _gemm_block_fn(self, Lp: int, Wp: int, nrp: int, ncp: int):
        backend = self.backend

        def f(p, kr0, nr, kc0, nc):
            r = self._slice_rows(p, Wp + kr0, nrp, nr)
            c = self._slice_rows(p, Wp + kc0, ncp, nc)
            if backend == "pallas":
                return kops.gemm_nt(r, c, backend="pallas")
            return r @ c.T

        return self._program(("gemm_block", Lp, Wp, nrp, ncp), lambda: jax.jit(f))

    def _one_factor_syrk(self, Lp: int, Wp: int):
        """Per-panel POTRF+TRSM+SYRK in XLA (traced under vmap by the XLA
        group programs).  Returns (factored panel, update matrix); the
        update is (Lp-Wp, Lp-Wp) with only the lower triangle meaningful
        ((0, 0) when the bucket has no tail)."""

        def one(p):
            # Panels store only the lower triangle (upper is zero).  The
            # scalar LAPACK lowering never reads the upper part, but the
            # BATCHED cholesky lowering does — mirror the strict lower
            # triangle to make the input symmetric before factoring.
            a = p[:Wp, :Wp]
            a = a + jnp.tril(a, -1).T
            ld = jax.lax.linalg.cholesky(a, symmetrize_input=False)
            if Lp > Wp:
                fp = jnp.concatenate([ld, trsm_rlt(ld, p[Wp:])], axis=0)
            else:
                fp = ld
            if Lp == Wp:
                return fp, jnp.zeros((0, 0), p.dtype)
            b = fp[Wp:]
            return fp, _mm(b, b.T)

        return one

    def _one_factor_syrk_guarded(self, Lp: int, Wp: int, clamp: bool):
        """Guarded per-panel POTRF+TRSM+SYRK for the xla chain: returns
        (factored panel, update matrix, status) where status is the same
        4-wide lane the fused Pallas kernel emits — (min pivot d^2,
        n clamped, nonfinite flag, perturbation magnitude).

        ``clamp=False`` keeps the fast ``lax.linalg.cholesky`` lowering and
        derives the status post hoc (on breakdown the lowering NaN-fills, so
        min pivot reads NaN — still detected via the nonfinite flag).
        ``clamp=True`` (the perturb retry path) runs an explicit rank-1
        column loop so pivots below ``thr`` (or below the element-growth
        floor) can be boosted with the same sign-flipping
        max(thr, |d2|, theta^2/max|diag|) rule as the Pallas kernel."""
        mp = Lp - Wp

        def status_of(fp, rows, w, mind2, ncl, mag):
            rI = jnp.arange(Lp, dtype=jnp.int32)[:, None]
            cI = jnp.arange(Wp, dtype=jnp.int32)[None, :]
            m = rows - w
            live = ((rI < w) & (cI < w) & (rI >= cI)) | (
                (rI >= Wp) & (rI < Wp + m) & (cI < w)
            )
            ok = jnp.all(jnp.isfinite(jnp.where(live, fp, 0.0)))
            nf = jnp.where(ok, 0.0, 1.0).astype(fp.dtype)
            return jnp.stack([mind2, ncl, nf, mag])

        def tail_u(fp, p):
            if mp == 0:
                return jnp.zeros((0, 0), p.dtype)
            b = fp[Wp:]
            return _mm(b, b.T)

        if not clamp:

            def one(p, rows, w, thr):
                a = p[:Wp, :Wp]
                a = a + jnp.tril(a, -1).T
                ld = jax.lax.linalg.cholesky(a, symmetrize_input=False)
                if Lp > Wp:
                    fp = jnp.concatenate([ld, trsm_rlt(ld, p[Wp:])], axis=0)
                else:
                    fp = ld
                dk = jnp.diagonal(ld)
                d2 = jnp.where(jnp.arange(Wp, dtype=jnp.int32) < w, dk * dk,
                               jnp.asarray(jnp.inf, p.dtype))
                mind2 = jnp.min(d2)  # NaN-propagating on breakdown
                zero = jnp.zeros((), p.dtype)
                return fp, tail_u(fp, p), status_of(
                    fp, rows, w, mind2, zero, zero
                )

            return one

        def one(p, rows, w, thr):
            # explicit right-looking column loop with the sign-flipping
            # clamp — mirrors kernels/fused.py col_body exactly
            rI = jnp.arange(Lp, dtype=jnp.int32)[:, None]
            cI = jnp.arange(Wp, dtype=jnp.int32)[None, :]
            m = rows - w
            keep = ((rI < w) & (cI < w)) | (
                (rI >= Wp) & (rI < Wp + m) & (cI < w)
            )
            a = jnp.where(keep, p, 0.0)
            a = jnp.where((rI == cI) & (rI >= w), 1.0, a)

            def col_step(k, carry):
                a, mind2, ncl, mag = carry
                colk = jnp.sum(jnp.where(cI == k, a, 0.0), axis=1,
                               keepdims=True)
                d2 = jnp.sum(jnp.where(rI == k, colk, 0.0))
                real = k < w
                # NaN-ignoring min (see kernels/fused.py): keep the negative
                # pivot value; NaN-only failures trip the nonfinite flag
                mind2 = jnp.where(real & (d2 < mind2), d2, mind2)
                # growth floor theta^2 * BETA / thr = theta^2 / max|diag|
                # (see kernels/fused.py col_body for the derivation)
                theta = jnp.max(jnp.where(rI > k, jnp.abs(colk), 0.0))
                gfloor = theta * theta * (
                    GFLOOR_MULT / jnp.maximum(thr, jnp.finfo(p.dtype).tiny))
                cl = real & (thr > 0) & (
                    jnp.logical_not(d2 >= thr)
                    | jnp.logical_not(d2 >= gfloor)
                )
                d2c = jnp.maximum(jnp.maximum(thr, jnp.abs(d2)), gfloor)
                d2c = jnp.where(jnp.isfinite(d2c), d2c, thr)
                ncl = ncl + jnp.where(cl, 1.0, 0.0).astype(ncl.dtype)
                dmag = jnp.where(jnp.isfinite(d2), d2c - d2, d2c)
                mag = mag + jnp.where(cl, dmag, 0.0).astype(mag.dtype)
                d2 = jnp.where(cl, d2c, d2)
                dk = jnp.sqrt(d2)
                colk = colk / dk
                below = jnp.where(rI > k, colk, 0.0)
                lcol = jnp.where(rI == k, dk, below)
                bd = jnp.where(cI > k, below[:Wp].reshape(1, Wp), 0.0)
                a = a - _mm(below, bd)
                return jnp.where(cI == k, lcol, a), mind2, ncl, mag

            zero = jnp.zeros((), p.dtype)
            fp, mind2, ncl, mag = jax.lax.fori_loop(
                jnp.int32(0), jnp.int32(Wp), col_step,
                (a, jnp.full((), jnp.inf, p.dtype), zero, zero)
            )
            return fp, tail_u(fp, p), status_of(fp, rows, w, mind2, ncl, mag)

        return one

    def _batch_factor_syrk_fn(self, Bp: int, Lp: int, Wp: int):
        """Batched fused program — ONE dispatch per (level, bucket) batch,
        taking (panels, rows, ws).  A bucket that ``uses_kernel`` runs as a
        single fused Pallas kernel taking the true per-lane extents (pad
        lanes and ragged tails are masked, not computed); any other vmaps
        the per-panel XLA POTRF+TRSM+SYRK chain.  Returns (factored panels,
        update matrices); the update output is (Bp, Lp-Wp, Lp-Wp) with only
        the lower triangle meaningful (the kernel zeroes the rest)."""
        if self.uses_kernel(Lp, Wp):

            def f(p, rows, ws):
                return fused_factor_syrk(p, rows, ws, interpret=kops._interpret())

            return self._program(
                ("batch_factor_syrk", Bp, Lp, Wp), lambda: jax.jit(f)
            )
        one = jax.vmap(self._one_factor_syrk(Lp, Wp))
        return self._program(
            ("batch_factor_syrk", Bp, Lp, Wp),
            lambda: jax.jit(lambda p, rows, ws: one(p)),
        )

    # -- device-resident programs (see repro.core.device_store) -------------
    #
    # The device-resident numeric phase is deliberately SCATTER-FREE: XLA
    # lowers scatter to a serial per-element loop on CPU (and it is slow on
    # TPU too), so assembly is reformulated as gathers + one running-sum
    # trick.  Update matrices are never scattered into ancestor storage;
    # instead each group's real update entries are packed (a gather) into a
    # preallocated device *pool* (a contiguous dynamic_update_slice), and
    # when an ancestor group is later gathered, its pending contributions are
    # summed by destination cell: with the group's incoming pool entries
    # gathered in destination order, one sorted segment sum
    # (``segment_sums``) yields every cell's sum.  Factored panels are
    # likewise never written back to flat storage: they are packed (a gather)
    # per group and concatenated at the end into the device-resident factor
    # the solve programs read.  All index arrays are host-precomputed
    # (repro.core.device_store.build_device_plan) and staged once.
    def _gather_group_fn(self, Bp: int, Lp: int, Wp: int, r: int, n: int):
        """Build one group's stacked padded panel buffer from the initial
        storage and the update pool: storage gather, contribution segment
        sums, zero/one extension, padded-layout gather."""

        def f(storage0, pool, cells, src, hi, pstart, rows, ws):
            pc = storage0[cells]  # (r,) the group's panel cells, packed
            if n:
                # incoming update entries, destination-sorted
                pc = pc - segment_sums(pool[src], hi)
            ext = jnp.concatenate(
                [pc, jnp.zeros(1, pc.dtype), jnp.ones(1, pc.dtype)]
            )
            # (Bp, Lp, Wp) stacked padded panels
            return ext[panel_index(jnp, pstart, rows, ws, Lp, Wp, r)]

        return self._program(
            ("gather_group", Bp, Lp, Wp, r, n), lambda: jax.jit(f)
        )

    def _pack_group_fn(self, Bp: int, Lp: int, Wp: int, r: int, n_out: int):
        """Pack one group's factored panels (-> the device factor) and its
        real update entries (-> the pool, one contiguous in-place slice)."""

        def f(fp, u, pool, ppack, upack, off):
            packed = fp.reshape(-1)[ppack]
            if n_out:
                pool = jax.lax.dynamic_update_slice(
                    pool, u.reshape(-1)[upack], (off,)
                )
            return packed, pool

        return self._program(
            ("pack_group", Bp, Lp, Wp, r, n_out),
            lambda: jax.jit(f, donate_argnums=2),
        )

    def _fused_group_fn(self, Bp: int, Lp: int, Wp: int, clen: int,
                        r: int, n_in: int, n_out: int, *,
                        guard: bool = False, clamp: bool = False,
                        backend: str | None = None):
        """ONE-dispatch group program: gather + apply pending updates +
        batched fused factor + pack, a single jitted call per (level x
        bucket) group — vs the three dispatches of gather_group /
        factor_group / pack_group.  ``chunk`` is the level's packed raw
        storage (staged per level so uploads overlap earlier levels'
        compute; see repro.core.device_store); ``lb`` (the group's offset in
        the chunk) and ``off`` (its pool slice start) are traced scalars so
        same-shape groups share one compile.

        ``guard`` (static, part of the program key) adds the per-lane status
        output — the program returns (packed, pool, st) and takes a trailing
        traced ``thr`` — while guard=False compiles the exact pre-guard
        program, so guard="off" keeps zero detection overhead.  ``clamp``
        (static) enables pivot perturbation at ``thr`` in the factor body.
        ``backend`` picks the factor program: 'pallas' (the kernel) or
        'xla', as ``_run_group_chain`` routes the bucket."""
        backend = backend or self.backend
        one = self._one_factor_syrk(Lp, Wp)
        one_g = self._one_factor_syrk_guarded(Lp, Wp, clamp) if guard else None

        def gather(chunk, pool, lb, src, hi, pstart, rows, ws):
            pc = jax.lax.dynamic_slice(chunk, (lb,), (r,))
            if n_in:
                # incoming update entries, destination-sorted
                pc = pc - segment_sums(pool[src], hi)
            ext = jnp.concatenate(
                [pc, jnp.zeros(1, pc.dtype), jnp.ones(1, pc.dtype)]
            )
            # (Bp, Lp, Wp) stacked padded panels
            return ext[panel_index(jnp, pstart, rows, ws, Lp, Wp, r)]

        def pack(fp, u, pool, ppack, upack, off):
            packed = fp.reshape(-1)[ppack]
            if n_out:
                pool = jax.lax.dynamic_update_slice(
                    pool, u.reshape(-1)[upack], (off,)
                )
            return packed, pool

        if guard:

            def f(chunk, pool, lb, off, src, hi, pstart, rows, ws,
                  ppack, upack, thr):
                buf = gather(chunk, pool, lb, src, hi, pstart, rows, ws)
                if backend == "pallas":
                    fp, u, st = fused_factor_syrk(
                        buf, rows, ws, interpret=kops._interpret(),
                        guard=True, thr=thr
                    )
                else:
                    fp, u, st = jax.vmap(one_g, in_axes=(0, 0, 0, None))(
                        buf, rows, ws, thr
                    )
                packed, pool = pack(fp, u, pool, ppack, upack, off)
                return packed, pool, st

        else:

            def f(chunk, pool, lb, off, src, hi, pstart, rows, ws,
                  ppack, upack):
                buf = gather(chunk, pool, lb, src, hi, pstart, rows, ws)
                if backend == "pallas":
                    fp, u = fused_factor_syrk(
                        buf, rows, ws, interpret=kops._interpret()
                    )
                else:
                    fp, u = jax.vmap(one)(buf)
                return pack(fp, u, pool, ppack, upack, off)

        return self._program(
            ("fused_group", Bp, Lp, Wp, clen, r, n_in, n_out,
             backend, guard, clamp),
            lambda: jax.jit(f, donate_argnums=1),
        )

    def _fused_group_many_fn(self, M: int, Bp: int, Lp: int, Wp: int,
                             clen: int, r: int, n_in: int, n_out: int, *,
                             guard: bool = False, clamp: bool = False,
                             backend: str | None = None):
        """Multi-matrix fused group program: the single-matrix
        ``_fused_group_fn`` with a leading matrix axis on every value buffer
        (``chunk`` (M, clen), ``pool`` (M, pool)) and the SAME index arrays
        for all M matrices — one pattern, M value streams.  The M stacked
        panel buffers collapse into one (M*Bp, Lp, Wp) batch so the factor
        runs as ONE dispatch of M*Bp lanes instead of M dispatches of Bp:
        per-group dispatch/driver overhead is paid once per group, not once
        per (matrix, group)."""
        backend = backend or self.backend
        one = self._one_factor_syrk(Lp, Wp)
        one_g = self._one_factor_syrk_guarded(Lp, Wp, clamp) if guard else None

        def gather(chunk, pool, lb, src, hi, pstart, rows, ws):
            pc = jax.lax.dynamic_slice(chunk, (jnp.zeros_like(lb), lb), (M, r))
            if n_in:
                # (M, n_in) destination-sorted entries
                pc = pc - segment_sums(pool[:, src], hi)
            ext = jnp.concatenate(
                [pc, jnp.zeros((M, 1), pc.dtype), jnp.ones((M, 1), pc.dtype)],
                axis=1,
            )
            gidx = panel_index(jnp, pstart, rows, ws, Lp, Wp, r)
            return ext[:, gidx].reshape(M * Bp, Lp, Wp)

        def pack(fp, u, pool, ppack, upack, off):
            packed = fp.reshape(M, -1)[:, ppack]
            if n_out:
                pool = jax.lax.dynamic_update_slice(
                    pool, u.reshape(M, -1)[:, upack], (jnp.zeros_like(off), off)
                )
            return packed, pool

        if guard:

            def f(chunk, pool, lb, off, src, hi, pstart, rows, ws,
                  ppack, upack, thr):
                buf = gather(chunk, pool, lb, src, hi, pstart, rows, ws)
                if backend == "pallas":
                    fp, u, st = fused_factor_syrk(
                        buf, jnp.tile(rows, M), jnp.tile(ws, M),
                        interpret=kops._interpret(), guard=True, thr=thr,
                    )
                else:
                    fp, u, st = jax.vmap(one_g, in_axes=(0, 0, 0, None))(
                        buf, jnp.tile(rows, M), jnp.tile(ws, M), thr
                    )
                packed, pool = pack(fp, u, pool, ppack, upack, off)
                return packed, pool, st.reshape(M, Bp, -1)

        else:

            def f(chunk, pool, lb, off, src, hi, pstart, rows, ws,
                  ppack, upack):
                buf = gather(chunk, pool, lb, src, hi, pstart, rows, ws)
                if backend == "pallas":
                    fp, u = fused_factor_syrk(
                        buf, jnp.tile(rows, M), jnp.tile(ws, M),
                        interpret=kops._interpret(),
                    )
                else:
                    fp, u = jax.vmap(one)(buf)
                return pack(fp, u, pool, ppack, upack, off)

        return self._program(
            ("fused_group_many", M, Bp, Lp, Wp, clen, r, n_in, n_out,
             backend, guard, clamp),
            lambda: jax.jit(f, donate_argnums=1),
        )

    # Solve programs run one WHOLE LEVEL per dispatch: a level's groups are
    # independent (antichain), so their updates chain on the donated y inside
    # one program — dispatch count is O(levels), not O(levels x buckets).
    # Each group's ``P`` is its stacked padded panel buffer and ``Dinv`` the
    # inverted diagonal blocks, both materialized ONCE from the device factor
    # at finalize time (repro.core.device_store): inverting the triangular
    # diagonal blocks up front turns every substitution step into batched
    # GEMMs (MAGMA's trsm strategy, same as kernels/trsm.py, and Li's
    # batched-TRSV result for sparse triangular solves on GPUs) — thousands
    # of tiny per-supernode triangular solves per solve call become a few
    # matmuls per level.  ``y`` is (n+1, nrhs) with a trash row at index n —
    # or, for an M-matrix batch, (M*(n+1), nrhs) with one trash row per
    # matrix (the ``trash`` argument lists them; the same level programs
    # serve both cases).  Pad reads hit the trash row, but the identity
    # extensions and zero pad rows/columns of P keep that junk out of every
    # real row; the trash rows are reset once per level only to keep their
    # values finite.
    def _invert_diag_fn(self, Bp: int, Lp: int, Wp: int):
        """Invert a group's stacked triangular diagonal blocks, the top
        (Wp, Wp) of its (Bp, Lp, Wp) panels (finalize-time only; XLA for
        either backend): ``L^{-1} = X^T`` for ``X L^T = I``."""

        def f(P):
            eye = jnp.eye(Wp, dtype=P.dtype)
            return jax.vmap(lambda L: trsm_rlt(L, eye).T)(P[:, :Wp, :])

        return self._program(("invert_diag", Bp, Lp, Wp), lambda: jax.jit(f))

    def _panels_fn(self, M: int, Bp: int, Lp: int, Wp: int, r: int):
        """Gather one group's stacked padded factored panels P from the
        concatenated device factor (finalize-time; see
        ``DevicePanelStore._materialize_panels``).  ``base`` (the group's
        offset in the factor) and ``total`` (the zero/one pair's offset)
        are traced.  With M matrices the M factors stack into one
        (M*Bp, Lp, Wp) batch and the solve targets shift by mi*(n+1)."""

        def f(factor_ext, pstart, rows, ws, cols, tails, base, total,
              stride):
            gidx = panel_index(jnp, pstart, rows, ws, Lp, Wp, r)
            sg = jnp.where(gidx < r, gidx + base, gidx - r + total)
            if M == 1:
                return factor_ext[sg], cols, tails
            shift = (jnp.arange(M, dtype=cols.dtype) * stride)[:, None, None]
            return (factor_ext[:, sg].reshape(M * Bp, Lp, Wp),
                    (cols[None] + shift).reshape(M * Bp, -1),
                    (tails[None] + shift).reshape(M * Bp, -1))

        return self._program(("panels", M, Bp, Lp, Wp, r),
                             lambda: jax.jit(f))

    def _solve_fwd_fn(self, shapes: tuple, nrhs: int, ntrash: int):
        """Forward substitution for one level: per group one batched
        Dinv-GEMM for the diagonal blocks + one batched GEMM scatter-add of
        the tails."""

        def f(y, trash, Ps, Dinvs, colss, tailss):
            for P, Dinv, cols, tails in zip(Ps, Dinvs, colss, tailss):
                Lp, Wp = P.shape[1], P.shape[2]
                z = _mm(Dinv, y[cols])              # (Bp, Wp, nrhs)
                y = scatter_rows(y, cols.reshape(-1),
                                 z.reshape(-1, z.shape[2]))
                if Lp > Wp:
                    u = _mm(P[:, Wp:, :], z)        # (Bp, Lp-Wp, nrhs)
                    y = scatter_rows(y, tails.reshape(-1),
                                     -u.reshape(-1, u.shape[2]), add=True)
            return y.at[trash].set(0.0)             # reset the trash row(s)

        return self._program(
            ("solve_fwd", shapes, nrhs, ntrash),
            lambda: jax.jit(f, donate_argnums=0),
        )

    def _solve_bwd_fn(self, shapes: tuple, nrhs: int, ntrash: int):
        """Backward substitution for one level."""

        def f(y, trash, Ps, Dinvs, colss, tailss):
            for P, Dinv, cols, tails in zip(Ps, Dinvs, colss, tailss):
                Lp, Wp = P.shape[1], P.shape[2]
                r = y[cols]                         # (Bp, Wp, nrhs)
                if Lp > Wp:
                    r = r - _mm(P[:, Wp:, :].transpose(0, 2, 1), y[tails])
                z = _mm(Dinv.transpose(0, 2, 1), r)  # (L^T)^{-1} = (L^{-1})^T
                y = scatter_rows(y, cols.reshape(-1),
                                 z.reshape(-1, z.shape[2]))
            return y.at[trash].set(0.0)

        return self._program(
            ("solve_bwd", shapes, nrhs, ntrash),
            lambda: jax.jit(f, donate_argnums=0),
        )

    def _stage_rhs_fn(self, n: int, nt: int):
        """Device-side RHS staging: permute a resident (n*, k) right-hand
        side into the padded solve layout (one trash row per matrix) without
        any host round trip — ``iperm`` maps padded row i to its source row
        (trash rows map to an arbitrary source; they are zeroed)."""

        def f(b, iperm, trash):
            y = b[iperm].astype(self.dtype)
            return y.at[trash].set(0.0)

        return self._program(("stage_rhs", n, nt), lambda: jax.jit(f))

    def _unstage_rhs_fn(self, n: int, nt: int):
        """Inverse of ``_stage_rhs_fn``: read the solution out of the padded
        solve layout back into natural row order, dropping trash rows."""

        def f(y, operm):
            return y[operm]

        return self._program(("unstage_rhs", n, nt), lambda: jax.jit(f))

    # -- engine protocol ----------------------------------------------------
    @staticmethod
    def _pack_panel(buf: np.ndarray, P: np.ndarray, w: int, Wp: int) -> None:
        """Pack one supernode panel into a zeroed (Lp, Wp) bucket buffer
        (diag block, identity extension, tail rows — see module docstring)."""
        rows = P.shape[0]
        buf[:w, :w] = P[:w]
        if Wp > w:
            idx = np.arange(w, Wp)
            buf[idx, idx] = 1.0
        buf[Wp:Wp + rows - w, :w] = P[w:]

    def stage(self, P: np.ndarray, w: int) -> _Handle:
        rows = P.shape[0]
        Lp, Wp = bucket_shape(rows, w)
        buf = np.zeros((Lp, Wp), dtype=self.dtype)
        self._pack_panel(buf, P, w, Wp)
        dev = jax.device_put(buf)
        self.stats["transfers_in"] += 1
        self.stats["bytes_in"] += buf.nbytes
        return _Handle(dev, rows, w, Lp, Wp)

    def factor(self, h: _Handle) -> None:
        self.stats["device_calls"] += 1
        if self.fused:
            h.dev, h._u = self._factor_syrk_fn(h.Lp, h.Wp)(
                h.dev, np.int32(h.rows), np.int32(h.w)
            )
        else:
            h.dev = self._factor_fn(h.Lp, h.Wp)(h.dev)
            h._u = None

    def read_panel(self, h: _Handle) -> np.ndarray:
        out = np.empty((h.rows, h.w), dtype=np.float64)
        dv = np.asarray(h.dev)  # synchronous transfer back (the sequential
        # path; the device-resident path instead overlaps its level-chunked
        # staging with compute — see repro.core.device_store)
        out[:h.w] = dv[:h.w, :h.w]
        out[h.w:] = dv[h.Wp:h.Wp + h.rows - h.w, :h.w]
        self.stats["transfers_out"] += 1
        self.stats["bytes_out"] += out.nbytes
        return out

    def syrk_tail(self, h: _Handle) -> np.ndarray:
        m = h.rows - h.w
        if getattr(h, "_u", None) is not None:
            u = h._u
        else:
            self.stats["device_calls"] += 1
            u = self._syrk_tail_fn(h.Lp, h.Wp)(h.dev)
        out = np.asarray(u)[:m, :m]
        self.stats["transfers_out"] += 1
        self.stats["bytes_out"] += out.nbytes
        return out

    def syrk_block(self, h: _Handle, k0: int, k1: int):
        nb = k1 - k0
        nbp = _bucket_nb(nb)
        self.stats["device_calls"] += 1
        u = self._syrk_block_fn(h.Lp, h.Wp, nbp)(h.dev, k0, nb)
        return u[:nb, :nb]

    def gemm_block(self, h: _Handle, kr0: int, kr1: int, kc0: int, kc1: int):
        nr, nc = kr1 - kr0, kc1 - kc0
        nrp, ncp = _bucket_nb(nr), _bucket_nb(nc)
        self.stats["device_calls"] += 1
        g = self._gemm_block_fn(h.Lp, h.Wp, nrp, ncp)(h.dev, kr0, nr, kc0, nc)
        return g[:nr, :nc]

    # -- batched protocol (level-scheduled path; see repro.core.schedule) ---
    #
    # A *batch* is a set of same-bucket supernodes from one elimination-tree
    # level.  ``stage_batch`` stacks their panels into ONE (Bp, Lp, Wp)
    # device buffer (one host->device transfer), ``factor_batch`` runs ONE
    # vmapped fused POTRF+TRSM+SYRK dispatch, and ``read_panels_batch`` /
    # ``syrk_tail_batch`` each bring everything back in ONE bulk transfer.
    # Pad lanes hold identity diagonal blocks so the math stays exact.
    def stage_batch(self, Ps: list, ws: list) -> _BatchHandle:
        B = len(Ps)
        shapes = {bucket_shape(P.shape[0], w) for P, w in zip(Ps, ws)}
        if len(shapes) != 1:
            raise ValueError(f"stage_batch: mixed buckets {sorted(shapes)}")
        (Lp, Wp), = shapes
        Bp = _bucket_batch(B)
        buf = np.zeros((Bp, Lp, Wp), dtype=self.dtype)
        for i, (P, w) in enumerate(zip(Ps, ws)):
            self._pack_panel(buf[i], P, w, Wp)
        if Bp > B:  # identity pad lanes: chol(I) = I, zero tails, zero updates
            idx = np.arange(Wp)
            buf[B:, idx, idx] = 1.0
        dev = jax.device_put(buf)
        self.stats["transfers_in"] += 1
        self.stats["bytes_in"] += buf.nbytes
        return _BatchHandle(dev, [P.shape[0] for P in Ps], list(ws), Lp, Wp, B)

    def factor_batch(self, hb: _BatchHandle) -> None:
        self.stats["device_calls"] += 1
        Bp = hb.dev.shape[0]
        fn = self._batch_factor_syrk_fn(Bp, hb.Lp, hb.Wp)
        rows = np.zeros(Bp, np.int32)
        ws = np.zeros(Bp, np.int32)
        rows[:hb.B] = hb.rows
        ws[:hb.B] = hb.ws
        hb.dev, hb._u = fn(hb.dev, rows, ws)

    def read_panels_batch(self, hb: _BatchHandle) -> list:
        dv = jax.device_get(hb.dev)  # one bulk transfer for the whole batch
        self.stats["transfers_out"] += 1
        outs = []
        for i in range(hb.B):
            rows, w = hb.rows[i], hb.ws[i]
            out = np.empty((rows, w), dtype=np.float64)
            out[:w] = dv[i, :w, :w]
            out[w:] = dv[i, hb.Wp:hb.Wp + rows - w, :w]
            self.stats["bytes_out"] += out.nbytes
            outs.append(out)
        return outs

    def syrk_tail_batch(self, hb: _BatchHandle) -> list:
        """Per-supernode update matrices (m x m, lower triangle valid;
        ``None`` for supernodes with no tail).  One bulk transfer."""
        if hb._u is None or hb._u.shape[1] == 0:
            return [None] * hb.B
        uv = jax.device_get(hb._u)
        self.stats["transfers_out"] += 1
        outs = []
        for i in range(hb.B):
            m = hb.rows[i] - hb.ws[i]
            if m == 0:
                outs.append(None)
                continue
            u = uv[i, :m, :m]
            self.stats["bytes_out"] += u.nbytes
            outs.append(u)
        return outs

    def release_batch(self, hb: _BatchHandle) -> None:
        hb.dev = None
        hb._u = None

    # -- device-resident protocol (repro.core.device_store) -----------------
    def put(self, x: np.ndarray):
        """Host -> device transfer (counted; device-resident staging path)."""
        if self.faults is not None:
            x = self.faults.on_put(self, x)
        dev = jax.device_put(x)
        self.stats["transfers_in"] += 1
        self.stats["bytes_in"] += x.nbytes
        return dev

    def get(self, x) -> np.ndarray:
        """Device -> host transfer (counted; device-resident read-back)."""
        out = np.asarray(jax.device_get(x))
        self.stats["transfers_out"] += 1
        self.stats["bytes_out"] += out.nbytes
        return out

    def gather_group(self, storage0, pool, g):
        """Build one group's stacked padded panel buffer on the device (see
        repro.core.device_store._DevGroup for ``g``).  Zero transfers."""
        self.stats["device_calls"] += 1
        fn = self._gather_group_fn(
            g.Bp, g.Lp, g.Wp, int(g.cells.shape[0]), int(g.src.shape[0])
        )
        return fn(storage0, pool, g.cells, g.src, g.hi, g.pstart, g.rows,
                  g.ws)

    def factor_group(self, buf, rows, ws):
        """One batched fused POTRF+TRSM+SYRK dispatch over a stacked buffer.
        ``rows``/``ws`` are the group's true per-lane extents (pad lanes 0),
        required by the masked kernel and ignored by the XLA program."""
        self.stats["device_calls"] += 1
        Bp, Lp, Wp = buf.shape
        return self._batch_factor_syrk_fn(Bp, Lp, Wp)(buf, rows, ws)

    def pack_group(self, fp, u, pool, g):
        """Pack one group's factored panels and update entries (in-place pool
        append).  Zero transfers."""
        self.stats["device_calls"] += 1
        self._note_donation(pool)
        Bp, Lp, Wp = fp.shape
        fn = self._pack_group_fn(
            Bp, Lp, Wp, int(g.ppack.shape[0]), int(g.upack.shape[0])
        )
        return fn(fp, u, pool, g.ppack, g.upack, g.off)

    def _group_tiers(self, first: str) -> list:
        """Fallback chain for fused-group dispatch: the program the shape
        routes to, then xla (if it was not the first), then a host
        re-factor of the failing group.  Bounded — each tier runs at most
        once per group."""
        tiers = [first]
        if first != "xla":
            tiers.append("xla")
        tiers.append("host")
        return tiers

    def _group_program(self, many: bool, chunk, g, guard: bool,
                       clamp: bool, backend: str):
        """The jitted program of one fused group dispatch on ``backend``."""
        r, n_in, n_out = g.r, int(g.src.shape[0]), int(g.upack.shape[0])
        if many:
            return self._fused_group_many_fn(
                int(chunk.shape[0]), g.Bp, g.Lp, g.Wp, int(chunk.shape[1]),
                r, n_in, n_out, guard=guard, clamp=clamp, backend=backend)
        return self._fused_group_fn(
            g.Bp, g.Lp, g.Wp, int(chunk.shape[0]), r, n_in, n_out,
            guard=guard, clamp=clamp, backend=backend)

    def _run_group_chain(self, many: bool, chunk, pool, g, lvl: int,
                         guard: bool, thr: float, clamp: bool):
        """Dispatch one fused group through the fallback chain.

        The first tier is the program ``uses_kernel`` picks from the bucket
        shape, and runs the fault-injection ``on_dispatch`` hook.  Only a
        runtime dispatch fault (``DispatchFault``) moves on to the
        next tier, logged as a ``fallback:<next tier>`` event and counted
        in ``self.fallbacks``; a lowering, compile or out-of-memory error
        propagates, since retrying it elsewhere would hide a program the
        device cannot run.  Re-dispatching the same donated pool buffer is
        safe on backends that ignore donation (CPU); on hardware that
        honours it the host tier re-derives everything from host copies.
        If every tier fails, the first error propagates."""
        first = ("pallas" if self.uses_kernel(g.Lp, g.Wp, guard=guard)
                 else "xla")
        args = (chunk, pool, g.lb, g.off, g.src, g.hi, g.pstart,
                g.rows, g.ws, g.ppack, g.upack)
        if guard:
            args += (thr,)
        first_err = None
        for i, be in enumerate(self._group_tiers(first)):
            if i > 0:
                self.fallbacks[be] = self.fallbacks.get(be, 0) + 1
                self._event(f"fallback:{be}", lvl)
            try:
                if i == 0 and self.faults is not None:
                    self.faults.on_dispatch(self, lvl)
                if be == "host":
                    out = self._host_fused_group(
                        chunk, pool, g, many=many, guard=guard, thr=thr,
                        clamp=clamp
                    )
                else:
                    out = self._group_program(many, chunk, g, guard, clamp,
                                              be)(*args)
                    self.group_programs[be] += 1
            except DispatchFault as e:
                if first_err is None:
                    first_err = e
                continue
            if self.faults is not None:
                out = self.faults.on_group_result(self, out, lvl)
            return out
        self.fallbacks["failed"] += 1
        raise first_err

    def fused_group(self, chunk, pool, g, lvl: int = -1, *,
                    guard: bool = False, thr: float = 0.0,
                    clamp: bool = False):
        """Run one (level x bucket) group end to end — gather + apply
        updates + factor + pack — as ONE device dispatch (vs the three of
        gather_group/factor_group/pack_group).  Zero transfers; the dispatch
        is logged to ``events`` for the async-staging order assertion.
        With ``guard`` the dispatch also returns the per-lane status rows
        (see kernels/fused.py STATUS_COLS); failures degrade through
        ``_run_group_chain``."""
        self.stats["device_calls"] += 1
        self._note_donation(pool, lvl)
        self._event("dispatch", lvl)
        return self._run_group_chain(False, chunk, pool, g, lvl,
                                     guard, thr, clamp)

    def fused_group_many(self, chunk, pool, g, lvl: int = -1, *,
                         guard: bool = False, thr: float = 0.0,
                         clamp: bool = False):
        """Multi-matrix ``fused_group``: M value streams (leading axis on
        ``chunk``/``pool``) through one pattern's index arrays, factored as
        ONE dispatch of M*Bp lanes.  Zero transfers.  Guarded dispatches
        return (packed, pool, st) with st (M, Bp, STATUS_COLS)."""
        self.stats["device_calls"] += 1
        self._note_donation(pool, lvl)
        self._event("dispatch", lvl)
        return self._run_group_chain(True, chunk, pool, g, lvl,
                                     guard, thr, clamp)

    def _host_fused_group(self, chunk, pool, g, *, many: bool, guard: bool,
                          thr: float, clamp: bool):
        """Last-resort tier: re-derive one group's gather + factor + pack in
        numpy from host copies of the operands.  Runs only when every device
        tier raised, so the transfers it needs are counted honestly."""
        Bp, Lp, Wp = g.Bp, g.Lp, g.Wp
        mp = Lp - Wp
        ch = np.asarray(jax.device_get(chunk), dtype=np.float64)
        # device_get can hand back a read-only view of the device buffer;
        # the pool is written below (update segments), so take a real copy
        po = np.array(jax.device_get(pool), dtype=np.float64)
        idx = {k: np.asarray(jax.device_get(getattr(g, k)))
               for k in ("src", "hi", "pstart", "rows", "ws",
                         "ppack", "upack")}
        idx["lo"] = np.concatenate([[0], idx["hi"][:-1]]).astype(
            idx["hi"].dtype)
        self.stats["transfers_out"] += 1
        self.stats["bytes_out"] += ch.nbytes + po.nbytes
        lb, off = int(g.lb), int(g.off)
        r = idx["ppack"].shape[0]
        if not many:
            ch = ch[None]
            po = po[None]
        M = ch.shape[0]
        packed = np.empty((M, r))
        sts = np.empty((M, Bp, 4))
        for mi in range(M):
            pc = ch[mi, lb:lb + r].copy()
            if idx["src"].size:
                vals = po[mi, idx["src"]]
                C = np.concatenate([[0.0], np.cumsum(vals)])
                pc -= C[idx["hi"]] - C[idx["lo"]]
            ext = np.concatenate([pc, [0.0], [1.0]])
            buf = ext[panel_index(np, idx["pstart"], idx["rows"], idx["ws"],
                                  Lp, Wp, r)]        # (Bp, Lp, Wp)
            fp = np.zeros_like(buf)
            u = np.zeros((Bp, mp, mp))
            for b in range(Bp):
                fp[b], ub, sts[mi, b] = _host_lane_factor(
                    buf[b], int(idx["rows"][b]), int(idx["ws"][b]), Wp,
                    thr if clamp else 0.0
                )
                if mp:
                    u[b] = ub
            packed[mi] = fp.reshape(Bp, -1).reshape(-1)[idx["ppack"]]
            if idx["upack"].size:
                po[mi, off:off + idx["upack"].size] = \
                    u.reshape(-1)[idx["upack"]]
        self.stats["transfers_in"] += 1
        self.stats["bytes_in"] += packed.nbytes + po.nbytes
        packed, po = packed.astype(self.dtype), po.astype(self.dtype)
        sts = sts.astype(self.dtype)
        if many:
            out_packed = jax.device_put(packed)
            out_pool = jax.device_put(po)
            st = jax.device_put(sts)
        else:
            out_packed = jax.device_put(packed[0])
            out_pool = jax.device_put(po[0])
            st = jax.device_put(sts[0])
        if guard:
            return out_packed, out_pool, st
        return out_packed, out_pool

    def invert_diag(self, P):
        """Invert one group's stacked diagonal blocks (finalize-time)."""
        self.stats["device_calls"] += 1
        return self._invert_diag_fn(*P.shape)(P)

    def solve_fwd_level(self, y, trash, Ps, Dinvs, colss, tailss):
        """One forward-substitution level against the device-resident RHS."""
        self.stats["device_calls"] += 1
        self._note_donation(y)
        shapes = tuple(P.shape for P in Ps)
        return self._solve_fwd_fn(shapes, int(y.shape[1]), int(trash.shape[0]))(
            y, trash, Ps, Dinvs, colss, tailss
        )

    def solve_bwd_level(self, y, trash, Ps, Dinvs, colss, tailss):
        """One backward-substitution level against the device-resident RHS."""
        self.stats["device_calls"] += 1
        self._note_donation(y)
        shapes = tuple(P.shape for P in Ps)
        return self._solve_bwd_fn(shapes, int(y.shape[1]), int(trash.shape[0]))(
            y, trash, Ps, Dinvs, colss, tailss
        )

    def stage_rhs(self, b, iperm, trash):
        """Permute a device-resident RHS into the padded solve layout (zero
        transfers; counted as a device call)."""
        self.stats["device_calls"] += 1
        return self._stage_rhs_fn(int(b.shape[0]), int(trash.shape[0]))(
            b, iperm, trash
        )

    def unstage_rhs(self, y, operm):
        """Read the padded solve layout back to natural order on the device
        (zero transfers; counted as a device call)."""
        self.stats["device_calls"] += 1
        return self._unstage_rhs_fn(int(y.shape[0]), int(operm.shape[0]))(
            y, operm
        )

    def fetch(self, x) -> np.ndarray:
        """Per-result device->host transfer (RLB v2's per-block mode)."""
        out = np.asarray(x)
        self.stats["transfers_out"] += 1
        self.stats["bytes_out"] += out.nbytes
        return out

    def gather(self, xs) -> list:
        out = jax.device_get(list(xs))  # one bulk transfer
        self.stats["transfers_out"] += 1
        self.stats["bytes_out"] += sum(int(np.asarray(x).nbytes) for x in out)
        return [np.asarray(x) for x in out]

    def release(self, h: _Handle) -> None:
        h.dev = None
        if hasattr(h, "_u"):
            h._u = None

    def flush(self) -> None:
        pass
