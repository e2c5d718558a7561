"""Fused Pallas supernode kernel: POTRF + TRSM + SYRK in ONE pallas_call.

The factorization offloads each (level x bucket) group of supernodes as a
stacked ``(Bp, Lp, Wp)`` buffer.  The unfused path runs three separate
device programs over it (batched cholesky, batched triangular solve,
batched SYRK) and relies on *staged* identity extensions to keep padded
cells exact — every pad lane and every ragged tail burns real flops.  This
kernel performs the whole pipeline per lane inside one kernel body:

    1. masked panel construction   — the true per-lane extents ``(rows, w)``
       arrive as scalar-prefetch arguments; iota predicates rebuild the
       identity-extended layout in VMEM from the raw panel, so staging needs
       no identity writes and pad cells can hold garbage;
    2. blocked POTRF+TRSM          — a static loop over ``nb``-column slabs:
       each slab is factored by an in-VMEM loop of rank-1 updates running
       over the FULL padded height (so the rectangular below-diagonal panel
       is triangular-solved in the same pass), then one MXU matmul pushes
       the slab's update into the trailing columns.  Slabs whose columns lie
       entirely in the identity extension (``k0 >= w``) are skipped with
       ``pl.when`` — a lane of width 5 in a 128-wide bucket factors one
       slab, not sixteen;
    3. tiled SYRK                  — the update matrix ``U = tril(T T^T)`` is
       gridded over ``tu``-wide column tiles (second grid dimension); tiles
       at or beyond the lane's true tail extent ``m`` are skipped entirely
       (``pl.when(tj*tu < m)``), so ragged tails cost flops proportional to
       ``m``, not to the bucket's ``Lp - Wp``.

Pad lanes are encoded as ``rows = w = 0``: the masked construction turns
them into pure identity panels, every slab and every SYRK tile is skipped,
and the outputs are written as identity / zero directly — zero flops.

The batch grid dimension is ``parallel``; the SYRK tile dimension is
``arbitrary`` so the VMEM scratch holding the factored panel persists from
the factor step (tile 0) into the later tiles.  See DESIGN.md in this
directory for the tiling/masking scheme and the 128-alignment argument.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
#: block-index zero for the index maps: a Python 0 would become an int64
#: constant under jax_enable_x64, which Mosaic cannot lower
_I0 = np.int32(0)

#: scoped-VMEM limit the kernel is compiled under (``vmem_limit_bytes``).
#: v5e has 128 MiB of VMEM per core; the compiler's default scoped limit is
#: 16 MiB.  ``fits_vmem`` routes buckets over this bound to the XLA group
#: program before anything is dispatched.
VMEM_LIMIT = 16 * 2 ** 20


def syrk_tile(mp: int, cap: int = 128) -> int:
    """SYRK column-tile width for a bucket tail of ``mp`` rows.  The u
    out-block is ``(1, mp, tu)``, and the TPU lowering needs its last dim
    to be a multiple of 128 or the whole dim: ``cap`` when it divides
    ``mp``, else one full-width tile (no tail skipping, no ragged tile)."""
    if mp <= 0:
        return 1
    return cap if mp % cap == 0 else mp


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) value: (8, 128) tiles."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * itemsize


def _live_u(Wp: int) -> int:
    """u blocks' worth of SYRK-step values the compiler keeps live, by
    panel width (fitted; kernels/DESIGN.md "Known limits")."""
    return 2 if Wp <= 64 else 6 if Wp <= 128 else 7


def bucket_vmem(Lp: int, Wp: int, *, itemsize: int = 4,
                guard: bool = False, nb: int = 128) -> dict:
    """Scoped-VMEM estimate of one grid step for bucket ``(Lp, Wp)``, in
    (8, 128) tiles: the double-buffered in/out blocks and the ``(Lp, Wp)``
    scratch of ``fused_factor_syrk``, plus the values the body keeps live.
    Those are four panels (the panel carried through the column loop and
    its masked copies), one more when slab updates rebuild the panel
    (``Wp > nb``), one more for the guard's nonfinite scan, and
    ``_live_u(Wp)`` u blocks for the SYRK tile.  Fitted to the smallest
    scoped limit the v5e compiler accepts, bisected per bucket: the table
    in kernels/DESIGN.md "Known limits" lists each reading beside this
    estimate, which is above it on every one."""
    mp = Lp - Wp
    tu = syrk_tile(mp) if mp else 0
    panel = _tile_bytes(Lp, Wp, itemsize)  # p_ref / fp_ref block, scratch
    blk_u = _tile_bytes(mp, tu, itemsize) if mp else 0  # u block (1, mp, tu)
    blk_st = _tile_bytes(1, STATUS_WIDTH, itemsize) if guard else 0
    blocks = 2 * (2 * panel + blk_u + blk_st) + panel
    internal = ((4 + (Wp > nb) + guard) * panel + _live_u(Wp) * blk_u)
    return {"Lp": Lp, "Wp": Wp, "mp": mp, "tu": tu,
            "block_in": panel, "block_fp": panel, "block_u": blk_u,
            "scratch": panel, "internal": internal,
            "vmem_bytes": blocks + internal}


def fits_vmem(Lp: int, Wp: int, itemsize: int, *, guard: bool = False) -> bool:
    """True when bucket ``(Lp, Wp)`` fits the kernel's scoped VMEM limit;
    the engine sends larger buckets to the XLA group program instead."""
    return bucket_vmem(Lp, Wp, itemsize=itemsize,
                       guard=guard)["vmem_bytes"] <= VMEM_LIMIT


#: status-lane row layout (one (1, 128) row per lane, panels.dtype):
#:   col 0  min unclamped pivot d^2 over the lane's true columns (inf if none)
#:   col 1  number of pivots clamped (perturbed) during elimination
#:   col 2  nonfinite flag (1.0 if any NaN/Inf in the factored panel)
#:   col 3  total perturbation magnitude sum(d2_clamped - d2)
STATUS_COLS = 4
STATUS_WIDTH = 128


def _fused_kernel(rows_ref, ws_ref, meta_ref, p_ref, fp_ref, u_ref, st_ref,
                  acc_ref, *, Lp: int, Wp: int, nb: int, tu: int,
                  guard: bool):
    b = pl.program_id(0)
    tj = pl.program_id(1)
    w = ws_ref[b]
    m = rows_ref[b] - w
    mp = Lp - Wp

    ri = jax.lax.broadcasted_iota(jnp.int32, (Lp, 1), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (1, Wp), 1)
    dt = p_ref.dtype
    # dtype-typed constants: under jax_enable_x64 a where() over two Python
    # floats is float64, which Mosaic cannot lower
    zero, one = jnp.zeros((), dt), jnp.ones((), dt)
    if guard:
        # perturbation threshold rides in as a float32 scalar-prefetch arg:
        # traced, so per-matrix thresholds never recompile.  thr == 0 means
        # detect-only (never clamps).
        thr = meta_ref[0].astype(dt)
        li = jax.lax.broadcasted_iota(jnp.int32, (1, STATUS_WIDTH), 1)
        tiny = jnp.asarray(jnp.finfo(dt).tiny, dt)

    @pl.when(tj == 0)
    def _factor():
        # 1. masked panel: keep the true diag block and the true tail rows,
        # zero everything else, then drop ones on the extension diagonal.
        # Equivalent to the staged identity extension, but computed from the
        # scalar-prefetched extents — pad cells may hold anything.
        a = p_ref[0]
        keep = ((ri < w) & (ci < w)) | ((ri >= Wp) & (ri < Wp + m) & (ci < w))
        a = jnp.where(keep, a, zero)
        a = jnp.where((ri == ci) & (ri >= w), one, a)
        acc_ref[...] = a
        if guard:
            st_ref[0] = jnp.where(li == 0, jnp.asarray(jnp.inf, dt), zero)

        # 2. blocked POTRF+TRSM over nb-column slabs.  Identity-extension
        # columns never receive updates (their rows of real columns are
        # masked to zero), so whole slabs past the lane's width skip.
        for k0 in range(0, Wp, nb):

            @pl.when(k0 < w)
            def _slab(k0=k0):
                a = acc_ref[...]
                hi = min(k0 + nb, Wp)

                def col_body(j, a, mind2, ncl, mag):
                    k = k0 + j
                    colk = jnp.sum(jnp.where(ci == k, a, 0.0), axis=1,
                                   keepdims=True)              # (Lp, 1)
                    d2 = jnp.sum(jnp.where(ri == k, colk, 0.0))
                    if guard:
                        # only the lane's true columns feed the status lane;
                        # identity-extension pivots (== 1) are not pivots
                        real = k < w
                        # NaN-ignoring min: keep the informative (negative)
                        # pivot even after later columns go NaN; a NaN-only
                        # failure is still caught by the nonfinite flag
                        mind2 = jnp.where(real & (d2 < mind2), d2, mind2)
                        # ~(d2 >= thr) also catches NaN pivots; thr == 0
                        # (detect-only) never clamps.  Clamp rule is
                        # sign-flipping with a GMW81-style growth floor,
                        # max(thr, |d2|, theta^2/max|diag(A)|):  boosting a
                        # genuinely negative pivot to a tiny thr would divide
                        # the column by sqrt(thr) and blow up the trailing
                        # update, so |d2| keeps flipped pivots bounded; and a
                        # zero pivot under large off-diagonals (saddle-point
                        # constraint rows after cascaded updates) must be
                        # floored at theta^2/max|diag| — theta the largest
                        # below-diagonal entry of the unscaled column — so
                        # the scaled column never exceeds sqrt(max|diag|)
                        # and element growth cannot compound geometrically.
                        # thr = GFLOOR_MULT * max|diag| by construction, so
                        # theta^2 * GFLOOR_MULT / thr recovers it with no
                        # extra kernel scalar.
                        # The perturbation stays a rank-(n clamped)
                        # modification that refinement with the perturbed
                        # factor as preconditioner undoes.
                        from repro.core.guard import GFLOOR_MULT

                        theta = jnp.max(
                            jnp.where(ri > k, jnp.abs(colk), 0.0)
                        )
                        gfloor = theta * theta * (
                            GFLOOR_MULT / jnp.maximum(thr, tiny)
                        )
                        cl = real & (thr > 0) & (
                            jnp.logical_not(d2 >= thr)
                            | jnp.logical_not(d2 >= gfloor)
                        )
                        d2c = jnp.maximum(
                            jnp.maximum(thr, jnp.abs(d2)), gfloor
                        )
                        d2c = jnp.where(jnp.isfinite(d2c), d2c, thr)
                        ncl = ncl + jnp.where(cl, one, zero)
                        dmag = jnp.where(jnp.isfinite(d2), d2c - d2, d2c)
                        mag = mag + jnp.where(cl, dmag, zero)
                        d2 = jnp.where(cl, d2c, d2)
                    dk = jnp.sqrt(d2)
                    colk = colk / dk
                    below = jnp.where(ri > k, colk, 0.0)
                    lcol = jnp.where(ri == k, dk, below)
                    # rank-1 update of the remaining slab columns; the row
                    # vector is `below` at the diagonal-block rows
                    trail = (ci > k) & (ci < hi)
                    bd = jnp.where(trail, below[:Wp].reshape(1, Wp), 0.0)
                    a = a - jnp.dot(below, bd, precision=_HI,
                                    preferred_element_type=dt)
                    return jnp.where(ci == k, lcol, a), mind2, ncl, mag

                # int32 loop bounds: under jax_enable_x64 Python-int bounds
                # give an int64 index, which Mosaic cannot lower
                lo_j, hi_j = jnp.int32(0), jnp.int32(hi - k0)
                if guard:
                    st = st_ref[0]
                    mind2 = jnp.sum(jnp.where(li == 0, st, 0.0))
                    ncl = jnp.sum(jnp.where(li == 1, st, 0.0))
                    mag = jnp.sum(jnp.where(li == 3, st, 0.0))

                    def col_step(j, carry):
                        return col_body(j, *carry)

                    a, mind2, ncl, mag = jax.lax.fori_loop(
                        lo_j, hi_j, col_step, (a, mind2, ncl, mag)
                    )
                    st_ref[0] = jnp.where(
                        li == 0, mind2,
                        jnp.where(li == 1, ncl, jnp.where(li == 3, mag, st)),
                    )
                else:

                    def col_step(j, a):
                        return col_body(j, a, None, None, None)[0]

                    a = jax.lax.fori_loop(lo_j, hi_j, col_step, a)
                if hi < Wp:
                    # one MXU matmul pushes the slab into trailing columns
                    slabL = a[:, k0:hi]                        # (Lp, nb)
                    down = slabL[hi:Wp, :]                     # (Wp-hi, nb)
                    upd = jnp.dot(slabL, down.T, precision=_HI,
                                  preferred_element_type=dt)
                    a = jnp.concatenate(
                        [a[:, :hi], a[:, hi:] - upd], axis=1
                    )
                acc_ref[...] = a

        fp_ref[0] = acc_ref[...]
        if guard:
            st = st_ref[0]
            # max over a dt-typed flag: a bool any() reduction is float64
            # under jax_enable_x64
            bad = jnp.max(jnp.where(jnp.isfinite(acc_ref[...]), zero, one))
            st_ref[0] = jnp.where(li == 2, bad, st)

    # 3. SYRK column tile tj of U = tril(T T^T), T the factored tail.
    # Tiles at/after the lane's true tail extent are skipped outright.
    if u_ref is not None:

        @pl.when(tj * tu < m)
        def _syrk_tile():
            tail = acc_ref[Wp:, :]                             # (mp, Wp)
            # a ref slice: Mosaic has no lowering for a value dynamic_slice
            r0 = pl.multiple_of(Wp + tj * tu, 8)
            blk = acc_ref[pl.ds(r0, tu), :]                    # (tu, Wp)
            part = jax.lax.dot_general(
                tail, blk, (((1,), (1,)), ((), ())), precision=_HI,
                preferred_element_type=dt,
            )
            rg = jax.lax.broadcasted_iota(jnp.int32, (mp, 1), 0)
            cg = tj * tu + jax.lax.broadcasted_iota(jnp.int32, (1, tu), 1)
            u_ref[0] = jnp.where(rg >= cg, part, zero)

        @pl.when(tj * tu >= m)
        def _skip_tile():
            u_ref[0] = jnp.zeros(u_ref.shape[1:], u_ref.dtype)

    @pl.when((tj == 0) & (w == 0))
    def _pad_lane():
        # pad lane (rows = w = 0): identity panel, no factor loop ran
        fp_ref[0] = jnp.where(ri == ci, one, zero)
        acc_ref[...] = fp_ref[0]


@functools.partial(jax.jit, static_argnames=("nb", "interpret", "guard"))
def fused_factor_syrk(
    panels: jax.Array,
    rows: jax.Array,
    ws: jax.Array,
    *,
    nb: int = 128,
    interpret: bool = False,
    guard: bool = False,
    thr=0.0,
) -> tuple[jax.Array, ...]:
    """Fused batched supernode factorization: ONE pallas_call for
    POTRF + TRSM + SYRK over a stacked group buffer.

    panels  (Bp, Lp, Wp) raw packed panels (padded layout: diag block in
            rows [0, w), tail rows at [Wp, Wp + rows - w)); identity
            extensions are optional — the kernel masks from the extents
    rows/ws int32 (Bp,) true per-lane extents; pad lanes are (0, 0)
    guard   (static) also emit a per-lane status row (see STATUS_COLS);
            ``thr`` (traced) is the pivot perturbation threshold — pivots
            with d^2 below it are clamped up to it and counted; thr = 0
            detects without clamping.  guard=False compiles the exact
            pre-guard program: zero detection overhead when off.

    Returns (fp, u): fp the factored panels in the same layout (identity
    extension in place, strict upper zero), u the (Bp, Lp-Wp, Lp-Wp) update
    matrices, lower triangle valid, zeros outside each lane's true (m, m).
    With guard=True returns (fp, u, st) where st is (Bp, STATUS_COLS):
    (min pivot d^2, n clamped, nonfinite flag) per lane.
    """
    Bp, Lp, Wp = panels.shape
    nb = min(nb, Wp)
    mp = Lp - Wp
    tu = syrk_tile(mp)
    ntj = max(1, mp // tu if mp else 1)
    rows = rows.astype(jnp.int32)
    ws = ws.astype(jnp.int32)

    out_shapes = [jax.ShapeDtypeStruct((Bp, Lp, Wp), panels.dtype)]
    out_specs = [pl.BlockSpec((1, Lp, Wp), lambda b, tj, *_: (b, _I0, _I0))]
    if mp:
        out_shapes.append(jax.ShapeDtypeStruct((Bp, mp, mp), panels.dtype))
        out_specs.append(pl.BlockSpec((1, mp, tu), lambda b, tj, *_: (b, _I0, tj)))
    if guard:
        # (Bp, 1, 128): the block's last two dims equal the array's, as the
        # TPU lowering requires of a block narrower than (8, 128)
        out_shapes.append(
            jax.ShapeDtypeStruct((Bp, 1, STATUS_WIDTH), panels.dtype)
        )
        out_specs.append(
            pl.BlockSpec((1, 1, STATUS_WIDTH), lambda b, tj, *_: (b, _I0, _I0))
        )

    body = functools.partial(
        _fused_kernel, Lp=Lp, Wp=Wp, nb=nb, tu=tu, guard=guard
    )
    if guard:
        def kernel(rows_ref, ws_ref, meta_ref, p_ref, *rest):
            outs, acc_ref = rest[:-1], rest[-1]
            body(rows_ref, ws_ref, meta_ref, p_ref, outs[0],
                 outs[1] if mp else None, outs[-1], acc_ref)
    else:
        def kernel(rows_ref, ws_ref, p_ref, *rest):
            outs, acc_ref = rest[:-1], rest[-1]
            body(rows_ref, ws_ref, None, p_ref, outs[0],
                 outs[1] if mp else None, None, acc_ref)

    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if guard else 2,
        grid=(Bp, ntj),
        in_specs=[pl.BlockSpec((1, Lp, Wp), lambda b, tj, *_: (b, _I0, _I0))],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((Lp, Wp), panels.dtype)],
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
        **kw,
    )
    if guard:
        meta = jnp.asarray(thr, jnp.float32).reshape(1)
        out = call(rows, ws, meta, panels)
    else:
        out = call(rows, ws, panels)
    fp = out[0]
    u = out[1] if mp else jnp.zeros((Bp, 0, 0), panels.dtype)
    if guard:
        return fp, u, out[-1][:, 0, :STATUS_COLS]
    return fp, u
