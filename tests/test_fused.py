"""The fused Pallas supernode kernel (repro.kernels.fused) and the fused
single-dispatch group pipeline built on it: ragged-extent masking against a
numpy reference, fused-vs-unfused factorization equivalence across backends
and generators, one-dispatch-per-group engine accounting, and the async
double-buffered staging order."""
import numpy as np
import pytest
import scipy.linalg as sla

from repro.core import (
    DeviceEngine,
    bucket_shape_fused,
    cached_schedule,
    cholesky,
    group_flop_stats,
    symbolic_pipeline,
)
from repro.kernels.fused import fused_factor_syrk, syrk_tile
from repro.sparse import (
    elasticity_3d,
    kkt_like,
    laplacian_2d,
    laplacian_3d,
    random_spd,
)

GENERATORS = [
    (laplacian_2d, {"nx": 24}),
    (laplacian_2d, {"nx": 20, "stencil": 9}),
    (laplacian_3d, {"nx": 8}),
    (elasticity_3d, {"nx": 5}),
    (kkt_like, {"nx": 16}),
    (random_spd, {"n": 80, "density": 0.06, "seed": 4}),
]


# ---------------------------------------------------------------------------
# the kernel itself, against a dense numpy/scipy reference
# ---------------------------------------------------------------------------
def _reference(panel, rows, w, Lp, Wp):
    """Expected (factored panel, update matrix) for one lane, built dense."""
    m = rows - w
    fp = np.zeros((Lp, Wp))
    fp[np.arange(Wp), np.arange(Wp)] = 1.0
    u = np.zeros((Lp - Wp, Lp - Wp))
    if w:
        D = panel[:w, :w]
        Ld = np.linalg.cholesky(D + np.tril(D, -1).T
                                - np.diag(np.diag(np.tril(D, -1).T)))
        fp[:w, :w] = np.tril(Ld)
        fp[np.arange(w), np.arange(w)] = np.diag(Ld)
        if m:
            T = sla.solve_triangular(Ld, panel[Wp:Wp + m, :w].T, lower=True).T
            fp[Wp:Wp + m, :w] = T
            u[:m, :m] = np.tril(T @ T.T)
    return fp, u


def _lane(rng, rows, w, Lp, Wp, garbage=False):
    """Build one raw staged lane: SPD diag block + tail rows; everything
    outside the true extents is zero, or random garbage when ``garbage``
    (the kernel must mask it out — no staged identity extension needed)."""
    p = (rng.standard_normal((Lp, Wp)) if garbage
         else np.zeros((Lp, Wp)))
    if w:
        G = rng.standard_normal((w, w))
        p[:w, :w] = np.tril(G @ G.T + w * np.eye(w))
        p[Wp:Wp + rows - w, :w] = rng.standard_normal((rows - w, w))
    return p


@pytest.mark.parametrize("extents,Lp,Wp", [
    # ragged mix incl. width-1 supernode and a pad lane
    ([(20, 8), (16, 16), (9, 1), (0, 0)], 32, 16),
    # rows == w (no tail) for the whole bucket: mp == 0 branch
    ([(8, 8), (5, 5)], 8, 8),
    # extents exactly on the bucket boundary (no masking slack at all)
    ([(32, 16), (32, 16)], 32, 16),
    # width-1 lanes only
    ([(6, 1), (1, 1), (3, 1)], 16, 8),
    # multi-slab blocked factorization (Wp > nb=128)
    ([(300, 130), (257, 100)], 512, 256),
    # odd tail: syrk_tile falls back to one full-width tile
    ([(19, 3)], 21, 4),
])
def test_fused_kernel_vs_reference(extents, Lp, Wp):
    rng = np.random.default_rng(0)
    panels = np.stack([_lane(rng, r, w, Lp, Wp, garbage=(w == 0))
                       for r, w in extents])
    rows = np.array([r for r, _ in extents], np.int32)
    ws = np.array([w for _, w in extents], np.int32)
    fp, u = fused_factor_syrk(panels, rows, ws, interpret=True)
    fp, u = np.asarray(fp), np.asarray(u)
    for i, (r, w) in enumerate(extents):
        efp, eu = _reference(panels[i], r, w, Lp, Wp)
        np.testing.assert_allclose(fp[i], efp, rtol=1e-12, atol=1e-12)
        if Lp > Wp:
            np.testing.assert_allclose(u[i], eu, rtol=1e-11, atol=1e-11)


def test_fused_kernel_masks_garbage_padding():
    """Pad cells may hold ANYTHING — the kernel rebuilds the identity-
    extended layout from the scalar-prefetched extents alone."""
    rng = np.random.default_rng(7)
    extents = [(40, 20), (33, 32), (10, 3)]
    Lp, Wp = 64, 32
    clean = np.stack([_lane(rng, r, w, Lp, Wp) for r, w in extents])
    dirty = np.stack([_lane(np.random.default_rng(100 + i), r, w, Lp, Wp,
                            garbage=True)
                      for i, (r, w) in enumerate(extents)])
    # make the true cells identical, leaving only the garbage different
    for i, (r, w) in enumerate(extents):
        dirty[i, :w, :w] = clean[i, :w, :w]
        dirty[i, Wp:Wp + r - w, :w] = clean[i, Wp:Wp + r - w, :w]
    rows = np.array([r for r, _ in extents], np.int32)
    ws = np.array([w for _, w in extents], np.int32)
    fc, uc = fused_factor_syrk(clean, rows, ws, interpret=True)
    fd, ud = fused_factor_syrk(dirty, rows, ws, interpret=True)
    np.testing.assert_allclose(np.asarray(fc), np.asarray(fd), rtol=0, atol=0)
    np.testing.assert_allclose(np.asarray(uc), np.asarray(ud), rtol=0, atol=0)


def test_syrk_tile_divides_tail():
    for mp in (0, 1, 8, 16, 48, 96, 127, 128, 1016):
        tu = syrk_tile(mp)
        assert tu >= 1
        if mp:
            assert mp % tu == 0  # tiles must tile the output exactly


def test_fused_bucket_family_pow2():
    for rows, w in [(1, 1), (9, 1), (20, 8), (130, 100), (700, 300),
                    (5000, 64)]:
        Lp, Wp = bucket_shape_fused(rows, w)
        mp = Lp - Wp
        assert Wp >= w and mp >= rows - w
        # width and tail are each a power of two (tail 0 when rows == w)
        assert Wp & (Wp - 1) == 0 and mp & (mp - 1) == 0
        assert syrk_tile(mp) >= min(8, max(1, mp))
        # the u out-block's last dim is 128-aligned or the whole tail
        assert mp == 0 or syrk_tile(mp) % 128 == 0 or syrk_tile(mp) == mp


# ---------------------------------------------------------------------------
# fused vs unfused pipeline equivalence, both backends, every generator
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("gen,kw", GENERATORS)
def test_fused_matches_unfused_oracle(backend, gen, kw):
    """The one-dispatch fused group path reproduces the three-dispatch
    oracle (and the host factorization) to residual level."""
    A = gen(**kw)
    sym, Ap = symbolic_pipeline(A)
    F_host = cholesky(A, method="rl", sym=sym, Aperm=Ap)
    F_fused = cholesky(A, sym=sym, Aperm=Ap,
                       device_engine=DeviceEngine(backend=backend))
    F_split = cholesky(A, sym=sym, Aperm=Ap,
                       device_engine=DeviceEngine(backend=backend,
                                                  fused_groups=False))
    for pf, ps, ph in zip(F_fused.panels, F_split.panels, F_host.panels):
        np.testing.assert_allclose(pf, ph, rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(pf, ps, rtol=1e-11, atol=1e-11)
    b = np.ones(A.shape[0])
    x = F_fused.solve(b)
    assert np.linalg.norm(A @ x - b) / np.linalg.norm(b) < 1e-9


# ---------------------------------------------------------------------------
# dispatch accounting + async double-buffered staging order
# ---------------------------------------------------------------------------
def test_fused_groups_one_dispatch_per_group():
    A = laplacian_3d(9)
    sym, Ap = symbolic_pipeline(A)
    for backend in ("xla", "pallas"):
        eng = DeviceEngine(backend=backend)
        F = cholesky(A, sym=sym, Aperm=Ap, device_engine=eng)
        assert F.stats["dispatches_per_group"] == 1
        assert eng.stats["device_calls"] == F.stats["schedule"]["batches"]


def test_async_staging_uploads_ahead_of_dispatch():
    """Double buffering: the level-(k+1) chunk upload is ISSUED before any
    level-k group dispatch (so the asynchronous device_put overlaps the
    level-k compute), for every level."""
    A = laplacian_3d(9)
    sym, Ap = symbolic_pipeline(A)
    eng = DeviceEngine()
    F = cholesky(A, sym=sym, Aperm=Ap, device_engine=eng)
    assert F.stats["staging"] == "async"
    n_levels = F.stats["schedule"]["levels"]
    assert n_levels > 2
    uploads = {lvl: i for i, (tag, lvl) in enumerate(eng.events)
               if tag == "upload"}
    first_dispatch = {}
    for i, (tag, lvl) in enumerate(eng.events):
        if tag == "dispatch":
            first_dispatch.setdefault(lvl, i)
    assert sorted(uploads) == list(range(n_levels))
    assert sorted(first_dispatch) == list(range(n_levels))
    for lvl in range(n_levels - 1):
        assert uploads[lvl + 1] < first_dispatch[lvl], (
            f"chunk {lvl + 1} upload issued after level-{lvl} dispatch"
        )


def test_sync_staging_matches_async_exactly():
    A = laplacian_2d(24)
    sym, Ap = symbolic_pipeline(A)
    Fa = cholesky(A, sym=sym, Aperm=Ap, device_engine=DeviceEngine())
    Fs = cholesky(A, sym=sym, Aperm=Ap, device_engine=DeviceEngine(),
                  staging="sync")
    for p1, p2 in zip(Fa.panels, Fs.panels):
        np.testing.assert_allclose(p1, p2, rtol=0, atol=0)


def test_staging_rejected_off_device_path():
    A = laplacian_2d(16)
    with pytest.raises(ValueError, match="staging"):
        cholesky(A, staging="async")
    with pytest.raises(ValueError, match="staging"):
        cholesky(A, device_engine=DeviceEngine(), assembly="host",
                 staging="async")


# ---------------------------------------------------------------------------
# padded-FLOP waste accounting
# ---------------------------------------------------------------------------
def test_group_flop_stats_orders():
    """true <= masked <= padded, and the masked model's waste is far below
    the padded model's on the fused (coarse pow2) bucket family."""
    A = laplacian_3d(10)
    sym, _ = symbolic_pipeline(A)
    st = group_flop_stats(sym, cached_schedule(sym, bucket="fused"))
    assert 0 < st["true"] <= st["masked"] <= st["padded"]
    assert st["masked_waste"] < st["padded_waste"]
    assert len(st["groups"]) == cached_schedule(sym, bucket="fused").n_batches
    for g in st["groups"]:
        assert g["true"] <= g["masked"] <= g["padded"]
