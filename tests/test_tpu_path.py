"""What the TPU path changed, checked on the CPU: a float32 device factor
refined to float64 accuracy, shape routing between the fused kernel and the
XLA group program, lowering errors that propagate instead of falling back,
the segment sums and panel index the device programs build, and the
compile-cache location.  The float32 dtype is steered in the tests
(``kops.device_dtype``); the library picks it from the platform."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.linalg as sl
import scipy.sparse.linalg as spl

from repro.core import DeviceEngine, cholesky, cholesky_many, engines
from repro.core import cached_schedule, symbolic_pipeline
from repro.core.device_store import build_device_plan
from repro.core.engines import panel_index, segment_sums
from repro.kernels import fused
from repro.kernels import ops as kops
from repro.launch import compile_cache
from repro.sparse import elasticity_3d, laplacian_2d, laplacian_3d


@pytest.fixture()
def f32(monkeypatch):
    monkeypatch.setattr(kops, "device_dtype", lambda: jnp.float32)


def _resid(A, x, b):
    R = A @ x.reshape(A.shape[0], -1) - b.reshape(A.shape[0], -1)
    return float(np.max(np.linalg.norm(R, axis=0)
                        / np.linalg.norm(b.reshape(A.shape[0], -1), axis=0)))


# ---------------------------------------------------------------------------
# float32 device factor, float64 answers through refinement
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("gen,kw", [(laplacian_2d, {"nx": 20}),
                                    (elasticity_3d, {"nx": 5})])
def test_f32_factor_refines_to_f64(f32, gen, kw):
    A = gen(**kw)
    eng = DeviceEngine(backend="xla")
    assert eng.dtype == np.float32
    F = cholesky(A, device_engine=eng)
    assert F.guard_A is not None  # a float32 factor refines against A
    rng = np.random.default_rng(0)
    b1 = rng.standard_normal(A.shape[0])
    b8 = rng.standard_normal((A.shape[0], 8))
    x_ref = spl.spsolve(A.tocsc(), b1)
    for b in (b1, b8):
        x = F.solve(b, backend="device")
        assert x.dtype == np.float64
        assert _resid(A, x, b) <= 1e-10
    x = F.solve(b1, backend="device")
    assert np.linalg.norm(x - x_ref) / np.linalg.norm(x_ref) <= 1e-9
    # the raw float32 solve alone is far from it: refinement did the work
    raw = F.solve(b1, backend="device", refine=False)
    assert _resid(A, raw, b1) > 1e-10
    assert len(F.ir_history[-1]) >= 2


def test_f32_batch_factor_refines(f32):
    A = laplacian_2d(12)
    As = [A, 2.0 * A]
    BF = cholesky_many(As, device_engine=DeviceEngine(backend="xla"))
    b = np.random.default_rng(1).standard_normal((2, A.shape[0], 3))
    x = BF.solve(b)
    for Ai, xi, bi in zip(As, x, b):
        assert _resid(Ai, xi, bi) <= 1e-10


def test_f64_factor_needs_no_refinement():
    F = cholesky(laplacian_2d(12), device_engine=DeviceEngine(backend="xla"))
    assert F.guard_A is None


# ---------------------------------------------------------------------------
# shape routing: kernel vs XLA group program, decided before dispatch
# ---------------------------------------------------------------------------
def test_router_sends_oversize_bucket_to_xla():
    eng = DeviceEngine(backend="pallas")
    assert eng.uses_kernel(256, 128)
    assert not fused.fits_vmem(8192, 8192, eng.dtype.itemsize)
    assert not eng.uses_kernel(8192, 8192)
    assert not DeviceEngine(backend="xla").uses_kernel(256, 128)


def test_routing_splits_one_factorization(monkeypatch):
    """With the VMEM bound lowered below the widest buckets, one
    factorization runs both programs, and agrees with the host factor."""
    A = laplacian_2d(16)
    sym, Ap = symbolic_pipeline(A)
    sched = cached_schedule(sym, bucket="fused")
    sizes = sorted(fused.bucket_vmem(bg.Lp, bg.Wp, itemsize=8)["vmem_bytes"]
                   for lg in sched.groups for bg in lg)
    monkeypatch.setattr(fused, "VMEM_LIMIT", sizes[len(sizes) // 2])
    eng = DeviceEngine(backend="pallas")
    F = cholesky(A, sym=sym, Aperm=Ap, device_engine=eng)
    assert eng.group_programs["pallas"] > 0 and eng.group_programs["xla"] > 0
    assert sum(eng.fallbacks.values()) == 0
    F_host = cholesky(A, sym=sym, Aperm=Ap)
    for pd, ph in zip(F.panels, F_host.panels):
        np.testing.assert_allclose(pd, ph, rtol=1e-9, atol=1e-9)


# ---------------------------------------------------------------------------
# no silent fallback
# ---------------------------------------------------------------------------
def test_lowering_error_propagates(monkeypatch):
    def refuse(*a, **kw):
        raise NotImplementedError(
            "Unimplemented primitive in Pallas TPU lowering: dynamic_slice")

    monkeypatch.setattr(engines, "fused_factor_syrk", refuse)
    eng = DeviceEngine(backend="pallas")
    with pytest.raises(NotImplementedError, match="Pallas TPU lowering"):
        cholesky(laplacian_2d(8), device_engine=eng)
    assert eng.fallbacks == {"xla": 0, "host": 0, "failed": 0}
    assert not any(t.startswith("fallback:") for t, _ in eng.events)


# ---------------------------------------------------------------------------
# the device programs' building blocks
# ---------------------------------------------------------------------------
def test_segment_sums_exact_per_cell_in_f32():
    """A large running total must not leak into small segments: the
    prefix-difference form loses them in float32, the segment sum not."""
    rng = np.random.default_rng(0)
    counts = rng.integers(0, 5, size=4000)
    counts[:50] = 3
    hi = np.cumsum(counts).astype(np.int32)
    vals = rng.uniform(0.5, 1.5, size=int(hi[-1]))
    vals[: int(hi[49])] *= 1e6   # a huge head of the running sum
    lo = np.concatenate([[0], hi[:-1]])
    exact = np.array([vals[a:b].sum() for a, b in zip(lo, hi)])
    got = np.asarray(segment_sums(jnp.asarray(vals, jnp.float32),
                                  jnp.asarray(hi)))
    tail = slice(50, None)
    np.testing.assert_allclose(got[tail], exact[tail], rtol=1e-6, atol=1e-6)
    C = np.concatenate([[0.0], np.cumsum(vals.astype(np.float32),
                                         dtype=np.float32)])
    prefix = C[hi] - C[lo]
    assert np.max(np.abs(prefix[tail] - exact[tail])) > 1e-2
    # and the multi-matrix (leading axis) form agrees row by row
    two = np.asarray(segment_sums(jnp.asarray(np.stack([vals, 2 * vals])),
                                  jnp.asarray(hi)))
    np.testing.assert_allclose(two[1], 2 * two[0], rtol=1e-12)


@pytest.mark.parametrize("W", [96, 256, 384])
def test_trsm_rlt_matches_triangular_solve(W):
    """The blocked tail TRSM (past one 128-column block) and the diagonal
    inverse built on it agree with scipy."""
    rng = np.random.default_rng(W)
    A = rng.standard_normal((W, W))
    L = np.linalg.cholesky(A @ A.T + W * np.eye(W))
    B = rng.standard_normal((70, W))
    X = np.asarray(engines.trsm_rlt(jnp.asarray(L), jnp.asarray(B)))
    ref = sl.solve_triangular(L, B.T, lower=True).T
    np.testing.assert_allclose(X, ref, rtol=1e-12, atol=1e-12)
    P = np.concatenate([L, B])[None]          # one (Lp, Wp) panel
    inv = np.asarray(DeviceEngine(backend="xla")._invert_diag_fn(
        1, W + 70, W)(jnp.asarray(P)))[0]
    np.testing.assert_allclose(inv @ L, np.eye(W), atol=1e-12)


def test_scatter_rows_in_pieces(monkeypatch):
    """Row scatters issued in pieces give the one-scatter result, repeated
    indices of an add accumulating across pieces."""
    monkeypatch.setattr(engines, "SCATTER_ROWS", 4)
    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.standard_normal((12, 3)))
    idx = jnp.asarray(rng.permutation(12)[:9])
    vals = jnp.asarray(rng.standard_normal((9, 3)))
    np.testing.assert_array_equal(engines.scatter_rows(y, idx, vals),
                                  y.at[idx].set(vals))
    dup = jnp.asarray([0, 5, 5, 2, 5, 0, 7, 7, 5])
    np.testing.assert_allclose(
        engines.scatter_rows(y, dup, vals, add=True),
        y.at[dup].add(vals), rtol=1e-14)


def _gidx_reference(g):
    """The padded-layout gather index built lane by lane, cell by cell."""
    r = g.cells.shape[0]
    ref = np.full((g.Bp, g.Lp, g.Wp), r)
    for i in range(g.Bp):
        rows, w, p = int(g.rows_arr[i]), int(g.ws_arr[i]), int(g.pstart[i])
        for k in range(rows):          # true panel row k, column c
            prow = k if k < w else g.Wp + k - w
            for c in range(w):
                ref[i, prow, c] = p + k * w + c
        for d in range(w, g.Wp):       # identity extension (pad lanes too)
            ref[i, d, d] = r + 1
    return ref


def test_panel_index_matches_host_plan():
    for A in (laplacian_3d(5), elasticity_3d(3)):
        sym, _ = symbolic_pipeline(A)
        for bucket in ("fused", "batch"):
            gp = build_device_plan(sym, cached_schedule(sym, bucket=bucket))
            for g in (g for lvl in gp.groups for g in lvl):
                idx = panel_index(np, g.pstart, g.rows_arr, g.ws_arr,
                                  g.Lp, g.Wp, g.cells.shape[0])
                np.testing.assert_array_equal(idx, _gidx_reference(g))


# ---------------------------------------------------------------------------
# persistent compilation cache location
# ---------------------------------------------------------------------------
def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == tmp_path
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == tmp_path
    # JAX reads the variable itself: no other path is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.compile_cache_dir()
    assert path == compile_cache.CHECKOUT / ".jax_cache"
    assert path == compile_cache.compile_cache_dir()  # not time/pid derived
    assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == path
        assert jax.config.jax_compilation_cache_dir == str(path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
