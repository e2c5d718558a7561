"""jit'd public wrappers around the Pallas kernels.

Pads arbitrary shapes to MXU-aligned multiples (zero rows / identity-extended
diagonals, which are exact no-ops for these operations), invokes the kernel,
and slices the result back.  ``backend`` picks the implementation:

    'pallas' — the Pallas kernels (TPU target; interpret=True on CPU)
    'xla'    — pure-jnp fallback (what XLA:TPU would emit without the custom
               kernels; also the fast path on this CPU-only container)

Default backend comes from REPRO_KERNEL_BACKEND, else 'pallas' on TPU and
'xla' elsewhere; ``device_dtype`` picks the device value dtype the same way.
Kernel-vs-oracle equivalence is enforced by the test suite.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import gemm as _gemm
from repro.kernels import potrf as _potrf
from repro.kernels import syrk as _syrk
from repro.kernels import trsm as _trsm
from repro.kernels import ref as _ref


def default_backend() -> str:
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        return env
    try:
        if any(d.platform == "tpu" for d in jax.devices()):
            return "pallas"
    except RuntimeError:
        pass
    return "xla"


def device_dtype():
    """Value dtype of the device numeric path: float32 on TPU, where Mosaic
    has no float64 (the float64 answer comes from refinement against ``A``,
    ``repro.core.refine``); float64 elsewhere."""
    return jnp.float32 if jax.default_backend() == "tpu" else jnp.float64


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad2(x: jax.Array, m: int, n: int) -> jax.Array:
    pm, pn = m - x.shape[0], n - x.shape[1]
    if pm == 0 and pn == 0:
        return x
    return jnp.pad(x, ((0, pm), (0, pn)))


def _pad_tri(L: jax.Array, w: int) -> jax.Array:
    """Pad a lower-triangular matrix to (w, w) with an identity extension so
    triangular solves against it remain exact."""
    d = L.shape[0]
    if d == w:
        return L
    out = jnp.zeros((w, w), L.dtype)
    out = out.at[:d, :d].set(L)
    out = out.at[jnp.arange(d, w), jnp.arange(d, w)].set(1.0)
    return out


def _pad_spd(A: jax.Array, w: int) -> jax.Array:
    """Pad an SPD matrix to (w, w) with an identity block (stays SPD)."""
    return _pad_tri(A, w)  # same construction


def _rnd(x: int, m: int = 128) -> int:
    return max(m, -(-x // m) * m)


def gemm_nt(a, b, *, backend: str | None = None, block: int = 128):
    """C = A @ B^T for arbitrary (M,K), (N,K)."""
    backend = backend or default_backend()
    if backend == "xla":
        return _ref.ref_gemm_nt(a, b)
    M, K = a.shape
    N = b.shape[0]
    Mp, Np, Kp = _rnd(M, block), _rnd(N, block), _rnd(K, block)
    out = _gemm.gemm_nt(
        _pad2(a, Mp, Kp), _pad2(b, Np, Kp),
        block_m=block, block_n=block, block_k=block, interpret=_interpret(),
    )
    return out[:M, :N]


def syrk_ln(a, *, backend: str | None = None, block: int = 128):
    """C = tril(A @ A^T)."""
    backend = backend or default_backend()
    if backend == "xla":
        return _ref.ref_syrk_ln(a)
    M, K = a.shape
    Mp, Kp = _rnd(M, block), _rnd(K, block)
    out = _syrk.syrk_ln(
        _pad2(a, Mp, Kp), block_m=block, block_k=block, interpret=_interpret()
    )
    return out[:M, :M]


def trsm_rlt(L, B, *, backend: str | None = None, block: int = 128):
    """X @ L^T = B  ->  X.  L: (W, W) lower, B: (M, W)."""
    backend = backend or default_backend()
    if backend == "xla":
        return _ref.ref_trsm_rlt(L, B)
    M, W = B.shape
    Mp, Wp = _rnd(M, block), _rnd(W, block)
    out = _trsm.trsm_rlt(
        _pad_tri(L, Wp), _pad2(B, Mp, Wp),
        block_m=block, nb=block, interpret=_interpret(),
    )
    return out[:M, :W]


def trsm_lln(L, B, *, backend: str | None = None, block: int = 128):
    """L @ X = B  ->  X.  L: (W, W) lower, B: (W, N).

    The solve phase's forward substitution per supernode.  Reuses the
    right-side Pallas kernel through a transpose: L X = B <=> X^T L^T = B^T.
    """
    backend = backend or default_backend()
    if backend == "xla":
        return _ref.ref_trsm_lln(L, B)
    return trsm_rlt(L, B.T, backend=backend, block=block).T


def trsm_llt(L, B, *, backend: str | None = None, block: int = 128):
    """L^T @ X = B  ->  X.  L: (W, W) lower, B: (W, N).

    The solve phase's backward substitution per supernode.  The Pallas kernel
    only applies L^{-T} from the right, so route through the persymmetric
    flip: J L^T J (J = row/col reversal) is again lower-triangular, and
        trsm_rlt(J L^T J, B^T J) = B^T J (J L^{-1} J) = B^T L^{-1} J = X^T J.
    """
    backend = backend or default_backend()
    if backend == "xla":
        return _ref.ref_trsm_llt(L, B)
    Lf = L.T[::-1, ::-1]
    R = trsm_rlt(Lf, B.T[:, ::-1], backend=backend, block=block)
    return R[:, ::-1].T


def potrf(A, *, backend: str | None = None, block: int = 128):
    """L = chol(A), lower.  A SPD (W, W)."""
    backend = backend or default_backend()
    if backend == "xla":
        return _ref.ref_potrf(A)
    W = A.shape[0]
    Wp = _rnd(W, block)
    out = _potrf.potrf(_pad_spd(A, Wp), nb=block, interpret=_interpret())
    return out[:W, :W]


def factor_panel(P, w: int, *, backend: str | None = None):
    """Fused supernode factorization: POTRF on P[:w,:w] + TRSM on P[w:].
    P: (rows, w).  Returns the factored panel."""
    Ld = potrf(P[:w, :w], backend=backend)
    if P.shape[0] > w:
        X = trsm_rlt(Ld, P[w:], backend=backend)
        return jnp.concatenate([Ld, X], axis=0)
    return Ld
