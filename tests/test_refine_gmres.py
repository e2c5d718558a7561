"""Refinement of a float32 device factor of an ill-conditioned SPD matrix.

The systems are the normal equations of an interior-point Newton step
(``conftest.ipm_normal_3d``) at barrier spreads where kappa(S) * eps32 is
near or above 1: a stationary refinement step then shrinks the error by
too little to halve the residual, so ``block_refine`` stops within a step
or two, far above float64 accuracy, and the solve has to finish in GMRES
(``refine._refine_one``).  A float32 solve of such a matrix is far from
linear, so only a flexible cycle, which updates x with the preconditioned
vectors it already holds, reaches what float64 allows: the refined
residual must come within ten times that of a float64 direct solve of the
same system.  The chip's float32 device values are steered in."""
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse.linalg as spla

from conftest import ipm_normal_3d
from repro.core import DeviceEngine, cholesky, counters, refine
from repro.kernels import ops as kops


def _resid(A, x, b):
    return float(np.linalg.norm(A @ x - b) / np.linalg.norm(b))


@pytest.mark.parametrize("grid,spread", [(8, 2e4), (8, 5e4), (12, 7e3)])
def test_float32_factor_refines_to_float64_direct_accuracy(
        grid, spread, monkeypatch):
    monkeypatch.setattr(kops, "device_dtype", lambda: jnp.float32)
    S, b = ipm_normal_3d(grid, spread, seed=3)
    opened, block = [], []
    span, block_refine = counters.span, refine.block_refine

    def spy_span(name, **meta):
        opened.append(name)
        return span(name, **meta)

    def spy_block(*args, **kw):
        out = block_refine(*args, **kw)
        block.append(len(out[2]))
        return out

    monkeypatch.setattr(counters, "span", spy_span)
    monkeypatch.setattr(refine, "block_refine", spy_block)
    F = cholesky(S, device_engine=DeviceEngine())
    x = F.solve(b, backend="device")
    direct = _resid(S, spla.spsolve(S, b), b)
    assert _resid(S, x, b) <= 10 * direct
    assert F.ir_history[-1][-1] == pytest.approx(_resid(S, x, b), rel=1e-6)
    assert "gmres" in opened
    # block_refine applied the factor len(history) + 1 times (the raw solve,
    # each step it kept and the one it refused) and _refine_one's guarded IR
    # step once more: every solve past those is a GMRES iteration
    assert F.refine_solves == [F.refine_solves[-1]]
    assert F.refine_solves[-1] > block[0] + 2
