"""Ahead-of-time compiles for one chip of a described TPU v5e, with no chip
attached: the fused kernel lowers with Mosaic at real buckets of the fused
bucket family, the VMEM router agrees with the compiler at its bound, and
the XLA group program compiles where the router sends a bucket past it.

The device configuration the library picks from the platform (float32
values, the compiled kernel) is steered in here, since JAX in this process
sees only the CPU.  The topology is described in a module fixture, never at
import, so every test worker collects the same tests."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import DeviceEngine
from repro.kernels import fused
from repro.kernels import ops as kops


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
        from jax.experimental import topologies

        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no libtpu / lock held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def on_chip(monkeypatch):
    """What the library picks on a TPU: float32 values, compiled kernel.
    The persistent compile cache stays off: these programs cannot be read
    back without a chip."""
    monkeypatch.setattr(kops, "device_dtype", lambda: jnp.float32)
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)


def _compile_kernel(chip, Bp, Lp, Wp, guard):
    f32, i32 = jnp.float32, jnp.int32
    args = [_sds(chip, (Bp, Lp, Wp), f32), _sds(chip, (Bp,), i32),
            _sds(chip, (Bp,), i32)]
    if guard:
        args.append(_sds(chip, (), f32))

        def f(p, rows, ws, thr):
            return fused.fused_factor_syrk(p, rows, ws, guard=True, thr=thr)
    else:

        def f(p, rows, ws):
            return fused.fused_factor_syrk(p, rows, ws)
    return jax.jit(f).lower(*args).compile()


# (Bp, Lp, Wp) buckets of the elasticity and Laplacian schedules:
# tail 128 (one 128-wide SYRK tile), tail 512 (four), tail 16 (a full-width
# tile, mp % 128 != 0), and a four-slab panel with a 128-row tail
@pytest.mark.parametrize("guard", [False, True])
@pytest.mark.parametrize("bucket", [(64, 192, 64), (16, 576, 64),
                                    (32, 24, 8), (2, 640, 512)])
def test_fused_kernel_compiles_for_v5e(chip, on_chip, bucket, guard):
    Bp, Lp, Wp = bucket
    assert fused.fits_vmem(Lp, Wp, 4, guard=guard)
    assert DeviceEngine(backend="pallas").uses_kernel(Lp, Wp, guard=guard)
    compiled = _compile_kernel(chip, Bp, Lp, Wp, guard)
    assert "tpu_custom_call" in compiled.as_text()


def _largest_kernel_tail(Wp, guard):
    """Largest tail ``mp`` of a width-``Wp`` fused-family bucket (``mp`` a
    power of two from 8, or 0) that the router still sends to the kernel."""
    best, mp = None, 0
    while fused.fits_vmem(Wp + mp, Wp, 4, guard=guard):
        best, mp = mp, max(8, 2 * mp)
    return best


# the widest bucket the router sends to the kernel at each family width:
# the compiler must accept every one (the estimate errs above it)
@pytest.mark.parametrize("guard", [False, True])
@pytest.mark.parametrize("Wp", [8, 16, 32, 64, 128, 256, 512])
def test_largest_kernel_bucket_per_width_compiles(chip, on_chip, Wp, guard):
    mp = _largest_kernel_tail(Wp, guard)
    assert mp is not None
    assert not fused.fits_vmem(Wp + max(8, 2 * mp), Wp, 4, guard=guard)
    compiled = _compile_kernel(chip, 2, Wp + mp, Wp, guard)
    assert "tpu_custom_call" in compiled.as_text()


NEAR = (1, 2112, 64)     # estimate 13.3 MiB, compiler needs 10.5 MiB
PAST = (1, 2304, 256)    # estimate 31.5 MiB, compiler needs 27.1 MiB


def test_bound_sits_between_near_and_past():
    est = {b: fused.bucket_vmem(b[1], b[2])["vmem_bytes"]
           for b in (NEAR, PAST)}
    assert est[NEAR] <= fused.VMEM_LIMIT < est[PAST]


def test_compiler_refuses_past_bound_and_router_picks_xla(chip, on_chip):
    _, Lp, Wp = PAST
    eng = DeviceEngine(backend="pallas")
    assert eng.dtype == jnp.float32
    assert not fused.fits_vmem(Lp, Wp, 4)
    assert not eng.uses_kernel(Lp, Wp)
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile_kernel(chip, *PAST, guard=False)


def test_xla_group_program_compiles_past_bound(chip, on_chip):
    Bp, Lp, Wp = PAST
    eng = DeviceEngine(backend="pallas")
    clen, r, n_in, n_out, pool = 700_000, 600_000, 900_000, 400_000, 2_000_000
    fn = eng._fused_group_fn(Bp, Lp, Wp, clen, r, n_in, n_out,
                             guard=True, backend="xla")
    f32, i32 = jnp.float32, jnp.int32
    args = [_sds(chip, (clen,), f32), _sds(chip, (pool,), f32),
            _sds(chip, (), i32), _sds(chip, (), i32),
            _sds(chip, (n_in,), i32), _sds(chip, (n_in,), i32),
            _sds(chip, (Bp,), i32), _sds(chip, (Bp,), i32),
            _sds(chip, (Bp,), i32), _sds(chip, (r,), i32),
            _sds(chip, (n_out,), i32), _sds(chip, (), f32)]
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text
    for scope in ("chol.assemble", "chol.xla_factor"):
        assert scope in text
    assert "chol.segment_ids" not in text


def test_kernel_custom_call_carries_its_name(chip, on_chip):
    text = _compile_kernel(chip, 16, 576, 64, guard=False).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert calls
    for line in calls:
        assert re.search(rf"%{fused.KERNEL_NAME}(\.\d+)? = ", line)
