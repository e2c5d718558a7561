"""Trace reduction: known busy time, kernel time and idle gaps."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import trace  # noqa: E402
from chipbench.trace import Op, Trace  # noqa: E402


def _synthetic():
    # window [100, 1100) ns; device 0 runs [100,300) and [250,400) (overlap)
    # and a kernel [600, 700); device 1 runs [100, 600)
    ops = [Op("fusion.1", "fusion.1 jit_f", 100, 200, 0),
           Op("fusion.2", "fusion.2 jit_f", 250, 150, 0),
           Op("custom-call.3", "custom-call.3 _fused_kernel x", 600, 100, 0),
           Op("fusion.1", "fusion.1 jit_f", 100, 500, 1),
           Op("late", "late", 1200, 50, 0)]
    spans = [("bench.window", 100, 1000), ("bench.factor", 100, 500),
             ("bench.solve", 600, 500)]
    return Trace(ops=ops, spans=spans, devices=2)


def test_busy_idle_and_kernel_time():
    tr = _synthetic()
    assert trace.window_seconds(tr) == pytest.approx(1000e-9)
    # device 0: 300 + 100 ns busy, device 1: 500 ns; mean 450 ns
    assert trace.busy_seconds(tr) == pytest.approx(450e-9)
    sec, n = trace.matching_seconds(tr, "_fused_kernel", "custom-call")
    assert (sec, n) == (pytest.approx(100e-9), 1)
    assert trace.matching_seconds(tr, "no_such_kernel") == (0.0, 0)


def test_top_ops_and_idle_gaps_named_by_open_span():
    tr = _synthetic()
    top = dict(trace.top_ops(tr))
    assert top["fusion.1"] == pytest.approx(700e-9)
    assert "late" not in top                     # outside the window
    gaps = trace.idle_gaps(tr)
    # device 0 idles [400, 600) in factor and [700, 1100) in solve
    assert gaps[0] == ["bench.solve", pytest.approx(400e-9)]
    assert gaps[1] == ["bench.factor", pytest.approx(200e-9)]


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(tmp_path)
    assert trace.window_seconds(tr) > 0
    assert any(name == "bench.window" for name, _s, _d in tr.spans)
