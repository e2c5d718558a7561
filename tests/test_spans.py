"""The solver's host spans and device scopes (``repro.core.counters``): each
is recorded where its work happens, inside the solver's own functions; the
span counts equal the engine's counters at the same boundaries; every scope
sits in the compiled program that holds its work; the kernel carries its
name.  The chip's float32 device values are steered in, so that solves
refine as they do there."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from conftest import ipm_normal_3d
from repro.core import DeviceEngine, PlanCache, cholesky, counters
from repro.core import device_store
from repro.kernels import fused
from repro.kernels import ops as kops
from repro.sparse import laplacian_2d

HOST_SPANS = ("factor", "fill", "stage", "dispatch", "readback", "solve",
              "refine")


def _host_spans(directory) -> list:
    """(name without the prefix, start, end) of every program span."""
    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(files[-1]))
    n = len(counters.SPAN_PREFIX)
    return [(ev.name[n:], ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(counters.SPAN_PREFIX)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One refactor-and-refined-solve step under the profiler, after a
    warm-up step: (spans, engine counter deltas over the step)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kops, "device_dtype", lambda: jnp.float32)
        A = laplacian_2d(14)
        plan = PlanCache().get(A)
        eng = DeviceEngine()
        b = np.random.default_rng(0).standard_normal(A.shape[0])
        cholesky(A, plan=plan, device_engine=eng).solve(b, backend="device")
        before = dict(eng.stats)
        where = tmp_path_factory.mktemp("trace")
        with jax.profiler.trace(str(where)):
            F = cholesky(A, plan=plan, device_engine=eng)
            x = F.solve(b, backend="device")
        delta = {k: eng.stats[k] - before[k] for k in before}
    assert F.guard_A is not None and len(F.ir_history[-1]) >= 2
    assert np.linalg.norm(A @ x - b) <= 1e-10 * np.linalg.norm(b)
    return _host_spans(where), delta


def _within(span, parents) -> bool:
    return any(p[1] <= span[1] and span[2] <= p[2] for p in parents)


def test_every_host_span_is_recorded(traced):
    spans, _ = traced
    assert {name for name, _s, _e in spans} == set(HOST_SPANS)


def test_spans_nest_where_the_work_happens(traced):
    spans, _ = traced
    of = {k: [s for s in spans if s[0] == k] for k in HOST_SPANS}
    assert len(of["factor"]) == 1 and len(of["refine"]) == 1
    outer = of["factor"] + of["solve"]
    for kind in ("stage", "dispatch", "readback"):
        assert of[kind] and all(_within(s, outer) for s in of[kind]), kind
    # every device solve of the refined solve applies the factor inside it
    assert len(of["solve"]) >= 2
    assert all(_within(s, of["refine"]) for s in of["solve"])
    assert all(f[2] <= of["factor"][0][1] for f in of["fill"])


def test_span_counts_equal_the_engine_counters(traced):
    spans, delta = traced
    count = {k: sum(1 for s in spans if s[0] == k) for k in HOST_SPANS}
    assert count["dispatch"] == delta["device_calls"] > 0
    assert count["readback"] == delta["transfers_out"] > 0


def test_scopes_are_in_the_compiled_programs(monkeypatch):
    """Each device scope in the compiled text of the programs that hold its
    work, on the CPU: ``op_name`` metadata survives compilation."""
    monkeypatch.setattr(kops, "device_dtype", lambda: jnp.float32)
    A = laplacian_2d(14)
    eng = DeviceEngine()
    texts: dict = {}
    real = eng._program

    def program(key, build):
        fn = real(key, build)

        def call(*args):
            texts.setdefault(key[0], []).append(
                fn.lower(*args).compile().as_text())
            return fn(*args)

        return call

    monkeypatch.setattr(eng, "_program", program)
    F = cholesky(A, plan=PlanCache().get(A), device_engine=eng)
    b = np.ones(A.shape[0])
    F.solve(b, backend="device")
    F.solve(jnp.asarray(b), backend="device", refine=False)  # resident RHS
    # program kind -> (its jitted name, its scopes): a name of its own keeps
    # a cached executable with other op_names from being served for it
    want = {"fused_group": ("fused_group", ("chol.assemble",
                                            "chol.xla_factor")),
            "panels": ("finalize_panels", ("chol.finalize",)),
            "invert_diag": ("invert_diag", ("chol.finalize",)),
            "solve_fwd": ("level_solve_fwd", ("chol.level_solve",)),
            "solve_bwd": ("level_solve_bwd", ("chol.level_solve",)),
            "stage_rhs": ("stage_rhs", ("chol.level_solve",)),
            "unstage_rhs": ("unstage_rhs", ("chol.level_solve",))}
    for kind, (name, scopes) in want.items():
        assert all(t.startswith(f"HloModule jit_{name},")
                   for t in texts[kind]), kind
        for scope in scopes:
            assert any(scope in t for t in texts[kind]), (kind, scope)
    # the segment ids come from the plan: no search, no scope of its own
    assert not any("chol.segment_ids" in t for t in texts["fused_group"])
    split = device_store._split_plan.lower(jnp.arange(6), ((0, (2,)), (2, (4,))))
    assert "chol.stage" in split.compile().as_text()
    ext = device_store._factor_ext.lower([jnp.ones(3)], np.zeros(2))
    assert "chol.finalize" in ext.compile().as_text()


def test_kernel_carries_its_name():
    panels = jax.ShapeDtypeStruct((2, 24, 8), jnp.float32)
    ext = jax.ShapeDtypeStruct((2,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, r, w: fused.fused_factor_syrk(p, r, w, interpret=True)
    )(panels, ext, ext)

    def calls(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (call,) = calls(jaxpr.jaxpr)
    assert call.params["name"] == fused.KERNEL_NAME
    assert fused.KERNEL_NAME == "fused_factor_syrk"


def test_instrumentation_docstring_names_every_span_and_counter():
    doc = counters.__doc__
    for name in HOST_SPANS + ("gmres",):
        assert counters.SPAN_PREFIX + name in doc, name
    assert "refine_solves" in doc and "``solves``" in doc


def test_gmres_cycles_and_the_solve_count_are_recorded(tmp_path,
                                                        monkeypatch):
    """An ill-conditioned SPD matrix finishes its refinement in GMRES:
    each cycle is a ``chol.gmres`` span inside ``chol.refine`` with device
    solves inside it, and ``chol.refine``'s ``solves`` argument is the
    factor's own count and the number of solves it holds."""
    monkeypatch.setattr(kops, "device_dtype", lambda: jnp.float32)
    A, b = ipm_normal_3d(8, 5e4, seed=3)
    F = cholesky(A, plan=PlanCache().get(A), device_engine=DeviceEngine())
    F.solve(b, backend="device", refine=False)      # compile the solve
    with jax.profiler.trace(str(tmp_path)):
        F.solve(b, backend="device")
    files = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))
    events = [ev for plane in ProfileData.from_file(str(files[-1])).planes
              if plane.name.startswith("/host:") for line in plane.lines
              for ev in line.events]
    of = {k: [(ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
              for ev in events if ev.name == counters.SPAN_PREFIX + k]
          for k in ("refine", "gmres", "solve")}
    (refine,) = of["refine"]
    assert of["gmres"] and all(_within(("", *g[:2]), [("", *refine[:2])])
                               for g in of["gmres"])
    assert all(any(_within(("", *s[:2]), [("", *g[:2])])
                   for s in of["solve"]) for g in of["gmres"])
    assert refine[2]["solves"] == F.refine_solves[-1] == len(of["solve"])
