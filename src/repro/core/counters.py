"""The program's instrumentation: build counters, host spans, device scopes.

Build counters
--------------
Every expensive symbolic-phase artifact — symbolic analysis, scatter plans,
level schedules, device index plans, fill plans — bumps a named counter when
it is *built* (never when a cached copy is returned).  The serving layer's
"repeat patterns skip analysis entirely" guarantee is enforced against these
counters: a cache hit must leave every one of them unchanged (see
tests/test_plan_cache.py and repro.launch.serve).

Deliberately a process-global registry (not per-object): the point is to
catch rebuilds wherever they happen, including paths that accidentally drop
a cached SymbolicFactor and re-analyze from scratch.

Spans and scopes
----------------
A user who profiles their own loop with ``jax.profiler.trace(dir)`` finds
the solver's layers by name in the trace, every name starting with
``SPAN_PREFIX``.  Host spans (``span``) are ``jax.profiler.TraceAnnotation``
s: they land on the host thread that ran them, on the clock the device
planes share, and nest by time (a span's parent is the span enclosing it).
Off the profiler one costs about a microsecond, so none sits inside a
per-supernode, per-entry or per-column loop:

    chol.factor    one device-resident factorization (the children below)
    chol.fill      a cached plan's value fill (``CachedPlan.fill_storage``)
    chol.stage     host-side staging: the index plan's concatenation and
                   upload, each level's chunk gather and upload, the solve's
                   auxiliary upload
    chol.dispatch  one device program dispatch: one span per increment of
                   ``DeviceEngine.stats["device_calls"]``
    chol.readback  one device-to-host transfer, including the wait for the
                   device: one span per increment of ``stats["transfers_out"]``
    chol.solve     one level-scheduled device solve
    chol.refine    one refined solve; its ``chol.solve`` children apply the
                   factor, the rest (its self time) is host residual work.
                   Its argument ``solves`` counts the times it applied the
                   factor, the raw solve included; the factor keeps the same
                   count per refined solve in ``CholeskyFactor.refine_solves``,
                   beside the residual trajectories of ``ir_history``
    chol.gmres     one GMRES cycle inside ``chol.refine`` (reached where
                   stationary refinement stalls); its ``chol.solve`` children
                   apply the factor, its self time is the orthogonalization,
                   the least-squares update and the true residuals

Device scopes (``scope``) are ``jax.named_scope`` s applied while a program
is traced, so they cost nothing at run time.  They land in each operation's
``op_name`` metadata (the trace viewer's ``tf_op``); XLA may fuse operations
of two neighbouring scopes under one of them.  JAX's persistent compilation
cache keys a program by its computation without that metadata, so a program
whose scopes change while its computation and its jitted function's name do
not is served the executable cached before, with the op_names it had then:
each scoped program is jitted under a name of its own, which the trace's
``XLA Modules`` line shows.  The scopes:

    chol.assemble     a group's pool gather, update sums, panel gather, pack
    chol.xla_factor   the XLA factor of a group program (POTRF+TRSM+SYRK)
    chol.finalize     the device factor's concatenation, its panel gathers
                      and diagonal inversions
    chol.level_solve  the substitution levels and the RHS (un)staging
    chol.stage        the device-side split of the staged index plan

The fused Pallas kernel is a custom call named ``fused_factor_syrk``.
"""
from __future__ import annotations

from collections import defaultdict

import jax

COUNTS: dict = defaultdict(int)

#: counter names bumped by the plan/analysis builders (one per artifact kind)
BUILD_KINDS = (
    "symbolic_analyze",   # repro.core.symbolic.symbolic_analyze
    "scatter_plan",       # repro.core.relind.build_scatter_plan
    "schedule",           # repro.core.schedule.build_schedule
    "device_plan",        # repro.core.device_store.build_device_plan
    "fill_plan",          # repro.core.plan_cache.build_fill_plan
)

#: first characters of every span and scope name
SPAN_PREFIX = "chol."


def bump(name: str) -> None:
    COUNTS[name] += 1


def snapshot() -> dict:
    """Copy of the current counters (for later ``delta``)."""
    return dict(COUNTS)


def delta(before: dict) -> dict:
    """Counters that changed since ``before`` (name -> increment)."""
    return {
        k: v - before.get(k, 0) for k, v in COUNTS.items() if v != before.get(k, 0)
    }


def span(name: str, **meta):
    """Host span ``chol.<name>`` (a context manager); ``meta`` becomes the
    event's arguments in the trace."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **meta)


def scope(name: str):
    """Device scope ``chol.<name>`` for the operations traced inside it (a
    context manager, used in jitted bodies)."""
    return jax.named_scope(SPAN_PREFIX + name)
