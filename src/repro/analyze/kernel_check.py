"""Pass 3 — kernel static analysis: prove each bucket's fused-kernel launch
fits the hardware before it runs.

The fused Pallas kernel (repro.kernels.fused) is launched once per distinct
bucket shape; its correctness and VMEM footprint are decided entirely by
``(Lp, Wp)`` — all statically known from the schedule.  This pass mirrors
the argument in kernels/DESIGN.md as *checked* invariants:

  * SYRK tile divisibility — ``tu = syrk_tile(mp)`` must divide ``mp``
    exactly: the grid has ``mp // tu`` column tiles and a non-dividing tile
    would leave update columns unwritten (wrong factor, not just slow);
  * 128-lane alignment     — the "fused" bucket family promises
    ``gcd(mp, 128) >= 8`` (both dims powers of two), keeping SYRK tiles
    sublane-aligned on the MXU; a family that breaks its own promise is an
    error, an unaligned tile on other families is a perf warning;
  * VMEM budget            — the kernel's scoped footprint per grid step
    (``kernels.fused.bucket_vmem``: the in/out BlockSpec blocks, double-
    buffered by the pipeline, the ``(Lp, Wp)`` scratch accumulator, and the
    values the body keeps live).  Estimated bytes are compared against a
    *target* cap: exceeding an explicitly requested cap (``--vmem-cap``) is
    an ERROR — the launch would be refused — while exceeding the 16 MiB
    reference (the kernel's compiled scoped limit) is a WARNING plus a
    headroom metric: the engine routes such buckets to the XLA program;
  * cost-model sanity      — ``group_flop_stats`` must satisfy
    true <= masked <= padded per group (the waste accounting the benchmarks
    and the masked-kernel design rely on).

Returns (findings, metrics); metrics feed ``BENCH_analyze.json`` (VMEM
headroom and waste ratios per bucket).
"""
from __future__ import annotations

import math

from repro.analyze.findings import Finding
from repro.kernels.fused import VMEM_LIMIT, bucket_vmem, syrk_tile

_P = "kernel"

#: reference per-core scoped-VMEM budget (bytes): the limit the fused kernel
#: is compiled under, ``kernels.fused.VMEM_LIMIT`` — the TPU compiler's
#: default scoped limit on v5e (16 MiB; the core has 128 MiB of VMEM).
#: ``bucket_vmem`` is the estimate the engine routes by: on every bucket
#: bisected on the v5e compiler it is above the smallest limit the compiler
#: accepts, by 1.13x-1.33x near this limit (table in kernels/DESIGN.md
#: "Known limits").
#: Exceeding the reference is a *warning* (the engine sends such buckets
#: to the XLA group program) unless the caller pins an explicit cap.
REFERENCE_VMEM = VMEM_LIMIT

__all__ = ["REFERENCE_VMEM", "bucket_vmem", "check_bucket", "check_kernels"]


def check_bucket(Lp: int, Wp: int, *, family: str | None = None,
                 vmem_cap: int | None = None,
                 reference: int = REFERENCE_VMEM, nb: int = 128) -> list:
    """All static checks for one bucket shape."""
    out: list = []
    loc = f"bucket ({Lp}, {Wp})"
    mp = Lp - Wp
    if mp < 0 or Wp <= 0:
        return [Finding("error", _P, "bucket-shape", loc,
                        "buckets satisfy Lp >= Wp > 0")]
    if mp:
        tu = syrk_tile(mp)
        if tu <= 0 or mp % tu != 0:
            out.append(Finding(
                "error", _P, "syrk-tile-divide", loc,
                "the SYRK tile width divides the bucket tail exactly "
                "(mp // tu grid tiles cover every update column)",
                f"mp={mp}, tu={tu}",
            ))
        aligned = math.gcd(mp, 128) >= 8
        if not aligned and family == "fused":
            out.append(Finding(
                "error", _P, "mxu-alignment", loc,
                "the fused bucket family keeps gcd(mp, 128) >= 8 "
                "(the checked form of kernels/DESIGN.md's argument)",
                f"gcd({mp}, 128) = {math.gcd(mp, 128)}",
            ))
        elif not aligned or tu % 8 != 0:
            out.append(Finding(
                "warning", _P, "unaligned-syrk-tile", loc,
                "SYRK tiles are sublane-aligned (multiples of 8)",
                f"mp={mp} falls back to tu={tu}",
            ))
    if Wp % 8 != 0 or Lp % 8 != 0:
        out.append(Finding(
            "warning", _P, "sublane-pad", loc,
            "bucket dims are multiples of the 8-row sublane "
            "(the compiler pads each dispatch otherwise)",
        ))
    if Wp >= 128 and Wp % min(nb, Wp) != 0:
        out.append(Finding(
            "warning", _P, "ragged-slab", loc,
            "the factor loop's nb-column slabs tile Wp evenly",
            f"Wp={Wp}, nb={min(nb, Wp)}",
        ))
    est = bucket_vmem(Lp, Wp)
    mib = est["vmem_bytes"] / 2 ** 20
    if vmem_cap is not None and est["vmem_bytes"] > vmem_cap:
        out.append(Finding(
            "error", _P, "vmem-overflow", loc,
            "the kernel's blocks + scratch fit the target's VMEM cap",
            f"estimate {mib:.1f} MiB > cap {vmem_cap / 2 ** 20:.1f} MiB "
            "— this launch OOMs on the requested target",
        ))
    elif est["vmem_bytes"] > reference:
        out.append(Finding(
            "warning", _P, "vmem-reference", loc,
            "the kernel's blocks + scratch fit the 16 MiB reference "
            "TPU VMEM budget",
            f"estimate {mib:.1f} MiB > reference "
            f"{reference / 2 ** 20:.0f} MiB — validate (or split the "
            "bucket / lower cell_budget) before running on hardware",
        ))
    return out


def check_kernels(sym, sched, *, family: str | None = None,
                  vmem_cap: int | None = None,
                  reference: int = REFERENCE_VMEM) -> tuple[list, dict]:
    """Static kernel checks + waste accounting for one schedule.

    Returns ``(findings, metrics)``; metrics carries the per-bucket VMEM
    table and the schedule's padded/masked flop-waste ratios."""
    from repro.core.schedule import group_flop_stats

    out: list = []
    buckets = sorted({(bg.Lp, bg.Wp) for lg in sched.groups for bg in lg})
    table = []
    for Lp, Wp in buckets:
        out += check_bucket(Lp, Wp, family=family, vmem_cap=vmem_cap,
                            reference=reference)
        est = bucket_vmem(Lp, Wp)
        est["vmem_mib"] = round(est["vmem_bytes"] / 2 ** 20, 2)
        est["headroom_ref_mib"] = round((reference - est["vmem_bytes"]) / 2 ** 20, 2)
        table.append(est)
    stats = group_flop_stats(sym, sched)
    for g in stats["groups"]:
        if not (g["true"] <= g["masked"] <= g["padded"]):
            out.append(Finding(
                "error", _P, "cost-model",
                f"level {g['level']} bucket ({g['Lp']}, {g['Wp']})",
                "column-op costs satisfy true <= masked <= padded",
                f"true={g['true']}, masked={g['masked']}, "
                f"padded={g['padded']}",
            ))
    metrics = {
        "buckets": table,
        "max_vmem_mib": max((b["vmem_mib"] for b in table), default=0.0),
        "padded_waste": stats["padded_waste"],
        "masked_waste": stats["masked_waste"],
    }
    return out, metrics
