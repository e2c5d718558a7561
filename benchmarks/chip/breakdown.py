#!/usr/bin/env python3
"""Where a traced run's window went, by the solver's own spans and scopes.

    python3 benchmarks/chip/breakdown.py --workload lap2d5_g512.refactor

Reads the trace that ``run.py --trace 1`` left for the cell under
``benchmarks/chip/.out/trace/`` and prints one JSON object: the device's
busy time, the device time under each scope and in the kernel, the share of
the busy time their union covers, the host time in each span (and its self
time), the idle gaps longer than 10 ms totalled by the innermost span open
in them, the longest of those gaps, and the longest operations outside
every scope.  A trace of a solver without spans or scopes reads zeros.
"""
import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

#: idle gaps shorter than this are not totalled
GAP_S = 0.010


def breakdown(tr) -> dict:
    from chipbench import spans, trace

    busy = trace.busy_seconds(tr)
    scopes = {w: spans.scope_seconds(tr, w)[0] for w in spans.SCOPES}
    scopes["kernel"] = spans.scope_seconds(tr, kernel=True)[0]
    covered = spans.scope_seconds(tr, *spans.SCOPES, kernel=True)[0]
    host = {}
    for name in sorted({n for n, _s, _d in tr.program_spans}):
        total, count = spans.span_seconds(tr, name)
        own, _ = spans.span_seconds(tr, name, self_time=True)
        host[name] = {"seconds": total, "self_seconds": own, "spans": count}
    gaps = spans.idle_gaps(tr, k=len(tr.ops) + 1, longer_than=GAP_S)
    by_name: dict = {}
    for name, sec in gaps:
        c = by_name.setdefault(name, {"gaps": 0, "seconds": 0.0})
        c["gaps"] += 1
        c["seconds"] += sec
    t0, t1 = tr.window()
    steps = sum(1 for n, s, _d in tr.spans
                if n == "bench.factor" and t0 <= s < t1)
    return {"window_s": trace.window_seconds(tr), "busy_s": busy,
            "steps": steps, "scope_s": scopes, "covered_s": covered,
            "covered_share": covered / busy if busy else None,
            "span_s": host, "gaps_over_10ms": by_name,
            "longest_gaps": gaps[:15],
            "unscoped_ops": spans.unscoped_ops(tr, 15)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    from chipbench import harness, spans, trace

    where = harness.OUT_DIR / "trace" / args.workload
    tr = trace.load(where)
    if not spans.attach(tr, where):
        print(f"breakdown.py: the trace under {where} could not be read",
              file=sys.stderr)
        return 1
    print(json.dumps(breakdown(tr)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
