"""The solver's own spans and scopes in a traced run.

The solver opens host spans and traces device scopes named ``chol.<layer>``
(``repro.core.counters``).  ``chipbench.trace.load`` keeps neither: it reads
the harness's ``bench.`` spans, and ``jax.profiler.ProfileData`` does not
show the ``op_name`` a program gave each operation (the trace viewer's
``tf_op``), which sits in the event's metadata.  ``attach`` reads both from
the same ``.xplane.pb`` and lays them on a loaded ``Trace``:

    tr.program_spans  [(name, start, dur)] of the host spans named chol.*
    tr.op_names       the op_name of each of ``tr.ops``, in order ("" where
                      the operation carries none)

A trace of a solver without spans or scopes gets empty lists, and every
function below then reads zero.  Times are nanoseconds on the trace's own
clock, as in ``chipbench.trace``.
"""
from __future__ import annotations

import bisect
import pathlib

from chipbench import trace as tracemod
from chipbench.trace import DEVICE_OPS_LINE, Trace

PREFIX = "chol."
#: device scopes of the solver that, with the kernel, should cover the
#: device's busy time
SCOPES = ("chol.assemble", "chol.xla_factor", "chol.finalize",
          "chol.level_solve", "chol.stage")
#: the fused kernel's instruction: a custom call named after its pallas_call
KERNEL = ("fused_factor_syrk", "custom-call")


# -- the trace file's wire format (XSpace, tsl/profiler/protobuf/xplane.proto)
def _varint(b, i: int) -> tuple:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field, None for fixed-width ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"unsupported wire type {kind}")
        yield key >> 3, v


def _device_op_names(raw: bytes) -> list:
    """The op_name of every event on the ``XLA Ops`` line of each device
    plane, in the order ``chipbench.trace.load`` reads them, each paired
    with the event's duration in ps: ``[(op_name, duration_ps)]``."""
    out: list = []
    for f, plane in _fields(memoryview(raw)):
        if f != 1:                                  # XSpace.planes
            continue
        name, lines, emeta, smeta = "", [], [], []
        for pf, v in _fields(plane):
            if pf == 2:                             # XPlane.name
                name = bytes(v).decode()
                if not name.startswith("/device:") or "CPU" in name:
                    break
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                emeta.append(v)
            elif pf == 5:
                smeta.append(v)
        else:
            out += _plane_op_names(lines, emeta, smeta)
    return out


def _plane_op_names(lines, emeta, smeta) -> list:
    stat_names = {}
    for entry in smeta:                             # map<int64, XStatMetadata>
        for ef, v in _fields(entry):
            if ef == 2:
                sid = sname = None
                for mf, mv in _fields(v):
                    if mf == 1:
                        sid = mv
                    elif mf == 2:
                        sname = bytes(mv).decode()
                stat_names[sid] = sname
    tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
    op_name = {}
    for entry in emeta:                             # map<int64, XEventMetadata>
        for ef, v in _fields(entry):
            if ef != 2:
                continue
            mid, found = None, ""
            for mf, mv in _fields(v):
                if mf == 1:
                    mid = mv
                elif mf == 5:                       # XEventMetadata.stats
                    stat = dict(_fields(mv))
                    if stat.get(1) != tf_op:
                        continue
                    if 5 in stat:                   # str_value
                        found = bytes(stat[5]).decode()
                    elif 7 in stat:                 # ref_value
                        found = stat_names.get(stat[7]) or ""
            op_name[mid] = found
    out = []
    for line in lines:
        fields = list(_fields(line))
        if not any(f == 2 and bytes(v).decode() == DEVICE_OPS_LINE
                   for f, v in fields):
            continue
        for f, ev in fields:
            if f == 4:                              # XLine.events
                e = dict(_fields(ev))
                out.append((op_name.get(e.get(1), ""), e.get(3, 0)))
    return out


# -- attaching them to a loaded trace ---------------------------------------
def attach(tr: Trace, directory) -> bool:
    """Read the program's spans and the op_names of ``tr.ops`` from the
    newest trace file under ``directory``.  False, and nothing attached,
    where that file is not the one ``tr`` was loaded from."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return False
    raw = files[-1].read_bytes()
    data = ProfileData.from_serialized_xspace(raw)
    spans, window = [], None
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append((ev.name, float(ev.start_ns),
                                  float(ev.duration_ns)))
                elif ev.name == tracemod.SPAN_PREFIX + "window":
                    window = (float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns))
    names = _device_op_names(raw)
    if (window is None or window != tr.window() or len(names) != len(tr.ops)
            or any(abs(o.dur - ps * 1e-3) > 1.0
                   for o, (_n, ps) in zip(tr.ops, names))):
        return False
    tr.program_spans = spans
    tr.op_names = [n for n, _ps in names]
    return True


def of_run(run):
    """The run's trace with the program's spans and scopes attached, or
    None where the run was not traced or its trace file is not found."""
    tr = run.trace
    if tr is None:
        return None
    if not hasattr(tr, "program_spans"):
        from chipbench import harness

        if not attach(tr, harness.OUT_DIR / "trace"):
            tr.program_spans = None             # read once per run
    return tr if tr.program_spans is not None else None


# -- reductions ---------------------------------------------------------------
def _in_window(tr: Trace, ivs) -> list:
    t0, t1 = tr.window()
    return [(max(s, t0), min(e, t1)) for s, e in ivs if s < t1 and e > t0]


def _length(ivs) -> float:
    return sum(e - s for s, e in tracemod._union(ivs))


def scope_seconds(tr: Trace, *words: str, kernel: bool = False) -> tuple:
    """(seconds, events): the time in which some operation traced under one
    of the scopes ``words`` ran (or, with ``kernel``, the fused kernel),
    inside the window, the union taken per device and summed over devices,
    so a ``while`` and the fusions inside it count once."""
    hits = [(o, n) for o, n in zip(tr.ops, tr.op_names)
            if any(w in n for w in words)
            or (kernel and all(k in o.label for k in KERNEL))]
    total = 0.0
    for d in range(tr.devices):
        total += _length(_in_window(
            tr, [(o.start, o.start + o.dur) for o, _n in hits
                 if o.device == d]))
    return total * 1e-9, len(hits)


def span_seconds(tr: Trace, name: str, self_time: bool = False) -> tuple:
    """(seconds, spans): the summed in-window duration of the program's
    spans named ``name``; with ``self_time``, less the union of the
    program's spans nested in each."""
    own = [(s, s + d) for n, s, d in tr.program_spans if n == name]
    every = sorted((s, s + d) for _n, s, d in tr.program_spans)
    starts = [s for s, _e in every]
    total = 0.0
    for s, e in own:
        ivs = _in_window(tr, [(s, e)])
        if not ivs:
            continue
        total += _length(ivs)
        if self_time:
            lo, hi = bisect.bisect_left(starts, s), bisect.bisect_right(
                starts, e)
            inner = [iv for iv in every[lo:hi]
                     if iv[1] <= e and iv != (s, e)]
            total -= _length(_in_window(tr, inner))
    return total * 1e-9, len(own)


def scope_ms(run, word: str):
    """Milliseconds per step under the device scope ``word``; None where the
    run was not traced or no operation carries the scope."""
    tr = of_run(run)
    if tr is None:
        return None
    seconds, events = scope_seconds(tr, word)
    return 1e3 * seconds / run.steps if events else None


def span_ms(run, name: str, self_time: bool = False):
    """Milliseconds per step in the program's host spans ``name``; None
    where the run was not traced or the program opened no such span."""
    tr = of_run(run)
    if tr is None:
        return None
    seconds, count = span_seconds(tr, name, self_time)
    return 1e3 * seconds / run.steps if count else None


def idle_gaps(tr: Trace, k: int = 10, longer_than: float = 0.0) -> list:
    """``chipbench.trace.idle_gaps`` with each gap named by the innermost
    span open at its middle among the harness's spans and the program's,
    keeping only gaps longer than ``longer_than`` seconds.  The longest are
    taken before any is named, so many spans cost little."""
    t0, t1 = tr.window()
    gaps, at = [], t0
    for s, e in tracemod._union(tracemod._clip(tr.ops, t0, t1, 0)):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    spans = tr.spans + tr.program_spans
    out = []
    for s, e in gaps[:k]:
        if (e - s) * 1e-9 <= longer_than:
            break
        mid = 0.5 * (s + e)
        open_ = [(st, -d, name) for name, st, d in spans
                 if st <= mid <= st + d]
        out.append([max(open_)[2] if open_ else "none", (e - s) * 1e-9])
    return out


def unscoped_ops(tr: Trace, k: int = 10) -> list:
    """The ``k`` operation names with the most device time in the window
    that no operation under a scope of ``SCOPES`` or the kernel overlaps:
    ``[[name, op_name, seconds], ...]``, summed over devices.  (A ``while``
    often carries no op_name; the scoped fusions inside it cover it.)"""

    def covered(o, n):
        return (any(w in n for w in SCOPES)
                or all(w in o.label for w in KERNEL))

    tot: dict = {}
    for d in range(tr.devices):
        mine = [(o, n) for o, n in zip(tr.ops, tr.op_names) if o.device == d]
        cover = tracemod._union(_in_window(
            tr, [(o.start, o.start + o.dur) for o, n in mine
                 if covered(o, n)]))
        starts = [cs for cs, _ce in cover]
        for o, n in mine:
            own = [] if covered(o, n) else _in_window(
                tr, [(o.start, o.start + o.dur)])
            if not own:
                continue
            (s, e), = own
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            inside = 0.0
            for cs, ce in cover[i:bisect.bisect_left(starts, e)]:
                inside += max(0.0, min(e, ce) - max(s, cs))
            if e - s > inside:
                key = (o.name, n)
                tot[key] = tot.get(key, 0.0) + (e - s - inside) * 1e-9
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, n, v] for (name, n), v in top]
