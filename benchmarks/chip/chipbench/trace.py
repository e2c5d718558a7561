"""Reduction of a profiler trace to device busy time, kernel time and the
idle gaps, with the host span that was open in each gap.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a small
``Trace``: the device operations (one list per device plane) and the host
spans that the harness opens (names starting ``bench.``).  Everything after
that works on plain tuples, so the arithmetic is tested on synthetic traces.
Times are nanoseconds on the trace's own clock, which host and device
planes share.
"""
from __future__ import annotations

import pathlib
from dataclasses import dataclass, field

#: the line of a device plane that holds one event per operation run
DEVICE_OPS_LINE = "XLA Ops"
#: host spans opened by the harness
SPAN_PREFIX = "bench."
#: an operation's name is its instruction's text; this much of it is kept
NAME_CHARS = 160


@dataclass
class Op:
    name: str       # the operation's name in its program
    label: str      # its name and every string its trace event carries
    start: float    # ns
    dur: float      # ns
    device: int


@dataclass
class Trace:
    ops: list = field(default_factory=list)      # [Op]
    spans: list = field(default_factory=list)    # [(name, start, dur)]
    devices: int = 0

    def window(self) -> tuple:
        """(start, end) of the ``bench.window`` span, in ns."""
        for name, start, dur in self.spans:
            if name == SPAN_PREFIX + "window":
                return start, start + dur
        raise ValueError("the trace holds no bench.window span")


def load(directory) -> Trace:
    """Read the newest trace file under ``directory``."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(str(files[-1]))
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = tr.devices
            found = False
            for line in plane.lines:
                if line.name != DEVICE_OPS_LINE:
                    continue
                found = True
                for ev in line.events:
                    stats = dict(ev.stats)
                    words = [ev.name] + [str(v) for v in stats.values()
                                         if isinstance(v, str)]
                    tr.ops.append(Op(ev.name[:NAME_CHARS], " ".join(words),
                                     float(ev.start_ns),
                                     float(ev.duration_ns), dev))
            if found:
                tr.devices += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        tr.spans.append((ev.name, float(ev.start_ns),
                                         float(ev.duration_ns)))
    return tr


def _union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(ops, t0: float, t1: float, device: int | None = None) -> list:
    return [(max(o.start, t0), min(o.start + o.dur, t1)) for o in ops
            if (device is None or o.device == device)
            and o.start < t1 and o.start + o.dur > t0]


def busy_seconds(tr: Trace) -> float:
    """Seconds in which some operation ran, inside the window, averaged
    over the device planes."""
    t0, t1 = tr.window()
    if not tr.devices:
        return 0.0
    tot = 0.0
    for d in range(tr.devices):
        tot += sum(e - s for s, e in _union(_clip(tr.ops, t0, t1, d)))
    return tot / tr.devices * 1e-9


def window_seconds(tr: Trace) -> float:
    t0, t1 = tr.window()
    return (t1 - t0) * 1e-9


def matching_seconds(tr: Trace, *words: str) -> tuple:
    """(seconds, events) of the operations whose label holds every one of
    ``words``, inside the window, summed over devices."""
    t0, t1 = tr.window()
    hits = [o for o in tr.ops if all(w in o.label for w in words)]
    return (sum(e - s for s, e in _clip(hits, t0, t1)) * 1e-9, len(hits))


def top_ops(tr: Trace, k: int = 10) -> list:
    """The ``k`` operation names that took most device time in the window:
    ``[[name, seconds], ...]``, summed over devices."""
    t0, t1 = tr.window()
    tot: dict = {}
    for o in tr.ops:
        s, e = max(o.start, t0), min(o.start + o.dur, t1)
        if e > s:
            tot[o.name] = tot.get(o.name, 0.0) + (e - s) * 1e-9
    return [[n, v] for n, v in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(tr: Trace, k: int = 10) -> list:
    """The ``k`` longest gaps in which device 0 ran nothing inside the
    window, each named by the innermost harness span open at its middle:
    ``[[span, seconds], ...]``."""
    t0, t1 = tr.window()
    busy = _union(_clip(tr.ops, t0, t1, 0))
    gaps, at = [], t0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        open_ = [(st, -d, name) for name, st, d in tr.spans
                 if st <= mid <= st + d]
        name = max(open_)[2] if open_ else "none"
        out.append([name, (e - s) * 1e-9])
    out.sort(key=lambda g: -g[1])
    return out[:k]
