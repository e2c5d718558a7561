"""Device solves that refinement applied per step: the ``solves`` argument
the program gives each ``chol.refine`` span (``refine_solve``'s count of
the times it applied the factor, the raw solve included; also kept in
``CholeskyFactor.refine_solves``), summed over the spans that start inside
the window.  None where the run was not traced or no span carries the
argument (a program that does not count them)."""
import pathlib

SPAN = "chol.refine"
ARG = "solves"
#: the harness's span around the measured window
WINDOW = "bench.window"


def counts(directory, window) -> list:
    """``ARG`` of every ``SPAN`` that starts inside ``window`` (t0, t1), in
    ns, read from the newest trace file under ``directory``; empty where
    that file is not the run's (its ``WINDOW`` span is another)."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return []
    data = ProfileData.from_file(str(files[-1]))
    t0, t1 = window
    events = [ev for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    if not any(ev.name == WINDOW and (ev.start_ns, ev.start_ns
                                      + ev.duration_ns) == (t0, t1)
               for ev in events):
        return []                       # another run's file
    out = []
    for ev in events:
        if ev.name == SPAN and t0 <= ev.start_ns < t1:
            v = dict(ev.stats).get(ARG)
            if v is not None:
                out.append(int(v))
    return out


def read(run):
    from chipbench import harness

    if run.trace is None:
        return None
    found = counts(harness.OUT_DIR / "trace", run.trace.window())
    return sum(found) / run.steps if found else None
