"""The solver's spans and scopes in a trace (``chipbench.spans``): scope
time counted once where a loop overlaps its fusions, span self time, window
clipping, idle gaps named by the program's spans, the op_names read from a
trace file's event metadata, and the nine readers that report them."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import harness, spans, trace  # noqa: E402
from chipbench.trace import Op, Trace  # noqa: E402

NESTED = "jit(f)/chol.assemble/chol.inner/while:"


def _synthetic():
    """Window [100, 1100) ns, device 0, two steps' worth of spans:

    - a ``while`` [100, 400) under a scope nested in assemble, overlapping
      its fusion [150, 350), a pack [400, 450) under assemble, an XLA
      factor [450, 550), the kernel [550, 600), a level solve [900, 1000),
      and finalize [1050, 1200) that runs past the window's end;
    - the host spans: a fill [100, 110), then factor [110, 600) with a
      stage and two dispatches inside it, then refine [610, 1150), past
      the window's end, holding a solve [850, 1050) with a readback
      [1020, 1050) inside it."""
    ops = [(Op("while.4", "while.4", 100, 300, 0), NESTED),
           (Op("fusion.7", "fusion.7", 150, 200, 0), NESTED[:-1]
            + "/body/gather:"),
           (Op("fusion.8", "fusion.8", 400, 50, 0),
            "jit(f)/chol.assemble/gather:"),
           (Op("fusion.9", "fusion.9", 450, 100, 0),
            "jit(f)/chol.xla_factor/dot_general:"),
           (Op("fused_factor_syrk.1", "fused_factor_syrk.1 = custom-call()",
               550, 50, 0), ""),
           (Op("fusion.3", "fusion.3", 900, 100, 0),
            "jit(f)/chol.level_solve/dot_general:"),
           (Op("fusion", "fusion", 1050, 150, 0),
            "jit(f)/chol.finalize/gather:"),
           (Op("copy", "copy", 1000, 20, 0), "")]
    tr = Trace(ops=[o for o, _n in ops],
               spans=[("bench.window", 100, 1000), ("bench.factor", 50, 550),
                      ("bench.solve", 600, 500)],
               devices=1)
    tr.op_names = [n for _o, n in ops]
    tr.program_spans = [("chol.fill", 100, 10), ("chol.factor", 110, 490),
                        ("chol.stage", 110, 20), ("chol.dispatch", 130, 10),
                        ("chol.dispatch", 140, 10),
                        ("chol.refine", 610, 540), ("chol.solve", 850, 200),
                        ("chol.readback", 1020, 30)]
    return tr


def test_scope_counts_a_loop_and_its_fusions_once():
    tr = _synthetic()
    assert spans.scope_seconds(tr, "chol.inner") == (
        pytest.approx(300e-9), 2)
    # assemble holds the nested scope: [100, 400) and [400, 450)
    assert spans.scope_seconds(tr, "chol.assemble")[0] == pytest.approx(
        350e-9)
    assert spans.scope_seconds(tr, kernel=True) == (pytest.approx(50e-9), 1)
    assert spans.scope_seconds(tr, "chol.nothing") == (0.0, 0)


def test_scope_time_is_clipped_to_the_window():
    tr = _synthetic()
    # finalize runs [1050, 1200); the window ends at 1100
    assert spans.scope_seconds(tr, "chol.finalize")[0] == pytest.approx(
        50e-9)
    # busy [100, 600), [900, 1020), [1050, 1100): all but the copy
    covered = spans.scope_seconds(tr, *spans.SCOPES, kernel=True)[0]
    assert covered == pytest.approx(650e-9)
    assert trace.busy_seconds(tr) == pytest.approx(670e-9)


def test_span_self_time_subtracts_nested_children():
    tr = _synthetic()
    # refine is clipped to [610, 1100); the solve [850, 1050) nests in it
    assert spans.span_seconds(tr, "chol.refine") == (pytest.approx(490e-9),
                                                      1)
    assert spans.span_seconds(tr, "chol.refine", self_time=True)[0] == (
        pytest.approx(290e-9))
    assert spans.span_seconds(tr, "chol.factor", self_time=True)[0] == (
        pytest.approx(450e-9))
    assert spans.span_seconds(tr, "chol.dispatch") == (pytest.approx(20e-9),
                                                        2)
    tr.program_spans.append(("chol.fill", 20, 40))     # before the window
    assert spans.span_seconds(tr, "chol.fill") == (pytest.approx(10e-9), 2)


def test_idle_gaps_are_named_by_the_innermost_program_span():
    tr = _synthetic()
    gaps = spans.idle_gaps(tr)
    # device 0 idles [600, 900) in refine and [1020, 1050) in the readback
    assert gaps[0] == ["chol.refine", pytest.approx(300e-9)]
    assert gaps[1] == ["chol.readback", pytest.approx(30e-9)]


def test_idle_gaps_without_program_spans_are_the_harness_gaps():
    tr = _synthetic()
    tr.program_spans = []
    assert spans.idle_gaps(tr) == trace.idle_gaps(tr)


def test_unscoped_ops_lists_what_no_scope_covers():
    tr = _synthetic()
    assert spans.unscoped_ops(tr) == [["copy", "", pytest.approx(20e-9)]]


# -- a trace file, written in the profiler's wire format --------------------
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, lines, events, stats=()):
    """XPlane: ``lines`` [(name, timestamp ns, [(metadata id, offset ps,
    duration ps)])], ``events`` {id: (name, [(stat id, str or ref)])},
    ``stats`` {id: name} (the stat metadata)."""
    fields = [(2, name)]
    for i, (lname, ts, evs) in enumerate(lines):
        fields.append((3, _msg((1, i), (2, lname), (3, ts),
                               *[(4, _msg((1, m), (2, off), (3, dur)))
                                 for m, off, dur in evs])))
    for mid, (ename, st) in events.items():
        meta = [(1, mid), (2, ename)] + [
            (5, _msg((1, sid), (5 if isinstance(v, str) else 7, v)))
            for sid, v in st]
        fields.append((4, _msg((1, mid), (2, _msg(*meta)))))
    for sid, sname in dict(stats).items():
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    return _msg(*fields)


def _write_trace(directory: pathlib.Path, with_program: bool) -> None:
    tf_op = ([(10, NESTED)] if with_program else [])
    device = _plane(
        "/device:TPU:0",
        [("XLA Modules", 1000, [(3, 0, 900_000)]),
         ("XLA Ops", 1000, [(1, 0, 500_000), (2, 100_000, 200_000)])],
        {1: ("%while.4 = while()", tf_op),
         2: ("%fusion.7 = fusion()", [(10, 11)] if with_program else []),
         3: ("jit_f(1)", [])},
        {10: "tf_op", 11: "jit(f)/chol.assemble/gather:"})
    names = {1: ("bench.window", []), 2: ("chol.dispatch", []),
             3: ("chol.readback", [])}
    evs = [(1, 0, 2_000_000)]
    if with_program:
        evs += [(2, 50_000, 300_000), (3, 500_000, 600_000)]
    host = _plane("/host:CPU", [("python3", 900, evs)], names)
    directory.mkdir(parents=True, exist_ok=True)
    (directory / "run.xplane.pb").write_bytes(_msg((1, device), (1, host)))


def test_attach_reads_op_names_and_spans_from_the_file(tmp_path):
    _write_trace(tmp_path, with_program=True)
    tr = trace.load(tmp_path)
    assert [o.dur for o in tr.ops] == [500.0, 200.0]
    assert spans.attach(tr, tmp_path)
    assert tr.op_names == [NESTED, "jit(f)/chol.assemble/gather:"]
    assert tr.program_spans == [("chol.dispatch", 950.0, 300.0),
                                ("chol.readback", 1400.0, 600.0)]
    assert spans.scope_seconds(tr, "chol.inner") == (
        pytest.approx(500e-9), 1)


def test_attach_refuses_another_runs_file(tmp_path):
    _write_trace(tmp_path / "a", with_program=True)
    tr = trace.load(tmp_path / "a")
    tr.spans = [("bench.window", 0.0, 10.0)]
    assert not spans.attach(tr, tmp_path / "a")
    assert not hasattr(tr, "program_spans")


# -- the readers ----------------------------------------------------------------
#: reader -> its value on ``_synthetic`` in ms per step (two steps)
READERS = {
    "assemble_ms.refactor": 350e-6 / 2,
    "xla_group_ms.refactor": 100e-6 / 2,
    "finalize_ms.refactor": 50e-6 / 2,
    "level_solve_ms.refactor": 100e-6 / 2,
    "fill_ms.refactor": 10e-6 / 2,
    "staging_ms.refactor": 20e-6 / 2,
    "dispatch_ms.refactor": 20e-6 / 2,
    "readback_ms.refactor": 30e-6 / 2,
    "refine_ms.refactor": 290e-6 / 2,
}


def _reader(name):
    spec = harness.Spec("x", 1, {}, {}, {}, [], [], BENCH)
    return spec.reader(name)


def _run(tr):
    return harness.RunRecord(steps=2, window_s=1e-6, counters={},
                             refine_steps=[], peak=None, trace=tr)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_value_and_none_without_a_trace(name, tmp_path, monkeypatch):
    read = _reader(name)
    assert read(_run(_synthetic())) == pytest.approx(READERS[name])
    assert read(_run(None)) is None
    # a solver without spans or scopes: the trace file holds none of them
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    _write_trace(tmp_path / "trace" / "cell", with_program=False)
    assert read(_run(trace.load(tmp_path / "trace" / "cell"))) is None
