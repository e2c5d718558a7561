"""The benchmark's copy of the work count agrees with the solver's."""
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import matrices, work  # noqa: E402

#: every generator file, at the size its ``TINY`` gives
TINY = [{"generator": p.stem, **matrices.generator(p.stem).TINY}
        for p in sorted((BENCH / "generators").glob("*.py"))]


def _groups(sym, sched):
    for lvl in sched.groups:
        for bg in lvl:
            ext = [(sym.width(int(s)),
                    int(sym.rows[int(s)].shape[0]) - sym.width(int(s)))
                   for s in bg.ids]
            yield ext


@pytest.mark.parametrize("bucket", ["batch", "fused"])
@pytest.mark.parametrize("cfg", TINY, ids=lambda c: c["generator"])
def test_column_ops_equal_group_flop_stats(cfg, bucket):
    from repro.core import symbolic_pipeline
    from repro.core.schedule import build_schedule, group_flop_stats

    sym, _ = symbolic_pipeline(matrices.matrix(cfg))
    sched = build_schedule(sym, bucket=bucket)
    ops = [sum(work.supernode_column_ops(w, m) for w, m in ext)
           for ext in _groups(sym, sched)]
    stats = group_flop_stats(sym, sched)
    assert sum(ops) == stats["true"]
    assert ops == [g["true"] for g in stats["groups"]]


@pytest.mark.parametrize("w,m", [(1, 0), (7, 0), (8, 5), (64, 300)])
def test_flops_are_column_ops_less_two_thirds_w_cubed(w, m):
    assert work.supernode_flops(w, m) == pytest.approx(
        work.supernode_column_ops(w, m) - 2 * w ** 3 / 3)


def test_least_seconds_takes_the_binding_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(200.0, 10.0, peak) == 2.0
    assert work.least_seconds(100.0, 50.0, peak) == 5.0



@pytest.mark.parametrize("w,m", [(1, 0), (4, 8), (64, 300)])
def test_least_bytes_are_the_lower_triangles_in_and_out(w, m):
    """Panel in and out (lower triangle of the diagonal block and the
    tail) and the update's lower triangle out; no padding."""
    L = np.tril(np.ones((w + m, w)))
    U = np.tril(np.ones((m, m)))
    assert work.supernode_bytes(w, m, 4) == 4 * (2 * L.sum() + U.sum())
