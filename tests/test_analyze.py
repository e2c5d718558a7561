"""Static analysis (repro.analyze): clean plans prove clean, and every pass
catches its seeded violation — a corrupted scatter index, a tampered device
plan, a reordered event trace, an oversized bucket, a tampered cache file."""
import copy
import pickle

import numpy as np
import pytest
import scipy.sparse as sp

from repro.analyze import (
    analyze_matrix,
    audit_engine,
    audit_trace,
    bucket_vmem,
    check_bucket,
    check_kernels,
    check_plan_file,
    lint_device_plan,
    lint_fill_plan,
    lint_plan_stack,
    lint_scatter_plan,
    lint_schedule,
    plan_happens_before,
    traced_factorization,
)
from repro.analyze.plan_lint import _pool_destinations
from repro.core import DeviceEngine, PlanCache, symbolic_pipeline
from repro.core.device_store import device_plan
from repro.core.plan_cache import (
    CachedPlan,
    build_fill_plan,
    canonical_csc,
    pattern_fingerprint,
)
from repro.core.relind import scatter_plan
from repro.core.schedule import cached_schedule
from repro.sparse import (
    elasticity_3d,
    kkt_like,
    laplacian_2d,
    laplacian_3d,
    random_spd,
)

GENERATORS = [
    pytest.param(laplacian_2d, {"nx": 20}, id="lap2d"),
    pytest.param(laplacian_2d, {"nx": 12, "stencil": 9}, id="lap2d9"),
    pytest.param(laplacian_3d, {"nx": 6}, id="lap3d"),
    pytest.param(laplacian_3d, {"nx": 5, "stencil": 27}, id="lap3d27"),
    pytest.param(elasticity_3d, {"nx": 3}, id="elast3d"),
    pytest.param(kkt_like, {"nx": 12}, id="kkt"),
    pytest.param(random_spd, {"n": 120, "density": 0.03, "seed": 1},
                 id="rand"),
]


def _errors(findings):
    return [f for f in findings if f.severity == "error"]


def _codes(findings):
    return {f.code for f in findings}


@pytest.fixture(scope="module")
def lap_sym():
    sym, _ = symbolic_pipeline(laplacian_2d(16))
    return sym


@pytest.fixture(scope="module")
def lap_sched(lap_sym):
    return cached_schedule(lap_sym, max_batch=256, bucket="batch")


@pytest.fixture(scope="module")
def lap_gp(lap_sym, lap_sched):
    return device_plan(lap_sym, lap_sched)


# ---------------------------------------------------------------------------
# clean plans prove clean (the CI gate's core claim)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fn,kw", GENERATORS)
def test_all_generators_zero_errors(fn, kw):
    rep = analyze_matrix(fn(**kw), name="t", families=("batch", "fused"))
    assert rep.errors == [], "\n".join(str(f) for f in rep.errors)


def test_report_statuses(lap_sym):
    rep = analyze_matrix(laplacian_2d(16), name="t", families=("batch",))
    assert rep.status("plan-lint") == "PASS"
    assert rep.status("hazard") == "PASS"
    assert rep.status("kernel") in ("PASS", "WARN")
    assert "families" in rep.metrics


# ---------------------------------------------------------------------------
# pass 1 mutations: corrupted index plans are caught, precisely
# ---------------------------------------------------------------------------
def _big_supernode(sym, min_m=2):
    for s in range(sym.nsuper):
        m = sym.rows[s].shape[0] - sym.width(s)
        if m >= min_m:
            return s, m
    pytest.skip("no supernode with enough tail rows")


def test_scatter_oob_caught(lap_sym):
    plan = copy.deepcopy(scatter_plan(lap_sym))
    s, m = _big_supernode(lap_sym)
    plan.dst[s][0] = plan.trash + 7  # lower-tri entry past real storage
    codes = _codes(_errors(lint_scatter_plan(lap_sym, plan)))
    assert "scatter-oob" in codes


def test_scatter_upper_not_trash_caught(lap_sym):
    plan = copy.deepcopy(scatter_plan(lap_sym))
    s, m = _big_supernode(lap_sym)
    plan.dst[s][1] = 0  # entry (0,1) is strict-upper: must be trash
    codes = _codes(_errors(lint_scatter_plan(lap_sym, plan)))
    assert "upper-not-trash" in codes


def test_scatter_dup_caught(lap_sym):
    plan = copy.deepcopy(scatter_plan(lap_sym))
    s, m = _big_supernode(lap_sym)
    D = plan.dst[s].reshape(m, m)
    D[1, 0] = D[0, 0]  # two update entries land on one cell
    codes = _codes(_errors(lint_scatter_plan(lap_sym, plan)))
    assert "scatter-dup" in codes


def test_scatter_wrong_cell_caught(lap_sym):
    # in-bounds, unique, but the WRONG cell: the semantic re-derivation
    # (decode destination back to ancestor row/column) must catch it
    plan = copy.deepcopy(scatter_plan(lap_sym))
    s, m = _big_supernode(lap_sym)
    D = plan.dst[s].reshape(m, m)
    a, b = int(D[0, 0]), int(D[1, 1])
    D[0, 0], D[1, 1] = b, a  # swap two diagonal destinations
    codes = _codes(_errors(lint_scatter_plan(lap_sym, plan)))
    assert codes & {"dest-column", "dest-row"}


def test_fill_plan_mutations_caught(lap_sym):
    A = canonical_csc(laplacian_2d(16))
    fs, fd = build_fill_plan(lap_sym, A)
    nnz = int(A.nnz)
    assert lint_fill_plan(lap_sym, fs, fd, nnz) == []
    bad = fd.copy()
    bad[0] = scatter_plan(lap_sym).trash  # route a fill into the trash cell
    assert "fill-dst-oob" in _codes(lint_fill_plan(lap_sym, fs, bad, nnz))
    bad = fd.copy()
    bad[0] = bad[1]
    assert "fill-dup" in _codes(lint_fill_plan(lap_sym, fs, bad, nnz))
    bad = fs.copy()
    bad[0] = nnz + 3
    assert "fill-src-oob" in _codes(lint_fill_plan(lap_sym, bad, fd, nnz))


def test_schedule_tampered_levels_caught(lap_sym, lap_sched):
    sched = copy.deepcopy(lap_sched)
    sparent = np.asarray(lap_sym.sparent)
    child = int(np.flatnonzero(sparent >= 0)[0])
    sched.levels[child] = sched.levels[sparent[child]]  # child at parent level
    codes = _codes(_errors(lint_schedule(lap_sym, sched)))
    assert codes & {"parent-level", "ancestor-order", "levels-value"}
    assert "parent-level" in codes


def test_schedule_dropped_member_caught(lap_sym, lap_sched):
    sched = copy.deepcopy(lap_sched)
    for lg in sched.groups:
        for bg in lg:
            if len(bg.ids) >= 2:
                bg.ids = np.asarray(bg.ids)[1:]
                codes = _codes(_errors(lint_schedule(lap_sym, sched)))
                assert "schedule-coverage" in codes
                return
    pytest.skip("no multi-member group")


def test_device_plan_pack_duplicate_caught(lap_sym, lap_sched, lap_gp):
    gp = copy.deepcopy(lap_gp)
    gp.cells_concat[0] = gp.cells_concat[1]  # one cell packed twice
    codes = _codes(_errors(lint_device_plan(lap_sym, lap_sched, gp)))
    assert "pack-coverage" in codes


def test_device_plan_segment_swap_caught(lap_sym, lap_sched, lap_gp):
    # swap two pool indices across segment boundaries: still a permutation
    # (pool-coverage holds) but two updates assemble into the wrong cells —
    # exactly the write-write/wrong-cell race the segment-map check targets
    gp = copy.deepcopy(lap_gp)
    for lg in gp.groups:
        for g in lg:
            n_in = np.asarray(g.src).shape[0]
            r = np.asarray(g.cells).shape[0]
            if n_in >= 2 and r >= 2 and int(g.hi[0]) < n_in:
                g.src[0], g.src[-1] = int(g.src[-1]), int(g.src[0])
                codes = _codes(_errors(
                    lint_device_plan(lap_sym, lap_sched, gp)))
                assert "segment-map" in codes
                return
    pytest.skip("no group with a multi-segment pool slice")


def test_device_plan_unsorted_seg_caught(lap_sym, lap_sched, lap_gp):
    # the sorted segment sum trusts the ids to be non-decreasing
    gp = copy.deepcopy(lap_gp)
    for lg in gp.groups:
        for g in lg:
            if g.seg.shape[0] >= 2 and g.seg[0] != g.seg[-1]:
                g.seg[0], g.seg[-1] = int(g.seg[-1]), int(g.seg[0])
                codes = _codes(_errors(
                    lint_device_plan(lap_sym, lap_sched, gp)))
                assert "segment-bounds" in codes
                return
    pytest.skip("no group with a multi-segment pool slice")


@pytest.mark.parametrize("bad", ["r", "-1"])
def test_device_plan_seg_out_of_range_caught(lap_sym, lap_sched, lap_gp, bad):
    # an id past the group's packed cells (or below 0) sums an entry into
    # no cell of this group
    gp = copy.deepcopy(lap_gp)
    for lg in gp.groups:
        for g in lg:
            if g.seg.shape[0]:
                if bad == "r":
                    g.seg[-1] = g.cells.shape[0]
                else:
                    g.seg[0] = -1
                codes = _codes(_errors(
                    lint_device_plan(lap_sym, lap_sched, gp)))
                assert "segment-bounds" in codes
                return
    pytest.skip("no group with incoming updates")


def test_device_plan_lost_update_caught(lap_sym, lap_sched, lap_gp):
    gp = copy.deepcopy(lap_gp)
    for lg in gp.groups:
        for g in lg:
            src = np.asarray(g.src)
            if src.shape[0] >= 2:
                g.src[0] = int(g.src[1])  # one slot consumed twice, one lost
                codes = _codes(_errors(
                    lint_device_plan(lap_sym, lap_sched, gp)))
                assert "pool-coverage" in codes
                return
    pytest.skip("no group with incoming updates")


# ---------------------------------------------------------------------------
# pass 2: happens-before, static + trace
# ---------------------------------------------------------------------------
def test_plan_happens_before_clean(lap_sym, lap_sched, lap_gp):
    assert plan_happens_before(lap_sym, lap_sched, lap_gp) == []


def test_pool_hb_violation_caught(lap_sym, lap_sched, lap_gp):
    dest, producer, pool_off = _pool_destinations(lap_sym, lap_sched, lap_gp)
    flat = [(li, g) for li, lg in enumerate(lap_gp.groups) for g in lg]
    glevel = np.array([li for li, _g in flat])
    gp = copy.deepcopy(lap_gp)
    gflat = [g for lg in gp.groups for g in lg]
    for k, (li, _g) in enumerate(flat):
        src = np.asarray(gflat[k].src)
        if src.size == 0:
            continue
        # point one read at a pool slot produced at this group's own level
        # or later — the assembly would read a not-yet-written entry
        late = np.flatnonzero(glevel[producer] >= li)
        if late.size:
            gflat[k].src[0] = int(late[0])
            findings = plan_happens_before(lap_sym, lap_sched, gp)
            assert "pool-hb" in _codes(_errors(findings))
            return
    pytest.skip("no constructible same-level read")


def test_audit_trace_clean():
    ev = [("upload", 0), ("upload", 1), ("dispatch", 0),
          ("upload", 2), ("dispatch", 1), ("dispatch", 2)]
    assert audit_trace(ev, n_levels=3) == []


def test_audit_trace_read_before_upload():
    ev = [("upload", 0), ("dispatch", 0), ("dispatch", 1), ("upload", 1)]
    codes = _codes(audit_trace(ev, n_levels=2))
    assert "read-before-upload" in codes


def test_audit_trace_level_order():
    ev = [("upload", 0), ("upload", 1), ("dispatch", 1), ("dispatch", 0)]
    assert "level-order" in _codes(_errors(audit_trace(ev)))


def test_audit_trace_missing_level():
    ev = [("upload", 0), ("dispatch", 0)]
    assert "missing-level" in _codes(_errors(audit_trace(ev, n_levels=3)))


def test_audit_trace_donation_reuse():
    ev = [("upload", 0), ("dispatch", 0), ("donation_reuse", 0)]
    assert "donation-reuse" in _codes(_errors(audit_trace(ev)))


def test_overflowed_trace_is_inconclusive_not_pass():
    # the dropped prefix could hide the upload: no PASS, no false FAIL
    ev = [("dispatch", 5), ("dispatch", 6)]
    findings = audit_trace(ev, n_levels=7, overflowed=True)
    assert _errors(findings) == []
    assert any(f.severity == "inconclusive" and f.code == "trace-truncated"
               for f in findings)


def test_engine_ring_buffer_overflow_flag():
    eng = DeviceEngine(backend="xla", events_cap=4)
    A = laplacian_2d(16)
    from repro.core import cholesky

    cholesky(A, device_engine=eng)
    assert eng.events_overflowed
    findings = audit_engine(eng)
    assert _errors(findings) == []
    assert any(f.code == "trace-truncated" for f in findings)
    eng.reset_events()
    assert not eng.events_overflowed and len(eng.events) == 0


def test_engine_donation_reuse_detected():
    eng = DeviceEngine(backend="xla")
    buf = object()
    eng._note_donation(buf, 0)
    eng._note_donation(buf, 1)  # same buffer donated twice: aliasing bug
    assert "donation-reuse" in _codes(_errors(audit_engine(eng)))


@pytest.mark.parametrize("staging", ["async", "sync"])
def test_traced_factorization_clean(staging):
    A = laplacian_2d(24)
    findings, eng, F = traced_factorization(A, backend="xla", staging=staging)
    assert _errors(findings) == [], "\n".join(map(str, findings))
    assert not eng.events_overflowed
    # the trace really covered the run: uploads + dispatches were recorded
    assert any(t == "dispatch" for t, _ in eng.events)


# ---------------------------------------------------------------------------
# pass 3: kernel static analysis
# ---------------------------------------------------------------------------
def test_bucket_vmem_estimate_shape():
    est = bucket_vmem(256, 128)  # float32, (8, 128)-tiled values
    assert est["mp"] == 128 and est["tu"] == 128
    panel, ublk = 256 * 128 * 4, 128 * 128 * 4
    blocks = 2 * (2 * panel + ublk) + panel   # double-buffered + scratch
    assert est["vmem_bytes"] == blocks + 4 * panel + 6 * ublk
    # a second slab adds a rebuilt panel; the guard adds its scan and
    # double-buffered (1, 128) status block
    wide = bucket_vmem(768, 256)
    panel, ublk = 768 * 256 * 4, 512 * 128 * 4
    blocks = 2 * (2 * panel + ublk) + panel
    assert wide["vmem_bytes"] == blocks + 5 * panel + 7 * ublk
    assert (bucket_vmem(768, 256, guard=True)["vmem_bytes"]
            == wide["vmem_bytes"] + panel + 2 * 8 * 128 * 4)


def test_check_bucket_clean_pow2():
    assert _errors(check_bucket(256, 128, family="fused")) == []


def test_oversized_bucket_overflows_explicit_cap():
    findings = check_bucket(512, 256, vmem_cap=2 ** 20)  # 1 MiB cap
    assert "vmem-overflow" in _codes(_errors(findings))


def test_vmem_reference_is_warning_not_error():
    findings = check_bucket(2048, 1024)  # ~80 MiB estimate, no cap given
    assert _errors(findings) == []
    assert "vmem-reference" in _codes(findings)


def test_fused_family_alignment_violation_is_error():
    # mp=12 has gcd(12,128)=4 < 8: breaks the fused family's promise
    findings = check_bucket(20, 8, family="fused")
    assert "mxu-alignment" in _codes(_errors(findings))
    # the same shape under no family claim is only a warning
    assert _errors(check_bucket(20, 8)) == []


def test_check_kernels_metrics(lap_sym):
    sched = cached_schedule(lap_sym, max_batch=256, bucket="fused")
    findings, metrics = check_kernels(lap_sym, sched, family="fused")
    assert _errors(findings) == []
    assert metrics["buckets"] and metrics["max_vmem_mib"] > 0
    for b in metrics["buckets"]:
        assert b["headroom_ref_mib"] == pytest.approx(
            16.0 - b["vmem_mib"], abs=0.01)
    assert 0.0 <= metrics["masked_waste"] <= metrics["padded_waste"]


# ---------------------------------------------------------------------------
# pass 4: cache integrity
# ---------------------------------------------------------------------------
@pytest.fixture()
def saved_plan(tmp_path):
    A = canonical_csc(laplacian_2d(16))
    cache = PlanCache(tmp_path)
    plan = cache.get(A)
    return A, plan, tmp_path / f"plan_{plan.key}.pkl"


def test_check_plan_file_clean(saved_plan):
    A, plan, path = saved_plan
    findings, loaded = check_plan_file(path, expect_key=plan.key)
    assert _errors(findings) == [], "\n".join(map(str, findings))
    assert loaded is not None and loaded.key == plan.key


def test_tampered_blob_digest_mismatch(saved_plan):
    _A, _plan, path = saved_plan
    env = pickle.loads(path.read_bytes())
    blob = bytearray(env["blob"])
    blob[len(blob) // 2] ^= 0xFF  # flip one byte deep in the payload
    env["blob"] = bytes(blob)
    path.write_bytes(pickle.dumps(env))
    findings, loaded = check_plan_file(path)
    assert loaded is None
    assert "digest-mismatch" in _codes(_errors(findings))
    with pytest.raises(ValueError, match="corrupt"):
        CachedPlan.load(path)


def test_stale_format_version_rejected(saved_plan):
    _A, _plan, path = saved_plan
    path.write_bytes(pickle.dumps({"version": -1}))
    findings, loaded = check_plan_file(path)
    assert loaded is None
    assert "format-version" in _codes(_errors(findings))
    with pytest.raises(ValueError, match="format version"):
        CachedPlan.load(path)


def test_wrong_pattern_fingerprint_rejected(saved_plan):
    _A, plan, path = saved_plan
    other = pattern_fingerprint(laplacian_2d(24))
    findings, loaded = check_plan_file(path, expect_key=other)
    assert loaded is None
    assert "fingerprint-mismatch" in _codes(_errors(findings))
    with pytest.raises(ValueError, match="fingerprint"):
        CachedPlan.load(path, expect_key=other)


def test_truncated_file_unreadable(saved_plan):
    _A, _plan, path = saved_plan
    path.write_bytes(path.read_bytes()[: path.stat().st_size // 3])
    findings, loaded = check_plan_file(path)
    assert loaded is None
    assert _codes(_errors(findings)) & {"unreadable", "digest-mismatch",
                                        "malformed"}


def test_cache_get_rejects_and_rebuilds(saved_plan):
    # a corrupted disk file must not crash or poison the server: the cache
    # counts a reject, rebuilds, and overwrites with a good file
    A, plan, path = saved_plan
    path.write_bytes(b"not a plan at all")
    cache = PlanCache(path.parent)
    p2 = cache.get(A)
    assert cache.disk_rejects == 1
    assert cache.stats["misses"] == 1 and cache.stats["disk_hits"] == 0
    assert p2.key == plan.key
    findings, _ = check_plan_file(path)  # the rewrite is clean again
    assert _errors(findings) == []


def test_cache_get_rejects_and_rebuilds_v2_plan(saved_plan):
    # a plan file of format 2 (segment ends ``hi`` in place of the segment
    # ids ``seg``) is rejected, never loaded, and rebuilt in the current
    # format
    A, plan, path = saved_plan
    env = pickle.loads(path.read_bytes())
    assert env["version"] == 3
    env["version"] = 2
    path.write_bytes(pickle.dumps(env))
    cache = PlanCache(path.parent)
    p2 = cache.get(A)
    assert cache.disk_rejects == 1
    assert cache.stats["misses"] == 1 and cache.stats["disk_hits"] == 0
    assert p2.key == plan.key
    assert pickle.loads(path.read_bytes())["version"] == 3
    gp = device_plan(p2.sym, cached_schedule(p2.sym, max_batch=256,
                                             bucket="batch"))
    groups = [g for lg in gp.groups for g in lg]
    assert groups and all("seg" in vars(g) and "hi" not in vars(g)
                          for g in groups)
    assert any(g.seg.shape[0] for g in groups)


def test_load_with_lint_gate(saved_plan):
    _A, plan, path = saved_plan
    loaded = CachedPlan.load(path, lint=True)  # clean plan passes the gate
    assert loaded.key == plan.key


# ---------------------------------------------------------------------------
# serving-layer hook: verify mode lints new plans and audits every trace
# ---------------------------------------------------------------------------
def test_server_verify_mode_clean():
    from repro.launch.serve import CholeskyServer

    srv = CholeskyServer(verify=True)
    A = laplacian_2d(14)
    h = srv.factor(A)
    srv.factor(sp.csc_matrix(A + 0.5 * sp.eye(A.shape[0])))  # repeat pattern
    x = srv.solve(h, np.ones(A.shape[0]))
    assert np.linalg.norm(A @ np.asarray(x) - 1.0) < 1e-8
    assert not [f for f in srv.verify_findings if f.severity == "error"]
    assert srv.report()["verify"] == {} or "error" not in srv.report()["verify"]


def test_server_verify_raises_on_bad_trace():
    from repro.launch.serve import CholeskyServer

    srv = CholeskyServer(verify=True)
    A = laplacian_2d(14)
    srv.factor(A)
    # seed a donation-reuse hazard into the engine's live trace: the next
    # request's audit must refuse to serve
    buf = object()
    srv.engine._note_donation(buf, 0)
    srv.engine._note_donation(buf, 0)
    with pytest.raises(RuntimeError, match="donation-reuse"):
        srv._audit_factor(srv.factors[0])


# ---------------------------------------------------------------------------
# property-based fuzz (the hypothesis tests only exist where it's installed;
# the parametrized generator sweep above covers the same property locally)
# ---------------------------------------------------------------------------
def _fuzz_lint(kind, size, seed):
    if kind == "lap2d":
        A = laplacian_2d(2 * size + 2)
    elif kind == "lap2d9":
        A = laplacian_2d(size + 3, stencil=9)
    elif kind == "lap3d":
        A = laplacian_3d(size)
    elif kind == "elast":
        A = elasticity_3d(max(size // 2, 2))
    elif kind == "kkt":
        A = kkt_like(size + 3, seed=seed % 7)
    else:
        A = random_spd(20 * size, density=0.05, seed=seed)
    sym, _ = symbolic_pipeline(A)
    findings = lint_plan_stack(sym, buckets=("batch", "fused"))
    findings += plan_happens_before(
        sym, cached_schedule(sym, max_batch=256, bucket="batch"))
    assert _errors(findings) == [], "\n".join(map(str, findings))


@pytest.mark.parametrize("seed", [2, 3, 5])
def test_random_spd_plan_lint_zero_errors(seed):
    _fuzz_lint("rand", 6, seed)


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
except ImportError:
    pass
else:
    @settings(max_examples=8, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(kind=st.sampled_from(["lap2d", "lap2d9", "lap3d", "elast", "kkt",
                                 "rand"]),
           size=st.integers(min_value=3, max_value=9),
           seed=st.integers(min_value=0, max_value=2 ** 16))
    def test_fuzz_plan_lint_zero_findings(kind, size, seed):
        _fuzz_lint(kind, size, seed)
