"""The check must come out false for the control and for each fault the
refactor cells can have, planted in the timed path underneath a whole run
(CPU, tiny sizes, the look for a chip skipped, float32 device values as on
the chip).  Faults of training or of several chips (half a batch averaged,
an exchange between chips left out) do not exist in these cells; the
nearest, half of a group's lanes left wrong, is planted instead."""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import control, harness, matrices  # noqa: E402

CELLS = [w["name"] for w in json.loads(
    (BENCH.parents[1] / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture
def spec(request, monkeypatch, tmp_path):
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    monkeypatch.setattr(kops, "device_dtype", lambda: jnp.float32)
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "cache")
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path / "out")
    monkeypatch.setattr(harness, "enable_caches", lambda: None)
    s = harness.load_spec(request.param)
    s.config.update(matrices.generator(s.config["generator"],
                                       s.bench_dir).TINY)
    return s


def _run(spec, seconds=0.5):
    return harness.run(spec, 2 ** 31 + 99, seconds, False, t_start=0.0,
                       require_tpu=False)


def _planted(monkeypatch, plant):
    """Plant a fault in the solver as set-up hands it over, so that the
    warm-up step compiles what the faulty path runs."""
    real = harness.prepare

    def prepare(spec):
        solver = real(spec)
        plant(solver)
        return solver

    monkeypatch.setattr(harness, "prepare", prepare)


class _GroupResult:
    """Engine fault hook that alters every group program's factored
    panels as they come off the device."""

    def __init__(self, alter):
        self.alter = alter

    def on_put(self, _eng, x):
        return x

    def on_dispatch(self, _eng, _lvl):
        pass

    def on_group_result(self, _eng, out, _lvl):
        return (self.alter(out[0]),) + tuple(out[1:])


@pytest.mark.parametrize("spec", CELLS, indirect=True)
def test_sound_run_is_correct(spec):
    assert _run(spec)["correct"] is True


@pytest.mark.parametrize("spec", CELLS, indirect=True)
def test_control_in_bfloat16_is_not_correct(spec, monkeypatch):
    _planted(monkeypatch, lambda s: control.bf16_storage(s.engine))
    out = _run(spec)
    assert out["correct"] is False
    c = out["checks"]
    assert c["factor_err"]["value"] > c["factor_err"]["limit"]


@pytest.mark.parametrize("spec", CELLS[:1], indirect=True)
def test_step_that_returns_its_state_unchanged(spec, monkeypatch):
    """Each step hands back the previous step's factor, in a window that
    holds one step, as the elasticity cell's does on the chip: the stale
    factor is the warm-up's."""
    import repro.core

    real, prev = repro.core.cholesky, []

    def stale(A, **kw):
        F = real(A, **kw)
        prev.append(F)
        return prev[-2] if len(prev) > 1 else F

    _planted(monkeypatch, lambda s: monkeypatch.setattr(
        repro.core, "cholesky", stale))
    out = _run(spec, seconds=0.0)
    assert out["attempted"] == 1
    assert out["correct"] is False
    assert out["checks"]["factor_err"]["value"] > \
        out["checks"]["factor_err"]["limit"]


@pytest.mark.parametrize("spec", CELLS[:1], indirect=True)
def test_factor_entry_altered_where_produced(spec, monkeypatch):
    _planted(monkeypatch, lambda s: setattr(
        s.engine, "faults", _GroupResult(lambda p: p.at[0].add(1e-3))))
    out = _run(spec)
    assert out["correct"] is False
    assert out["checks"]["factor_err"]["value"] > \
        out["checks"]["factor_err"]["limit"]


@pytest.mark.parametrize("spec", CELLS[1:], indirect=True)
def test_half_of_a_groups_lanes_left_wrong(spec, monkeypatch):
    def half(p):
        return p.at[: p.shape[0] // 2].multiply(0.5)

    _planted(monkeypatch, lambda s: setattr(
        s.engine, "faults", _GroupResult(half)))
    assert _run(spec)["correct"] is False


@pytest.mark.parametrize("spec", CELLS[1:], indirect=True)
def test_answer_altered_where_produced(spec, monkeypatch):
    from repro.core.numeric import CholeskyFactor

    real = CholeskyFactor.solve

    def solve(self, b, **kw):
        x = real(self, b, **kw)
        if kw.get("refine", None) is None:
            x = x.copy()
            x[0] += 1e-6 * abs(x).max()
        return x

    _planted(monkeypatch, lambda s: monkeypatch.setattr(
        CholeskyFactor, "solve", solve))
    out = _run(spec)
    assert out["correct"] is False
    assert out["checks"]["refined_resid"]["value"] > \
        out["checks"]["refined_resid"]["limit"]
