"""Seeded SPD matrices on a fixed sparsity pattern, one matrix class per
configuration.

A configuration names its matrix class by ``generator``; the class lives in
``generators/<generator>.py`` under the benchmark's directory and is found
by that name, so a new class is a new file.  A generator file exposes

    n_coefficients(cfg) -> int        how many coefficients one matrix takes
    matrix(cfg, coef) -> csc_matrix   ``coef=None`` gives unit coefficients
    TINY                              the config keys that shrink it to a
                                      size the CPU tests can factor

and reads its own size keys from ``cfg``.

A coefficient scales one part of the operator (an edge's conductance, an
element's stiffness), as a heterogeneous or nonlinear material does from
one Newton or time step to the next.  Every coefficient is positive, so
each matrix is SPD and the pattern never changes.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib

import numpy as np
import scipy.sparse as sp

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


def _canonical(A) -> sp.csc_matrix:
    A = sp.csc_matrix(A)
    A.sum_duplicates()
    A.sort_indices()
    return A


@functools.lru_cache(maxsize=None)
def generator(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The module ``generators/<name>.py``, loaded once per process (its
    own caches then hold across the matrices of a ring)."""
    path = pathlib.Path(bench_dir) / "generators" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_generator_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def matrix(cfg: dict, coef: np.ndarray | None = None,
           bench_dir: pathlib.Path = BENCH_DIR) -> sp.csc_matrix:
    """One matrix of the configuration; ``coef=None`` gives the unit
    coefficients."""
    return generator(cfg["generator"], bench_dir).matrix(cfg, coef)


def coefficients(rng: np.random.Generator, cfg: dict, spread: float,
                 bench_dir: pathlib.Path = BENCH_DIR) -> np.ndarray:
    """Coefficients drawn log-uniformly from [1/spread, spread]."""
    k = generator(cfg["generator"], bench_dir).n_coefficients(cfg)
    return np.exp(rng.uniform(-np.log(spread), np.log(spread), size=k))
