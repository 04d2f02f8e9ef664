"""The Cholesky cell: its work count, its plain reference's comparison, and
its rehearsal's counters and span metrics."""

from __future__ import annotations

import math
from pathlib import Path

import numpy as np
import pytest

import reference_cholesky
import run_cell
from test_chip_rehearsal import cpu_env, last_json, run_cmd
from test_chip_spans import SPAN_METRICS

HERE = Path(__file__).resolve().parent
CELL = "cholesky.n20480.ib1024"


def _program():
    return run_cell.load_module(HERE / "programs" / "cholesky.py")


def _spd(n, seed):
    g = np.random.default_rng(seed).standard_normal((n, n))
    return (g @ g.T / n + np.eye(n)).astype(np.float32)


@pytest.mark.parametrize("nt", [1, 2, 4, 8])
def test_work_is_dpotrf_and_the_dag_does_at_least_it(nt):
    import jax.numpy as jnp
    from repro import core as bind
    from repro.linalg.cholesky import cholesky_tiles
    from repro.linalg.tiles import Tiled
    ib = 4
    n = nt * ib
    w = _program().work({"n": n, "ib": ib})
    # LAPACK's n^3/3 + n^2/2 + n/6, a whole number for every n
    assert w["flops"] * 6 == 2 * n ** 3 + 3 * n ** 2 + n
    assert w["bytes"] == 4 * n * (n + 1)
    with bind.Workflow() as wf:
        a = Tiled.from_array(wf, jnp.asarray(_spd(n, nt)), ib, "A")
        cholesky_tiles(wf, a, Tiled.like(a, "C"))
        gemm = sum(op.flops for op in wf.ops if op.name == "pgemm")
        recorded = sum(op.flops for op in wf.ops)
    assert gemm == math.comb(nt + 1, 3) * 2 * ib ** 3
    assert recorded >= w["flops"]


def test_check_reads_a_good_factor_and_refuses_a_bad_one():
    import jax.numpy as jnp
    ib, a = 16, _spd(64, 3)
    good = np.linalg.cholesky(a.astype(np.float64)).astype(np.float32)
    got = reference_cholesky.check(jnp.asarray(a), jnp.asarray(good), ib)
    assert set(got) == {"tile_rel_err", "residual_rel"}
    assert 0 < got["tile_rel_err"] < 1e-5 and 0 < got["residual_rel"] < 1e-5
    # a non-zero upper tile is infinitely wrong, however small
    upper = good.copy()
    upper[0, ib] = 1e-30
    assert reference_cholesky.check(
        jnp.asarray(a), jnp.asarray(upper), ib)["tile_rel_err"] == np.inf
    # a non-SPD input: the factor is NaN, and both numbers read inf
    bad = a.copy()
    bad[40, 40] = -1.0
    nan_factor = np.full_like(good, np.nan)
    got = reference_cholesky.check(jnp.asarray(bad), jnp.asarray(nan_factor),
                                   ib)
    assert got == {"tile_rel_err": np.inf, "residual_rel": np.inf}


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    proc = run_cmd(["--workload", CELL, "--seed", str(2 ** 31 + 991),
                    "--seconds", "1", "--trace", "1", "--cpu-rehearsal"],
                   cpu_env(tmp_path_factory.mktemp("cholesky")))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return last_json(proc.stdout)


def test_rehearsal_counters_are_the_closed_forms(rehearsal):
    nt = 20     # the cell's grid, which the rehearsal keeps
    # five levels a step (potrf, trsm, transpose, pgemm, isub) and a lone
    # potrf last; one op at each potrf and throughout the step with one
    # tile below the diagonal
    waves, single = 5 * nt - 4, nt + 4
    counters = rehearsal["counters_per_solve"]
    # potrf, trsm, transpose, and a pgemm and an isub per tile update
    assert counters["ops_executed"] == (
        nt + 2 * math.comb(nt, 2) + 2 * math.comb(nt + 1, 3)) == 3060
    assert counters["wavefronts"] == waves
    assert counters["single_op_wavefronts"] == single
    metrics = rehearsal["metrics"]
    assert metrics["wavefronts"]["value"] == waves
    assert metrics["single_op_wavefronts"]["value"] == single


def test_span_metrics_cover_the_flush(rehearsal):
    metrics = {k: v["value"] for k, v in rehearsal["metrics"].items()}
    parts = sum(metrics.get(name, 0.0) for name in SPAN_METRICS)
    assert 0.9 <= parts / metrics["flush_ms"] <= 1.05, metrics
