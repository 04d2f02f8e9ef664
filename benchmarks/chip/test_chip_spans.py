"""The readers of the executor's spans: per-solve attribution of the span
history on hand-written trees, nothing read from a program without one,
and each cell's rehearsal, whose span metrics cover its ``flush_ms``."""

from __future__ import annotations

import collections
import json
from pathlib import Path

import pytest

import bind_spans
import run_cell
from test_chip_rehearsal import cpu_env, last_json, run_cmd

HERE = Path(__file__).resolve().parent
SPAN_METRICS = ("plan_ms", "dispatch_ms", "rows_ms", "fetch_ms", "ship_ms")


def _read(name, rec):
    return run_cell.load_module(HERE / "metrics" / f"{name}.py").read(rec)


def _record():
    # solve 1 at 10-20 s, solve 2 at 30-40 s
    return {"host_solves": [{"start": 10.0, "end": 20.0},
                            {"start": 30.0, "end": 40.0}]}


def test_span_trees_go_to_the_solve_around_them(monkeypatch, capsys):
    from repro.core import stats
    history = collections.deque([
        (5.0, 6.0, {"bind.plan": 9.0}, 100),       # before the window
        (11.0, 12.0, {"bind.fetch": 0.1, "bind.flush": 0.2,
                      "bind.plan": 0.3, "bind.rows": 0.4}, 10),
        (12.0, 13.0, {"bind.fetch": 0.1, "bind.assemble": 0.05}, 0),
        (31.0, 32.0, {"bind.fetch": 0.3, "bind.level": 0.5,
                      "bind.ship": 0.2, "bind.rows": 0.6}, 30),
        (39.5, 41.0, {"bind.plan": 9.0}, 100),     # past the solve's end
    ])
    monkeypatch.setattr(stats, "SPAN_HISTORY", history)
    rec = _record()
    assert _read("plan_ms", rec) == pytest.approx(1e3 * 0.3 / 2)
    assert _read("dispatch_ms", rec) == pytest.approx(1e3 * 0.7 / 2)
    assert _read("rows_ms", rec) == pytest.approx(1e3 * 1.0 / 2)
    assert _read("fetch_ms", rec) == pytest.approx(1e3 * 0.55 / 2)
    assert _read("ship_ms", rec) == pytest.approx(1e3 * 0.2 / 2)
    assert _read("rows_materialized", rec) == pytest.approx(20.0)
    assert "left out" not in capsys.readouterr().err
    # a solve older than the oldest tree kept is left out, and said so
    history.popleft()
    history.popleft()
    assert _read("rows_materialized", rec) == pytest.approx(30.0)
    assert ("1 of 2 window solves are older than the span history"
            in capsys.readouterr().err)


@pytest.mark.parametrize("history", [None, ()])
def test_nothing_to_read_without_a_span_history(history, monkeypatch):
    from repro.core import stats
    if history is None:
        monkeypatch.delattr(stats, "SPAN_HISTORY")
    else:
        monkeypatch.setattr(stats, "SPAN_HISTORY", collections.deque())
    assert bind_spans.per_solve(_record()) is None
    for name in SPAN_METRICS + ("rows_materialized",):
        assert _read(name, _record()) is None


# rows a solve materialises on the chip's tile grid, which the rehearsal
# keeps: they depend on the grid, not on the tile size
ROWS = {"listing1.n8192.ib1024": 1152, "strassen.n8192.ib1024": 3874,
        "listing1.n8192.ib1024.x4": 1152}


@pytest.mark.parametrize("cell", sorted(ROWS))
def test_span_metrics_cover_the_flush(cell, tmp_path):
    proc = run_cmd(["--workload", cell, "--seed", str(2 ** 31 + 77),
                    "--seconds", "1", "--trace", "1", "--cpu-rehearsal"],
                   cpu_env(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    metrics = {k: v["value"] for k, v in last_json(proc.stdout)
               ["metrics"].items()}
    assert metrics["rows_materialized"] == ROWS[cell]
    parts = sum(metrics.get(name, 0.0) for name in SPAN_METRICS)
    assert 0.9 <= parts / metrics["flush_ms"] <= 1.05, json.dumps(metrics)
