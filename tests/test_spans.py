"""Host spans of the executor (``repro.core.stats.Span``): nesting and
self time in the span history, the profiler annotation of each span, the
history's row count against an independent count of the lazy rows a
solve concretises, and a spill that raises."""

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as bind
from repro.core import scheduler
from repro.core.backends import base, fused
from repro.core.backends.base import BatchSlice
from repro.core.backends.mesh import MeshBackend
from repro.core.stats import SPAN_HISTORY, Span
from repro.linalg.distributed import (distributed_gemm_listing1,
                                      make_distributed_inputs)
from repro.linalg.strassen import gemm_strassen
from repro.linalg.tiles import Tiled

# the benchmark's tile grid (N = 8192, ib = 1024) at 32 x 32 tiles: the
# row count depends on the grid, not on the tile size
IB, NT = 32, 8


def _inputs():
    n = IB * NT
    return (jax.random.normal(jax.random.key(0), (n, n), jnp.float32),
            jax.random.normal(jax.random.key(1), (n, n), jnp.float32))


def _listing1(A, B, backend):
    ex = bind.LocalExecutor(4, backend=backend)
    with bind.Workflow(n_nodes=4, executor=ex) as wf:
        a, b, c = make_distributed_inputs(wf, A, B, ib=IB, NP=2, NQ=2)
        distributed_gemm_listing1(wf, a, b, c, 2, 2)
        C = c.to_array()
    return ex, C


def _trees_since(t0):
    return [h for h in SPAN_HISTORY if h[0] >= t0]


def _names(trees):
    return {name for _, _, tree, _ in trees for name in tree}


def _strassen(A, B, backend):
    ex = bind.LocalExecutor(1, backend=backend)
    with bind.Workflow(n_nodes=1, executor=ex) as wf:
        a = Tiled.from_array(wf, A, IB, "A")
        b = Tiled.from_array(wf, B, IB, "B")
        c = Tiled.zeros(wf, NT, NT, IB, A.dtype, "C", xp=jnp)
        gemm_strassen(a, b, c)
        C = c.to_array()
    return ex, C


def test_self_time_is_the_total_less_the_spans_inside():
    with Span("outer"):
        time.sleep(0.01)
        for _ in range(2):
            with Span("inner") as sp:
                time.sleep(0.01)
                sp.rows = 3
    start, end, tree, rows = SPAN_HISTORY[-1]
    assert set(tree) == {"outer", "inner"}
    assert tree["inner"] >= 0.02 and 0.01 <= tree["outer"] < end - start
    assert tree["outer"] + tree["inner"] == pytest.approx(end - start)
    assert rows == 6


def test_a_raising_span_closes_and_counts():
    with pytest.raises(ValueError):
        with Span("outer"):
            with Span("inner") as sp:
                sp.rows = 2
                raise ValueError
    start, end, tree, rows = SPAN_HISTORY[-1]
    assert set(tree) == {"outer", "inner"} and rows == 2
    with Span("after"):                     # the stack is empty again
        pass
    assert set(SPAN_HISTORY[-1][2]) == {"after"}


@pytest.mark.parametrize("program", [_listing1, _strassen])
def test_flush_spans_cover_the_fetch(program):
    A, B = _inputs()
    backend = MeshBackend()
    program(A, B, backend)                  # compile outside the timing
    t0 = time.perf_counter()
    program(A, B, backend)
    trees = _trees_since(t0)
    assert {"bind.flush", "bind.plan", "bind.level", "bind.rows",
            "bind.fetch", "bind.assemble"} <= _names(trees)
    # a tree per tile fetch and one for the assembly; the one flush, with
    # its planning and levels, nests in the first fetch
    assert len(trees) == NT * NT + 1
    assert sum("bind.fetch" in t for _, _, t, _ in trees) == NT * NT
    flush_trees = [h for h in trees if "bind.flush" in h[2]]
    assert len(flush_trees) == 1
    start, end, tree, _ = flush_trees[0]
    assert {"bind.plan", "bind.level"} <= set(tree)
    # no self time is negative or exceeds its tree; the self times of
    # each tree add up to its outermost span's duration
    for start, end, tree, _ in trees:
        assert all(0 <= own <= end - start for own in tree.values())
        assert sum(tree.values()) == pytest.approx(end - start, rel=0.01)


@pytest.mark.parametrize("program,rows", [(_listing1, 512),
                                          (_strassen, 372)])
def test_rows_materialized_matches_an_independent_count(program, rows,
                                                        monkeypatch):
    """Every ``BatchSlice.materialize`` call and every row a spill
    concretises, counted outside the program's own accounting."""
    A, B = _inputs()
    backend = MeshBackend()
    program(A, B, backend)
    counted = {"materialize": 0, "spilled": 0}
    materialize = BatchSlice.materialize
    spill = base.spill_dead_buckets

    def counting_materialize(self):
        counted["materialize"] += 1
        return materialize(self)

    def counting_spill(ex):
        n = spill(ex)
        counted["spilled"] += n
        return n

    monkeypatch.setattr(BatchSlice, "materialize", counting_materialize)
    monkeypatch.setattr(fused, "spill_dead_buckets", counting_spill)
    monkeypatch.setattr(scheduler, "spill_dead_buckets", counting_spill)
    t0 = time.perf_counter()
    _, C = program(A, B, backend)
    history_rows = sum(n for _, _, _, n in _trees_since(t0))
    assert history_rows == sum(counted.values()) == rows
    ref = jnp.dot(A, B, precision="highest")
    assert float(jnp.max(jnp.abs(C - ref))) < 1e-3 * float(
        jnp.max(jnp.abs(ref)))


# lazy rows a warm solve concretises on the benchmark's tile grid, by site,
# and the rows its buckets read inside their executables instead
SITES = {_listing1: ({"ships": 256, "spill": 192, "fetch": 64}, 640),
         _strassen: ({"spill": 364, "fetch": 8}, 3502)}


@pytest.mark.parametrize("program", list(SITES), ids=lambda f: f.__name__)
def test_rows_concretised_by_site(program, monkeypatch):
    """No bucket column concretises a row: ships, spills and the fetch of
    ``C`` are all that is left, and the span history counts them."""
    A, B = _inputs()
    backend = MeshBackend()
    program(A, B, backend)
    counted = collections.Counter()
    sites = []
    materialize = BatchSlice.materialize

    def counting_materialize(self):
        counted[sites[-1] if sites else "other"] += 1
        return materialize(self)

    def within(site, fn):
        def wrapped(*args, **kwargs):
            sites.append(site)
            try:
                return fn(*args, **kwargs)
            finally:
                sites.pop()
        return wrapped

    def counting_spill(ex):
        n = spill(ex)
        counted["spill"] += n
        return n

    spill = base.spill_dead_buckets
    monkeypatch.setattr(BatchSlice, "materialize", counting_materialize)
    monkeypatch.setattr(fused, "materialize_rows",
                        within("columns", fused.materialize_rows))
    monkeypatch.setattr(
        fused.FusedBatchBackend, "_materialize_shipped",
        within("ships", fused.FusedBatchBackend._materialize_shipped))
    monkeypatch.setattr(scheduler.LocalExecutor, "value",
                        within("fetch", scheduler.LocalExecutor.value))
    monkeypatch.setattr(fused, "spill_dead_buckets", counting_spill)
    monkeypatch.setattr(scheduler, "spill_dead_buckets", counting_spill)
    gathered = backend.rows_gathered
    t0 = time.perf_counter()
    program(A, B, backend)
    by_site, in_buckets = SITES[program]
    assert {k: v for k, v in counted.items() if v} == by_site
    assert backend.rows_gathered - gathered == in_buckets
    assert backend.columns_unplaced == 0
    assert sum(n for _, _, _, n in _trees_since(t0)) == sum(by_site.values())


def test_serial_backend_materialises_no_rows():
    A, B = _inputs()
    t0 = time.perf_counter()
    _listing1(A, B, "serial")
    trees = _trees_since(t0)
    assert sum(n for _, _, _, n in trees) == 0
    assert "bind.rows" not in _names(trees)
    assert sum("bind.flush" in t for _, _, t, _ in trees) == 1


@bind.op
def _scale(a: bind.InOut, s: bind.In):
    return a * s


@bind.op
def _shift(a: bind.InOut, s: bind.In):
    return a + s


class _FailsOnce:
    """A bucket's stacked buffer whose first row take raises, as an
    allocation that runs out of device memory would."""

    def __init__(self, buffer):
        self.buffer = buffer
        self.failed = False

    def __getitem__(self, idx):
        if not self.failed:
            self.failed = True
            raise RuntimeError("RESOURCE_EXHAUSTED: row take")
        return self.buffer[idx]


def test_a_spill_that_raises_leaves_the_survivors_to_the_abort():
    """A bucket whose spill raises stays registered with its live rows, so
    the failed flush's clean-up spills them and releases the buffer."""
    n = 4
    fb = fused.FusedBatchBackend()
    ex = bind.LocalExecutor(1, backend=fb)
    with bind.Workflow(executor=ex) as wf:
        xs = [wf.array(jnp.full((4, 4), float(i + 1), jnp.float32), f"x{i}")
              for i in range(n)]
        for x in xs:
            _scale(x, 2.0)                  # one bucket of n lazy rows
        wf.sync()
        ex.flush()
        bucket, = ex._lazy_buckets
        proxy = bucket.buffer = _FailsOnce(bucket.buffer)
        for x in xs[1:]:
            _shift(x, 1.0)                  # row 0 survives: it spills
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            wf.sync()
            ex.flush()
        assert proxy.failed
        assert bucket not in ex._lazy_buckets
        head = ex._stores[0][xs[0].ref.head.key]
        assert type(head) is not BatchSlice
        np.testing.assert_allclose(np.asarray(head), np.full((4, 4), 2.0))


def test_spans_are_profiler_annotations(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with Span("bind.test_outer"):
            with Span("bind.test_inner"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"bind.test_outer", "bind.test_inner"} <= names
