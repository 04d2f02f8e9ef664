"""Lazy rows a fused bucket reads inside its own executable.

A bucket argument column that holds lazy rows (:class:`BatchSlice`) but is
not one whole buffer in order goes into the bucket's executable as its
source buffers, its concrete payloads and each member's ``(source, row)``;
no row is concretised first.  Values stay bitwise those of the serial
backend, and buckets that pick other rows from sources of the same shapes
share one executable.  The four-device mesh case runs in a subprocess, so
this process keeps its one CPU device.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro import core as bind
from repro.core.backends.base import BatchSlice

jnp = pytest.importorskip("jax.numpy")

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "..", "src")


@bind.op
def _scale(a: bind.InOut, s: bind.In):
    return a * s


@bind.op
def _consume(x: bind.In, out: bind.InOut):
    return out * 0.5 + x


def _tile(v):
    return jnp.arange(16, dtype=jnp.float32).reshape(4, 4) / 7.0 + v


# each read: ("x" | "y", i) is the lazy row i of the x or y bucket's
# buffer; ("z", i) is the concrete payload z[i]
CASES = {
    "permuted_subset": [("x", 4), ("x", 1), ("x", 3)],
    "two_buffers": [("x", 2), ("y", 5), ("x", 0), ("y", 1)],
    "rows_and_payloads": [("x", 5), ("z", 0), ("x", 2), ("z", 1)],
}


def _run(backend, reads):
    """Two buckets of six lazy rows each, then one bucket of consumers
    reading ``reads``; returns the consumers' values."""
    ex = bind.LocalExecutor(1, backend=backend)
    with bind.Workflow(executor=ex) as wf:
        src = {k: [wf.array(_tile(10 * j + i), f"{k}{i}") for i in range(6)]
               for j, k in enumerate("xyz")}
        for k, s in (("x", 2.0), ("y", 3.0)):
            for a in src[k]:
                _scale(a, s)
            wf.sync()
            ex.flush()
        if isinstance(backend, bind.FusedBatchBackend):
            lazy = {k: type(ex._stores[0][src[k][0].ref.head.key])
                    is BatchSlice for k in "xyz"}
            assert lazy == {"x": True, "y": True, "z": False}
        outs = [wf.array(_tile(100 + j), f"o{j}") for j in range(len(reads))]
        for (k, i), o in zip(reads, outs):
            _consume(src[k][i], o)
        return [np.asarray(wf.fetch(o)) for o in outs]


@pytest.mark.parametrize("case", sorted(CASES))
def test_bucket_reads_lazy_rows_bitwise_as_serial(case):
    reads = CASES[case]
    fb = bind.FusedBatchBackend()
    got = _run(fb, reads)
    want = _run("serial", reads)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert fb.rows_gathered == sum(k != "z" for k, _ in reads)
    assert fb.columns_unplaced == 0


def test_other_rows_of_the_same_sources_share_one_executable():
    """Buckets reading other rows from sources of the same shapes replay
    the executable compiled for the first."""
    cache = bind.ExecutableCache()
    fb = bind.FusedBatchBackend()
    ex = bind.LocalExecutor(1, backend=fb, executable_cache=cache)
    with bind.Workflow(executor=ex) as wf:
        xs = [wf.array(_tile(i), f"x{i}") for i in range(6)]
        for x in xs:
            _scale(x, 2.0)
        wf.sync()
        ex.flush()
        values = []
        for picked in ((0, 1), (4, 2), (5, 3)):
            outs = [wf.array(_tile(100 + j)) for j in range(len(picked))]
            for i, o in zip(picked, outs):
                _consume(xs[i], o)
            wf.sync()
            ex.flush()
            if not values:
                compiled = (cache.compiles, len(cache))
            values += [(j, i, np.asarray(wf.fetch(o)))
                       for j, (i, o) in enumerate(zip(picked, outs))]
        assert (cache.compiles, len(cache)) == compiled
    assert fb.rows_gathered == 6 and fb.batches_dispatched == 4
    for j, i, v in values:
        want = np.asarray(_tile(100 + j)) * 0.5 + np.asarray(_tile(i)) * 2.0
        np.testing.assert_allclose(v, want, rtol=1e-6)


def test_a_source_elsewhere_concretises_the_column(monkeypatch):
    """A column whose source buffer the bucket may not read in place takes
    the concrete-row path, counted, with the same values."""
    reads = CASES["two_buffers"]
    monkeypatch.setattr(bind.FusedBatchBackend, "_reads_in_place",
                        lambda self, p, buffer: False)
    fb = bind.FusedBatchBackend()
    got = _run(fb, reads)
    monkeypatch.undo()
    for g, w in zip(got, _run("serial", reads)):
        np.testing.assert_array_equal(g, w)
    assert fb.rows_gathered == 0 and fb.columns_unplaced == 1


def test_mesh_buckets_read_rows_on_their_rank_device():
    """Four host devices: each rank's buckets read their rows on the rank's
    device; nothing is misplaced and values match serial."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "_bucket_rows_mesh.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "OK" in out.stdout
