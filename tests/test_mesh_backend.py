"""Mesh backend: pallas chain lowering, ship-schedule pricing, fallbacks.

The mesh backend's multi-device behaviour (real ``shard_map`` collectives,
8 fake CPU devices) runs in a subprocess self-test — the main pytest
process must keep its single CPU device.  Everything testable on one
device lives here directly:

* ``lookup_chain_pallas`` compiles a whole chain into one ``pallas_call``
  (interpret mode) with *bitwise* parity against the python loop;
* ``MeshBackend(pallas=True)`` dispatches exactly one compiled executable
  per kernel-tagged chain, counter-asserted, and falls back to the generic
  scan for untagged bodies;
* on a single-device host the backend degrades to ``fused`` exactly
  (no collectives, identical values/transfers);
* ``estimated_makespan`` prices the same transfer stream differently
  under flat/ring/fat-tree topology models — the signal
  ``schedule_for_topology`` keys off.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro import core as bind
from repro.core.backends.mesh import MeshBackend
from repro.core.lowering import SHIP_SCHEDULES, schedule_for_topology
from repro.kernels.gemm.ops import gemm_tile
from repro.kernels.linear_scan.ops import scan_step
from repro.launch.mesh import make_topology

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

# The fallback tests below assert what the backend must NOT do without a
# device axis; under a multi-device run (CI's XLA_FLAGS job) the lowering
# legitimately activates and the selftest covers that arm instead.
_single_device_only = pytest.mark.skipif(
    len(jax.devices()) > 1, reason="host has a real device axis")

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run_module(mod: str, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", mod],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"{mod} failed:\n{out.stdout}\n{out.stderr}"
    return out.stdout


def _consume(x, out):
    return out + x


_consume.__bind_intents__ = (bind.In, bind.InOut)


def _scale(a, s):
    return a * s


_scale.__bind_intents__ = (bind.InOut, bind.In)


def _plain_step(y, a, x):
    """scan_step's body without the ``__bind_kernel__`` tag."""
    return a * y + x


_plain_step.__bind_intents__ = (bind.InOut, bind.In, bind.In)


# ---------------------------------------------------------------------------
# lookup_chain_pallas: one pallas_call per chain, bitwise vs python loop
# ---------------------------------------------------------------------------

def test_lookup_chain_pallas_matches_python_loop_bitwise():
    cache = bind.ExecutableCache()
    n_levels = 6
    y0 = jnp.linspace(-1.0, 1.0, 16, dtype=jnp.float32).reshape(4, 4)
    xs = jnp.stack([jnp.full((4, 4), float(i + 1), jnp.float32)
                    for i in range(n_levels)])
    layout = ("single", "const", "xs")
    call = cache.lookup_chain_pallas(scan_step, layout, n_levels, 0,
                                     [y0, 0.5, xs], interpret=True)
    out = np.asarray(call(y0, 0.5, xs))
    ref = y0
    for i in range(n_levels):
        ref = scan_step(ref, 0.5, xs[i])
    np.testing.assert_array_equal(out, np.asarray(ref))
    assert cache.compiles == 1
    # warm re-resolution: same signature, zero recompiles
    again = cache.lookup_chain_pallas(scan_step, layout, n_levels, 0,
                                      [y0, 0.5, xs], interpret=True)
    np.testing.assert_array_equal(np.asarray(again(y0, 0.5, xs)), out)
    assert cache.compiles == 1


def test_lookup_chain_pallas_dot_body():
    cache = bind.ExecutableCache()
    n_levels = 4
    rng = np.random.default_rng(3)
    c0 = jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)
    layout = ("single", "single", "single")
    call = cache.lookup_chain_pallas(gemm_tile, layout, n_levels, 0,
                                     [c0, a, b], interpret=True)
    out = np.asarray(call(c0, a, b))
    ref = c0
    for _ in range(n_levels):
        ref = gemm_tile(ref, a, b)
    np.testing.assert_array_equal(out, np.asarray(ref))


def test_lookup_chain_pallas_row_blocks_bitwise():
    """Carries taller than one row block run on a (row blocks, levels)
    grid: ewise bodies block every operand, dot bodies block the carry and
    its row-aligned operand and read the contraction partner whole — and
    stay bitwise equal to the python loop."""
    from repro.core.executable_cache import _MAX_BLOCK_ROWS
    rows, n_levels = 2 * _MAX_BLOCK_ROWS, 3
    rng = np.random.default_rng(5)
    y0 = jnp.asarray(rng.normal(size=(rows, 8)), jnp.float32)
    xs = jnp.asarray(rng.normal(size=(n_levels, rows, 8)), jnp.float32)
    cs = jnp.asarray([0.5, 0.25, 2.0], jnp.float32)
    b = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    cache = bind.ExecutableCache()
    scan = cache.lookup_chain_pallas(scan_step, ("single", "xs_const", "xs"),
                                     n_levels, 0, [y0, cs, xs],
                                     interpret=True)
    dot = cache.lookup_chain_pallas(gemm_tile, ("single", "xs", "single"),
                                    n_levels, 0, [y0, xs, b],
                                    interpret=True)
    ref_scan = ref_dot = y0
    for i in range(n_levels):
        ref_scan = scan_step(ref_scan, cs[i], xs[i])
        ref_dot = gemm_tile(ref_dot, xs[i], b)
    np.testing.assert_array_equal(np.asarray(scan(y0, cs, xs)),
                                  np.asarray(ref_scan))
    np.testing.assert_array_equal(np.asarray(dot(y0, xs, b)),
                                  np.asarray(ref_dot))


# ---------------------------------------------------------------------------
# Dispatch counters and fallbacks through the full backend
# ---------------------------------------------------------------------------

def _chain_workflow(backend, fn, depth=8, cache=None):
    ex = bind.LocalExecutor(1, mode="plan", backend=backend,
                            executable_cache=cache)
    with bind.Workflow(n_nodes=1, executor=ex) as wf:
        y = wf.array(jnp.linspace(0.0, 1.0, 16, dtype=jnp.float32), "y")
        for i in range(depth):
            x = wf.array(jnp.full(16, float(2 ** (i % 3)), jnp.float32))
            wf.call(fn, (y, 0.5, x), name=fn.__name__)
        return np.asarray(wf.fetch(y))


def test_pallas_chain_one_executable_per_chain():
    cache = bind.ExecutableCache()
    mb = MeshBackend(pallas=True)       # force lowering on 1 device
    out = _chain_workflow(mb, scan_step, cache=cache)
    ref = _chain_workflow("serial", scan_step)
    np.testing.assert_array_equal(out, ref)
    assert mb.pallas_chains_dispatched == 1
    assert mb.ops_pallas == 8
    assert cache.compiles == 1          # ONE compiled executable
    assert mb.interpret                 # CPU host: Pallas interpret mode


def test_mesh_backend_interprets_on_cpu():
    """The Pallas mode follows the platform: interpreted on the CPU, and
    ``pallas="auto"`` arms chain lowering on one CPU device only when a
    mesh exists (on a TPU it is armed on one chip too)."""
    mb = MeshBackend()
    assert mb._devices[0].platform == "cpu"
    assert mb.interpret
    assert mb._pallas_enabled() == (len(jax.devices()) >= 2)
    assert MeshBackend(pallas=True)._pallas_enabled()
    assert not MeshBackend(pallas=False)._pallas_enabled()


def test_pallas_lowering_failure_raises(monkeypatch):
    """A kernel-tagged chain whose Pallas build fails must fail the flush,
    not quietly pin the fn to another path."""
    from repro.core import executable_cache

    def broken(*_a, **_k):
        raise RuntimeError("forced pallas lowering failure")

    monkeypatch.setattr(executable_cache, "chain_pallas_call", broken)
    mb = MeshBackend(pallas=True)
    with pytest.raises(RuntimeError, match="forced pallas lowering"):
        _chain_workflow(mb, scan_step, cache=bind.ExecutableCache())
    assert mb.pallas_chains_dispatched == 0
    assert mb.chains_dispatched == 0
    assert scan_step not in mb._no_chain     # nothing pinned


def test_untagged_body_falls_back_to_generic_scan():
    mb = MeshBackend(pallas=True)
    out = _chain_workflow(mb, _plain_step)
    ref = _chain_workflow("serial", _plain_step)
    np.testing.assert_array_equal(out, ref)
    assert mb.pallas_chains_dispatched == 0     # untagged: not lowerable
    assert mb.chains_dispatched >= 1            # generic scan still fused


@_single_device_only
def test_pallas_auto_disabled_on_single_device():
    """``pallas="auto"`` must not lower on a single-device host — the
    graceful-fallback contract (the multi-device selftest proves the
    opposite arm)."""
    mb = MeshBackend()
    out = _chain_workflow(mb, scan_step)
    ref = _chain_workflow("serial", scan_step)
    np.testing.assert_array_equal(out, ref)
    assert mb.pallas_chains_dispatched == 0
    assert mb.chains_dispatched >= 1


def _ship_workflow(backend):
    ex = bind.LocalExecutor(4, collective_mode="tree", mode="plan",
                            backend=backend)
    with bind.Workflow(n_nodes=4, executor=ex) as wf:
        x = wf.array(jnp.arange(32, dtype=jnp.float32), "x")
        outs = [wf.array(jnp.zeros(32, jnp.float32)) for _ in range(3)]
        with bind.node(0):
            wf.call(_scale, (x, 2.0), name="scale")
        for r in range(3):
            with bind.node(r + 1):
                wf.call(_consume, (x, outs[r]), name="consume")
        vals = [np.asarray(wf.fetch(o)) for o in outs]
    return vals, list(ex.stats.transfers), ex.stats


@_single_device_only
def test_single_device_degrades_to_fused_exactly():
    vals_m, tr_m, _ = _ship_workflow(MeshBackend())
    vals_f, tr_f, _ = _ship_workflow("fused")
    vals_s, tr_s, _ = _ship_workflow("serial")
    assert tr_m == tr_f == tr_s
    for a, b in zip(vals_m, vals_s):
        np.testing.assert_array_equal(a, b)
    mb = MeshBackend()
    _ship_workflow(mb)
    assert mb.ships_lowered == 0        # no second device: nothing lowered


# ---------------------------------------------------------------------------
# Topology model: same transfers, different prices, schedule selection
# ---------------------------------------------------------------------------

def test_ship_schedules_priced_differently_by_makespan():
    """The topology model is what makes schedule choice meaningful: one
    transfer stream, three different estimated makespans (hop counts and
    per-link costs differ across flat/ring/fat-tree)."""
    _, _, stats = _ship_workflow("serial")
    prices = {kind: stats.estimated_makespan(make_topology(kind, 4))
              for kind in ("flat", "ring", "fat-tree")}
    assert all(p > 0 for p in prices.values())
    assert len(set(prices.values())) == 3, prices


def test_schedule_for_topology_mapping():
    assert schedule_for_topology(None) == "tree"
    assert schedule_for_topology(make_topology("flat", 4)) == "tree"
    assert schedule_for_topology(make_topology("ring", 4)) == "ring"
    assert (schedule_for_topology(make_topology("fat-tree", 4))
            == "hierarchical")
    assert set(SHIP_SCHEDULES) == {"tree", "ring", "hierarchical"}


# ---------------------------------------------------------------------------
# Multi-device: collectives + parity, in a subprocess (8 fake devices)
# ---------------------------------------------------------------------------

def test_mesh_backend_multidevice_selftest():
    assert "OK" in _run_module("repro.launch.selftest_mesh")
