"""Process-pool backend: real OS-process execution behind the virtual ledger.

The conformance fuzzer (``tests/test_conformance.py``) owns breadth —
random workflows × 50 pinned seeds on the procs backend, value/dtype
parity, byte-identical transfer streams, plus ``--faults`` chaos seeds
that SIGKILL real workers.  This module owns the *mechanisms*: shared-pool
reuse and respawn-after-kill, the steady-state delta protocol (one control
message per worker per warm iteration), serial fallback for unpicklable op
functions, supervisor heartbeats and hang detection (a stuck — not dead —
worker must surface as a permanent ``RankFailure``), the threads backend's
dispatch-cost threshold, and the ``Topology.calibrate`` fit.

Op functions live at module level so pool workers can unpickle them by
reference (the worker re-imports this module — keep imports light).
"""

import os
import time

import numpy as np
import pytest

from repro import core as bind
from repro.core import FaultInjector, LocalExecutor
from repro.core.backends import procs as procs_mod
from repro.core.backends.procs import ProcessPoolBackend
from repro.core.backends.threadpool import ThreadPoolBackend
from repro.runtime.supervisor import heartbeat_age


@bind.op
def _step(c: bind.InOut, s: bind.In):
    return c * 1.01 + s


@bind.op
def _mix(c: bind.InOut, o: bind.In):
    return c + 0.5 * o


@bind.op
def _hang_step(c: bind.InOut, s: bind.In):
    # sleeps only inside the rank-1 pool worker: the op body stops touching
    # the heartbeat file, which is exactly what a wedged worker looks like
    if procs_mod._CURRENT_RANK == 1:
        time.sleep(60.0)
    return c * 1.01 + s


@bind.op
def _mark_cpu_only(c: bind.InOut):
    # 1 where the executing process was started with JAX_PLATFORMS=cpu
    return c + float(os.environ.get("JAX_PLATFORMS") == "cpu")


def _chains(wf, arrs, depth, mix_at=(), step=_step):
    n = len(arrs)
    for lv in range(depth):
        for r, a in enumerate(arrs):
            with bind.node(r):
                step(a, 1.5)
        if lv in mix_at:
            for r, a in enumerate(arrs):
                with bind.node(r):
                    _mix(a, arrs[(r + 1) % n])


def _run(build, n_nodes, injector=None, backend="serial", seed_arrays=None):
    ex = LocalExecutor(n_nodes, mode="plan", backend=backend,
                       fault_injector=injector)
    with bind.Workflow(n_nodes=n_nodes, executor=ex) as wf:
        if seed_arrays is None:
            arrs = [wf.array(np.arange(8.0) + r, rank=r)
                    for r in range(n_nodes)]
        else:
            arrs = [wf.array(a, rank=r) for r, a in enumerate(seed_arrays)]
        build(wf, arrs)
        wf.sync()
        vals = [np.asarray(wf.fetch(a)) for a in arrs]
    return vals, ex.stats, ex


# ---------------------------------------------------------------------------
# parity: values, transfer stream, stats — np and jax payloads
# ---------------------------------------------------------------------------

def test_procs_matches_serial_with_ships_and_gc():
    n = 3
    build = lambda wf, arrs: _chains(wf, arrs, 6, mix_at=(1, 4))
    ref, ref_st, _ = _run(build, n)
    vals, st, _ = _run(build, n, backend="procs")
    for a, b in zip(ref, vals):
        np.testing.assert_array_equal(a, b)
        assert a.dtype == b.dtype
    assert st.transfers == ref_st.transfers          # byte-identical stream
    assert st.ops_executed == ref_st.ops_executed
    assert st.wavefronts == ref_st.wavefronts
    assert st.bytes_transferred == ref_st.bytes_transferred
    assert st.peak_live_bytes >= ref_st.peak_live_bytes
    assert st.control_messages > 0 and ref_st.control_messages == 0


def test_procs_jax_payload_roundtrip():
    jnp = pytest.importorskip("jax.numpy")
    n = 2
    seeds = [jnp.arange(16.0) + r for r in range(n)]
    build = lambda wf, arrs: _chains(wf, arrs, 4, mix_at=(2,))
    ref, _, _ = _run(build, n, seed_arrays=seeds)
    vals, _, _ = _run(build, n, backend="procs", seed_arrays=seeds)
    for a, b in zip(ref, vals):
        np.testing.assert_allclose(a, b, rtol=0, atol=0)
        assert a.dtype == b.dtype


def test_workers_start_with_cpu_only_jax(monkeypatch):
    """Pool workers must never claim an accelerator the parent holds: they
    start with ``JAX_PLATFORMS=cpu`` whatever the parent's environment."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    n = 4           # a rank count no other test here uses: a fresh pool

    def build(wf, arrs):
        for r, a in enumerate(arrs):
            with bind.node(r):
                _mark_cpu_only(a)

    vals, _, _ = _run(build, n, backend="procs")
    for r, v in enumerate(vals):
        np.testing.assert_array_equal(v, np.arange(8.0) + r + 1.0)
    assert "JAX_PLATFORMS" not in os.environ    # the parent's env restored


def test_fetch_is_zero_copy_shm_view():
    """PR-8 bugfix: fetching a procs-resident NumPy payload attaches a
    *read-only view* of the worker's shared-memory segment instead of
    copying it out.  ``stats.fetch_bytes_copied`` accounts every byte any
    fetch path actually copies — the NumPy shm path must add zero, while
    a JAX payload pays exactly one host->device copy of its own size."""
    n = 2
    ex = LocalExecutor(n, mode="plan", backend="procs")
    with bind.Workflow(n_nodes=n, executor=ex) as wf:
        a = wf.array(np.arange(64.0).reshape(8, 8), rank=0)
        with bind.node(0):
            _step(a, 1.5)
        wf.sync()
    ex.flush()
    st = ex.stats
    assert st.fetch_bytes_copied == 0
    v = ex.value(a.ref.head)
    assert isinstance(v, np.ndarray) and not v.flags.writeable
    assert st.fetch_bytes_copied == 0            # the no-copy assertion
    np.testing.assert_array_equal(
        v, np.arange(64.0).reshape(8, 8) * 1.01 + 1.5)
    # write-back: the view is cached in the store, so a second fetch
    # returns the same object without re-attaching the segment
    assert ex.value(a.ref.head) is v

    # JAX payload on the same executor: exactly one accounted copy
    jnp = pytest.importorskip("jax.numpy")
    with bind.Workflow(n_nodes=n, executor=ex) as wf2:
        c = wf2.array(jnp.arange(16.0), rank=1)
        with bind.node(1):
            _step(c, 0.5)
        wf2.sync()
    ex.flush()
    vc = ex.value(c.ref.head)
    assert st.fetch_bytes_copied == np.asarray(vc).nbytes
    np.testing.assert_allclose(np.asarray(vc),
                               np.arange(16.0) * 1.01 + 0.5)


# ---------------------------------------------------------------------------
# steady-state protocol: warm loop iterations cost one message per worker
# ---------------------------------------------------------------------------

def test_steady_state_iterations_send_one_message_per_worker():
    n = 2
    ex = LocalExecutor(n, mode="plan", backend="procs")
    marks = []
    with bind.Workflow(n_nodes=n, executor=ex) as wf:
        arrs = [wf.array(np.arange(8.0) + r, rank=r) for r in range(n)]
        for _ in range(5):
            _chains(wf, arrs, 2, mix_at=(1,))
            wf.sync()
            ex.flush()
            marks.append(ex.stats.control_messages)
        vals = [np.asarray(wf.fetch(a)) for a in arrs]
    # iteration 1 ships the sliced plan (+ run); from the first trace-cache
    # hit on, each iteration is exactly one "run" message per worker
    deltas = [b - a for a, b in zip(marks, marks[1:])]
    assert deltas[-1] == n and deltas[-2] == n, (marks, deltas)
    assert marks[0] > n                       # cold iteration paid the plan
    ref, _, _ = _run(lambda wf, a: [_chains(wf, a, 2, mix_at=(1,))
                                    for _ in range(5)], n)
    for a, b in zip(ref, vals):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# failure mechanics: respawn after SIGKILL, heartbeats, hang detection
# ---------------------------------------------------------------------------

def test_sigkill_respawns_worker_and_recovers():
    n = 2
    build = lambda wf, arrs: _chains(wf, arrs, 5, mix_at=(2,))
    ref, _, _ = _run(build, n)
    _run(build, n, backend="procs")           # warm the shared 2-rank pool
    pool = procs_mod._POOLS[n]
    pid_before = pool.procs[1].pid
    for r in pool.alive_ranks():              # satellite: supervisor protocol
        assert heartbeat_age(pool.hb_path(r), pool.spawned_at[r]) < 60.0
    inj = FaultInjector.kill_rank(1, 2)
    vals, st, ex = _run(build, n, inj, backend="procs")
    for a, b in zip(ref, vals):
        np.testing.assert_array_equal(a, b)
    assert st.recoveries == 1
    assert inj.fired and inj.fired[0]["kind"] == "kill"
    assert pool.procs[1].pid != pid_before    # transient death => respawn
    assert pool.alive[1]


def test_hung_worker_heartbeat_timeout_is_permanent():
    # rank 1's worker wedges inside an op body (no SIGKILL — the process
    # stays alive but stops heartbeating); the frontend must detect the
    # stale heartbeat, kill it, and decommission permanently (PR-6 rebind)
    n = 3
    build = lambda wf, arrs: _chains(wf, arrs, 3, step=_hang_step)
    ref, _, _ = _run(build, n)                # frontend rank is None: no hang
    backend = ProcessPoolBackend(heartbeat_timeout=1.0,
                                 heartbeat_interval=0.1)
    vals, st, ex = _run(build, n, backend=backend)
    for a, b in zip(ref, vals):
        np.testing.assert_array_equal(a, b)
    assert st.recoveries == 1
    assert 1 in ex._decommissioned            # hang == permanent
    assert not ex._stores[1]
    assert all(1 not in ranks for ranks in ex._where.values())


# ---------------------------------------------------------------------------
# graceful degradation: unpicklable op functions fall back to serial
# ---------------------------------------------------------------------------

def test_unpicklable_fn_falls_back_to_serial():
    @bind.op
    def local_step(c: bind.InOut, s: bind.In):  # closure: not picklable
        return c * 2.0 + s

    def build(wf, arrs):
        for _ in range(3):
            for r, a in enumerate(arrs):
                with bind.node(r):
                    local_step(a, 1.0)

    ref, ref_st, _ = _run(build, 2)
    vals, st, _ = _run(build, 2, backend="procs")
    for a, b in zip(ref, vals):
        np.testing.assert_array_equal(a, b)
    assert st.transfers == ref_st.transfers
    assert st.recoveries == 0


# ---------------------------------------------------------------------------
# satellite: threads dispatch-cost threshold
# ---------------------------------------------------------------------------

def test_threads_inline_small_levels():
    n = 2
    build = lambda wf, arrs: _chains(wf, arrs, 4, mix_at=(1,))
    ref, _, _ = _run(build, n)

    small = ThreadPoolBackend()               # 8-float payloads ≪ threshold
    vals, _, _ = _run(build, n, backend=small)
    for a, b in zip(ref, vals):
        np.testing.assert_array_equal(a, b)
    # every level is below break-even, so the whole plan now delegates to
    # the serial tight loop before per-level inlining even gets a look-in
    assert small.plans_delegated > 0 and small.pooled_levels == 0

    forced = ThreadPoolBackend(dispatch_threshold=0)   # 0 disables inlining
    vals, _, _ = _run(build, n, backend=forced)
    for a, b in zip(ref, vals):
        np.testing.assert_array_equal(a, b)
    assert forced.pooled_levels > 0 and forced.inlined_levels == 0


# ---------------------------------------------------------------------------
# satellite: Topology.calibrate fits measured samples exactly
# ---------------------------------------------------------------------------

def test_topology_calibrate_recovers_constants():
    from repro.launch.mesh import make_topology

    topo = make_topology("flat", 4)
    rate, alpha, beta = 2e9, 2e-6, 1.0 / 5e9
    samples = [{"flops": f, "seconds": f / rate}
               for f in (1e6, 4e6, 9e6)]
    samples += [{"nbytes": b, "hops": h, "seconds": h * alpha + b * beta}
                for b, h in ((1 << 10, 1), (1 << 20, 1), (1 << 20, 3))]
    fit = topo.calibrate(samples)
    assert fit.flops_per_s == pytest.approx(rate, rel=1e-9)
    assert fit.latency_s == pytest.approx(alpha, rel=1e-6)
    assert fit.bandwidth_Bps == pytest.approx(1.0 / beta, rel=1e-6)
    assert fit.kind == "flat" and fit.n_nodes == 4

    # compute-only samples must leave the transfer constants untouched
    fit2 = topo.calibrate([{"flops": 1e6, "seconds": 1e-3}])
    assert fit2.latency_s == topo.latency_s
    assert fit2.bandwidth_Bps == topo.bandwidth_Bps
