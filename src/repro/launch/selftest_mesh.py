"""Multi-device self-test for the mesh backend — run as a subprocess.

``python -m repro.launch.selftest_mesh`` forces 8 fake CPU devices (BEFORE
importing jax) and validates the device-mesh execution path end to end:

* the rooted broadcast schedules in ``repro.core.lowering`` (``tree`` /
  ``ring`` / ``hierarchical``) deliver the root's bits to every rank, for
  every root, under ``shard_map``;
* ``backend="mesh"`` replays a ship-heavy workflow with values AND the
  transfer-event stream byte-identical to serial while actually running
  the ships as collectives (``ships_lowered`` counter), under all three
  schedules;
* a kernel-tagged chain dispatches exactly ONE compiled pallas executable
  (``pallas_chains_dispatched`` / ``ExecutableCache.compiles``) with
  bitwise value parity against serial;
* Listing 1 on JAX inputs over 4 ranks places rank ``r``'s payloads on
  device ``r`` (initial tiles and shipped replicas alike) and matches the
  NumPy run.

Prints ``OK`` on success; any assertion failure exits nonzero.  Kept as a
module (not a test file) so the main pytest process keeps 1 device.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import core as bind  # noqa: E402
from repro.core import lowering  # noqa: E402
from repro.core.backends.mesh import MeshBackend  # noqa: E402
from repro.kernels.linear_scan.ops import scan_step  # noqa: E402
from repro.launch.mesh import make_topology  # noqa: E402

N = 8


def _run_1d(fn, x):
    mesh = jax.make_mesh((N,), ("i",))
    f = jax.shard_map(fn, mesh=mesh, in_specs=P("i"), out_specs=P("i"),
                  check_vma=False)
    return np.asarray(jax.jit(f)(x))


def _consume(x, out):
    return out + x


_consume.__bind_intents__ = (bind.In, bind.InOut)


def _scale(a, s):
    return a * s


_scale.__bind_intents__ = (bind.InOut, bind.In)


def check_rooted_broadcasts() -> None:
    """Every schedule × every root: rank r ends with root's row, bitwise."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(N, 16)).astype(np.float32)
    for schedule in lowering.SHIP_SCHEDULES:
        for root in range(N):
            out = _run_1d(
                lambda v, s=schedule, r=root: lowering.broadcast_by_schedule(
                    v, s, "i", root=r, arity=4), x)
            np.testing.assert_array_equal(
                out, np.tile(x[root], (N, 1)),
                err_msg=f"{schedule} root={root}")


def _ship_workflow(backend, topo=None):
    """One producer rank, seven consumer ranks — every read is a broadcast
    ship of a jax payload."""
    ex = bind.LocalExecutor(N, collective_mode="tree", mode="plan",
                            backend=backend, topology=topo)
    with bind.Workflow(n_nodes=N, executor=ex) as wf:
        x = wf.array(jnp.arange(64, dtype=jnp.float32), "x")
        outs = [wf.array(jnp.full(64, float(r), jnp.float32))
                for r in range(N - 1)]
        with bind.node(0):
            wf.call(_scale, (x, 2.0), name="scale")
        for r in range(N - 1):
            with bind.node(r + 1):
                wf.call(_consume, (x, outs[r]), name="consume")
        vals = [np.asarray(wf.fetch(o)) for o in outs]
    tr = [(e.version_key, e.src, e.dst, e.nbytes, e.round_id, e.collective,
           e.wavefront) for e in ex.stats.transfers]
    return vals, tr


def check_ship_lowering() -> None:
    ref_vals, ref_tr = _ship_workflow("serial")
    assert ref_tr, "reference workflow shipped nothing"
    topos = {"tree": None, "ring": make_topology("ring", N),
             "hierarchical": make_topology("fat-tree", N)}
    for schedule, topo in topos.items():
        mb = MeshBackend()
        vals, tr = _ship_workflow(mb, topo)
        assert mb._schedule_eff == schedule, (schedule, mb._schedule_eff)
        assert mb.ships_lowered > 0, f"{schedule}: nothing lowered"
        assert mb.ships_simulated == 0, f"{schedule}: fell back"
        assert tr == ref_tr, f"{schedule}: transfer stream diverged"
        for a, b in zip(vals, ref_vals):
            np.testing.assert_array_equal(a, b, err_msg=schedule)


def check_pallas_chain() -> None:
    depth = 8

    def run(backend, cache=None):
        ex = bind.LocalExecutor(1, mode="plan", backend=backend,
                                executable_cache=cache)
        with bind.Workflow(n_nodes=1, executor=ex) as wf:
            y = wf.array(jnp.linspace(0., 1., 16, dtype=jnp.float32), "y")
            for i in range(depth):
                x = wf.array(jnp.full(16, float(2 ** (i % 3)), jnp.float32))
                wf.call(scan_step, (y, 0.5, x), name="scan_step")
            return np.asarray(wf.fetch(y))

    cache = bind.ExecutableCache()
    mb = MeshBackend()          # pallas="auto": armed, 8 devices present
    out = run(mb, cache)
    ref = run("serial")
    np.testing.assert_array_equal(out, ref)
    assert mb.pallas_chains_dispatched == 1, mb.pallas_chains_dispatched
    assert mb.ops_pallas == depth
    assert cache.compiles == 1, cache.compiles   # ONE executable per chain
    assert mb.interpret


def check_rank_placement() -> None:
    from repro.linalg.distributed import (distributed_gemm_listing1,
                                          make_distributed_inputs)
    rng = np.random.default_rng(1)
    A = rng.normal(size=(32, 32)).astype(np.float32)
    B = rng.normal(size=(32, 32)).astype(np.float32)
    mb = MeshBackend()
    ex = bind.LocalExecutor(4, backend=mb)
    with bind.Workflow(n_nodes=4, executor=ex) as wf:
        a, b, c = make_distributed_inputs(wf, jnp.asarray(A), jnp.asarray(B),
                                          ib=8, NP=2, NQ=2)
        distributed_gemm_listing1(wf, a, b, c, 2, 2)
        out = c.to_array()
        assert mb.ships_lowered > 0 and mb.ships_simulated == 0, (
            mb.ships_lowered, mb.ships_simulated)
        assert not mb.misplaced(ex), mb.misplaced(ex)[:4]
        held = {r: len(ex._stores[r]) for r in range(4)}
        assert all(held.values()), held
    assert isinstance(out, jax.Array)
    np.testing.assert_allclose(np.asarray(out), A @ B, rtol=1e-4, atol=1e-4)


def main() -> None:
    assert len(jax.devices()) == N, jax.devices()
    check_rooted_broadcasts()
    check_ship_lowering()
    check_pallas_chain()
    check_rank_placement()
    print("OK")


if __name__ == "__main__":
    main()
