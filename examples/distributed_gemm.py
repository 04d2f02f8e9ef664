"""Paper Listing 1, both ways, in one process:

1. the Bind-model version on simulated nodes (implicit transfers, explicit
   log-reduction tree, execution stats), and
2. the TPU lowering via shard_map over the devices that exist (a 1x1 mesh
   on one device), tree vs ring reduction schedules.  The same lowering on
   8 fake CPU devices is ``python -m repro.launch.selftest_distgemm``.

    PYTHONPATH=src python examples/distributed_gemm.py
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def bind_version() -> None:
    from repro.launch.mesh import make_topology
    from repro.linalg.distributed import run_distributed_gemm

    rng = np.random.default_rng(0)
    NP = NQ = 2
    A = rng.normal(size=(128, 128))
    B = rng.normal(size=(128, 128))
    topo = make_topology("ring", NP * NQ)
    for backend in ("serial", "threads", "fused"):
        out, stats, est = run_distributed_gemm(
            A, B, ib=32, NP=NP, NQ=NQ, backend=backend, topology=topo)
        np.testing.assert_allclose(out, A @ B, rtol=1e-9)
        print(f"[bind]  4 nodes, backend={backend:7s}: "
              f"{stats.message_count} implicit transfers, "
              f"{stats.bytes_transferred/1e6:.2f} MB, "
              f"critical path {stats.critical_path}, "
              f"est. comm makespan {est*1e6:.1f} us on a ring")


def shardmap_version() -> None:
    import jax
    from repro.linalg.distributed import distributed_gemm_shardmap

    rng = np.random.default_rng(0)
    A = rng.normal(size=(64, 32)).astype(np.float32)
    B = rng.normal(size=(32, 48)).astype(np.float32)
    n = len(jax.devices())
    q = 2 if n % 2 == 0 else 1
    mesh = jax.make_mesh((n // q, q), ("p", "q"))
    for schedule in ("tree", "ring"):
        fn = distributed_gemm_shardmap(mesh, schedule=schedule)
        # f32 passes on a TPU too (its default matmul precision is bf16)
        with jax.default_matmul_precision("highest"):
            out = np.asarray(fn(A, B))
        np.testing.assert_allclose(out, A @ B, rtol=2e-4, atol=2e-4)
        print(f"[tpu lowering] {mesh.devices.shape} mesh, "
              f"schedule={schedule}: OK")


def main() -> None:
    bind_version()
    shardmap_version()
    print("OK")


if __name__ == "__main__":
    main()
