"""Multi-device self-test for core.lowering — run as a subprocess.

``python -m repro.launch.selftest_collectives`` forces 8 fake CPU devices
(BEFORE importing jax) and validates every collective schedule in
``repro.core.lowering`` against the psum/broadcast oracle under shard_map.
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
from repro.core import lowering  # noqa: E402


def _run_1d(fn, x, n=8):
    mesh = jax.make_mesh((n,), ("i",))
    f = jax.shard_map(
        fn, mesh=mesh, in_specs=P("i"), out_specs=P("i"), check_vma=False
    )
    return np.asarray(jax.jit(f)(x))


def main() -> None:
    rng = np.random.default_rng(0)

    for n in (8,):
        for shape in ((8, 4), (8, 16, 3)):
            x = rng.normal(size=shape).astype(np.float32)
            per = x.reshape(n, -1)
            total = per.sum(axis=0)

            # tree_allreduce == sum on every rank
            out = _run_1d(lambda v: lowering.tree_allreduce(v, "i"), x)
            np.testing.assert_allclose(
                out.reshape(n, -1), np.tile(total, (n, 1)), rtol=1e-5
            )

            # tree_reduce: rank 0 row holds the sum
            out = _run_1d(lambda v: lowering.tree_reduce(v, "i"), x)
            np.testing.assert_allclose(out.reshape(n, -1)[0], total, rtol=1e-5)

            # tree_broadcast: everyone ends with rank 0's row
            out = _run_1d(lambda v: lowering.tree_broadcast(v, "i"), x)
            np.testing.assert_allclose(
                out.reshape(n, -1), np.tile(per[0], (n, 1)), rtol=1e-6
            )

            # ring == psum oracle
            out = _run_1d(lambda v: lowering.ring_allreduce(v, "i"), x)
            np.testing.assert_allclose(
                out.reshape(n, -1), np.tile(total, (n, 1)), rtol=1e-5
            )

    # hierarchical on a (2,4) mesh == psum over both axes
    mesh = jax.make_mesh((2, 4), ("pod", "data"))
    x = rng.normal(size=(8, 4)).astype(np.float32)  # 8 = 2*4 shards of (1,4)

    def hier(v):
        return lowering.hierarchical_allreduce(v, "data", "pod", scatter_dimension=1)

    f = jax.shard_map(
        hier, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
        check_vma=False,
    )
    out = np.asarray(jax.jit(f)(x))
    total = x.reshape(8, 1, 4).sum(axis=0)
    np.testing.assert_allclose(out.reshape(8, 1, 4), np.tile(total, (8, 1, 1)), rtol=1e-5)

    # allreduce_by_schedule dispatch: all three agree on a (2,4) mesh
    for schedule in lowering.GRAD_SYNC_SCHEDULES:
        def sync(v, s=schedule):
            return lowering.allreduce_by_schedule(
                v, s, data_axes=("pod", "data")
            )

        f = jax.shard_map(
            sync, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
            check_vma=False,
        )
        out = np.asarray(jax.jit(f)(x))
        np.testing.assert_allclose(
            out.reshape(8, 1, 4), np.tile(total, (8, 1, 1)), rtol=1e-5,
            err_msg=f"schedule={schedule}",
        )

    # sync_gradients over a pytree, mean semantics
    grads = {
        "w": rng.normal(size=(8, 4)).astype(np.float32),
        "b": rng.normal(size=(8,)).astype(np.float32),
    }

    def sync_tree(g):
        return lowering.sync_gradients(g, "hierarchical", ("pod", "data"))

    f = jax.shard_map(
        sync_tree, mesh=mesh,
        in_specs=({"w": P(("pod", "data")), "b": P(("pod", "data"))},),
        out_specs={"w": P(("pod", "data")), "b": P(("pod", "data"))},
        check_vma=False,
    )
    out = jax.jit(f)(grads)
    np.testing.assert_allclose(
        np.asarray(out["w"]).reshape(8, 1, 4),
        np.tile(grads["w"].reshape(8, 1, 4).mean(axis=0), (8, 1, 1)),
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(out["b"]).reshape(8, 1),
        np.tile(grads["b"].reshape(8, 1).mean(axis=0), (8, 1)),
        rtol=1e-5,
    )

    print("OK")


if __name__ == "__main__":
    main()
