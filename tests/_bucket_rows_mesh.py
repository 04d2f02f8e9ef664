"""Four-device check of the lazy rows a mesh backend's bucket reads inside
its executable; run as a script with four host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``) by
``test_bucket_rows.py``.  Prints ``OK``."""

import numpy as np
import jax
import jax.numpy as jnp

from repro import core as bind
from repro.core.backends.mesh import MeshBackend
from repro.linalg.distributed import (distributed_gemm_listing1,
                                      make_distributed_inputs)

RANKS = 4


def _scale(a, s):
    return a * s


_scale.__bind_intents__ = (bind.InOut, bind.In)


def _consume(x, out):
    return out * 0.5 + x


_consume.__bind_intents__ = (bind.In, bind.InOut)


def _tile(v):
    return jnp.arange(16, dtype=jnp.float32).reshape(4, 4) / 7.0 + v


def _per_rank(backend):
    """On every rank, two buckets of lazy rows, then consumers reading a
    permuted subset of one, rows of both and a concrete payload."""
    ex = bind.LocalExecutor(RANKS, backend=backend)
    with bind.Workflow(n_nodes=RANKS, executor=ex) as wf:
        src = {(r, k): [wf.array(_tile(100 * r + 10 * j + i), rank=r)
                        for i in range(4)]
               for r in range(RANKS) for j, k in enumerate("xyz")}
        for k, s in (("x", 2.0), ("y", 3.0)):
            for r in range(RANKS):
                with bind.node(r):
                    for a in src[r, k]:
                        wf.call(_scale, (a, s), name="scale")
            wf.sync()
            ex.flush()
        reads = [("x", 3), ("y", 1), ("x", 0), ("z", 2), ("y", 2)]
        outs = {}
        for r in range(RANKS):
            outs[r] = [wf.array(_tile(-r - j), rank=r)
                       for j in range(len(reads))]
            with bind.node(r):
                for (k, i), o in zip(reads, outs[r]):
                    wf.call(_consume, (src[r, k][i], o), name="consume")
        wf.sync()
        ex.flush()
        if isinstance(backend, MeshBackend):
            assert not backend.misplaced(ex), backend.misplaced(ex)[:4]
        return [np.asarray(wf.fetch(o)) for r in range(RANKS)
                for o in outs[r]]


def check_per_rank_rows() -> None:
    mb = MeshBackend()
    got = _per_rank(mb)
    for g, w in zip(got, _per_rank("serial")):
        np.testing.assert_array_equal(g, w)
    assert mb.rows_gathered == RANKS * 4, mb.rows_gathered
    assert mb.columns_unplaced == 0, mb.columns_unplaced


def check_listing1() -> None:
    rng = np.random.default_rng(3)
    A = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))
    B = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))
    mb = MeshBackend()
    ex = bind.LocalExecutor(RANKS, backend=mb)
    with bind.Workflow(n_nodes=RANKS, executor=ex) as wf:
        a, b, c = make_distributed_inputs(wf, A, B, ib=8, NP=2, NQ=2)
        distributed_gemm_listing1(wf, a, b, c, 2, 2)
        wf.sync()
        ex.flush()
        assert mb.ships_lowered > 0 and mb.ships_simulated == 0
        assert not mb.misplaced(ex), mb.misplaced(ex)[:4]
        out = c.to_array()
    assert mb.rows_gathered > 0 and mb.columns_unplaced == 0, (
        mb.rows_gathered, mb.columns_unplaced)
    ref = jnp.dot(A, B, precision="highest")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def main() -> None:
    assert len(jax.devices()) == RANKS, jax.devices()
    check_per_rank_rows()
    check_listing1()
    print("OK")


if __name__ == "__main__":
    main()
