"""Distributed classical GEMM with logarithmic reduction (paper Listing 1, Fig. 3/4).

Two implementations of the same algorithm:

* :func:`distributed_gemm_listing1` — the paper-faithful 18-line version over
  the Bind model: per-``j`` partial products placed on node
  ``(i % NP) * NQ + j % NQ``, accumulated by the explicit binary tree
  ``for (s = 1; s < nt; s *= 2)`` with the listing's slot rotation, executed
  by the LocalExecutor (validates semantics + collective accounting).

* :func:`distributed_gemm_shardmap` — the TPU lowering: the same partial-sum
  + log-reduction structure expressed as a ``shard_map`` over a (p, q) mesh,
  with the reduction schedule selectable (paper's binary tree vs the
  torus-native psum) — the unit of the §Perf collective ablation.
"""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import core as bind
from repro.core import lowering
from .tiles import Tiled, _t_iadd


def _p_gemm(a, b):
    return a @ b


def owner_rank(i: int, j: int, NP: int, NQ: int) -> int:
    """Paper's placement: ``bind::node p((i % NP) * NQ + j % NQ)``."""
    return (i % NP) * NQ + j % NQ


def distributed_gemm_listing1(
    wf: bind.Workflow, a: Tiled, b: Tiled, c: Tiled, NP: int, NQ: int
) -> None:
    """``c += a @ b`` exactly as the paper's Listing 1 (block loops elided to
    the per-tile level; the ``ii/kk`` blocking is a locality optimisation that
    does not change the DAG)."""
    nt = a.nt
    for i in range(c.mt):
        for k in range(c.nt):
            # slot w holds the partial of j = (w + k) % nt  (listing's rotation)
            r: list = [None] * nt
            for j in range(nt):
                with bind.node(owner_rank(i, j, NP, NQ)):
                    r[(nt - k + j) % nt] = wf.apply(
                        _p_gemm, (a.tile(i, j), b.tile(j, k)), name="pgemm"
                    )
            # logarithmic reduction: for (s = 1; s < nt; s *= 2)
            s = 1
            while s < nt:
                w = s
                while w < nt:
                    with bind.node((i % NP) * NQ + ((k + w - s) % nt) % NQ):
                        wf.call(_t_iadd, (r[w - s], r[w]), name="iadd")
                    w += s * 2
                s *= 2
            with bind.node(owner_rank(i, k, NP, NQ)):
                wf.call(_t_iadd, (c.tile(i, k), r[0]), name="iadd")


def make_distributed_inputs(wf: bind.Workflow, A, B, ib: int, NP: int,
                            NQ: int):
    """Tile + distribute operands the way the algorithm's placement expects
    (NumPy inputs give host tiles, JAX inputs device tiles)."""
    a = Tiled.from_array(wf, A, ib, "A", rank_of=lambda i, j: owner_rank(i, j, NP, NQ))
    b = Tiled.from_array(wf, B, ib, "B", rank_of=lambda j, k: owner_rank(k, j, NP, NQ))
    mt, nt = A.shape[0] // ib, B.shape[1] // ib
    c = Tiled.zeros(wf, mt, nt, ib, A.dtype, "C",
                    rank_of=lambda i, k: owner_rank(i, k, NP, NQ), xp=a.xp)
    return a, b, c


def run_distributed_gemm(
    A, B, *, ib: int, NP: int, NQ: int,
    collective_mode: str = "tree", backend="serial",
    topology=None,
) -> tuple:
    """Record + execute Listing 1 end-to-end on a chosen execution backend.

    Convenience driver for ablations: returns ``(C, stats, est_makespan)``
    where ``est_makespan`` is the simulated communication makespan under
    ``topology`` (``0.0`` when no topology is given).  ``backend`` is a
    :mod:`repro.core.backends` name or instance — all backends produce
    identical values and transfer streams, so this is the knob for timing
    comparisons only.  ``A``/``B`` are NumPy (host tiles, ``C`` NumPy) or
    JAX arrays (device tiles, ``C`` a JAX array — see
    :meth:`Tiled.to_array`).
    """
    ex = bind.LocalExecutor(NP * NQ, collective_mode=collective_mode,
                            backend=backend)
    with bind.Workflow(n_nodes=NP * NQ, executor=ex) as wf:
        a, b, c = make_distributed_inputs(wf, A, B, ib=ib, NP=NP, NQ=NQ)
        distributed_gemm_listing1(wf, a, b, c, NP, NQ)
        out = c.to_array()
    est = ex.stats.estimated_makespan(topology) if topology is not None else 0.0
    return out, ex.stats, est


# ---------------------------------------------------------------------------
# TPU lowering
# ---------------------------------------------------------------------------

def distributed_gemm_shardmap(
    mesh, *, schedule: str = "tree", p_axis: str = "p", q_axis: str = "q"
):
    """Build a jitted ``(A, B) -> A @ B`` over a (p, q) mesh.

    A is block-distributed ``(i→p, j→q)`` and B ``(j→q)`` — the exact data
    placement of Listing 1; each device computes its local partial GEMM and
    the ``q`` axis reduces it with the chosen schedule (``"tree"`` is the
    paper's logarithmic reduction, ``"ring"`` the torus-native psum).
    """

    def local(a_blk, b_blk):
        part = a_blk @ b_blk  # (M/p, N) partial over the q axis
        if schedule == "tree":
            part = lowering.tree_allreduce(part, q_axis)
        elif schedule == "ring":
            part = lax.psum(part, q_axis)
        else:
            raise ValueError(schedule)
        return part

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(p_axis, q_axis), P(q_axis, None)),
        out_specs=P(p_axis, None),
        check_vma=False,
    )
    return jax.jit(fn)
