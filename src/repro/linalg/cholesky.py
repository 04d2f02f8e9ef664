"""Tiled Cholesky factorization over Bind: the right-looking tile algorithm
of PLASMA's DPOTRF (Buttari, Langou, Kurzak, Dongarra, "A class of parallel
tiled linear algebra algorithms for multicore architectures", Parallel
Computing 35(1), 2009).

``A = L Lᵀ`` for a symmetric positive-definite ``A`` held as an ``nt × nt``
grid of device tiles.  Step ``k`` factors the diagonal tile, solves the panel
below it against that factor, and updates the trailing lower tiles; every
tile operation is one Bind transaction, so the whole factorization is one
DAG of ``5 nt - 4`` wavefronts whose critical path runs through a single
``potrf`` per step, beside update levels whose width falls from
``nt (nt - 1) / 2`` to 1.

The trailing update uses the very tile transactions of the GEMM programs:
the partial product ``distributed._p_gemm`` and the accumulation
``tiles._t_isub``, looked up on their modules each time a factorization is
recorded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import core as bind
from . import distributed, tiles
from .tiles import Tiled


def _t_potrf(a):
    """The lower Cholesky factor of one diagonal tile (its lower triangle
    is read, as DPOTRF reads it)."""
    return jnp.linalg.cholesky(a, symmetrize_input=False)


_t_potrf.__bind_intents__ = (bind.In,)


def _t_trsm(a, l):
    """``a l⁻ᵀ`` for a lower-triangular ``l``: the panel tile of the factor,
    from ``l xᵀ = aᵀ``."""
    return jax.scipy.linalg.solve_triangular(l, a.T, lower=True).T


_t_trsm.__bind_intents__ = (bind.In, bind.In)


def _t_transpose(out, a):
    return a.T


_t_transpose.__bind_intents__ = (bind.Out, bind.In)


@functools.cache
def _into(fn):
    """``fn`` as an op that writes its result into its first argument, a
    workspace tile (``Out``), under ``fn``'s own name."""
    def op(out, *args):
        return fn(*args)

    op.__name__ = fn.__name__
    op.__bind_intents__ = (bind.Out, bind.In, bind.In)
    return op


def cholesky_tiles(wf: bind.Workflow, a: Tiled, c: Tiled) -> None:
    """Record the factorization ``A = L Lᵀ`` of the SPD matrix in ``a``'s
    lower tiles (its upper tiles are not read).

    Every tile of ``c`` is replaced: the lower ones by the tiles of ``L``,
    the upper ones by one zero tile.  ``a``'s lower tiles below the first
    column are overwritten by the trailing updates.  For ``k = 0 .. nt-1``::

        L_kk = potrf(A_kk)
        L_ik = A_ik L_kk⁻ᵀ                   (trsm, i > k)
        T_j = L_jkᵀ                         (transpose, j > k)
        P_ij = L_ik T_j;  A_ij -= P_ij       (pgemm + isub, i >= j > k)

    ``T_j`` and ``P_ij`` are workspace tiles, written anew at each step:
    the executor keeps every array's newest version until the flush ends,
    so a fresh array per product would hold all of them (1520 tiles at
    ``nt = 20``), where workspaces hold one per tile.  The diagonal update
    is a full GEMM, not a SYRK: it computes both triangles of
    ``L_jk L_jkᵀ``, about 7% more FLOPs than LAPACK's count at ``nt = 20``.
    Each op records its FLOPs (``OpNode.flops``).
    """
    nt, ib = a.nt, a.ib
    assert a.mt == nt and (c.mt, c.nt) == (nt, nt), "square grids only"
    p_gemm, t_isub = _into(distributed._p_gemm), tiles._t_isub
    zero = c.xp.zeros((ib, ib), c.dtype)   # no op writes a payload in place
    for i in range(nt):
        for j in range(i + 1, nt):
            c.set_tile(i, j, wf.array(zero, f"{c.name}[{i},{j}]"))
    t = {j: wf.array(zero, f"T[{j}]") for j in range(1, nt)}
    p = {(i, j): wf.array(zero, f"P[{i},{j}]")
         for j in range(1, nt) for i in range(j, nt)}
    for k in range(nt):
        c.set_tile(k, k, wf.apply(
            _t_potrf, (a.tile(k, k),), name="potrf",
            flops=(2 * ib ** 3 + 3 * ib ** 2 + ib) // 6))
        for i in range(k + 1, nt):
            c.set_tile(i, k, wf.apply(
                _t_trsm, (a.tile(i, k), c.tile(k, k)), name="trsm",
                flops=ib ** 3))
        for j in range(k + 1, nt):
            wf.call(_t_transpose, (t[j], c.tile(j, k)), name="transpose")
        for j in range(k + 1, nt):
            for i in range(j, nt):
                wf.call(p_gemm, (p[i, j], c.tile(i, k), t[j]), name="pgemm",
                        flops=2 * ib ** 3)
                wf.call(t_isub, (a.tile(i, j), p[i, j]), name="isub",
                        flops=ib ** 2)
