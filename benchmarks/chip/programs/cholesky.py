"""Tiled Cholesky factorization, PLASMA's tile DPOTRF (Buttari, Langou,
Kurzak, Dongarra, Parallel Computing 35(1), 2009, arXiv:0709.1272), on one
rank, written through the public API.

One solve splits the SPD matrix ``A`` into tiles with
``Tiled.from_array``, records ``cholesky_tiles`` into a fresh ``Workflow``
on a ``LocalExecutor(backend=MeshBackend())``, fetches the factor ``C`` as
one device array and waits for it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import bind_counters
import reference_cholesky
from repro import core as bind
from repro.core.backends.mesh import MeshBackend
from repro.linalg.cholesky import cholesky_tiles
from repro.linalg.tiles import Tiled


def new_backend(cfg: dict):
    """The run's one backend: it keeps its compiled ship collectives from
    solve to solve."""
    return MeshBackend()


def solve(cfg: dict, a_in, span, backend):
    """One whole factorization of ``a_in``; returns ``(C, counters)``.
    Beside the backend's and executor's counters, ``single_op_wavefronts``
    counts the solve's levels of one op, the latency-bound ones."""
    before = bind_counters.backend_counts(backend)
    ex = bind.LocalExecutor(1, backend=backend)
    with bind.Workflow(n_nodes=1, executor=ex) as wf:
        with span("record"):
            a = Tiled.from_array(wf, a_in, cfg["ib"], "A")
            c = Tiled.like(a, "C")
            cholesky_tiles(wf, a, c)
        with span("flush"):
            C = c.to_array()
    with span("wait"):
        C.block_until_ready()
    counters = bind_counters.per_solve(before, backend, ex)
    counters["single_op_wavefronts"] = sum(
        w == 1 for w in ex.stats.wavefronts)
    return C, counters


def rehearsal(cfg: dict) -> dict:
    """The configuration at the CPU rehearsal's size: the tile grid kept,
    tiles of 32 x 32."""
    return dict(cfg, ib=32, n=32 * (cfg["n"] // cfg["ib"]))


def work(cfg: dict) -> dict:
    """LAPACK's DPOTRF count, ``n^3/3 + n^2/2 + n/6`` operations, and the
    lower triangle read once and the factor written once."""
    n, item = cfg["n"], 4
    return {"flops": (2 * n ** 3 + 3 * n ** 2 + n) // 6,
            "bytes": 2 * item * n * (n + 1) // 2}


@partial(jax.jit, static_argnums=(1, 2, 3))
def _spd(key, n: int, block: int, delta: float):
    """``G G^T / n + delta I`` with ``G`` standard normal, its product one
    row block at a time, and its upper triangle the mirror of its lower."""
    g = jax.random.normal(key, (n, n), jnp.float32)
    rows = jax.lax.map(
        lambda r: jnp.dot(r, g.T, precision=jax.lax.Precision.HIGHEST),
        g.reshape(n // block, block, n))
    a = rows.reshape(n, n) / n
    return (jnp.tril(a) + jnp.tril(a, -1).T
            + delta * jnp.eye(n, dtype=jnp.float32))


def make_inputs(cfg: dict, key, count: int) -> list:
    """``count`` SPD matrices, made on the device one after another."""
    return [_spd(k, cfg["n"], cfg["ib"], cfg["delta"])
            for k in jax.random.split(key, count)]


def check(cfg: dict, a, C) -> dict:
    """The numbers compared, each against ``cfg["limits"]`` under its name:
    the worst lower tile's relative error of ``C`` against the plain
    reference, and the backward error ``||C C^T - A|| / ||A||``."""
    return reference_cholesky.check(a, C, cfg["ib"])
