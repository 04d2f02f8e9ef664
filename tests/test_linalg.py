"""Paper §IV-A: tiled Strassen + Listing-1 distributed GEMM vs numpy oracle."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import core as bind
from repro.linalg import Tiled, gemm_strassen
from repro.linalg.distributed import (
    distributed_gemm_listing1,
    make_distributed_inputs,
    owner_rank,
)
from repro.linalg.strassen import strassen_flops
from repro.linalg.tiles import gemm_tiles


def _random(m, n, rng, dtype=np.float64):
    return rng.normal(size=(m, n)).astype(dtype)


# ---------------------------------------------------------------------------
# Tiles container
# ---------------------------------------------------------------------------

def test_tiles_roundtrip(rng):
    A = _random(12, 8, rng)
    with bind.Workflow() as wf:
        t = Tiled.from_array(wf, A, ib=4)
        np.testing.assert_allclose(t.to_array(), A)


def test_tiles_subset_iadd(rng):
    A, B = _random(8, 8, rng), _random(8, 8, rng)
    with bind.Workflow() as wf:
        ta = Tiled.from_array(wf, A, ib=4)
        tb = Tiled.from_array(wf, B, ib=4)
        view = ta.subset(0, 0, 1, 2)   # top half
        view += tb.subset(1, 0, 1, 2)  # += bottom half of B
        out = ta.to_array()
    exp = A.copy()
    exp[:4] += B[4:]
    np.testing.assert_allclose(out, exp)


def test_classical_tiled_gemm(rng):
    A, B = _random(8, 12, rng), _random(12, 4, rng)
    with bind.Workflow() as wf:
        ta = Tiled.from_array(wf, A, ib=4)
        tb = Tiled.from_array(wf, B, ib=4)
        tc = Tiled.zeros(wf, 2, 1, 4)
        gemm_tiles(ta, tb, tc)
        np.testing.assert_allclose(tc.to_array(), A @ B, rtol=1e-10)


# ---------------------------------------------------------------------------
# Strassen (Fig. 2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nt,ib", [(2, 4), (4, 4), (8, 2)])
def test_strassen_matches_numpy(nt, ib, rng):
    n = nt * ib
    A, B = _random(n, n, rng), _random(n, n, rng)
    with bind.Workflow() as wf:
        ta = Tiled.from_array(wf, A, ib=ib)
        tb = Tiled.from_array(wf, B, ib=ib)
        tc = Tiled.zeros(wf, nt, nt, ib)
        gemm_strassen(ta, tb, tc)
        np.testing.assert_allclose(tc.to_array(), A @ B, rtol=1e-9)


def test_strassen_flop_savings_and_parallelism(rng):
    """Depth-d recursion does 7^d leaf gemms (vs 8^d classical) and the DAG
    exposes them as wide wavefronts — the paper's Fig. 2 mechanism."""
    nt, ib = 4, 2
    n = nt * ib
    A, B = _random(n, n, rng), _random(n, n, rng)
    with bind.Workflow() as wf:
        ta = Tiled.from_array(wf, A, ib=ib)
        tb = Tiled.from_array(wf, B, ib=ib)
        tc = Tiled.zeros(wf, nt, nt, ib)
        gemm_strassen(ta, tb, tc)
        ex = bind.LocalExecutor(1)
        ex.run(wf)
    n_leaf_gemms = sum(1 for op in wf.ops if op.name == "gemm")
    assert n_leaf_gemms == 7 ** 2          # two recursion levels
    assert ex.stats.max_parallelism >= 49  # all leaves in one wavefront
    assert strassen_flops(n, ib) == 49 * 2 * ib ** 3


def test_strassen_leaf_cutoff(rng):
    """leaf_nt>1 stops the recursion early (the paper tunes this trade-off)."""
    nt, ib = 4, 2
    n = nt * ib
    A, B = _random(n, n, rng), _random(n, n, rng)
    with bind.Workflow() as wf:
        ta = Tiled.from_array(wf, A, ib=ib)
        tb = Tiled.from_array(wf, B, ib=ib)
        tc = Tiled.zeros(wf, nt, nt, ib)
        gemm_strassen(ta, tb, tc, leaf_nt=2)
        np.testing.assert_allclose(tc.to_array(), A @ B, rtol=1e-9)
    assert sum(1 for op in wf.ops if op.name == "gemm") == 7 * 8


# ---------------------------------------------------------------------------
# Distributed GEMM with logarithmic reduction (Listing 1, Fig. 3/4)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("NP,NQ,mt,nt,ib", [(2, 2, 4, 4, 4), (2, 4, 4, 8, 2), (1, 1, 2, 2, 4)])
def test_distributed_gemm_listing1(NP, NQ, mt, nt, ib, rng):
    M, K, N = mt * ib, nt * ib, nt * ib
    A, B = _random(M, K, rng), _random(K, N, rng)
    ex = bind.LocalExecutor(NP * NQ, collective_mode="tree")
    with bind.Workflow(n_nodes=NP * NQ, executor=ex) as wf:
        a, b, c = make_distributed_inputs(wf, A, B, ib, NP, NQ)
        distributed_gemm_listing1(wf, a, b, c, NP, NQ)
        np.testing.assert_allclose(c.to_array(), A @ B, rtol=1e-9)


def test_distributed_gemm_log_depth(rng):
    """The reduction of each output tile is a binary tree: with nt=8 partials
    the accumulation chain depth is log2(8)=3, not 7."""
    NP = NQ = 2
    nt = 8
    ib = 2
    A, B = _random(nt * ib, nt * ib, rng), _random(nt * ib, nt * ib, rng)
    ex = bind.LocalExecutor(NP * NQ)
    with bind.Workflow(n_nodes=NP * NQ, executor=ex) as wf:
        a, b, c = make_distributed_inputs(wf, A, B, ib, NP, NQ)
        distributed_gemm_listing1(wf, a, b, c, NP, NQ)
        wf.sync()
    # wavefront structure: pgemms (1) + log2(nt) reduction levels (+ final add)
    assert ex.stats.critical_path <= 1 + int(np.log2(nt)) + 1
    np.testing.assert_allclose(c.to_array(), A @ B, rtol=1e-9)


@given(
    np_=st.integers(1, 3), nq=st.integers(1, 3),
    mt=st.integers(1, 3), nt=st.integers(1, 3),
)
@settings(max_examples=12, deadline=None)
def test_distributed_gemm_property(np_, nq, mt, nt):
    """Any grid × any block partition computes the right product."""
    rng = np.random.default_rng(np_ * 100 + nq * 10 + mt)
    ib = 2
    A = rng.normal(size=(mt * ib, nt * ib))
    B = rng.normal(size=(nt * ib, nt * ib))
    with bind.Workflow(n_nodes=np_ * nq) as wf:
        a, b, c = make_distributed_inputs(wf, A, B, ib, np_, nq)
        distributed_gemm_listing1(wf, a, b, c, np_, nq)
        np.testing.assert_allclose(c.to_array(), A @ B, rtol=1e-8)


def test_owner_rank_matches_listing():
    assert owner_rank(3, 5, 2, 4) == (3 % 2) * 4 + 5 % 4  # == 5


def test_distributed_gemm_device_inputs_stay_on_device():
    """JAX inputs give device tiles in the input dtype: the result is a
    device array and every op ran compiled (no Python fallback)."""
    import jax
    import jax.numpy as jnp
    from repro.core.executable_cache import EXEC_CACHE
    from repro.linalg.distributed import run_distributed_gemm

    rng = np.random.default_rng(7)
    A = rng.normal(size=(48, 48)).astype(np.float32)
    B = rng.normal(size=(48, 48)).astype(np.float32)
    EXEC_CACHE.clear()
    out, stats, _ = run_distributed_gemm(jnp.asarray(A), jnp.asarray(B),
                                         ib=12, NP=2, NQ=2, backend="mesh")
    assert isinstance(out, jax.Array) and out.dtype == jnp.float32
    assert EXEC_CACHE.fallbacks == 0
    assert EXEC_CACHE.compiles > 0
    assert stats.ops_executed > 0
    np.testing.assert_allclose(np.asarray(out), A @ B, rtol=1e-4, atol=1e-4)
