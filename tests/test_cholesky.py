"""Tiled Cholesky (PLASMA's tile DPOTRF) over Bind against NumPy float64,
on the serial, fused and mesh backends."""

from collections import Counter
from math import comb

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as bind
from repro.linalg.cholesky import cholesky_tiles
from repro.linalg.tiles import Tiled

N = 256
BACKENDS = ("serial", "fused", "mesh")


def _spd(n, seed, delta=1.0):
    """``G Gᵀ / n + delta I`` in float64, G standard normal: eigenvalues in
    about ``[delta, delta + 4]``."""
    g = np.random.default_rng(seed).standard_normal((n, n))
    return g @ g.T / n + delta * np.eye(n)


def _factor(a64, ib, backend):
    """The factor of ``a64`` (made f32 on the device), and the executor."""
    ex = bind.LocalExecutor(1, backend=backend)
    with bind.Workflow(n_nodes=1, executor=ex) as wf:
        a = Tiled.from_array(wf, jnp.asarray(a64, jnp.float32), ib, "A")
        c = Tiled.like(a, "C")
        cholesky_tiles(wf, a, c)
        out = np.asarray(c.to_array(), np.float64)
    return out, ex, wf


@pytest.fixture(scope="module")
def factors():
    """Each backend's factor of one seeded input at each tile size."""
    a64 = _spd(N, 7)
    return a64, {(ib, b): _factor(a64, ib, b)[0]
                 for ib in (32, 64) for b in BACKENDS}


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("ib", [32, 64])
def test_factor_matches_numpy_float64(factors, ib, backend):
    a64, got = factors
    out = got[(ib, backend)]
    ref = np.linalg.cholesky(a64)
    # the upper tiles are exact zeros; the lower agree with float64 to f32
    # rounding: the input's own rounding to f32 moves the factor by ~3e-8
    # of its norm (eigenvalues in [1, 5], so the factor's condition is
    # about sqrt(5)), the factorization's f32 arithmetic by a few times
    # that (5e-8 read at n = 256).  5e-7 leaves ten times that room; a
    # bf16 pass (~4e-3) or one lost update fails it by orders
    assert np.all(np.triu(out, 1) == 0.0)
    rel = np.linalg.norm(out - ref) / np.linalg.norm(ref)
    assert rel < 5e-7, rel
    # backward error, the residual that HPL-style checks use (7e-8 read)
    res = np.linalg.norm(out @ out.T - a64) / np.linalg.norm(a64)
    assert res < 5e-7, res


@pytest.mark.parametrize("ib", [32, 64])
def test_backends_agree(factors, ib):
    _, got = factors
    serial = got[(ib, "serial")]
    # the same tile ops in f32: batching under vmap may change the order
    # of a reduction inside a kernel, never the algorithm, so the backends
    # agree to a few f32 ulps of the factor's entries (at most 1.5 here,
    # where an ulp is 1.2e-7)
    for backend in ("fused", "mesh"):
        np.testing.assert_allclose(got[(ib, backend)], serial,
                                   rtol=0, atol=1e-6)


@pytest.mark.parametrize("nt", [1, 2, 3, 4, 8])
def test_op_counts_and_wavefronts(nt):
    ib = 8
    _, ex, wf = _factor(_spd(nt * ib, 3), ib, "fused")
    ops = Counter(op.name for op in wf.ops)
    assert ops == Counter({"potrf": nt, "trsm": comb(nt, 2),
                   "transpose": comb(nt, 2), "pgemm": comb(nt + 1, 3),
                   "isub": comb(nt + 1, 3)})
    widths = ex.stats.wavefronts
    # five levels a step (potrf, trsm, transpose, pgemm, isub), the last
    # step a lone potrf; levels of one op: each potrf, and the whole step
    # with one tile below the diagonal
    assert len(widths) == (5 * nt - 4 if nt > 1 else 1)
    assert sum(w == 1 for w in widths) == (nt + 4 if nt > 1 else 1)
    assert sum(widths) == sum(ops.values())


def test_recorded_flops_cover_lapacks_count():
    nt, ib = 4, 8
    n = nt * ib
    _, _, wf = _factor(_spd(n, 5), ib, "serial")
    recorded = sum(op.flops for op in wf.ops)
    lapack = n ** 3 / 3 + n ** 2 / 2 + n / 6
    # the full-GEMM diagonal update and the trsm panels: more, never less
    assert lapack <= recorded <= 1.5 * lapack


def test_non_spd_input_gives_non_finite_factor():
    a64 = _spd(64, 11)
    a64[40, 40] = -1.0
    out, _, _ = _factor(a64, 16, "fused")
    assert not np.all(np.isfinite(out))


def test_one_tile_is_the_tiles_own_factor():
    """One tile: the factor is ``jnp.linalg.cholesky`` of the tile."""
    a64 = _spd(16, 2)
    out, _, _ = _factor(a64, 16, "serial")
    ref = jax.device_get(jnp.linalg.cholesky(jnp.asarray(a64, jnp.float32)))
    np.testing.assert_array_equal(out, np.asarray(ref, np.float64))
