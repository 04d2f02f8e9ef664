"""The plain reference of the Cholesky factorization and the comparison
that decides ``correct``.

The reference is ``jax.lax.linalg.cholesky`` of the whole matrix in
float32 under ``jax.default_matmul_precision("highest")``, reading the
lower triangle as LAPACK's DPOTRF does, on JAX's CPU backend: that is
LAPACK's SPOTRF, an implementation independent of the chip's, and it
compiles at once where the TPU's Cholesky takes about two minutes to
compile at N = 20480.  It imports nothing of the system under test.  The
comparison gives two numbers:

* ``tile_rel_err``: the worst lower ``ib x ib`` tile's relative Frobenius
  error ``||C_t - R_t||_F / ||R_t||_F`` against the reference, so that one
  wrong tile is not averaged away by the others.  The factor's upper tiles
  must be exact zeros: a non-zero one reads as infinitely wrong;
* ``residual_rel``: the backward error ``||C C^T - A||_F / ||A||_F``, one
  row block at a time at ``HIGHEST`` on the device that holds ``A``, as
  HPL-style checks compute it.

A number that is not finite reads as ``inf``: NaN would pass no ``>``
test, and a NaN factor must fail.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@jax.jit
def _cholesky(a):
    return jax.lax.linalg.cholesky(a, symmetrize_input=False)


def host(x):
    """``x`` on JAX's CPU device."""
    return jax.device_put(x, jax.devices("cpu")[0])


def cholesky(a):
    """The lower Cholesky factor of ``a`` in f32, from its lower triangle,
    computed on the CPU."""
    with jax.default_matmul_precision("highest"):
        return _cholesky(host(a))


@partial(jax.jit, static_argnums=2)
def _tile_errors(c, ref, ib: int):
    nt = ref.shape[0] // ib

    def tiles(x):
        return x.reshape(nt, ib, nt, ib).astype(jnp.float32)

    diff = jnp.sqrt(jnp.sum(jnp.square(tiles(c) - tiles(ref)), axis=(1, 3)))
    norm = jnp.sqrt(jnp.sum(jnp.square(tiles(ref)), axis=(1, 3)))
    upper_nonzero = jnp.any(tiles(c) != 0, axis=(1, 3))
    lower = jnp.tril(jnp.ones((nt, nt), bool))
    errs = jnp.where(lower, diff / norm,
                     jnp.where(upper_nonzero, jnp.inf, 0.0))
    return jnp.max(jnp.where(jnp.isfinite(errs), errs, jnp.inf))


@partial(jax.jit, static_argnums=2)
def _residual(a, c, block: int):
    def row_block(start):
        rows = jax.lax.dynamic_slice_in_dim(c, start, block)
        prod = jax.lax.dot_general(rows, c, (((1,), (1,)), ((), ())),
                                   precision=HIGHEST)
        err = prod - jax.lax.dynamic_slice_in_dim(a, start, block)
        return jnp.sum(jnp.square(err))

    starts = jnp.arange(0, a.shape[0], block)
    err = jnp.sqrt(jnp.sum(jax.lax.map(row_block, starts)))
    rel = err / jnp.sqrt(jnp.sum(jnp.square(a)))
    return jnp.where(jnp.isfinite(rel), rel, jnp.inf)


def check(a, c, ib: int) -> dict:
    """``tile_rel_err`` and ``residual_rel`` of the factor ``c`` of ``a``."""
    c = jnp.asarray(c)
    tile_rel_err = float(_tile_errors(host(c), cholesky(a), ib))
    return {"tile_rel_err": tile_rel_err,
            "residual_rel": float(_residual(a, c, ib))}
