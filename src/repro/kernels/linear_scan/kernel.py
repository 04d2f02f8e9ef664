"""Pallas TPU chunked linear-scan kernel: y_t = a_t ⊙ y_{t-1} + x_t.

The recurrence behind RG-LRU (RecurrentGemma) and the sLSTM cell/normaliser
states.  GPU implementations lean on warp-level shuffles; the TPU-native
adaptation is *chunked*: the sequence is cut into VMEM-resident blocks, a
log-depth (Hillis–Steele) scan runs **inside** the block on the VPU, and a tiny
(1, d) carry persists in VMEM scratch across the sequential grid sweep —
sequential dependencies cross blocks only through that carry, so HBM traffic
is exactly one read of (a, x) and one write of y.

grid = (batch, seq/bs); the seq axis is innermost and iterated in order
(TPU grids are sequential), which is what makes the carry trick legal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block_scan(a, x):
    """Inclusive prefix scan of the (a, x) affine maps down axis 0.

    Hillis–Steele, log2(rows) steps: each step composes every row with the
    row ``k`` above it, brought in by a sublane rotation (``pltpu.roll``);
    rows ``< k`` have nothing above and keep their value.  Only full-block
    rolls and selects, so Mosaic lowers it (an in-kernel
    ``lax.associative_scan`` slices down to zero-size vectors, which it
    refuses).
    """
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    k = 1
    while k < a.shape[0]:
        a_up = pltpu.roll(a, k, 0)
        x_up = pltpu.roll(x, k, 0)
        has_up = row >= k
        # (a, x) ∘ (a_up, x_up): y = a*(a_up*y_prev + x_up) + x
        x = jnp.where(has_up, a * x_up + x, x)
        a = jnp.where(has_up, a * a_up, a)
        k *= 2
    return a, x


def _linear_scan_kernel(a_ref, x_ref, y_ref, h_ref, *, n_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)      # (bs, d)
    x = x_ref[0].astype(jnp.float32)      # (bs, d)
    # In-block prefix scan (log2(bs) VPU steps):
    #   y_t = A_t * h_in + X_t with (A, X) = scan of (a, x)
    A, X = _block_scan(a, x)
    h_in = h_ref[...]                     # (1, d)
    y = A * h_in + X
    y_ref[0] = y.astype(y_ref.dtype)
    h_ref[...] = y[-1:, :]


def linear_scan_pallas(
    a: jax.Array,   # (B, S, D) decay gates
    x: jax.Array,   # (B, S, D) inputs
    *,
    bs: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, s, d = a.shape
    assert x.shape == a.shape
    bs = min(bs, s)
    assert s % bs == 0, (s, bs)
    n_chunks = s // bs
    kernel = functools.partial(_linear_scan_kernel, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=(b, n_chunks),
        in_specs=[
            pl.BlockSpec((1, bs, d), lambda bb, c: (bb, c, 0)),
            pl.BlockSpec((1, bs, d), lambda bb, c: (bb, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, d), lambda bb, c: (bb, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        interpret=interpret,
    )(a, x)
