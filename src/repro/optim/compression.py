"""Gradient compression for scarce cross-pod links: int8 block quantisation
with error feedback.

Cross-pod all-reduce is the one collective whose bandwidth does not scale
with pod count (§Perf).  Block-wise symmetric int8 quantisation cuts those
bytes 4× (fp32) / 2× (bf16); the quantisation residual is fed back into the
next step's gradient (error feedback), which keeps SGD convergence intact
(Karimireddy et al. 2019) — property-tested in tests/test_optim.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


BLOCK = 256


def _pad_flat(x: jax.Array) -> tuple[jax.Array, int]:
    flat = x.reshape(-1)
    pad = (-flat.size) % BLOCK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x (any shape, float) -> (int8 codes, per-block fp32 scales)."""
    flat, _ = _pad_flat(x.astype(jnp.float32))
    blocks = flat.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    safe = jnp.where(scale == 0, 1.0, scale)
    codes = jnp.clip(jnp.round(blocks / safe), -127, 127).astype(jnp.int8)
    return codes, scale[:, 0]


def dequantize_int8(codes: jax.Array, scale: jax.Array, shape, dtype=jnp.float32):
    blocks = codes.astype(jnp.float32) * scale[:, None]
    flat = blocks.reshape(-1)
    n = 1
    for s in shape:
        n *= s
    return flat[:n].reshape(shape).astype(dtype)


def compressed_allreduce(
    x: jax.Array, axis_name: str, *, error: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """All-reduce `x` over `axis_name` moving int8 on the wire, with error
    feedback.

    Per-block scales make a direct int8 psum ill-defined, so the schedule is
    all-gather(int8 codes + fp32 scales) → local dequantise-and-sum: received
    bytes ≈ n·B/4 instead of ring-fp32's ≈ 2·B — a real 4× (pod=2: 8×) cut
    on the cross-pod hop this is used for.  Returns (mean fp32, residual).
    """
    xf = x.astype(jnp.float32)
    if error is not None:
        xf = xf + error
    codes, scale = quantize_int8(xf)          # codes: (nb, BLOCK) int8
    q = dequantize_int8(codes, scale, xf.shape)
    new_error = xf - q                         # what compression lost
    n = lax.axis_size(axis_name)
    all_codes = lax.all_gather(codes, axis_name)      # (n, nb, BLOCK) s8
    all_scales = lax.all_gather(scale, axis_name)     # (n, nb) f32
    blocks = all_codes.astype(jnp.float32) * all_scales[..., None]
    flat = blocks.sum(axis=0).reshape(-1)
    size = 1
    for s in xf.shape:
        size *= s
    summed = flat[:size].reshape(xf.shape)
    return summed / n, new_error
