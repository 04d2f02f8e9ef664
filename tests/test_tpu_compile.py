"""Compile-only tests for the TPU v5e: the device path's kernels at real widths.

Nothing runs here.  The TPU compiler, installed with jaxlib, compiles for a
``v5e:2x2`` host that is described, not attached, and refuses what the chip
would refuse (VMEM overflow, tiling the kernel cannot lower) — failures
interpret-mode tests cannot see.  Each kernel case asserts the compiled
program holds a Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  All such tests live in this one file so that one worker
loads it.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.executable_cache import chain_pallas_call
from repro.core.lowering import SHIP_SCHEDULES, broadcast_by_schedule
from repro.kernels.flash_attention.ops import attn_step, flash_attention
from repro.kernels.gemm.ops import gemm_tile, matmul
from repro.kernels.linear_scan.ops import linear_scan, scan_step


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("body", [scan_step, gemm_tile, attn_step],
                         ids=lambda f: f.__name__)
def test_chain_pallas_compiles_at_1024_f32(one_chip, body):
    """The engine's chain kernel: one 1024x1024 f32 carry, 8 levels, every
    exterior varying per level (the widest VMEM demand of each tag)."""
    levels = 8
    tile = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=one_chip)
    stacked = jax.ShapeDtypeStruct((levels, 1024, 1024), jnp.float32,
                                   sharding=one_chip)
    n_ext = len(body.__bind_intents__) - 1
    layout = ("single",) + ("xs",) * n_ext
    call = chain_pallas_call(body, layout, levels, 0, interpret=False)
    compiled = call.lower(tile, *([stacked] * n_ext)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_matmul_pallas_compiles_8192_bf16(one_chip):
    x = jax.ShapeDtypeStruct((8192, 8192), jnp.bfloat16, sharding=one_chip)
    _kernel_compiles(matmul, x, x)


def test_flash_attention_compiles_4096_bf16(one_chip):
    q = jax.ShapeDtypeStruct((1, 8, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)
    _kernel_compiles(flash_attention, q, q, q)


def test_linear_scan_compiles_4096x1024_f32(one_chip):
    x = jax.ShapeDtypeStruct((4, 4096, 1024), jnp.float32, sharding=one_chip)
    _kernel_compiles(linear_scan, x, x)


@pytest.mark.parametrize("schedule", SHIP_SCHEDULES)
def test_broadcast_schedule_compiles_on_4_chips(topo, schedule):
    """A lowered ship: the rooted broadcast under ``shard_map`` over the
    four described chips becomes collective-permutes."""
    mesh = Mesh(np.array(topo.devices[:4]), ("r",))
    spec = P("r", None)

    def body(x):
        return broadcast_by_schedule(x, schedule, "r", root=1, arity=2)

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                               out_specs=spec, check_vma=False))
    x = jax.ShapeDtypeStruct((4, 1024 * 1024), jnp.float32,
                             sharding=NamedSharding(mesh, spec))
    text = fn.lower(x).compile().as_text()
    assert "collective-permute" in text
