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

from repro.core.executable_cache import bucket_call, chain_pallas_call
from repro.core.lowering import SHIP_SCHEDULES, broadcast_by_schedule
from repro.kernels.flash_attention.ops import attn_step, flash_attention
from repro.kernels.gemm.ops import gemm_tile, matmul
from repro.kernels.linear_scan.ops import linear_scan, scan_step
from repro.linalg.cholesky import _t_potrf, _t_trsm
from repro.linalg.tiles import _t_add, _t_iadd


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


# a fused bucket whose columns read lazy rows of 1024^2 f32 tiles: Listing
# 1's reduction level (256 rows of a 512-row buffer per column) and a
# Strassen add (rows of two buffers interleaved with concrete tiles)
BUCKET_ROWS = {
    "one_source": (_t_iadd, 256, ((512, 0), (512, 0))),
    "two_sources_and_tiles": (_t_add, 120, ((343, 100, 16), (49, 49, 0))),
}


@pytest.mark.parametrize("case", sorted(BUCKET_ROWS))
def test_bucket_reading_rows_compiles_at_1024_f32(one_chip, case):
    """The rows are copied out one by one: the compiled bucket holds no
    copy of a whole source buffer, only its stacked columns."""
    fn, n, columns = BUCKET_ROWS[case]

    def tiles(*lead):
        return jax.ShapeDtypeStruct(lead + (1024, 1024), jnp.float32,
                                    sharding=one_chip)

    layout, args = [], []
    for *buffers, n_tiles in columns:
        layout.append(("rows", len(buffers), n_tiles))
        args += [tiles(rows) for rows in buffers] + [tiles()] * n_tiles
        args.append(jax.ShapeDtypeStruct((n, 2), jnp.int32,
                                         sharding=one_chip))
    compiled = bucket_call(fn, tuple(layout), n).lower(*args).compile()
    column_bytes = n * 1024 * 1024 * 4
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= len(columns) * column_bytes + (64 << 20))


# the Cholesky panel at its cell's tiles, 1024^2 f32 at ``highest``: the
# diagonal tile's factor alone, and the first step's trsm bucket (19 tiles,
# the diagonal factor stacked with each)
PANEL = {
    "potrf": (_t_potrf, None, 1, 1),
    "trsm": (_t_trsm, ("flat", "flat"), 19, 38),
}


@pytest.mark.parametrize("case", sorted(PANEL))
def test_cholesky_panel_compiles_at_1024_f32(one_chip, case):
    fn, layout, n, n_tiles = PANEL[case]
    tile = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=one_chip)
    call = jax.jit(fn) if layout is None else bucket_call(fn, layout, n)
    with jax.default_matmul_precision("highest"):
        out = call.lower(*[tile] * n_tiles).compile().memory_analysis()
    assert out.output_size_in_bytes == n * 1024 * 1024 * 4


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
