"""Compile-only checks of the serving path's Pallas kernels for a TPU v5e.

Each kernel is lowered and compiled by the TPU compiler for a *described*
v5e chip (no chip attached; nothing runs) at vdit-paper widths: B=1,
24 heads of 128, 32,768 video tokens on a (32, 32, 32) grid plus 256
text tokens, d_model 3072.  Interpret-mode tests cannot see what this
catches: unaligned blocks, bf16 ops the v5e VPU/EUP lack, vector shape
casts Mosaic refuses, and scalar-prefetch tables larger than SMEM.

The topology is described inside a module-scoped fixture — never at
import — so every test worker collects the same tests and only the one
running this file loads the TPU library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

B, H, D = 1, 24, 128
GRID = (32, 32, 32)
N_IMG = GRID[0] * GRID[1] * GRID[2]
N = N_IMG + 256
D_MODEL = 3072


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Compiles for a described chip cannot be read back from the
    # persistent cache; keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash.ops import flash_attention

    _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False),
             one_chip, *[((B, H, N, D), jnp.bfloat16)] * 3)


def test_ripple_attention_compiles(one_chip):
    from repro.kernels.ripple.ops import ripple_attention_pallas

    _compile(lambda q, k, v: ripple_attention_pallas(q, k, v,
                                                     interpret=False),
             one_chip, *[((B, H, N, D), jnp.bfloat16)] * 3)


@pytest.mark.parametrize("dtype,grid,d,granularity", [
    (jnp.bfloat16, GRID, D, "channel"),
    (jnp.bfloat16, GRID, D, "token"),
    (jnp.float32, GRID, D, "channel"),
    (jnp.bfloat16, (8, 16, 16), 64, "channel"),
])
def test_fused_reuse_snap_compiles(one_chip, dtype, grid, d, granularity):
    from repro.kernels.reuse_mask.ops import fused_reuse_snap

    n = grid[0] * grid[1] * grid[2]
    _compile(lambda x, th: fused_reuse_snap(x, th, grid=grid,
                                            granularity=granularity,
                                            interpret=False),
             one_chip, ((B, H, n, d), dtype), ((3,), jnp.float32))


def test_reuse_snap_compiles(one_chip):
    from repro.kernels.reuse_mask.ops import reuse_snap

    _compile(lambda x, th: reuse_snap(x, th, interpret=False), one_chip,
             ((B, H, N_IMG, D), jnp.bfloat16), ((), jnp.float32))


@pytest.mark.parametrize("n", [N, 8192])
def test_sparse_attention_compiles(one_chip, n):
    from repro.kernels.sparse.ops import sparse_attention_pallas

    nb = -(-n // 128)
    _compile(lambda q, k, v, m: sparse_attention_pallas(
        q, k, v, block_map=m, interpret=False), one_chip,
        *[((B, H, n, D), jnp.bfloat16)] * 3, ((B, H, nb, nb), jnp.int32))


def test_adaln_modulate_compiles(one_chip):
    from repro.kernels.adaln.ops import adaln_modulate

    _compile(lambda x, s, c: adaln_modulate(x, s, c, interpret=False),
             one_chip, ((B, N, D_MODEL), jnp.bfloat16),
             ((B, D_MODEL), jnp.bfloat16), ((B, D_MODEL), jnp.bfloat16))
