"""Fused adaLN-zero modulation Pallas kernel (DiT hot path).

DiT blocks apply, per token row x and per-sample conditioning vectors
(shift, scale, gate):

    y = LayerNorm(x) * (1 + scale) + shift         (pre-block)
    r = residual + gate * f(y)                     (post-block)

The pre-block form is fused here: one VMEM pass computes the
parameter-free LayerNorm statistics and the modulation, instead of four
HBM round trips.  Token rows tile the grid; conditioning vectors are
broadcast per sample.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _adaln_kernel(x_ref, shift_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (bt, d)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    norm = xc * jax.lax.rsqrt(var + eps)
    scale = scale_ref[...].astype(jnp.float32)  # (1, d)
    shift = shift_ref[...].astype(jnp.float32)
    o_ref[...] = (norm * (1.0 + scale) + shift).astype(o_ref.dtype)


def adaln_modulate_kernel(x: jax.Array, shift: jax.Array, scale: jax.Array,
                          *, eps: float = 1e-6, block_t: int = 256,
                          interpret: bool = False) -> jax.Array:
    """x: (B, N, d); shift/scale: (B, d) per-sample conditioning."""
    B, N, d = x.shape
    block_t = min(block_t, N)
    assert N % block_t == 0
    grid = (B, N // block_t)
    kernel = functools.partial(_adaln_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_t, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, d), lambda b, i: (b, 0)),
            pl.BlockSpec((None, d), lambda b, i: (b, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_t, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(x, shift, scale)
