"""Tiled online-softmax (flash) attention Pallas TPU kernel.

Baseline kernel every attention call in the framework can route through.
Grid: (batch·heads, q_blocks, k_blocks) with the k dimension innermost
("arbitrary" semantics) carrying running max / sum / accumulator in VMEM
scratch.  Block shapes are MXU-aligned (multiples of 128 on the token
dims; head_dim padded to 128 by the ops wrapper when needed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    m_prev = m_ref[...][:, :1]                      # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)       # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                          # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                 # (bq, 1)
    l_new = alpha * l_ref[...][:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :1]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """q: (BH, Nq, d), k: (BH, Nk, d), v: (BH, Nk, dv) -> (BH, Nq, dv).

    Nq/Nk must be divisible by the block sizes (ops.py pads).
    """
    BH, Nq, d = q.shape
    Nk = k.shape[1]
    dv = v.shape[2]
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    block_q = min(block_q, Nq)
    block_k = min(block_k, Nk)
    nq = Nq // block_q
    nk = Nk // block_k
    assert Nq % block_q == 0 and Nk % block_k == 0, (Nq, Nk, block_q, block_k)

    kernel = functools.partial(_flash_kernel, scale=scale, nk=nk)
    grid = (BH, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((None, block_k, dv), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, dv), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Nq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
