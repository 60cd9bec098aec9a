"""TimeRipple pair-collapse flash attention Pallas TPU kernel.

This is the TPU-native execution of the paper's reuse (DESIGN.md §4).
Operands arrive pair-split: ``x_even``/``x_odd`` hold the window
representatives and followers of adjacent window-2 pairs.  Two per-block
scalar flag vectors (SMEM, scalar-prefetched) mark blocks whose pairs are
*fully* snapped:

* ``k_flags[b, ki] == 1`` → every K pair in block ki is value-identical:
  the kernel computes **one** score matmul (q·k_evenᵀ) with softmax
  multiplicity 2 and **one** AV matmul against (v_even + v_odd) — the
  exact collapse identity — instead of two of each.
* ``q_flags[b, qi] == 1`` → every Q pair in block qi is value-identical:
  the odd-row state is never computed; the even-row output is copied at
  the end.

Fully-collapsed (q, k) block pairs therefore run 2 MXU matmuls instead
of 8 — a real 75% skip, not the paper's proportional estimate.  Mixed
blocks fall back to dense-snapped compute and stay bit-exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_LANES = 128


def _rowmax(s):
    return jnp.max(s, axis=1, keepdims=True)


def _ripple_kernel(
    q_flags_ref, k_flags_ref,          # scalar prefetch (SMEM)
    q_e_ref, q_o_ref, k_e_ref, k_o_ref, v_e_ref, v_o_ref,
    o_e_ref, o_o_ref,
    m_e, l_e, acc_e, m_o, l_o, acc_o,
    *, scale: float, nk: int,
):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    qf = q_flags_ref[b, qi]
    kf = k_flags_ref[b, ki]

    @pl.when(ki == 0)
    def _init():
        for m, l, a in ((m_e, l_e, acc_e), (m_o, l_o, acc_o)):
            m[...] = jnp.full_like(m, -jnp.inf)
            l[...] = jnp.zeros_like(l)
            a[...] = jnp.zeros_like(a)

    k_e = k_e_ref[...]
    v_e = v_e_ref[...]

    def dot(a, b_, transpose_b=True):
        dims = (((1,), (1,)), ((), ())) if transpose_b else (((1,), (0,)), ((), ()))
        return jax.lax.dot_general(a, b_, dims, preferred_element_type=jnp.float32)

    def update_half(q, m, l, acc):
        """One online-softmax update for one row-parity half."""
        s_ee = dot(q, k_e) * scale  # always needed: representative columns

        @pl.when(kf == 1)
        def _collapsed():
            m_prev = m[...][:, :1]
            m_new = jnp.maximum(m_prev, _rowmax(s_ee))
            p = jnp.exp(s_ee - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l[...] = jnp.broadcast_to(
                alpha * l[...][:, :1] + 2.0 * jnp.sum(p, axis=1, keepdims=True),
                l.shape)
            v_sum = (v_e + v_o_ref[...]).astype(jnp.float32)
            acc[...] = acc[...] * alpha + dot(p, v_sum, transpose_b=False)
            m[...] = jnp.broadcast_to(m_new, m.shape)

        @pl.when(kf == 0)
        def _dense():
            k_o = k_o_ref[...]
            v_o = v_o_ref[...]
            s_eo = dot(q, k_o) * scale
            m_prev = m[...][:, :1]
            m_new = jnp.maximum(m_prev,
                                jnp.maximum(_rowmax(s_ee), _rowmax(s_eo)))
            p_ee = jnp.exp(s_ee - m_new)
            p_eo = jnp.exp(s_eo - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l[...] = jnp.broadcast_to(
                alpha * l[...][:, :1]
                + jnp.sum(p_ee, axis=1, keepdims=True)
                + jnp.sum(p_eo, axis=1, keepdims=True),
                l.shape)
            acc[...] = (acc[...] * alpha
                        + dot(p_ee, v_e.astype(jnp.float32), transpose_b=False)
                        + dot(p_eo, v_o.astype(jnp.float32), transpose_b=False))
            m[...] = jnp.broadcast_to(m_new, m.shape)

    update_half(q_e_ref[...], m_e, l_e, acc_e)

    @pl.when(qf == 0)
    def _odd_rows():
        update_half(q_o_ref[...], m_o, l_o, acc_o)

    @pl.when(ki == nk - 1)
    def _finish():
        out_e = (acc_e[...] / l_e[...][:, :1]).astype(o_e_ref.dtype)
        o_e_ref[...] = out_e

        @pl.when(qf == 1)
        def _copy():
            o_o_ref[...] = out_e  # followers reuse the representative row

        @pl.when(qf == 0)
        def _own():
            o_o_ref[...] = (acc_o[...] / l_o[...][:, :1]).astype(o_o_ref.dtype)


def ripple_attention_kernel(
    q_even, q_odd, k_even, k_odd, v_even, v_odd,
    q_flags, k_flags,
    *, scale: float, block_q: int = 128, block_k: int = 128,
    interpret: bool = False,
):
    """All pair-split operands: (BH, Npairs, d/dv); flags (BH, nblocks) int32.

    Returns (o_even, o_odd): (BH, Nq_pairs, dv) each.
    """
    BH, nq_pairs, d = q_even.shape
    nk_pairs = k_even.shape[1]
    dv = v_even.shape[2]
    block_q = min(block_q, nq_pairs)
    block_k = min(block_k, nk_pairs)
    assert nq_pairs % block_q == 0 and nk_pairs % block_k == 0
    nq = nq_pairs // block_q
    nk = nk_pairs // block_k
    assert q_flags.shape == (BH, nq) and k_flags.shape == (BH, nk)

    kernel = functools.partial(_ripple_kernel, scale=scale, nk=nk)
    grid = (BH, nq, nk)

    def qmap(b, qi, ki, *_):
        return (b, qi, 0)

    def kmap(b, qi, ki, *_):
        return (b, ki, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), qmap),
            pl.BlockSpec((None, block_q, d), qmap),
            pl.BlockSpec((None, block_k, d), kmap),
            pl.BlockSpec((None, block_k, d), kmap),
            pl.BlockSpec((None, block_k, dv), kmap),
            pl.BlockSpec((None, block_k, dv), kmap),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, dv), qmap),
            pl.BlockSpec((None, block_q, dv), qmap),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, nq_pairs, dv), q_even.dtype),
            jax.ShapeDtypeStruct((BH, nq_pairs, dv), q_even.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q_flags, k_flags, q_even, q_odd, k_even, k_odd, v_even, v_odd)
