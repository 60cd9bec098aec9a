"""Block-sparse masked flash-attention Pallas TPU kernel (DESIGN.md §12).

The dense flash kernel's online-softmax loop, driven by a per-
``(q_block, k_block)`` scalar-prefetched **block map** with three
states:

* ``SKIP`` (0)    — the tile contributes nothing: no score matmul, no
  softmax update, no AV matmul.  This is where a mask-emitting policy's
  modeled savings become real MXU skips.
* ``FULL`` (1)    — every entry of the tile is kept: dense tile on the
  mask-free fast path (the bias block is never read).
* ``PARTIAL`` (2) — the tile is mixed: dense tile plus the additive
  logit bias applied in-kernel (−inf entries drop exactly, matching the
  host-side masked softmax).

Two fetch-index tables make the skips pay in HBM traffic too, not just
MXU work: the K/V (and bias) index maps remap a skipped tile's block
index to the **last non-skipped** one, so consecutive grid steps over
skipped tiles resolve to the same block and the Pallas pipeline elides
the copy instead of streaming tiles the kernel would never read.

The state and both fetch indices travel packed into one int32 per tile
(:func:`pack_table`) in a flat scalar-prefetched table, and the kernel
runs one ``pallas_call`` per chunk of (batch·head) rows sized so that
chunk's table fits SMEM (1 MiB on v5e): at 33k tokens one row's table
alone is 266 KB, and three unpacked (BH, nq, nk) tables would need
~10 MB.

The running max is initialized to a large *finite* negative
(``_M_INIT``) rather than −inf so a partial tile whose entire row is
masked (bias −inf) still produces ``exp(−inf − m) == 0`` instead of
``exp(−inf + inf) == NaN``; rows that never meet a non-skipped tile end
with ``l == 0`` and emit zeros (the pure-jnp oracle in ``ref.py``
mirrors both conventions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_LANES = 128
# Finite stand-in for -inf in the running max: keeps exp(s - m) defined
# when every score of a partial tile's row is bias-masked to -inf.
_M_INIT = -1e30

# Block-map states (int32).
SKIP, FULL, PARTIAL = 0, 1, 2
# Packed tile entry: state in bits 0-1, the K/V fetch index in bits
# 2-16, the bias fetch index in bits 17-31.
_IDX_BITS = 15
_IDX_MASK = (1 << _IDX_BITS) - 1
# SMEM bytes one call's packed table may take (SMEM is 1 MiB on v5e).
_TABLE_BYTES = 512 * 1024


def pack_table(block_map, k_fetch, bias_fetch):
    """(state, k_fetch, bias_fetch) int32 tables -> one packed table."""
    return (block_map | (k_fetch << 2)
            | (bias_fetch << (2 + _IDX_BITS))).astype(jnp.int32)


def _state(e):
    return e & 3


def _k_fetch(e):
    return (e >> 2) & _IDX_MASK


def _bias_fetch(e):
    return (e >> (2 + _IDX_BITS)) & _IDX_MASK


def _sparse_kernel(tab_ref, q_ref, k_ref, v_ref, bias_ref,
                   *refs, scale: float, nq: int, nk: int, with_state: bool):
    if with_state:
        # Cross-hop accumulator convention (DESIGN.md §14): the running
        # (m, l, acc) softmax state enters as three carry inputs and
        # leaves as three extra outputs, so ring hops chain the online
        # softmax exactly as consecutive k-blocks do within one call.
        (m_in_ref, l_in_ref, acc_in_ref,
         o_ref, m_out_ref, l_out_ref, acc_out_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    state = _state(tab_ref[(b * nq + qi) * nk + ki])

    @pl.when(ki == 0)
    def _init():
        if with_state:
            m_ref[...] = m_in_ref[...]
            l_ref[...] = l_in_ref[...]
            acc_ref[...] = acc_in_ref[...]
        else:
            m_ref[...] = jnp.full_like(m_ref, _M_INIT)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    def scores():
        return jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    def update(s):
        """One online-softmax update on this tile's scores."""
        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[...][:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    # SKIP tiles fall through: no matmuls, no softmax-state update.
    @pl.when(state == FULL)
    def _full():
        update(scores())

    @pl.when(state == PARTIAL)
    def _partial():
        update(scores() + bias_ref[...].astype(jnp.float32))

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...][:, :1]
        # l == 0: every tile of the row was skipped / fully masked.
        out = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
        o_ref[...] = out.astype(o_ref.dtype)
        if with_state:
            m_out_ref[...] = m_ref[...]
            l_out_ref[...] = l_ref[...]
            acc_out_ref[...] = acc_ref[...]


def _tile(nq: int, nk: int):
    """Flat index of tile (b, qi, ki) in a chunk's packed table."""
    return lambda b, qi, ki: (b * nq + qi) * nk + ki


def _sparse_call(q, k, v, bias, tab, carry, *, base: int, rows: int,
                 scale: float, block_q: int, block_k: int, interpret: bool):
    """One ``pallas_call`` over the ``rows`` (batch·head) rows starting
    at ``base``: the full operands stay in HBM and the index maps add
    ``base``, so no operand is sliced or copied per chunk."""
    BH, Nq, d = q.shape
    dv = v.shape[2]
    nq = Nq // block_q
    nk = k.shape[1] // block_k
    tile = _tile(nq, nk)
    dummy_bias = bias.shape[0] == 1 and bias.shape[1:] == (block_q, block_k)
    with_state = carry is not None

    kernel = functools.partial(_sparse_kernel, scale=scale, nq=nq, nk=nk,
                               with_state=with_state)

    def qmap(b, qi, ki, tab_ref):
        return (base + b, qi, 0)

    def omap(b, qi, ki, tab_ref):
        return (b, qi, 0)

    def kvmap(b, qi, ki, tab_ref):
        return (base + b, _k_fetch(tab_ref[tile(b, qi, ki)]), 0)

    if dummy_bias:
        def biasmap(b, qi, ki, tab_ref):
            return (0, 0, 0)
    else:
        def biasmap(b, qi, ki, tab_ref):
            return (base + b, qi, _bias_fetch(tab_ref[tile(b, qi, ki)]))

    in_specs = [
        pl.BlockSpec((None, block_q, d), qmap),
        pl.BlockSpec((None, block_k, d), kvmap),
        pl.BlockSpec((None, block_k, dv), kvmap),
        pl.BlockSpec((None, block_q, block_k), biasmap),
    ]
    out_specs = pl.BlockSpec((None, block_q, dv), omap)
    out_shape = jax.ShapeDtypeStruct((rows, Nq, dv), q.dtype)
    operands = (tab, q, k, v, bias)
    if with_state:
        f32 = jnp.float32
        mspec = pl.BlockSpec((None, block_q, _LANES), qmap)
        accspec = pl.BlockSpec((None, block_q, dv), qmap)
        m_out = pl.BlockSpec((None, block_q, _LANES), omap)
        in_specs = in_specs + [mspec, mspec, accspec]
        out_specs = [out_specs, m_out, m_out, out_specs]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((rows, Nq, _LANES), f32),
                     jax.ShapeDtypeStruct((rows, Nq, _LANES), f32),
                     jax.ShapeDtypeStruct((rows, Nq, dv), f32)]
        operands = operands + tuple(carry)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)


def sparse_attention_kernel(
    q, k, v, bias, block_map, k_fetch, bias_fetch,
    *, scale: float, block_q: int = 128, block_k: int = 128,
    interpret: bool = False, carry=None,
):
    """q: (BH, Nq, d), k/v: (BH, Nk, d|dv), bias: (BH, Nq, Nk) f32 or a
    (1, block_q, block_k) zero dummy when no policy bias exists.

    block_map: (BH, nq, nk) int32 of SKIP/FULL/PARTIAL states.
    k_fetch / bias_fetch: (BH, nq, nk) int32 fetch-index tables — for
    each grid step the k-block (resp. bias-block) index to resident in
    VMEM; equal to ``ki`` wherever the state needs the block and to the
    last needed index elsewhere (so the pipeline elides the copy).

    Returns (BH, Nq, dv); with ``carry`` — a running-softmax
    ``(m, l, acc)`` triple of shapes ((BH, Nq, _LANES) f32 ×2,
    (BH, Nq, dv) f32) from a previous call — the online softmax resumes
    from that state instead of the fresh ``(_M_INIT, 0, 0)`` and the
    updated triple is returned alongside: ``(o, (m, l, acc))``.  This is
    the cross-hop accumulator convention of the ring driver
    (DESIGN.md §14): chaining calls over column slices of the key axis
    is the same online-softmax recurrence as the kernel's own k-block
    loop, so the final ``acc / l`` matches a single full-width call up
    to hop-ordering rounding.
    """
    BH, Nq, d = q.shape
    Nk = k.shape[1]
    dv = v.shape[2]
    assert Nq % block_q == 0 and Nk % block_k == 0, (Nq, Nk, block_q, block_k)
    nq = Nq // block_q
    nk = Nk // block_k
    assert block_map.shape == (BH, nq, nk), (block_map.shape, BH, nq, nk)
    assert nk <= _IDX_MASK + 1, f"{nk} key blocks overflow the packed table"
    carry_f = None
    if carry is not None:
        m_in, l_in, acc_in = carry
        assert m_in.shape == (BH, Nq, _LANES) and \
            l_in.shape == (BH, Nq, _LANES) and \
            acc_in.shape == (BH, Nq, dv), (m_in.shape, l_in.shape,
                                           acc_in.shape)
        carry_f = tuple(c.astype(jnp.float32) for c in carry)
    tab = pack_table(block_map, k_fetch, bias_fetch)
    per_row = nq * nk * 4
    chunk = max(1, min(BH, _TABLE_BYTES // per_row))
    parts = []
    for base in range(0, BH, chunk):
        rows = min(chunk, BH - base)
        parts.append(_sparse_call(
            q, k, v, bias, tab[base:base + rows].reshape(-1), carry_f,
            base=base, rows=rows, scale=scale, block_q=block_q,
            block_k=block_k, interpret=interpret))
    if carry is None:
        return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    o, m, l, acc = (jnp.concatenate(xs, axis=0) if len(xs) > 1 else xs[0]
                    for xs in zip(*parts))
    return o, (m, l, acc)
