"""TimeRipple pair-collapse flash attention Pallas TPU kernel.

This is the TPU-native execution of the paper's reuse (DESIGN.md §4).
Operands arrive pair-split: ``x_even``/``x_odd`` hold the window
representatives and followers of adjacent window-2 pairs.  One grid step
covers a compute tile of ``tile_q × tile_k`` pairs; the collapse flags
(SMEM, scalar-prefetched) are coarser on the Q side than on the K side:

* ``k_flags[b, j] == 1`` → every K pair in the ``block_k``-pair flag
  block j is value-identical: the kernel computes **one** score matmul
  (q·k_evenᵀ) with softmax multiplicity 2 and **one** AV matmul against
  (v_even + v_odd) — the exact collapse identity — instead of two of
  each.  A K tile none of whose flag blocks is collapsed takes one
  online-softmax update over the whole tile; any other walks its flag
  blocks one at a time.
* ``q_flags[b, qi] == 1`` → every Q pair of Q tile qi is
  value-identical: the odd-row state is never computed; the even-row
  output is copied at the end.

Fully-collapsed (q, k) block pairs therefore run 2 MXU matmuls instead
of 8 — a real 75% skip, not the paper's proportional estimate.  Mixed
blocks fall back to dense-snapped compute and stay bit-exact.  Pairs
past ``n_pairs`` are zero padding: padded keys are masked on the last K
tile, padded query rows are computed and dropped by the wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_LANES = 128
_VMEM_DEFAULT = 16 * 2 ** 20   # Mosaic's scoped-VMEM limit when none is set


def _vmem_bytes(tq: int, tk: int, d: int, dv: int, itemsize: int) -> int:
    """VMEM a grid step needs: double-buffered operand and output
    tiles, the six scratch accumulators, and the f32 score and
    probability tiles of both key halves."""
    tiles = 2 * (2 * tq * d + 2 * tk * (d + dv) + 2 * tq * dv) * itemsize
    scratch = 4 * (4 * tq * _LANES + 2 * tq * dv)
    scores = 4 * 4 * tq * tk
    return tiles + scratch + scores


def _rowmax(s):
    return jnp.max(s, axis=1, keepdims=True)


def _dot(a, b, transpose_b=True):
    dims = (((1,), (1,)), ((), ())) if transpose_b else (((1,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _online_update(q, ks, vs, m, l, acc, *, scale, weight, limit):
    """One online-softmax update of one row-parity half against the key
    tiles ``ks`` (values ``vs``), each key counted ``weight`` times in
    the softmax sum.  ``limit``: None, or the number of real keys in the
    tiles; columns from it on are padding and get no weight."""
    ss = [_dot(q, k) * scale for k in ks]
    if limit is not None:
        col = jax.lax.broadcasted_iota(jnp.int32, ss[0].shape, 1)
        ss = [jnp.where(col < limit, s, -jnp.inf) for s in ss]
    m_prev = m[...][:, :1]
    m_new = functools.reduce(jnp.maximum, [_rowmax(s) for s in ss], m_prev)
    ps = [jnp.exp(s - m_new) for s in ss]
    alpha = jnp.exp(m_prev - m_new)
    rowsum = functools.reduce(
        jnp.add, [jnp.sum(p, axis=1, keepdims=True) for p in ps])
    if weight != 1:
        rowsum = weight * rowsum
    l[...] = jnp.broadcast_to(alpha * l[...][:, :1] + rowsum, l.shape)
    pv = functools.reduce(jnp.add, [
        _dot(p.astype(v.dtype), v, transpose_b=False) for p, v in zip(ps, vs)])
    acc[...] = acc[...] * alpha + pv
    m[...] = jnp.broadcast_to(m_new, m.shape)


def _ripple_kernel(
    q_flags_ref, k_flags_ref,          # scalar prefetch (SMEM)
    q_e_ref, q_o_ref, k_e_ref, k_o_ref, v_e_ref, v_o_ref,
    o_e_ref, o_o_ref,
    m_e, l_e, acc_e, m_o, l_o, acc_o,
    *, scale: float, nk: int, block_k: int, n_pairs: int,
):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    tile_k = k_e_ref.shape[0]
    nsub = tile_k // block_k
    padded = nk * tile_k != n_pairs
    qf = q_flags_ref[b, qi]
    n_collapsed = functools.reduce(
        jnp.add, [k_flags_ref[b, ki * nsub + j] for j in range(nsub)])

    @pl.when(ki == 0)
    def _init():
        for m, l, a in ((m_e, l_e, acc_e), (m_o, l_o, acc_o)):
            m[...] = jnp.full_like(m, -jnp.inf)
            l[...] = jnp.zeros_like(l)
            a[...] = jnp.zeros_like(a)

    def update_half(q_ref, m, l, acc):
        q = q_ref[...]
        update = functools.partial(_online_update, m=m, l=l, acc=acc,
                                   scale=scale)

        def whole_tile(limit):
            update(q, (k_e_ref[...], k_o_ref[...]),
                   (v_e_ref[...], v_o_ref[...]), weight=1, limit=limit)

        if padded:
            last = ki == nk - 1
            pl.when((n_collapsed == 0) & jnp.logical_not(last))(
                lambda: whole_tile(None))
            pl.when((n_collapsed == 0) & last)(
                lambda: whole_tile(n_pairs - ki * tile_k))
        else:
            pl.when(n_collapsed == 0)(lambda: whole_tile(None))

        @pl.when(n_collapsed > 0)
        def _flag_blocks():
            def body(j, carry):
                rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
                limit = (n_pairs - ki * tile_k - j * block_k) if padded else None
                k_e = k_e_ref[rows, :]
                v_e = v_e_ref[rows, :]

                @pl.when(k_flags_ref[b, ki * nsub + j] == 1)
                def _collapsed():
                    v_sum = (v_e.astype(jnp.float32)
                             + v_o_ref[rows, :].astype(jnp.float32))
                    update(q, (k_e,), (v_sum.astype(v_e.dtype),),
                           weight=2, limit=limit)

                @pl.when(k_flags_ref[b, ki * nsub + j] == 0)
                def _dense():
                    update(q, (k_e, k_o_ref[rows, :]), (v_e, v_o_ref[rows, :]),
                           weight=1, limit=limit)

                return carry

            jax.lax.fori_loop(0, nsub, body, 0)

    update_half(q_e_ref, m_e, l_e, acc_e)

    @pl.when(qf == 0)
    def _odd_rows():
        update_half(q_o_ref, m_o, l_o, acc_o)

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
    *, scale: float, n_pairs: int, tile_q: int, tile_k: int, block_k: int,
    interpret: bool = False,
):
    """Pair-split operands (BH, Npairs, d/dv), zero-padded to tile
    multiples, of which the first ``n_pairs`` are real.  ``q_flags``
    (BH, Npairs/tile_q) flags whole Q tiles, ``k_flags``
    (BH, Npairs/block_k) flags ``block_k``-pair blocks; both int32.

    Returns (o_even, o_odd): (BH, Nq_pairs, dv) each.
    """
    BH, nq_pairs, d = q_even.shape
    nk_pairs = k_even.shape[1]
    dv = v_even.shape[2]
    assert nq_pairs % tile_q == 0 and nk_pairs % tile_k == 0
    assert tile_k % block_k == 0
    assert nk_pairs - tile_k < n_pairs <= min(nq_pairs, nk_pairs)
    nq = nq_pairs // tile_q
    nk = nk_pairs // tile_k
    assert q_flags.shape == (BH, nq)
    assert k_flags.shape == (BH, nk_pairs // block_k)

    vmem = _vmem_bytes(tile_q, tile_k, d, dv, q_even.dtype.itemsize)
    kernel = functools.partial(_ripple_kernel, scale=scale, nk=nk,
                               block_k=block_k, n_pairs=n_pairs)
    grid = (BH, nq, nk)

    def qmap(b, qi, ki, *_):
        return (b, qi, 0)

    def kmap(b, qi, ki, *_):
        return (b, ki, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, tile_q, d), qmap),
            pl.BlockSpec((None, tile_q, d), qmap),
            pl.BlockSpec((None, tile_k, d), kmap),
            pl.BlockSpec((None, tile_k, d), kmap),
            pl.BlockSpec((None, tile_k, dv), kmap),
            pl.BlockSpec((None, tile_k, dv), kmap),
        ],
        out_specs=[
            pl.BlockSpec((None, tile_q, dv), qmap),
            pl.BlockSpec((None, tile_q, dv), qmap),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_q, _LANES), jnp.float32),
            pltpu.VMEM((tile_q, _LANES), jnp.float32),
            pltpu.VMEM((tile_q, dv), jnp.float32),
            pltpu.VMEM((tile_q, _LANES), jnp.float32),
            pltpu.VMEM((tile_q, _LANES), jnp.float32),
            pltpu.VMEM((tile_q, dv), jnp.float32),
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
            vmem_limit_bytes=None if vmem <= _VMEM_DEFAULT else 2 * vmem,
        ),
        interpret=interpret,
    )(q_flags, k_flags, q_even, q_odd, k_even, k_odd, v_even, v_odd)
