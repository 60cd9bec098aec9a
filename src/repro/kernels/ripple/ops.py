"""Jitted wrapper for the ripple (pair-collapse) attention kernel.

Accepts standard (B, H, N, d) snapped operands, derives the per-block
collapse flags from value equality, pair-splits, pads to block multiples
(padded K pairs attend to nothing via a flag channel), runs the kernel,
and re-interleaves the two output halves.

Also exports :func:`ripple_block_stats` so benchmarks can report the
fraction of MXU work the kernel actually skipped (the *structural*
savings, as opposed to the paper's partial-score accounting).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ripple.kernel import ripple_attention_kernel
from repro.kernels.ripple.ref import block_flags, split_pairs
from repro.utils.platform import on_tpu

_PAD_NEG = -1e9


@functools.partial(jax.jit,
                   static_argnames=("window", "block_q", "block_k", "interpret"))
def ripple_attention_pallas(q, k, v, *, bias: Optional[jax.Array] = None,
                            window: int = 2, block_q: int = 128,
                            block_k: int = 128,
                            interpret: bool | None = None):
    """q,k,v: (B, H, N, d) snapped operands -> (B, H, N, dv)."""
    assert bias is None, "ripple kernel path does not take a bias"
    assert window == 2, "kernel implements the paper's window-2 sweet spot"
    if interpret is None:
        interpret = not on_tpu()
    B, H, N, d = q.shape
    dv = v.shape[-1]
    assert N % 2 == 0, "pair-collapse needs an even token count"
    scale = float(1.0 / (d ** 0.5))

    qf = q.reshape(B * H, N, d)
    kf = k.reshape(B * H, N, d)
    vf = v.reshape(B * H, N, dv)
    q_e, q_o = split_pairs(qf)
    k_e, k_o = split_pairs(kf)
    v_e, v_o = split_pairs(vf)

    P = N // 2
    bq = min(block_q, P)
    bk = min(block_k, P)
    Pq = -(-P // bq) * bq
    Pk = -(-P // bk) * bk

    def pad(x, target):
        padw = target - x.shape[1]
        if padw <= 0:
            return x
        return jnp.pad(x, ((0, 0), (0, padw), (0, 0)))

    q_e, q_o = pad(q_e, Pq), pad(q_o, Pq)
    k_e, k_o, v_e, v_o = pad(k_e, Pk), pad(k_o, Pk), pad(v_e, Pk), pad(v_o, Pk)
    if Pk != P or Pq != P:
        # flag channel: queries project 1, padded keys project −1e9.
        ones_q = jnp.ones((B * H, Pq, 1), q_e.dtype)
        flag_k = jnp.zeros((B * H, Pk, 1), k_e.dtype)
        kmask = (jnp.arange(Pk) >= P)[None, :, None]
        flag_k = jnp.where(kmask, _PAD_NEG, flag_k)
        q_e = jnp.concatenate([q_e, ones_q], axis=-1)
        q_o = jnp.concatenate([q_o, ones_q], axis=-1)
        k_e = jnp.concatenate([k_e, flag_k], axis=-1)
        k_o = jnp.concatenate([k_o, flag_k], axis=-1)

    qflags = block_flags(q_e, q_o, bq)
    kflags = block_flags(k_e, k_o, bk)

    o_e, o_o = ripple_attention_kernel(
        q_e, q_o, k_e, k_o, v_e, v_o, qflags, kflags,
        scale=scale, block_q=bq, block_k=bk, interpret=interpret)
    o = jnp.stack([o_e[:, :P], o_o[:, :P]], axis=2)  # (BH, P, 2, dv)
    return o.reshape(B, H, N, dv)


def ripple_block_stats(q, k, *, block_q: int = 128, block_k: int = 128):
    """Fraction of MXU matmul work the kernel skips for these operands.

    Per (q, k) block pair the dense cost is 8 block-matmuls; k-collapse
    alone leaves 4 (scores s_ee/s_oe + AV even/odd → wait, see kernel:
    collapsed-k does 1 score + 1 AV per row half), q-collapse halves the
    row halves.  cost = (2 − qc) · (1 + 1 if kc else 2 + 2)/... computed
    explicitly below; dense = 8.
    """
    B, H, N, d = q.shape
    qf2 = q.reshape(B * H, N, d)
    kf2 = k.reshape(B * H, N, d)
    q_e, q_o = split_pairs(qf2)
    k_e, k_o = split_pairs(kf2)
    P = N // 2
    bq, bk = min(block_q, P), min(block_k, P)
    qc = block_flags(q_e[:, : (P // bq) * bq], q_o[:, : (P // bq) * bq], bq)
    kc = block_flags(k_e[:, : (P // bk) * bk], k_o[:, : (P // bk) * bk], bk)
    # per (qi, ki): row halves computed = 2 - qc; matmuls per half = 2 if kc else 4
    halves = (2.0 - qc.astype(jnp.float32))[:, :, None]          # (BH, nq, 1)
    per_half = jnp.where(kc.astype(jnp.float32)[:, None, :] > 0, 2.0, 4.0)
    cost = jnp.mean(halves * per_half) / 8.0
    return 1.0 - cost
