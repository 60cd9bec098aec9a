"""Jitted wrapper for the ripple (pair-collapse) attention kernel.

Accepts standard (B, H, N, d) snapped operands, derives the per-block
collapse flags from value equality, pair-splits, pads to block multiples
(padded K pairs attend to nothing via a flag channel), runs the kernel,
and re-interleaves the two output halves.

With ``return_skipped`` the wrapper also returns the share of MXU work
the kernel skipped, :func:`ripple_block_stats` of the very flags it ran
on (the *structural* savings, as opposed to the paper's partial-score
accounting); :func:`ripple_block_flags` gives those flags without
running the kernel.
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


def _pair_operands(q, k, v, block_q: int, block_k: int):
    """Pair-split (B·H, pairs, d) kernel operands, padded to block
    multiples, and their per-block collapse flags.  Returns
    ``(operands, (q_flags, k_flags), (block_q, block_k))``."""
    B, H, N, d = q.shape
    dv = v.shape[-1]
    assert N % 2 == 0, "pair-collapse needs an even token count"
    q_e, q_o = split_pairs(q.reshape(B * H, N, d))
    k_e, k_o = split_pairs(k.reshape(B * H, N, d))
    v_e, v_o = split_pairs(v.reshape(B * H, N, dv))

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

    flags = (block_flags(q_e, q_o, bq), block_flags(k_e, k_o, bk))
    return (q_e, q_o, k_e, k_o, v_e, v_o), flags, (bq, bk)


@functools.partial(jax.jit,
                   static_argnames=("window", "block_q", "block_k",
                                    "interpret", "return_skipped"))
def ripple_attention_pallas(q, k, v, *, bias: Optional[jax.Array] = None,
                            window: int = 2, block_q: int = 128,
                            block_k: int = 128,
                            interpret: bool | None = None,
                            return_skipped: bool = False):
    """q,k,v: (B, H, N, d) snapped operands -> (B, H, N, dv), or
    ``(out, skipped)`` with ``return_skipped``: the share of MXU work the
    kernel skipped, :func:`ripple_block_stats` of the flags it ran on."""
    assert bias is None, "ripple kernel path does not take a bias"
    assert window == 2, "kernel implements the paper's window-2 sweet spot"
    if interpret is None:
        interpret = not on_tpu()
    B, H, N, d = q.shape
    dv = v.shape[-1]
    scale = float(1.0 / (d ** 0.5))
    operands, (qflags, kflags), (bq, bk) = _pair_operands(
        q, k, v, block_q, block_k)
    o_e, o_o = ripple_attention_kernel(
        *operands, qflags, kflags,
        scale=scale, block_q=bq, block_k=bk, interpret=interpret)
    P = N // 2
    o = jnp.stack([o_e[:, :P], o_o[:, :P]], axis=2)  # (BH, P, 2, dv)
    out = o.reshape(B, H, N, dv)
    if return_skipped:
        return out, ripple_block_stats(qflags, kflags)
    return out


def ripple_block_flags(q, k, *, block_q: int = 128, block_k: int = 128):
    """The (q_flags, k_flags) the kernel would run on for these snapped
    operands, without running it."""
    _, flags, _ = _pair_operands(q, k, k, block_q, block_k)
    return flags


def ripple_block_stats(q_flags, k_flags):
    """Share of the kernel's MXU matmul work skipped under these block
    flags ((B·H, q blocks) and (B·H, k blocks), 1 = collapsed).

    Cost model (``kernel.py``): a (q block, k block) pair runs each of
    its two row halves (the even and odd rows of the pairs) through 2
    score and 2 AV matmuls, 8 in all.  A collapsed K block leaves 1
    score and 1 AV matmul per row half; a collapsed Q block skips the
    odd half.  So a pair costs ``(2 − q_flag) · (4 − 2·k_flag)`` of 8,
    and the skipped share is one minus the mean cost over 8.
    """
    halves = jnp.mean(2.0 - q_flags.astype(jnp.float32), axis=-1)
    per_half = jnp.mean(4.0 - 2.0 * k_flags.astype(jnp.float32), axis=-1)
    return 1.0 - jnp.mean(halves * per_half) / 8.0
