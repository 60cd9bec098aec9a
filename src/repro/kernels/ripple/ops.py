"""Jitted wrapper for the ripple (pair-collapse) attention kernel.

Accepts standard (B, H, N, d) snapped operands, derives the per-block
collapse flags from value equality, pair-splits, pads the pair axis with
zeros to a multiple of the compute tile (the kernel masks padded keys),
runs the kernel, and re-interleaves the two output halves.

Two sizes are kept apart (DESIGN.md §4): ``block_q``/``block_k`` are the
granularity of the collapse flags, the dispatch plan's blocks; the
compute tile a grid step covers is chosen here from the operands' shape
by :func:`compute_tiles`, a multiple of the flag blocks.

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

# Compute-tile rule, measured on a TPU v5e (DESIGN.md §4).  A grid step
# costs about 1.9 µs plus 0.21 µs per 128×128-pair block of work, so its
# fixed cost is worth _STEP_COST blocks; past _MAX_TILE pairs a side the
# time per block stops falling and larger tiles only pad.
_STEP_COST = 9.0
_MAX_TILE = 768


def compute_tiles(n_pairs: int, block_q: int, block_k: int):
    """(tile_q, tile_k) pairs per grid step for ``n_pairs`` pairs and
    these flag blocks: multiples of the flag blocks of at most
    ``_MAX_TILE``, the pair that minimizes the modelled time, padded
    work included — ``steps × (_STEP_COST + work per step)``."""

    def candidates(block):
        top = max(block, min(_MAX_TILE, -(-n_pairs // block) * block))
        return range(block, top + 1, block)

    def cost(tq, tk):
        steps = -(-n_pairs // tq) * -(-n_pairs // tk)
        return steps * (_STEP_COST + tq * tk / 128 ** 2), -tq * tk

    return min(((tq, tk) for tq in candidates(block_q)
                for tk in candidates(block_k)), key=lambda t: cost(*t))


def _pad(x, target):
    padw = target - x.shape[1]
    return x if padw == 0 else jnp.pad(x, ((0, 0), (0, padw), (0, 0)))


def _pair_operands(q, k, v, block_q: int, block_k: int):
    """Pair-split (B·H, pairs, d) kernel operands, zero-padded to tile
    multiples, and the collapse flags.  Returns ``(operands,
    (q_tile_flags, k_flags), (q_flags, k_flags_real), (bk, tq, tk))``:
    the kernel's flags — one per Q tile, one per ``bk``-pair K block
    with padded blocks 0 — and the flags per flag block over the real
    pairs, each Q block carrying its tile's flag (what the kernel
    executes, for :func:`ripple_block_stats`)."""
    B, H, N, d = q.shape
    dv = v.shape[-1]
    assert N % 2 == 0, "pair-collapse needs an even token count"
    P = N // 2
    bq = min(block_q, P)
    bk = min(block_k, P)
    tq, tk = compute_tiles(P, bq, bk)
    Pq = -(-P // tq) * tq
    Pk = -(-P // tk) * tk

    q_e, q_o = split_pairs(q.reshape(B * H, N, d))
    k_e, k_o = split_pairs(k.reshape(B * H, N, d))
    v_e, v_o = split_pairs(v.reshape(B * H, N, dv))
    q_e, q_o = _pad(q_e, Pq), _pad(q_o, Pq)
    k_e, k_o, v_e, v_o = (_pad(x, Pk) for x in (k_e, k_o, v_e, v_o))

    # Padded pairs are zero in both halves, so they read as collapsed:
    # a block's flag is that of its real pairs.
    q_blocks = block_flags(q_e, q_o, bq)            # (BH, Pq/bq)
    q_tiles = jnp.min(q_blocks.reshape(B * H, Pq // tq, tq // bq), axis=-1)
    nq_real, nk_real = -(-P // bq), -(-P // bk)
    q_exec = jnp.repeat(q_tiles, tq // bq, axis=1)[:, :nq_real]
    k_blocks = block_flags(k_e, k_o, bk)            # (BH, Pk/bk)
    k_real = k_blocks[:, :nk_real]
    # Blocks of padding alone take the whole-tile update, not the
    # per-block walk.
    k_kernel = jnp.where(jnp.arange(Pk // bk) < nk_real, k_blocks, 0)
    return ((q_e, q_o, k_e, k_o, v_e, v_o), (q_tiles, k_kernel),
            (q_exec, k_real), (bk, tq, tk))


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
    P = N // 2
    operands, kernel_flags, exec_flags, (bk, tq, tk) = _pair_operands(
        q, k, v, block_q, block_k)
    o_e, o_o = ripple_attention_kernel(
        *operands, *kernel_flags,
        scale=float(1.0 / (d ** 0.5)), n_pairs=P, tile_q=tq, tile_k=tk,
        block_k=bk, interpret=interpret)
    o = jnp.stack([o_e[:, :P], o_o[:, :P]], axis=2)  # (BH, P, 2, dv)
    out = o.reshape(B, H, N, dv)
    if return_skipped:
        return out, ripple_block_stats(*exec_flags)
    return out


def ripple_block_flags(q, k, *, block_q: int = 128, block_k: int = 128):
    """The (q_flags, k_flags) the kernel executes for these snapped
    operands, one per flag block of real pairs, without running it: a Q
    block carries its compute tile's flag."""
    _, _, flags, _ = _pair_operands(q, k, k, block_q, block_k)
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
