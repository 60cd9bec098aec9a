"""Jitted public wrapper for the block-sparse masked flash kernel.

Handles (B, H, N, d) layouts, pads token dims to block multiples
(padded keys are neutralized with the same flag-channel trick as the
dense flash wrapper, so every block-map state stays correct on the
padded tail), builds the scalar-prefetched fetch-index tables that let
the kernel elide DMA for skipped tiles, and runs in interpret mode on
CPU.

Also home of the policy-facing helpers:

* :func:`block_map_from_keep` — tile a boolean keep-mask into the
  kernel's SKIP/FULL/PARTIAL states (how SVG's head-classified masks
  become a block map, DESIGN.md §12).
* :func:`sparse_block_stats` — realized skipped-tile fraction, the
  *structural* savings a mask policy actually gets on this backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.sparse.kernel import (_LANES, _M_INIT, FULL, PARTIAL,
                                         SKIP, sparse_attention_kernel)
from repro.kernels.sparse.ref import sparse_grid
from repro.utils.platform import on_tpu

__all__ = ["FULL", "PARTIAL", "SKIP", "block_map_from_keep",
           "sparse_attention_pallas", "sparse_block_stats", "sparse_grid"]

_NEG_INF = -1e30


def _pad_to(x, target, axis):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def block_map_from_keep(keep: jax.Array, block_q: int,
                        block_k: int) -> jax.Array:
    """(..., Nq, Nk) bool keep-mask -> (..., nq, nk) int32 block map.

    A tile that keeps everything is FULL (mask-free fast path), one that
    keeps nothing is SKIP, anything mixed is PARTIAL (the −inf bias is
    applied in-kernel).  Ragged edges are padded with the edge value so
    padding can never flip a FULL/SKIP verdict to PARTIAL.
    """
    *lead, n_q, n_k = keep.shape
    bq, bk, nq, nk = sparse_grid(n_q, n_k, block_q, block_k)
    widths = [(0, 0)] * len(lead) + [(0, nq * bq - n_q), (0, nk * bk - n_k)]
    tiled = jnp.pad(keep, widths, mode="edge") \
        .reshape(*lead, nq, bq, nk, bk)
    any_keep = jnp.any(tiled, axis=(-3, -1))
    all_keep = jnp.all(tiled, axis=(-3, -1))
    return jnp.where(all_keep, FULL,
                     jnp.where(any_keep, PARTIAL, SKIP)).astype(jnp.int32)


def sparse_block_stats(block_map: jax.Array) -> jax.Array:
    """Fraction of (q_block, k_block) tiles the kernel skips outright —
    score matmul, softmax update, and AV matmul all elided."""
    return jnp.mean((block_map == SKIP).astype(jnp.float32))


def _fetch_table(needed: jax.Array) -> jax.Array:
    """Per-tile fetch index: ``ki`` where ``needed``, else the last
    needed index (0 before any) — consecutive equal indices make the
    Pallas pipeline skip the corresponding HBM→VMEM copy."""
    nk = needed.shape[-1]
    ki = jnp.arange(nk, dtype=jnp.int32)
    marked = jnp.where(needed, ki, -1)
    last = jax.lax.cummax(marked, axis=needed.ndim - 1)
    return jnp.maximum(last, 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret", "return_state"))
def sparse_attention_pallas(q, k, v, *, bias=None, block_map=None,
                            block_q: int = 128, block_k: int = 128,
                            interpret: bool | None = None,
                            carry=None, return_state: bool = False):
    """q,k,v: (B, H, N, d) -> (B, H, N, dv).

    ``block_map``: (..., nq, nk) int states broadcastable over (B, H),
    tiled as :func:`sparse_grid` tiles the (Nq, Nk) score map.  ``None``
    degrades gracefully: all-PARTIAL when a ``bias`` exists (dense
    masked flash attention), all-FULL otherwise (plain flash).  ``bias``
    is additive on logits and read only inside PARTIAL tiles — FULL
    tiles must correspond to an all-zero bias region, SKIP tiles to
    all-−inf (``block_map_from_keep`` guarantees both).

    Ring-hop chaining (DESIGN.md §14): with ``return_state=True`` the
    call also returns the online-softmax state ``(m, l, acc)`` of shapes
    ((B, H, Nq) f32 ×2, (B, H, Nq, dv) f32); feeding that triple back as
    ``carry`` on the next call — against the *next* key slice — resumes
    the accumulation, so a chain of calls over column slices of K equals
    one full-width call up to summation-order rounding.  The per-call
    ``out`` is the normalized prefix result; only the last hop's ``out``
    (or an explicit ``acc / l``) is the final answer.
    """
    if interpret is None:
        interpret = not on_tpu()
    B, H, Nq, d = q.shape
    Nk = k.shape[2]
    dv = v.shape[3]
    scale = float(1.0 / (d ** 0.5))
    bq, bk, nq, nk = sparse_grid(Nq, Nk, block_q, block_k)
    Nq_p, Nk_p = nq * bq, nk * bk

    qf = _pad_to(q, Nq_p, 2).reshape(B * H, Nq_p, d)
    kf = _pad_to(k, Nk_p, 2).reshape(B * H, Nk_p, d)
    vf = _pad_to(v, Nk_p, 2).reshape(B * H, Nk_p, dv)
    if Nk_p != Nk:
        # Padded keys attend to nothing: flag channel projects a huge
        # negative for them (queries project 1), exactly as in flash/ops.
        flag_q = jnp.ones((B * H, Nq_p, 1), qf.dtype)
        flag_k = jnp.zeros((B * H, Nk_p, 1), kf.dtype)
        kmask = (jnp.arange(Nk_p) >= Nk)[None, :, None]
        flag_k = jnp.where(kmask, _NEG_INF / 128.0, flag_k)
        qf = jnp.concatenate([qf, flag_q], axis=-1)
        kf = jnp.concatenate([kf, flag_k], axis=-1)

    if block_map is None:
        state = PARTIAL if bias is not None else FULL
        bmap = jnp.full((B * H, nq, nk), state, jnp.int32)
    else:
        bmap = jnp.broadcast_to(block_map, (B, H, nq, nk)) \
            .reshape(B * H, nq, nk).astype(jnp.int32)

    k_fetch = _fetch_table(bmap != SKIP)
    bias_fetch = _fetch_table(bmap == PARTIAL)

    if bias is None:
        bias_f = jnp.zeros((1, bq, bk), jnp.float32)
    else:
        bias_f = jnp.broadcast_to(bias.astype(jnp.float32),
                                  (B, H, Nq, Nk)).reshape(B * H, Nq, Nk)
        bias_f = _pad_to(_pad_to(bias_f, Nq_p, 1), Nk_p, 2)

    carry_f = None
    if carry is not None or return_state:
        if carry is None:
            m_c = jnp.full((B, H, Nq), _M_INIT, jnp.float32)
            l_c = jnp.zeros((B, H, Nq), jnp.float32)
            acc_c = jnp.zeros((B, H, Nq, dv), jnp.float32)
        else:
            m_c, l_c, acc_c = carry
        # Padded query rows carry the fresh state so they stay inert.
        m_c = jnp.pad(m_c.astype(jnp.float32), [(0, 0), (0, 0),
                      (0, Nq_p - Nq)], constant_values=_M_INIT)
        l_c = _pad_to(l_c.astype(jnp.float32), Nq_p, 2)
        acc_c = _pad_to(acc_c.astype(jnp.float32), Nq_p, 2)
        carry_f = (jnp.broadcast_to(m_c.reshape(B * H, Nq_p, 1),
                                    (B * H, Nq_p, _LANES)),
                   jnp.broadcast_to(l_c.reshape(B * H, Nq_p, 1),
                                    (B * H, Nq_p, _LANES)),
                   acc_c.reshape(B * H, Nq_p, dv))

    res = sparse_attention_kernel(
        qf, kf, vf, bias_f, bmap, k_fetch, bias_fetch,
        scale=scale, block_q=bq, block_k=bk, interpret=interpret,
        carry=carry_f)
    if carry_f is not None:
        out, (m, l, acc) = res
        state = (m[:, :, 0].reshape(B, H, Nq_p)[:, :, :Nq],
                 l[:, :, 0].reshape(B, H, Nq_p)[:, :, :Nq],
                 acc.reshape(B, H, Nq_p, dv)[:, :, :Nq, :])
        out = out.reshape(B, H, Nq_p, dv)[:, :, :Nq, :]
        return (out, state) if return_state else out
    return res.reshape(B, H, Nq_p, dv)[:, :, :Nq, :]
