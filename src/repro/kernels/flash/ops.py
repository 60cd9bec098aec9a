"""Jitted public wrapper for the flash attention kernel.

Handles (B, H, N, d) layouts, non-aligned sequence lengths (zero-pad +
renormalization via a padding key that attends nowhere), and interpret
mode on CPU (kernel body executed in Python for correctness validation —
this container has no TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash.kernel import flash_attention as _kernel
from repro.utils.platform import on_tpu

_NEG_INF = -1e30


def _pad_to(x, target, axis):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q,k,v: (B, H, N, d) -> (B, H, N, dv)."""
    if interpret is None:
        interpret = not on_tpu()
    B, H, Nq, d = q.shape
    Nk = k.shape[2]
    dv = v.shape[3]
    scale = float(1.0 / (d ** 0.5))

    bq = min(block_q, max(Nq, 1))
    bk = min(block_k, max(Nk, 1))
    Nq_p = -(-Nq // bq) * bq
    Nk_p = -(-Nk // bk) * bk

    qp = _pad_to(q, Nq_p, 2).reshape(B * H, Nq_p, d)
    kp = _pad_to(k, Nk_p, 2).reshape(B * H, Nk_p, d)
    vp = _pad_to(v, Nk_p, 2).reshape(B * H, Nk_p, dv)
    if Nk_p != Nk:
        # Padded keys must attend to nothing: push their logits to -inf by
        # scaling a huge negative into the padded K rows via a bias trick —
        # cheaper: set padded K rows to 0 and subtract mass afterwards is
        # wrong; instead give padded keys a large negative projection on a
        # constant channel. Simplest correct route: extend d by one channel
        # that is 1 for queries and -inf-ish for padded keys.
        flag_q = jnp.ones((B * H, Nq_p, 1), qp.dtype)
        flag_k = jnp.zeros((B * H, Nk_p, 1), kp.dtype)
        flag_k = flag_k.at[:, Nk:, :].set(_NEG_INF * scale * 0 + _NEG_INF / 128.0)
        qp = jnp.concatenate([qp, flag_q], axis=-1)
        kp = jnp.concatenate([kp, flag_k], axis=-1)
        # keep the same softmax scale as the unpadded head_dim
        scale_eff = scale
    else:
        scale_eff = scale

    out = _kernel(qp, kp, vp, scale=scale_eff, block_q=bq, block_k=bk,
                  interpret=interpret)
    return out.reshape(B, H, Nq_p, dv)[:, :, :Nq, :]
