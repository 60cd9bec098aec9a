"""Jitted wrapper for the fused adaLN modulation kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.adaln.kernel import adaln_modulate_kernel
from repro.utils.platform import on_tpu


@functools.partial(jax.jit, static_argnames=("eps", "block_t", "interpret"))
def adaln_modulate(x, shift, scale, *, eps: float = 1e-6, block_t: int = 256,
                   interpret: bool | None = None):
    """x: (B, N, d); shift/scale: (B, d)."""
    if interpret is None:
        interpret = not on_tpu()
    B, N, d = x.shape
    bt = min(block_t, N)
    Np = -(-N // bt) * bt
    xp = jnp.pad(x, ((0, 0), (0, Np - N), (0, 0))) if Np != N else x
    out = adaln_modulate_kernel(xp, shift, scale, eps=eps, block_t=bt,
                                interpret=interpret)
    return out[:, :N]
