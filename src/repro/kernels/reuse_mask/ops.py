"""Jitted wrappers for the reuse-snap kernels.

* :func:`reuse_snap` — single-axis adjacent-pair snap on (B, H, N, d)
  operands (permute with ``core.collapse.pair_major_order`` for t/y axes
  first).
* :func:`fused_reuse_snap` / :func:`fused_compute_reuse` — the full
  fused multi-axis Δ-check + OR-aggregated snap (DESIGN.md §8), the
  on-device replacement for the host-side ``core.reuse.compute_reuse``
  hot path.  :func:`fused_reuse_eligible` tells callers (the dispatch
  layer) whether a (grid, config) combination can take the fused path.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.reuse_mask.kernel import fused_reuse_kernel, reuse_snap_kernel
from repro.utils.platform import on_tpu


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def reuse_snap(x, theta, *, block: int = 256, interpret: bool | None = None):
    """x: (B, H, N, d), theta: scalar -> (snapped x, mask int8)."""
    if interpret is None:
        interpret = not on_tpu()
    B, H, N, d = x.shape
    assert N % 2 == 0
    P = N // 2
    xr = x.reshape(B * H, N, d)
    x_e, x_o = xr[:, 0::2], xr[:, 1::2]

    blk = min(block, P)
    Pp = -(-P // blk) * blk
    if Pp != P:
        padw = ((0, 0), (0, Pp - P), (0, 0))
        x_e = jnp.pad(x_e, padw)
        x_o = jnp.pad(x_o, padw)
    # θ rounded to x's dtype (the host path's compare), carried as f32.
    th = jnp.asarray([theta], jnp.float32).astype(x.dtype) \
        .astype(jnp.float32)
    o_o, m_o = reuse_snap_kernel(x_e, x_o, th, block=blk, interpret=interpret)
    o_o, m_o = o_o[:, :P], m_o[:, :P]

    snapped = jnp.stack([xr[:, 0::2], o_o], axis=2).reshape(B * H, N, d)
    mask = jnp.stack([jnp.zeros_like(m_o), m_o], axis=2).reshape(B * H, N, d)
    return snapped.reshape(B, H, N, d), mask.reshape(B, H, N, d)


# ---------------------------------------------------------------------------
# Fused multi-axis path (DESIGN.md §8)
# ---------------------------------------------------------------------------

# Target tokens per VMEM tile; the real block rounds down to a multiple
# of 2·W that divides a frame.
_TARGET_BLOCK = 2048


def fused_reuse_eligible(grid: Tuple[int, int, int], *, window: int = 2,
                         granularity: str = "channel",
                         axes: Sequence[str] = ("t", "x", "y")) -> bool:
    """Can the fused kernel reproduce ``compute_reuse`` for this setup?

    Requirements: the paper's window-2 sweet spot, channel/token
    granularity (the RoPE-'group' gate stays on the host path), even
    spatial dims, and an even frame count whenever the temporal check is
    active (T == 1 is fine — the t check never fires there, exactly as
    on the host).
    """
    T, H, W = grid
    if window != 2 or granularity not in ("channel", "token"):
        return False
    if H < 2 or H % 2 or W < 2 or W % 2:
        return False
    if "t" in axes and T > 1 and T % 2:
        return False
    if not set(axes) <= {"t", "x", "y"}:
        return False
    return True


# Sublane tiling of the int8 mask output, the coarsest dtype the kernel
# stores: a block must be a multiple of it (or the whole frame).
_ROW_TILE = 32


def _pick_block(H: int, W: int) -> int:
    """Largest multiple of 2·W that divides H·W, is a multiple of the
    (32, 128) int8 tiling and stays ≲ the VMEM target; the whole frame
    (always a legal block) when no such slab exists."""
    fits = [m * 2 * W for m in range(1, H // 2 + 1)
            if (H // 2) % m == 0 and (m * 2 * W) % _ROW_TILE == 0
            and m * 2 * W <= _TARGET_BLOCK]
    return max(fits, default=H * W)


@functools.partial(
    jax.jit,
    static_argnames=("grid", "axes", "granularity", "block", "interpret"))
def fused_reuse_snap(x: jax.Array, thetas: jax.Array, *,
                     grid: Tuple[int, int, int],
                     axes: Tuple[str, ...] = ("t", "x", "y"),
                     granularity: str = "channel",
                     block: int = 0,
                     interpret: bool | None = None):
    """x: (..., N, d) grid tokens in (t, y, x) row-major order;
    thetas: (3,) f32 in (θt, θx, θy) order.  Returns (snapped, mask:bool)
    shaped like x — the fused equivalent of ``compute_reuse`` restricted
    to its eligible shapes (see :func:`fused_reuse_eligible`) — and
    ``counts`` (..., d) f32, the snapped entries per channel, summed from
    the kernel's int8 mask (one read of it; the bool mask is dead unless
    a caller reads it).
    """
    if interpret is None:
        interpret = not on_tpu()
    T, H, W = grid
    *lead, N, d = x.shape
    assert N == T * H * W, (N, grid)
    R = math.prod(lead) if lead else 1
    with_t = ("t" in axes) and T >= 2
    TT = 2 if with_t else 1
    S = H * W
    blk = block or _pick_block(H, W)
    x4 = x.reshape(R * (T // TT), TT, S, d)
    th = thetas.astype(x.dtype).astype(jnp.float32)
    snapped, mask = fused_reuse_kernel(
        x4, th, axes=axes, granularity=granularity, width=W,
        with_t=with_t, block=blk, interpret=interpret)
    counts = jnp.sum(mask, axis=(1, 2), dtype=jnp.float32)   # (G, d)
    counts = counts.reshape(R, T // TT, d).sum(axis=1).reshape(*lead, d)
    return (snapped.reshape(*lead, N, d),
            mask.reshape(*lead, N, d).astype(jnp.bool_), counts)


def fused_compute_reuse(x: jax.Array, grid: Tuple[int, int, int],
                        thetas: Dict[str, jax.Array], *,
                        axes: Sequence[str] = ("t", "x", "y"),
                        granularity: str = "channel",
                        interpret: bool | None = None):
    """Dict-theta convenience mirroring ``compute_reuse``'s signature.

    Returns (snapped, mask, counts) as :func:`fused_reuse_snap`.  Callers
    must have checked :func:`fused_reuse_eligible` first.
    """
    th = jnp.stack([jnp.asarray(thetas.get(a, 0.0), jnp.float32)
                    for a in ("t", "x", "y")])
    return fused_reuse_snap(x, th, grid=grid, axes=tuple(axes),
                            granularity=granularity, interpret=interpret)

