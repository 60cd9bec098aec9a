"""Fused Δ-check + snap Pallas kernels (paper Fig. 6 steps ①-②).

Two kernels live here:

* :func:`reuse_snap_kernel` — the original single-axis pair kernel.
  Computes, for adjacent window-2 pairs of tokens (pair-major layout —
  callers permute other axes into adjacency with
  ``core.collapse.pair_major_order``):

      Δ_c   = |x[2j+1, c] − x[2j, c]| / 2          (Eq. 3 for K=2)
      snap  = Δ_c < θ
      out[2j+1, c] = snap ? x[2j, c] : x[2j+1, c]

  in one VMEM pass, emitting the snapped operand and the mask.

* :func:`fused_reuse_kernel` — the full TimeRipple step ①-② pipeline
  (DESIGN.md §8): windowed Δ checks along **all three** grid axes
  (t, x, y) plus the OR-aggregation into the final snap mask with the
  same first-wins axis priority as ``core.reuse.compute_reuse``, in one
  kernel launch.  Each program owns one frame *pair* (or a slab of it),
  so the t-partner, the y-row partner and the x-neighbour of every token
  are all resident in the same VMEM tile and the whole check costs one
  HBM read + two writes instead of the ~3 axis passes (slice, sub, abs,
  cmp, repeat, select each) of the host-side path.

θ arrives via scalar prefetch in both kernels so the same compiled
kernel serves every denoising step's threshold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _below(delta, theta):
    """Δ < θ, compared in f32: the v5e VPU has no bf16 compare.  θ
    arrives already rounded to Δ's dtype, and both sides widen exactly,
    so the verdict equals a compare in Δ's own dtype."""
    return delta.astype(jnp.float32) < theta


def _reuse_kernel(theta_ref, x_e_ref, x_o_ref, out_o_ref, mask_o_ref):
    theta = theta_ref[0]
    x_e = x_e_ref[...]
    x_o = x_o_ref[...]
    delta = jnp.abs(x_o - x_e) * 0.5
    snap = _below(delta, theta)
    out_o_ref[...] = jnp.where(snap, x_e, x_o)
    mask_o_ref[...] = jnp.where(snap, 1, 0).astype(jnp.int8)


def reuse_snap_kernel(x_even: jax.Array, x_odd: jax.Array, theta: jax.Array,
                      *, block: int = 256, interpret: bool = False):
    """x_even/x_odd: (R, P, d) pair-split tokens; theta: (1,) f32,
    already rounded to the tokens' dtype.

    Returns (snapped_odd, mask_odd:int8); the even (representative) half
    is unchanged by definition.
    """
    R, P, d = x_even.shape
    block = min(block, P)
    assert P % block == 0
    grid = (R, P // block)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block, d), lambda r, i, *_: (r, i, 0)),
            pl.BlockSpec((None, block, d), lambda r, i, *_: (r, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block, d), lambda r, i, *_: (r, i, 0)),
            pl.BlockSpec((None, block, d), lambda r, i, *_: (r, i, 0)),
        ],
    )
    return pl.pallas_call(
        _reuse_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, P, d), x_even.dtype),
            jax.ShapeDtypeStruct((R, P, d), jnp.int8),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(theta, x_even, x_odd)


# ---------------------------------------------------------------------------
# Fused multi-axis Δ-check + snap (DESIGN.md §8)
# ---------------------------------------------------------------------------

_AXIS_SLOT = {"t": 0, "x": 1, "y": 2}  # θ prefetch layout


def _gate(delta, theta, granularity: str):
    """Δ < θ at the requested granularity, broadcast back to Δ's shape."""
    if granularity == "channel":
        return _below(delta, theta)
    # 'token': the mean Δ over channels gates every channel of the token.
    ok = _below(jnp.mean(delta, axis=-1, keepdims=True), theta)
    return jnp.broadcast_to(ok, delta.shape)


def _delta2(a0, a1):
    """Window-2 Eq. 3 Δ, with the same op sequence as the host's
    ``reuse.window_delta`` (mean, square, mean, sqrt) — algebraically
    |a1−a0|/2, but kept bitwise-identical so a threshold can never land
    between the two paths' roundings and flip a mask bit.  The sqrt
    runs in f32 and rounds back (the v5e EUP has no bf16 transcendental
    unit; XLA widens a bf16 sqrt the same way)."""
    m = (a0 + a1) * 0.5
    var = (jnp.square(a0 - m) + jnp.square(a1 - m)) * 0.5
    return jnp.sqrt(var.astype(jnp.float32)).astype(var.dtype)


def _roll_rows(x, shift: int):
    """``x`` shifted ``shift`` token rows down (``out[i] = x[i - shift]``)
    — a sublane rotate, done in f32 (exact for every input dtype)."""
    return pltpu.roll(x.astype(jnp.float32), shift, 0).astype(x.dtype)


def _fused_kernel(theta_ref, x_ref, out_ref, mask_ref,
                  *, axes, granularity: str, width: int, with_t: bool):
    """One program = one (frame-pair, token-slab) tile.

    x_ref: (TT, block, d) with TT == 2 when the temporal check is live
    (tile rows are the even/odd frames of one t-pair) and TT == 1 for
    single-frame grids.  ``block`` is a multiple of ``2 * width`` so both
    x-neighbours and both y-row partners of every token sit in-tile.

    Every partner is reached by a row rotate of the frame slab (x: one
    token back, y: one image row back) or by the other frame (t), so
    the kernel never reshapes or stacks a mask: the per-axis verdicts
    are plain elementwise values gated by the row parity from an iota.
    """
    TT, block, d = x_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (block, d), 0)
    follows_x = (row % 2) == 1                  # odd token of an x-pair
    follows_y = (row % (2 * width)) >= width    # odd row of a y-pair
    x0 = x_ref[0]
    for f in range(TT):
        xf = x_ref[f]
        checks = {}
        if with_t and f == 1:
            # t axis: Δ between the two frames; only the odd one snaps.
            checks["t"] = (_gate(_delta2(x0, xf), theta_ref[_AXIS_SLOT["t"]],
                                 granularity), x0)
        rep = _roll_rows(xf, 1)
        ok = _gate(_delta2(rep, xf), theta_ref[_AXIS_SLOT["x"]], granularity)
        checks["x"] = (jnp.logical_and(follows_x, ok), rep)
        rep = _roll_rows(xf, width)
        ok = _gate(_delta2(rep, xf), theta_ref[_AXIS_SLOT["y"]], granularity)
        checks["y"] = (jnp.logical_and(follows_y, ok), rep)

        # Step ② OR-aggregation, first-wins copy-source priority (the
        # same semantics as core.reuse.compute_reuse — all masks derive
        # from the *original* operand, not the progressively snapped
        # one).
        snapped = xf
        claimed = jnp.zeros((block, d), jnp.int32)
        for a in axes:
            if a not in checks:
                continue
            ok, rep = checks[a]
            snapped = jnp.where(jnp.logical_and(ok, claimed == 0),
                                rep, snapped)
            claimed = jnp.where(ok, 1, claimed)
        out_ref[f] = snapped
        mask_ref[f] = claimed.astype(mask_ref.dtype)


def fused_reuse_kernel(x: jax.Array, thetas: jax.Array, *,
                       axes, granularity: str, width: int, with_t: bool,
                       block: int, interpret: bool = False):
    """x: (G, TT, S, d) frame-pair-major grid tokens; thetas: (3,) [θt, θx, θy].

    G indexes (lead × frame-pair), TT ∈ {1, 2} is the pair dim, S = H·W
    tokens per frame.  Returns (snapped, mask:int8) shaped like x.
    """
    G, TT, S, d = x.shape
    assert TT == (2 if with_t else 1)
    assert S % block == 0 and block % (2 * width) == 0, (S, block, width)
    grid = (G, S // block)

    kernel = functools.partial(_fused_kernel, axes=tuple(axes),
                               granularity=granularity, width=width,
                               with_t=with_t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, TT, block, d), lambda g, i, *_: (g, 0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, TT, block, d), lambda g, i, *_: (g, 0, i, 0)),
            pl.BlockSpec((None, TT, block, d), lambda g, i, *_: (g, 0, i, 0)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((G, TT, S, d), x.dtype),
            jax.ShapeDtypeStruct((G, TT, S, d), jnp.int8),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(thetas, x)
