"""Pluggable reuse-policy registry — the *strategy* seam of the
attention-dispatch layer (DESIGN.md §11).

TimeRipple's channel-wise spatio-temporal reuse is one way to exploit
latent-space correlation; Sparse VideoGen's spatial/temporal head
classification (arXiv 2502.01776) and Sparse-vDiT's pattern-per-head
sparsity (arXiv 2506.03065) are others.  A :class:`ReusePolicy` owns
every strategy-specific choice:

  * the per-step threshold schedule (:meth:`ReusePolicy.thetas_for`),
  * offline calibration against sample activations
    (:meth:`ReusePolicy.calibrate`),
  * the mask / snap decision itself (:meth:`ReusePolicy.decide`,
    returning one :class:`ReuseDecision`),
  * the expected-savings estimate and stats
    (:meth:`ReusePolicy.stats`).

``core.dispatch.attention_dispatch`` consumes the decision uniformly —
it executes the planned backend on ``decision.q`` / ``decision.k`` with
``decision.bias`` and never inspects which strategy produced them.
Adding a new sparsity idea is therefore a :func:`register_policy` call
(~50 lines), not a fork of the dispatch pipeline; ``--policy NAME`` on
the launchers selects it end-to-end, and the serving engine buckets
per-request on the policy name.

Built-in policies:

  ``ripple``     the paper: windowed Δ-checks snap Q/K entries to their
                 window representative (Eq. 3/4 schedule, ``core.reuse``)
  ``svg``        Sparse VideoGen-style head-classified spatial/temporal
                 block masks (``core.svg_mask``) as a logit bias plus a
                 tiled block map the sparse backend skips (DESIGN.md §12)
  ``equal_mse``  ripple's decision with the Fig. 9 equal-impact
                 per-step schedule (``core.calibrate``) instead of the
                 linear ramp
  ``dense``      no-op baseline; plans resolve straight to the dense
                 backend
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import RippleConfig
from repro.core import reuse as reuse_lib
from repro.core import savings as savings_lib
from repro.core.reuse import AXES
from repro.core.schedule import axis_thresholds
from repro.core.svg_mask import svg_logit_bias


@dataclasses.dataclass
class RippleStats:
    savings: jax.Array             # paper accounting (partial-score reuse)
    structural_savings: jax.Array  # realized by the collapse path
    q_snap_frac: jax.Array
    k_snap_frac: jax.Array


jax.tree_util.register_dataclass(
    RippleStats,
    data_fields=["savings", "structural_savings", "q_snap_frac",
                 "k_snap_frac"],
    meta_fields=[])


@dataclasses.dataclass
class ReuseDecision:
    """What one policy decided for one attention call.

    ``q`` / ``k`` are the operands the backend must execute on (snapped
    by operand-rewriting policies, untouched otherwise); ``bias`` is the
    combined additive logit bias (the caller's bias plus any mask the
    policy emits).  ``q_mask`` / ``k_mask`` are boolean snap masks for
    the savings accounting (None for policies that never snap), and
    ``savings`` is the policy's expected-savings estimate for this call.

    ``block_map`` (DESIGN.md §12) is the per-(q_block, k_block) tile
    state map for the block-sparse backend — int32 skip/full/partial
    states broadcastable over (batch, heads), tiled with the
    ``block_shape`` the dispatcher passed to :meth:`ReusePolicy.decide`.
    Policies derive it from their masks; None means every tile runs.

    ``q_src`` / ``k_src`` (only with ``want_plan=True``, DESIGN.md §13)
    are int32 snap-source token maps over the decided grid segment —
    the cacheable half of an operand-rewriting decision: re-applying it
    to fresh operands is one ``take_along_axis`` gather.

    Registered as a jax pytree (``active_axes`` / ``window`` are static
    metadata) so whole decisions can flow through ``lax.cond`` — the
    decision cache's refresh-vs-reuse branch point.
    """

    q: jax.Array
    k: jax.Array
    thetas: Dict[str, jax.Array]
    active_axes: Tuple[str, ...]
    bias: Optional[jax.Array] = None
    q_mask: Optional[jax.Array] = None
    k_mask: Optional[jax.Array] = None
    savings: Optional[jax.Array] = None
    block_map: Optional[jax.Array] = None
    window: int = 2  # collapse-window size the masks were computed with
    q_src: Optional[jax.Array] = None
    k_src: Optional[jax.Array] = None


jax.tree_util.register_dataclass(
    ReuseDecision,
    data_fields=["q", "k", "thetas", "bias", "q_mask", "k_mask", "savings",
                 "block_map", "q_src", "k_src"],
    meta_fields=["active_axes", "window"])


def zero_inactive_axes(thetas: Dict[str, jax.Array],
                       active_axes: Sequence[str]) -> Dict[str, jax.Array]:
    """Disable the Δ-check on axes outside ``active_axes`` (Δ ≥ 0, so a
    zero threshold can never fire)."""
    out = dict(thetas)
    for a in AXES:
        if a not in active_axes:
            out[a] = jnp.zeros(())
    return out


def _zero_thetas() -> Dict[str, jax.Array]:
    return {a: jnp.zeros(()) for a in AXES}


class ReusePolicy:
    """Base class / protocol for reuse policies.

    Out-of-tree strategies subclass this (or duck-type it), override
    :meth:`decide` (and usually :meth:`thetas_for`), and call
    :func:`register_policy`.  The three class attributes tell plan
    resolution what the policy needs — they gate backend selection
    without the dispatch layer knowing the strategy itself:

      ``emits_bias``       decide() may attach a logit bias (mask
                           policies) → backends that can't take a bias
                           (auto-Pallas, collapse) are avoided
      ``snaps_operands``   decide() may rewrite Q/K entries → the
                           collapse backend is worth choosing
      ``is_dense``         no-op baseline → plans resolve to 'dense'
      ``emits_block_map``  decide() can tile its mask into a sparse
                           block map → the block-sparse backend realizes
                           the mask as skipped tiles (DESIGN.md §12)
      ``caches_decisions`` decide(want_plan=True) emits a reusable plan
                           (snap-source maps / bias / block map) that
                           :meth:`apply_decision` can re-apply to fresh
                           operands — the cross-step decision cache
                           (DESIGN.md §13).  Policies written before the
                           cache existed default to False and keep
                           their original ``decide`` signature.
    """

    name: str = ""
    emits_bias: bool = False
    snaps_operands: bool = True
    is_dense: bool = False
    emits_block_map: bool = False
    caches_decisions: bool = False
    # Cache-capable policies whose decision is a *constant* of the
    # trajectory (offline-searched masks, core/patterns.py): the
    # decision cache refreshes at step 0 only — no drift stat, no
    # reuse_every cadence, no final-step re-decide (DESIGN.md §16).
    plan_once: bool = False

    def will_emit_bias(self, cfg: RippleConfig) -> bool:
        """Will :meth:`decide` attach a logit bias under this config?
        Backend resolution uses this (not ``emits_bias`` directly) so
        config-conditional masks — e.g. ripple's ``cfg.svg_mask`` combo
        — are also kept off the biasless backends."""
        return self.emits_bias

    def will_emit_block_map(self, cfg: RippleConfig) -> bool:
        """Will :meth:`decide` produce a ``ReuseDecision.block_map``
        when given a ``block_shape``?  Plan resolution prefers the
        block-sparse backend for such policies (DESIGN.md §12)."""
        return self.emits_block_map

    def will_cache_decisions(self, cfg: RippleConfig) -> bool:
        """Can this policy's decision be cached across steps under this
        config (DESIGN.md §13)?  The dispatcher passes ``want_plan=True``
        to :meth:`decide` — and calls :meth:`apply_decision` on cache
        hits — only when this returns True, so pre-cache policies keep
        their original signature."""
        return self.caches_decisions

    def will_seq_shard(self, cfg: RippleConfig) -> bool:
        """Does the context-parallel ring path (DESIGN.md §14) know how
        to run this policy's decision shard-locally when the token axis
        is sharded over a ``seq`` mesh axis?  Policies that return False
        fall back to the replicated token axis (batch/head sharding
        still applies) — the ring never guesses."""
        return False

    def plan_token(self, cfg: Optional[RippleConfig] = None):
        """Hashable token identifying external state the decision bakes
        in as compile-time constants (e.g. the pattern artifact's
        content-hash version, DESIGN.md §16).  The dispatch plan cache
        and the serving bucket key mix it in, so swapping the external
        state can never replay a stale compiled plan.  None when the
        policy has no such state."""
        return None

    # -- per-step threshold schedule ------------------------------------

    def thetas_for(self, cfg: RippleConfig, step, total_steps,
                   thetas: Optional[Dict[str, jax.Array]] = None
                   ) -> Dict[str, jax.Array]:
        """Per-axis thresholds for one denoising step.  ``thetas`` is a
        caller override (already-derived values); implementations must
        still apply their axis gating to it.  Must be jittable in
        ``step`` (samplers scan over steps)."""
        return _zero_thetas()

    # -- offline calibration --------------------------------------------

    def calibrate(self, q: jax.Array, k: jax.Array,
                  grid: Tuple[int, int, int], cfg: RippleConfig,
                  target_savings: float) -> Dict[str, object]:
        """Fit strategy parameters on sample Q/K activations.  Returns a
        dict of ``RippleConfig`` field overrides (possibly empty) to
        apply via ``dataclasses.replace`` — how the Tbl. 1
        hyper-parameters were found for the paper's policy."""
        return {}

    # -- the mask / snap decision ---------------------------------------

    def decide(self, q: jax.Array, k: jax.Array, *,
               grid: Tuple[int, int, int], cfg: RippleConfig,
               thetas: Dict[str, jax.Array],
               bias: Optional[jax.Array] = None,
               grid_slice: Optional[Tuple[int, int]] = None,
               fused: bool = False,
               block_shape: Optional[Tuple[int, int]] = None
               ) -> ReuseDecision:
        """The strategy itself.  Shard-oblivious by contract: it must
        produce identical values on the full operands and on one
        shard_map shard (decisions may only look along the t/x/y token
        axes, never across batch or heads — DESIGN.md §10).

        ``block_shape`` is the resolved plan's (block_q, block_k) — the
        dispatcher passes it **only** when the block-sparse backend was
        planned (so policies written before it existed keep working);
        block-map policies tile their masks with it (DESIGN.md §12).

        Cache-capable policies (``caches_decisions``) additionally take
        ``want_plan`` (again passed only when the capability is
        declared) and populate ``ReuseDecision.q_src`` / ``k_src`` when
        it is set, so the dispatcher can carry the decision across
        steps (DESIGN.md §13)."""
        raise NotImplementedError

    # -- cross-step decision reuse (DESIGN.md §13) ----------------------

    def apply_decision(self, q: jax.Array, k: jax.Array, cached, *,
                       grid: Tuple[int, int, int], cfg: RippleConfig,
                       thetas: Dict[str, jax.Array],
                       grid_slice: Optional[Tuple[int, int]] = None
                       ) -> ReuseDecision:
        """Re-apply a cached decision to *fresh* operands — the cheap
        half of the plan/apply split.  ``cached`` is the
        :class:`~repro.core.decision_cache.CachedDecision` an earlier
        ``decide(want_plan=True)`` produced for identically-shaped
        operands: snap-source maps are replayed as one gather each, the
        cached bias / block map are attached verbatim.  The per-step
        math stays exact — only the decision is stale.

        The base implementation covers both built-in shapes (operand
        rewriting via ``q_src``/``k_src``, mask emission via
        ``bias``/``block_map``); override for exotic plans.  Must
        produce a ReuseDecision with the same pytree structure as the
        corresponding ``decide(want_plan=True)`` call — the dispatcher
        selects between the two under ``lax.cond``.
        """
        q_s, q_mask = replay_snap(q, cached.q_idx, grid_slice,
                                  self.snaps_operands)
        k_s, k_mask = replay_snap(k, cached.k_idx, grid_slice,
                                  self.snaps_operands)
        if q_mask is not None and k_mask is not None:
            sav = savings_lib.partial_score_savings(q_mask, k_mask)
        elif cached.bias is not None:
            # mask policies: skippable score fraction = masked density
            sav = 1.0 - jnp.mean((cached.bias >= 0.0).astype(jnp.float32))
        else:
            sav = jnp.zeros(())
        return ReuseDecision(
            q=q_s, k=k_s, thetas=thetas,
            active_axes=tuple(cfg.axes) if self.snaps_operands else (),
            bias=cached.bias, q_mask=q_mask, k_mask=k_mask, savings=sav,
            block_map=cached.block_map,
            window=cfg.window if self.snaps_operands else 2,
            q_src=cached.q_idx, k_src=cached.k_idx)

    # -- savings accounting ---------------------------------------------

    def stats(self, decision: ReuseDecision) -> RippleStats:
        """RippleStats for ``with_stats=True`` callers."""
        zero = jnp.zeros(())
        realized = None
        if decision.block_map is not None:
            # A block map means the sparse backend executed this
            # decision: what's *realized* is its skipped-tile fraction,
            # not the collapse-path accounting (which never ran).
            from repro.kernels.sparse.ops import sparse_block_stats

            realized = sparse_block_stats(decision.block_map)
        if decision.q_mask is None or decision.k_mask is None:
            s = decision.savings if decision.savings is not None else zero
            return RippleStats(
                savings=s,
                structural_savings=realized if realized is not None else s,
                q_snap_frac=zero, k_snap_frac=zero)
        return RippleStats(
            savings=savings_lib.partial_score_savings(
                decision.q_mask, decision.k_mask),
            structural_savings=(
                realized if realized is not None
                else savings_lib.collapse_savings(
                    decision.q_mask, decision.k_mask, decision.window)),
            q_snap_frac=jnp.mean(decision.q_mask.astype(jnp.float32)),
            k_snap_frac=jnp.mean(decision.k_mask.astype(jnp.float32)),
        )


def _keep_block_map(keep: jax.Array,
                    block_shape: Optional[Tuple[int, int]]):
    """Tile a boolean keep-mask into sparse-backend states, or None when
    the dispatcher didn't plan the sparse backend (no ``block_shape``)."""
    if block_shape is None:
        return None
    from repro.kernels.sparse.ops import block_map_from_keep

    return block_map_from_keep(keep, *block_shape)


# ---------------------------------------------------------------------------
# Snap helpers shared by the operand-rewriting policies (the Fig. 6
# step ①-② pipeline, fused on-device or host-side per the plan)
# ---------------------------------------------------------------------------


def _snap_segment(seg, grid, thetas, cfg: RippleConfig, active_axes,
                  use_fused: bool, want_src: bool = False):
    """Step ①-② on one contiguous grid segment: fused kernel when the
    plan asks for it and the shape qualifies, host pipeline otherwise.
    ``want_src`` forces the host pipeline (the fused kernel does not
    expose snap sources) and additionally returns the source map —
    bitwise-equal outputs either way (the fused-mask parity contract)."""
    if use_fused and not want_src:
        from repro.kernels.reuse_mask.ops import (fused_compute_reuse,
                                                  fused_reuse_eligible)
        if fused_reuse_eligible(grid, window=cfg.window,
                                granularity=cfg.granularity,
                                axes=active_axes):
            s, m = fused_compute_reuse(seg, grid, thetas, axes=active_axes,
                                       granularity=cfg.granularity)
            return s, m, None
    r = reuse_lib.compute_reuse(
        seg, grid, thetas, axes=active_axes, window=cfg.window,
        granularity=cfg.granularity, channel_groups=cfg.channel_groups,
        want_src=want_src)
    return r.snapped, r.mask, r.src_idx


def snap_operand(x, do: bool, grid, thetas, cfg: RippleConfig, active_axes,
                 grid_slice, use_fused: bool, want_src: bool = False):
    """Snap one operand (or pass it through with an all-False mask when
    ``do`` is off).  ``grid_slice`` restricts snapping to the grid
    tokens of a mixed text+grid sequence.  Returns ``(snapped, mask,
    src)`` where ``src`` is the segment-scoped snap-source map (None
    unless ``want_src`` and ``do``)."""
    if not do:
        return x, jnp.zeros(x.shape, jnp.bool_), None
    if grid_slice is None:
        return _snap_segment(x, grid, thetas, cfg, active_axes, use_fused,
                             want_src)
    s, n = grid_slice
    seg = jax.lax.slice_in_dim(x, s, s + n, axis=-2)
    snapped_seg, mask_seg, src_seg = _snap_segment(
        seg, grid, thetas, cfg, active_axes, use_fused, want_src)
    snapped = jax.lax.dynamic_update_slice_in_dim(x, snapped_seg, s, axis=-2)
    mask = jnp.zeros(x.shape, jnp.bool_)
    mask = jax.lax.dynamic_update_slice_in_dim(mask, mask_seg, s, axis=-2)
    return snapped, mask, src_seg


def replay_snap(x, src, grid_slice, snaps_operands: bool):
    """Re-apply a cached snap-source map to a fresh operand: one
    ``take_along_axis`` gather over the grid segment (DESIGN.md §13).
    Returns ``(snapped, mask)``; with ``src is None`` the operand passes
    through (all-False mask for snap policies, no mask otherwise, so the
    pytree structure matches the corresponding decide branch)."""
    if src is None:
        return x, (jnp.zeros(x.shape, jnp.bool_) if snaps_operands else None)
    if grid_slice is None:
        snapped = jnp.take_along_axis(x, src, axis=-2)
        mask = src != jnp.arange(x.shape[-2], dtype=src.dtype)[:, None]
        return snapped, mask
    s, n = grid_slice
    seg = jax.lax.slice_in_dim(x, s, s + n, axis=-2)
    snapped_seg = jnp.take_along_axis(seg, src, axis=-2)
    snapped = jax.lax.dynamic_update_slice_in_dim(x, snapped_seg, s, axis=-2)
    mask_seg = src != jnp.arange(n, dtype=src.dtype)[:, None]
    mask = jnp.zeros(x.shape, jnp.bool_)
    mask = jax.lax.dynamic_update_slice_in_dim(mask, mask_seg, s, axis=-2)
    return snapped, mask


# ---------------------------------------------------------------------------
# Built-in policies
# ---------------------------------------------------------------------------


class RipplePolicy(ReusePolicy):
    """The paper's policy: Eq. 4 linear-ramp schedule + windowed Δ-check
    snapping on Q/K (``cfg.svg_mask`` additionally composes the SVG
    block mask on top, the TIMERIPPLE+SVG row of Tbl. 2)."""

    name = "ripple"
    caches_decisions = True

    def will_emit_bias(self, cfg):
        return self.emits_bias or cfg.svg_mask

    def will_emit_block_map(self, cfg):
        # The SVG combo's block mask tiles into skip/full/partial states,
        # so the sparse backend can realize it (snapping still happens;
        # only the pair-collapse structural win is traded away).
        return self.emits_block_map or cfg.svg_mask

    def will_seq_shard(self, cfg):
        # Pure snapping shards cleanly (halo exchange covers the window);
        # the +SVG combo would need the mask *and* snap paths fused on
        # the ring, which the ring driver doesn't implement — fall back.
        return not cfg.svg_mask

    def thetas_for(self, cfg, step, total_steps, thetas=None):
        if thetas is None:
            assert step is not None and total_steps is not None, (
                "attention_dispatch needs explicit thetas or "
                "(step, total_steps)")
            thetas = axis_thresholds(cfg, step, total_steps)
        return zero_inactive_axes(thetas, tuple(cfg.axes))

    def calibrate(self, q, k, grid, cfg, target_savings):
        from repro.core.calibrate import calibrate_threshold

        theta = calibrate_threshold(q, k, grid, cfg, target_savings)
        return {"fixed_threshold": theta}

    def decide(self, q, k, *, grid, cfg, thetas, bias=None, grid_slice=None,
               fused=False, block_shape=None, want_plan=False):
        active_axes = tuple(cfg.axes)
        q_s, q_mask, q_src = snap_operand(q, cfg.snap_q, grid, thetas, cfg,
                                          active_axes, grid_slice, fused,
                                          want_src=want_plan)
        k_s, k_mask, k_src = snap_operand(k, cfg.snap_k, grid, thetas, cfg,
                                          active_axes, grid_slice, fused,
                                          want_src=want_plan)
        block_map = None
        if cfg.svg_mask:
            keep, bias = svg_logit_bias(q_s, k_s, grid, grid_slice, bias)
            block_map = _keep_block_map(keep, block_shape)
        return ReuseDecision(
            q=q_s, k=k_s, thetas=thetas, active_axes=active_axes, bias=bias,
            q_mask=q_mask, k_mask=k_mask,
            savings=savings_lib.partial_score_savings(q_mask, k_mask),
            block_map=block_map, window=cfg.window,
            q_src=q_src, k_src=k_src)


class EqualMSEPolicy(RipplePolicy):
    """Ripple's decision under the Fig. 9 equal-impact schedule.

    The analytical step-sensitivity model (``core.calibrate``): the MSE
    a fixed θ induces decays log-linearly in the denoising step
    (``fit_step_sensitivity``), and at a fixed step MSE grows ~θ², so
    holding the induced MSE constant at its i_min level gives

        θ_i = θ_min · exp(−slope · (i − i_min) / 2)

    clipped to [θ_min, θ_max].  A table calibrated offline by
    ``equal_mse_schedule`` against *measured* MSEs overrides the
    analytic form (:meth:`from_schedule`).
    """

    name = "equal_mse"

    def __init__(self, mse_slope: float = -0.15,
                 theta_table: Optional[np.ndarray] = None,
                 table_i_min: Optional[int] = None):
        self.mse_slope = float(mse_slope)
        self.theta_table = (None if theta_table is None
                            else np.asarray(theta_table, np.float32))
        self.table_i_min = table_i_min

    @classmethod
    def from_schedule(cls, thetas: np.ndarray, i_min: int,
                      name: Optional[str] = None) -> "EqualMSEPolicy":
        """Wrap a per-step θ table from ``calibrate.equal_mse_schedule``."""
        pol = cls(theta_table=thetas, table_i_min=i_min)
        if name is not None:
            pol.name = name
        return pol

    def _shared_theta(self, cfg: RippleConfig, step, total_steps):
        i_min = (self.table_i_min if self.table_i_min is not None
                 else cfg.i_min)
        if self.theta_table is not None:
            tbl = jnp.asarray(self.theta_table, jnp.float32)
            idx = jnp.clip(jnp.asarray(step, jnp.int32) - i_min, 0,
                           tbl.shape[0] - 1)
            theta = tbl[idx]
        else:
            i = jnp.asarray(step, jnp.float32)
            lo = min(cfg.theta_min, cfg.theta_max)
            hi = max(cfg.theta_min, cfg.theta_max)
            ramp = cfg.theta_min * jnp.exp(
                -0.5 * self.mse_slope * (i - i_min))
            theta = jnp.clip(ramp, lo, hi)
        active = jnp.logical_and(
            jnp.asarray(step) >= i_min,
            jnp.asarray(step) < jnp.asarray(total_steps) - 1)
        return jnp.where(active, theta, 0.0)

    def thetas_for(self, cfg, step, total_steps, thetas=None):
        if thetas is None:
            assert step is not None and total_steps is not None, (
                "equal_mse needs explicit thetas or (step, total_steps)")
            shared = self._shared_theta(cfg, step, total_steps)
            thetas = {a: shared for a in AXES}
        return zero_inactive_axes(thetas, tuple(cfg.axes))


class SVGPolicy(ReusePolicy):
    """Sparse VideoGen-style structured masking, promoted from the
    TIMERIPPLE+SVG combination to a standalone strategy: each head is
    classified online as spatial (frame-block-diagonal) or temporal
    (strided-diagonal) and the losing mask's blocks are dropped via a
    −inf logit bias.  Q/K are never rewritten."""

    name = "svg"
    emits_bias = True
    snaps_operands = False
    emits_block_map = True
    caches_decisions = True

    def will_seq_shard(self, cfg):
        # Head classification has a sharded twin (classify_heads_sharded)
        # and the masks are row-separable, so each shard rebuilds its own
        # bias rows exactly.
        return True

    def thetas_for(self, cfg, step, total_steps, thetas=None):
        return _zero_thetas()  # no Δ-thresholds; masks are classified

    def decide(self, q, k, *, grid, cfg, thetas, bias=None, grid_slice=None,
               fused=False, block_shape=None, want_plan=False):
        # The whole decision is the (bias, block_map) pair, which the
        # cache carries verbatim — a cache hit skips the online head
        # classification entirely (no want_plan-specific work needed).
        keep, bias = svg_logit_bias(q, k, grid, grid_slice, bias)
        return ReuseDecision(
            q=q, k=k, thetas=thetas, active_axes=(), bias=bias,
            savings=1.0 - jnp.mean(keep.astype(jnp.float32)),
            block_map=_keep_block_map(keep, block_shape))

    def stats(self, decision):
        zero = jnp.zeros(())
        # savings = skippable score fraction (mask density); structural
        # = the tile fraction the block-sparse backend skips outright —
        # 0 when no block map was planned (reference execution computes
        # the full dense score matrix and only zeroes weights).
        if decision.block_map is not None:
            from repro.kernels.sparse.ops import sparse_block_stats

            structural = sparse_block_stats(decision.block_map)
        else:
            structural = zero
        return RippleStats(savings=decision.savings,
                           structural_savings=structural,
                           q_snap_frac=zero, k_snap_frac=zero)


class DensePolicy(ReusePolicy):
    """No-op baseline: every plan resolves to the dense backend, so
    ``--policy dense`` measures the exact cost of turning reuse off
    without touching the config."""

    name = "dense"
    snaps_operands = False
    is_dense = True

    def decide(self, q, k, *, grid, cfg, thetas, bias=None, grid_slice=None,
               fused=False, block_shape=None):
        return ReuseDecision(q=q, k=k, thetas=thetas, active_axes=(),
                             bias=bias, savings=jnp.zeros(()))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: "OrderedDict[str, ReusePolicy]" = OrderedDict()


def register_policy(policy: ReusePolicy, *, name: Optional[str] = None,
                    override: bool = False) -> ReusePolicy:
    """Register ``policy`` under ``name`` (default ``policy.name``).

    Registration is the whole integration surface: a registered name is
    immediately valid as ``RippleConfig.policy``, as
    ``attention_dispatch(..., policy=...)``, as a per-request
    ``GenRequest.policy``, and as ``--policy`` on the launchers.  Plan
    caches key on the policy *name*, so re-registering (``override``)
    takes effect for new plans without a cache flush.
    """
    n = name or getattr(policy, "name", "")
    if not n or not isinstance(n, str):
        raise ValueError(f"policy {policy!r} needs a non-empty string name")
    if n in _REGISTRY and not override:
        raise ValueError(
            f"policy {n!r} already registered (pass override=True to "
            f"replace it)")
    _REGISTRY[n] = policy
    return policy


def get_policy(name) -> ReusePolicy:
    """Look up a registered policy; ReusePolicy instances pass through."""
    if isinstance(name, ReusePolicy):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown reuse policy {name!r}; registered: "
                       f"{list_policies()}") from None


def list_policies() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


register_policy(RipplePolicy())
register_policy(SVGPolicy())
register_policy(EqualMSEPolicy())
register_policy(DensePolicy())

# The pattern-search policies (``static``, ``rainfusion``) live in
# core/patterns.py and register themselves on import; importing here
# makes every registry consumer see them without a separate import.
from repro.core import patterns as _patterns  # noqa: E402,F401
