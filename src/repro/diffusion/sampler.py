"""Samplers: DDIM (for DDPM-trained DiT/UNet) and Euler rectified flow
(for MMDiT/vDiT).  The denoising loop is where the paper lives: every
step's index feeds the Eq. 4 threshold schedule of TimeRipple, so the
model function receives (x_t, t_cont, step, total_steps).

``denoise_fn(x, t, step) -> eps/velocity`` closes over params, text
conditioning and the RippleConfig; samplers stay model-agnostic.

Cross-step decision cache (DESIGN.md §13): pass ``decision_state`` (the
model's per-layer stacked CachedDecision, e.g. from
``launch.workloads.vdit_decision_state``) and the contract becomes
``denoise_fn(x, t, step, state) -> (eps/velocity, state)`` — the state
rides the denoising scan's carry, so the reuse decision is recomputed
only on the ``reuse_every`` cadence (or drift), and the sampler returns
``(x, final_state)`` so callers can report cache hit rates.

Per-step counters: with ``with_stats`` the model function appends a
pytree of step counters to its return (``(out, stats)``, or ``(out,
state, stats)`` with a decision state) and the sampler appends them,
stacked along a leading step axis, to its own return.

Each step's update is traced under ``jax.named_scope("sampler")``, so
the profiler can tell it from the model's work.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.diffusion.schedule import DDPMSchedule, RectifiedFlowSchedule


def ddim_sample(
    denoise_fn: Callable,
    x_T: jax.Array,
    schedule: DDPMSchedule,
    num_steps: int,
    *,
    eta: float = 0.0,
    rng: Optional[jax.Array] = None,
    decision_state=None,
    step_offset=0,
    total_steps: Optional[int] = None,
    sentinel: bool = False,
    with_stats: bool = False,
):
    """DDIM sampler. denoise_fn(x, t_int (B,), step_idx) -> eps.

    With ``decision_state`` the model's decision cache rides the scan
    carry (``denoise_fn(x, t, step, state) -> (eps, state)``) and the
    sampler returns ``(x, final_state)``.

    With ``sentinel`` (guardrails, DESIGN.md §17) a running i32 count of
    non-finite latent entries rides the carry — one elementwise
    ``isfinite`` per step — and is appended to the return, so the
    serving engine can trip its degradation ladder without a host
    round-trip per step.

    Chunked execution (streaming delivery, DESIGN.md §15.3): pass
    ``total_steps=T`` (the full schedule length) and run the scan in
    slices — ``step_offset`` steps already done, ``num_steps`` to run
    now — feeding each chunk's output ``x`` (and decision state) into
    the next chunk's input.  The per-step math is identical to the
    monolithic call: the timestep table is built from ``total_steps``
    and the body indexes it by absolute step, so chaining chunks
    reproduces the single-scan result exactly.  ``step_offset`` may be
    a traced int32 scalar, letting one compiled chunk serve every
    offset.  The deterministic path (``rng=None``, the serving default)
    carries no cross-chunk RNG; chunked stochastic sampling (``eta >
    0``) needs the caller to split a fresh key per chunk.

    That chunk-chaining exactness is also the crash-recovery contract
    (DESIGN.md §18): a run resumed from a chunk-boundary checkpoint
    ``(x, decision_state, step_offset)`` replays exactly the remaining
    schedule slice, so warm restart and router failover reproduce the
    uninterrupted trajectory bitwise."""
    total = num_steps if total_steps is None else total_steps
    T = schedule.num_train_steps
    ts = jnp.linspace(T - 1, 0, total).astype(jnp.int32)
    alpha_bars = schedule.alpha_bars()
    B = x_T.shape[0]
    bshape = (-1,) + (1,) * (x_T.ndim - 1)

    def body(carry, si):
        x, rng, dstate, nf = carry
        t = ts[si]
        eps, dstate, stats = _call(denoise_fn, x, jnp.full((B,), t), si,
                                   dstate, with_stats)
        with jax.named_scope("sampler"):
            t_prev = jnp.where(si + 1 < total,
                               ts[jnp.minimum(si + 1, total - 1)], -1)
            ab_t = alpha_bars[t]
            ab_prev = jnp.where(t_prev >= 0,
                                alpha_bars[jnp.maximum(t_prev, 0)], 1.0)
            x0 = (x - jnp.sqrt(1 - ab_t) * eps) / jnp.sqrt(ab_t)
            sigma = eta * jnp.sqrt((1 - ab_prev) / (1 - ab_t)) * \
                jnp.sqrt(1 - ab_t / ab_prev)
            dir_xt = jnp.sqrt(jnp.maximum(1 - ab_prev - sigma ** 2,
                                          0.0)) * eps
            if rng is not None:
                rng, sub = jax.random.split(rng)
                noise = jax.random.normal(sub, x.shape, x.dtype)
            else:
                noise = jnp.zeros_like(x)
            x = jnp.sqrt(ab_prev) * x0 + dir_xt + sigma * noise
            if sentinel:
                nf = nf + jnp.sum(~jnp.isfinite(x)).astype(jnp.int32)
        return (x, rng, dstate, nf), stats

    (x, _, dstate, nf), stats = jax.lax.scan(
        body, (x_T, rng if rng is not None else jax.random.PRNGKey(0),
               decision_state, jnp.zeros((), jnp.int32)),
        jnp.arange(num_steps) + step_offset)
    return _result(x, dstate, nf, stats, decision_state is not None,
                   sentinel, with_stats)


def euler_flow_sample(
    denoise_fn: Callable,
    x_T: jax.Array,
    num_steps: int,
    *,
    schedule: Optional[RectifiedFlowSchedule] = None,
    decision_state=None,
    step_offset=0,
    total_steps: Optional[int] = None,
    sentinel: bool = False,
    with_stats: bool = False,
):
    """Euler ODE integration of rectified flow from t=1 (noise) to t=0.
    denoise_fn(x, t_cont (B,), step_idx) -> velocity (noise - x0).

    With ``decision_state`` the model's decision cache rides the scan
    carry (``denoise_fn(x, t, step, state) -> (v, state)``) and the
    sampler returns ``(x, final_state)``.  ``step_offset`` /
    ``total_steps`` slice the integration for chunked streaming exactly
    as in :func:`ddim_sample`; ``sentinel`` appends a running non-finite
    latent count to the return, as there."""
    total = num_steps if total_steps is None else total_steps
    B = x_T.shape[0]
    ts = jnp.linspace(1.0, 0.0, total + 1)

    def body(carry, si):
        x, dstate, nf = carry
        t, t_next = ts[si], ts[si + 1]
        v, dstate, stats = _call(denoise_fn, x, jnp.full((B,), t), si,
                                 dstate, with_stats)
        with jax.named_scope("sampler"):
            x = x + (t_next - t) * v
            if sentinel:
                nf = nf + jnp.sum(~jnp.isfinite(x)).astype(jnp.int32)
        return (x, dstate, nf), stats

    (x, dstate, nf), stats = jax.lax.scan(
        body, (x_T, decision_state, jnp.zeros((), jnp.int32)),
        jnp.arange(num_steps) + step_offset)
    return _result(x, dstate, nf, stats, decision_state is not None,
                   sentinel, with_stats)


def _call(denoise_fn, x, t, si, dstate, with_stats):
    """One model call under the sampler contract: ``(out, state,
    stats)``, with None for what the call does not return."""
    res = (denoise_fn(x, t, si) if dstate is None
           else denoise_fn(x, t, si, dstate))
    if dstate is None and not with_stats:
        return res, None, None
    res = list(res)
    stats = res.pop() if with_stats else None
    return res[0], (res[1] if dstate is not None else None), stats


def _result(x, dstate, nf, stats, has_state, sentinel, with_stats):
    """``x``, then the final decision state, the non-finite count and the
    per-step stats, each where asked for."""
    out = (x,) + ((dstate,) if has_state else ()) \
        + ((nf,) if sentinel else ()) + ((stats,) if with_stats else ())
    return out if len(out) > 1 else x


def cfg_wrap(denoise_fn: Callable, guidance: float) -> Callable:
    """Classifier-free guidance: denoise_fn must accept ``cond`` batches
    stacked [uncond; cond] and return stacked outputs."""

    def wrapped(x, t, step):
        out = denoise_fn(jnp.concatenate([x, x]), jnp.concatenate([t, t]), step)
        un, co = jnp.split(out, 2, axis=0)
        return un + guidance * (co - un)

    return wrapped
