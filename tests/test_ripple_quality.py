"""Quality-claim tests: the paper's core comparative claims, validated on
correlated synthetic latents (DESIGN.md §9.3).

These mirror the benchmarks but as pass/fail invariants:
  * Fig. 7 — reuse beats masking AND beats skip-same-selection by a wide
    MSE margin at matched savings;
  * calibration hits a target savings ratio;
  * the savings the stats report are what the masks actually imply.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import RippleConfig
from repro.core import reuse, savings
from repro.core.calibrate import calibrate_threshold
from repro.core.dispatch import attention_dispatch, dense_attention
from repro.data.synthetic import correlated_video_latents

GRID = (8, 8, 8)
N = 8 * 8 * 8
D = 32


def _correlated_qk(seed=0):
    lat = correlated_video_latents(jax.random.PRNGKey(seed), 1, GRID, D,
                                   temporal_rho=0.95, spatial_smooth=2)
    x = lat.reshape(1, 1, N, D)
    wq = 0.5 * jax.random.normal(jax.random.PRNGKey(seed + 1), (D, D))
    wk = 0.5 * jax.random.normal(jax.random.PRNGKey(seed + 2), (D, D))
    return jnp.einsum("bhnd,df->bhnf", x, wq), \
        jnp.einsum("bhnd,df->bhnf", x, wk)


def _attn_mse(q1, k1, q2, k2, v):
    scale = 1 / np.sqrt(D)
    a = dense_attention(q1, k1, v, scale)
    b = dense_attention(q2, k2, v, scale)
    return float(jnp.mean(jnp.square(a - b)))


def test_reuse_beats_masking_at_matched_savings():
    """Paper Fig. 7: at the same token-saving ratio, reuse has (much)
    lower output MSE than (a) masking the lowest-magnitude entries and
    (b) skipping exactly the entries reuse would have reused."""
    q, k = _correlated_qk()
    v = jax.random.normal(jax.random.PRNGKey(9), (1, 1, N, D))
    th = {a: jnp.asarray(0.35) for a in ("t", "x", "y")}
    rq = reuse.compute_reuse(q, GRID, th)
    rk = reuse.compute_reuse(k, GRID, th)
    ratio = float(savings.partial_score_savings(rq.mask, rk.mask))
    assert 0.2 < ratio < 0.99

    mse_reuse = _attn_mse(rq.snapped, rk.snapped, q, k, v)

    # baseline 2 (same selection, zeroed instead of reused)
    q_skip = jnp.where(rq.mask, 0.0, q)
    k_skip = jnp.where(rk.mask, 0.0, k)
    mse_skip = _attn_mse(q_skip, k_skip, q, k, v)

    # baseline 1 (mask lowest-|value| entries at the same per-operand rate)
    def low_mask(x, frac):
        thr = jnp.quantile(jnp.abs(x), frac)
        return jnp.where(jnp.abs(x) < thr, 0.0, x)

    q_mask = low_mask(q, float(rq.mask.mean()))
    k_mask = low_mask(k, float(rk.mask.mean()))
    mse_mask = _attn_mse(q_mask, k_mask, q, k, v)

    # order-of-magnitude vs skip-same-selection (the paper's headline);
    # clearly better (>2x) vs magnitude masking on synthetic latents.
    assert mse_reuse < mse_skip / 5, (mse_reuse, mse_skip)
    assert mse_reuse < mse_mask / 2, (mse_reuse, mse_mask)


def test_calibration_hits_target_savings():
    q, k = _correlated_qk(3)
    cfg = RippleConfig(enabled=True)
    for target in (0.5, 0.75):
        theta = calibrate_threshold(q, k, GRID, cfg, target, tol=0.02)
        got = _savings_at(q, k, theta, cfg)
        assert abs(got - target) < 0.05, (target, theta, got)


def _savings_at(q, k, theta, cfg):
    th = {a: jnp.asarray(theta) for a in ("t", "x", "y")}
    rq = reuse.compute_reuse(q, GRID, th, axes=cfg.axes, window=cfg.window)
    rk = reuse.compute_reuse(k, GRID, th, axes=cfg.axes, window=cfg.window)
    return float(savings.partial_score_savings(rq.mask, rk.mask))


def test_error_monotone_in_savings():
    """More reuse ⇒ more error (sanity for the threshold/quality dial)."""
    q, k = _correlated_qk(5)
    v = jax.random.normal(jax.random.PRNGKey(6), (1, 1, N, D))
    last_mse = -1.0
    for theta in (0.1, 0.4, 1.0):
        th = {a: jnp.asarray(theta) for a in ("t", "x", "y")}
        rq = reuse.compute_reuse(q, GRID, th)
        rk = reuse.compute_reuse(k, GRID, th)
        mse = _attn_mse(rq.snapped, rk.snapped, q, k, v)
        assert mse >= last_mse
        last_mse = mse


def test_window2_saves_more_than_window4_on_moderate_correlation():
    """Paper Fig. 11: larger windows reduce eligible tokens (all K members
    must agree), so savings drop — window 2 is the sweet spot."""
    q, k = _correlated_qk(7)
    cfg2 = RippleConfig(enabled=True, window=2)
    cfg4 = RippleConfig(enabled=True, window=4)
    s2 = _savings_at(q, k, 0.3, cfg2)
    s4_cfg = RippleConfig(enabled=True, window=4)
    th = {a: jnp.asarray(0.3) for a in ("t", "x", "y")}
    rq = reuse.compute_reuse(q, GRID, th, window=4)
    rk = reuse.compute_reuse(k, GRID, th, window=4)
    s4 = float(savings.partial_score_savings(rq.mask, rk.mask))
    assert s2 > s4


def test_structural_savings_materialize_on_redundant_data():
    """On highly-redundant latents the collapse path actually skips
    blocks (token-granularity snapping makes full pairs)."""
    q, k = _correlated_qk(11)
    cfg = RippleConfig(enabled=True, granularity="token",
                       fixed_threshold=0.5, i_min=0, i_max=1,
                       execution="collapse")
    out, stats = attention_dispatch(
        q, k, jax.random.normal(jax.random.PRNGKey(12), (1, 1, N, D)),
        grid=GRID, cfg=cfg, step=jnp.asarray(0), total_steps=10,
        with_stats=True)
    assert float(stats.structural_savings) > 0.1
