"""Unit + property tests for the TimeRipple core (paper §3.3 semantics)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config.base import RippleConfig
from repro.core import reuse, savings
from repro.core.collapse import (collapsed_attention, pair_flags,
                                 pair_major_order)
from repro.core.dispatch import attention_dispatch, dense_attention
from repro.core.schedule import axis_thresholds, threshold_for_step

GRID = (4, 4, 6)
N = GRID[0] * GRID[1] * GRID[2]
D = 16


def _qk(seed=0, shape=(2, 3, N, D)):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def thetas(v):
    return {a: jnp.asarray(v, jnp.float32) for a in ("t", "x", "y")}


class TestEq3Delta:
    def test_window2_matches_halved_absdiff(self):
        x = _qk(1)
        delta, rep = reuse.window_delta(x.reshape(2, 3, *GRID, D), -4, 2)
        xg = np.asarray(x).reshape(2, 3, *GRID, D)
        expect = np.abs(xg[..., 1::2, :, :, :] - xg[..., 0::2, :, :, :]) / 2
        np.testing.assert_allclose(np.asarray(delta), expect, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(rep), xg[..., 0::2, :, :, :])

    def test_window4_population_std(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 5))
        delta, rep = reuse.window_delta(x, 0, 4)
        xg = np.asarray(x).reshape(2, 4, 5)
        np.testing.assert_allclose(np.asarray(delta), xg.std(axis=1),
                                   rtol=1e-5)

    def test_remainder_excluded(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (7, 5))
        delta, rep = reuse.window_delta(x, 0, 2)
        assert delta.shape == (3, 5)  # 7 // 2 windows


class TestReuseMasks:
    def test_zero_threshold_never_snaps(self):
        r = reuse.compute_reuse(_qk(), GRID, thetas(0.0))
        assert not bool(r.mask.any())
        np.testing.assert_array_equal(np.asarray(r.snapped),
                                      np.asarray(_qk()))

    def test_infinite_threshold_snaps_all_followers(self):
        r = reuse.compute_reuse(_qk(), GRID, thetas(1e9))
        # OR over 3 axes with window 2: follower fraction 1 - (1/2)^3
        assert abs(float(r.mask.mean()) - (1 - 0.5 ** 3)) < 1e-6

    def test_representative_never_snapped(self):
        r = reuse.compute_reuse(_qk(), GRID, thetas(1e9), axes=("x",))
        m = np.asarray(r.mask).reshape(2, 3, *GRID, D)
        assert not m[..., 0::2, :].any()
        assert m[..., 1::2, :].all()

    def test_snapped_values_equal_representative(self):
        r = reuse.compute_reuse(_qk(5), GRID, thetas(0.7))
        x = np.asarray(_qk(5)).reshape(2, 3, *GRID, D)
        s = np.asarray(r.snapped).reshape(2, 3, *GRID, D)
        m = np.asarray(r.mask).reshape(2, 3, *GRID, D)
        # wherever not snapped, value unchanged
        np.testing.assert_array_equal(s[~m], x[~m])
        # x-axis followers snapped by the x test copy their x-neighbor
        rx = reuse.compute_reuse(_qk(5), GRID, thetas(0.7), axes=("x",))
        sx = np.asarray(rx.snapped).reshape(2, 3, *GRID, D)
        mx = np.asarray(rx.mask).reshape(2, 3, *GRID, D)
        rep = np.repeat(x[..., 0::2, :], 2, axis=-2)
        np.testing.assert_array_equal(sx[mx], rep[mx])

    @settings(max_examples=20, deadline=None)
    @given(lo=st.floats(0.0, 0.5), hi=st.floats(0.5, 2.0))
    def test_mask_monotone_in_threshold(self, lo, hi):
        x = _qk(7, (1, 1, N, D))
        m_lo = reuse.compute_reuse(x, GRID, thetas(lo)).mask
        m_hi = reuse.compute_reuse(x, GRID, thetas(hi)).mask
        assert bool(jnp.all(jnp.logical_or(~m_lo, m_hi)))  # lo ⊆ hi

    def test_token_granularity_gates_whole_tokens(self):
        r = reuse.compute_reuse(_qk(9), GRID, thetas(0.8),
                                granularity="token")
        m = np.asarray(r.mask)
        per_tok = m.all(axis=-1) | (~m.any(axis=-1))
        assert per_tok.all()

    def test_grid_mismatch_raises(self):
        with pytest.raises(ValueError):
            reuse.compute_reuse(_qk(), (3, 3, 3), thetas(1.0))


class TestSavings:
    def test_matches_bruteforce(self):
        rng = np.random.default_rng(0)
        qm = rng.random((1, 1, 12, 5)) < 0.4
        km = rng.random((1, 1, 12, 5)) < 0.2
        got = float(savings.partial_score_savings(jnp.asarray(qm),
                                                  jnp.asarray(km)))
        # brute force: product (i,j,c) computed iff neither snapped
        computed = 0
        for c in range(5):
            fq = qm[0, 0, :, c].mean()
            fk = km[0, 0, :, c].mean()
            computed += (1 - fq) * (1 - fk)
        expect = 1 - computed / 5
        assert abs(got - expect) < 1e-6

    def test_theoretical_speedup_formula(self):
        s = savings.theoretical_speedup(0.78, jnp.asarray(0.85))
        assert abs(float(s) - 1 / (1 - 0.78 * 0.85)) < 1e-6


class TestSchedule:
    CFG = RippleConfig(enabled=True, theta_min=0.2, theta_max=0.5,
                       i_min=10, i_max=20)

    def test_dense_before_imin_and_last_step(self):
        assert float(threshold_for_step(self.CFG, 0, 50)) == 0.0
        assert float(threshold_for_step(self.CFG, 9, 50)) == 0.0
        assert float(threshold_for_step(self.CFG, 49, 50)) == 0.0

    def test_linear_ramp_and_plateau(self):
        t10 = float(threshold_for_step(self.CFG, 10, 50))
        t15 = float(threshold_for_step(self.CFG, 15, 50))
        t20 = float(threshold_for_step(self.CFG, 20, 50))
        t40 = float(threshold_for_step(self.CFG, 40, 50))
        assert abs(t10 - 0.2) < 1e-6
        assert abs(t15 - 0.35) < 1e-6
        assert abs(t20 - 0.5) < 1e-6
        assert abs(t40 - 0.5) < 1e-6  # plateau at theta_max

    def test_axis_override(self):
        cfg = RippleConfig(enabled=True, theta_min=0.2, theta_max=0.5,
                           i_min=0, i_max=10, theta_t=0.9)
        th = axis_thresholds(cfg, 5, 50)
        assert abs(float(th["t"]) - 0.9) < 1e-6
        assert float(th["x"]) == float(th["y"])

    def test_fixed_threshold_mode(self):
        cfg = RippleConfig(enabled=True, fixed_threshold=0.33, i_min=0,
                           i_max=10)
        assert abs(float(threshold_for_step(cfg, 5, 50)) - 0.33) < 1e-6


class TestCollapse:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 100), frac=st.floats(0.0, 1.0))
    def test_collapse_equals_dense_snapped(self, seed, frac):
        key = jax.random.PRNGKey(seed)
        x = jax.random.normal(key, (1, 2, 32, 8))
        e, o = x[..., 0::2, :], x[..., 1::2, :]
        coll = jax.random.uniform(jax.random.fold_in(key, 1),
                                  (1, 2, 16, 1)) < frac
        o = jnp.where(coll, e, o)
        snapped = jnp.stack([e, o], axis=3).reshape(1, 2, 32, 8)
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 32, 8))
        scale = 1 / np.sqrt(8)
        dense = dense_attention(snapped, snapped, v, scale)
        col = collapsed_attention(snapped, snapped, v, scale=scale)
        np.testing.assert_allclose(np.asarray(col), np.asarray(dense),
                                   atol=2e-5)

    def test_pair_flags_value_equality(self):
        x = jnp.asarray([[1., 2.], [1., 2.], [3., 4.], [5., 6.]])[None]
        f = pair_flags(x)
        np.testing.assert_array_equal(np.asarray(f[0]), [True, False])

    def test_pair_major_order_permutation_and_adjacency(self):
        for axis in ("t", "x", "y"):
            perm = pair_major_order(GRID, axis)
            assert sorted(perm.tolist()) == list(range(N))
        # after t-pair-major reorder, positions 2j and 2j+1 are t-partners
        perm = pair_major_order(GRID, "t")
        T, H, W = GRID
        coords = np.unravel_index(perm, GRID)
        t, y, x = coords
        assert ((t[0::2] + 1 == t[1::2]) & (y[0::2] == y[1::2])
                & (x[0::2] == x[1::2])).all()


class TestRippleAttention:
    CFG = RippleConfig(enabled=True, theta_min=0.2, theta_max=0.5,
                       i_min=2, i_max=6)

    def test_dense_when_disabled(self):
        q, k, v = _qk(1), _qk(2), _qk(3)
        out = attention_dispatch(q, k, v, grid=GRID, cfg=RippleConfig())
        ref = dense_attention(q, k, v, 1 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)

    def test_dense_at_early_steps(self):
        q, k, v = _qk(1), _qk(2), _qk(3)
        out = attention_dispatch(q, k, v, grid=GRID, cfg=self.CFG,
                               step=jnp.asarray(0), total_steps=10)
        ref = dense_attention(q, k, v, 1 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)

    def test_collapse_execution_matches_reference(self):
        import dataclasses
        q, k, v = _qk(1), _qk(2), _qk(3)
        cfg_ref = dataclasses.replace(self.CFG, execution="reference")
        cfg_col = dataclasses.replace(self.CFG, execution="collapse")
        o1 = attention_dispatch(q, k, v, grid=GRID, cfg=cfg_ref,
                              step=jnp.asarray(5), total_steps=10)
        o2 = attention_dispatch(q, k, v, grid=GRID, cfg=cfg_col,
                              step=jnp.asarray(5), total_steps=10)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5)

    def test_grid_slice_protects_text_tokens(self):
        L = 8
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, L + N, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, L + N, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, L + N, D))
        out, stats = attention_dispatch(
            q, k, v, grid=GRID, cfg=self.CFG, step=jnp.asarray(5),
            total_steps=10, grid_slice=(L, N), with_stats=True)
        assert out.shape == q.shape
        assert float(stats.savings) > 0

    def test_stats_savings_match_calibration(self):
        q, k, v = _qk(1), _qk(2), _qk(3)
        _, stats = attention_dispatch(q, k, v, grid=GRID, cfg=self.CFG,
                                    step=jnp.asarray(6), total_steps=10,
                                    with_stats=True)
        assert 0.0 < float(stats.savings) < 1.0
