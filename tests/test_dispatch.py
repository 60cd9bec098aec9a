"""Tests for the unified attention-dispatch layer (DESIGN.md §8):
backend equivalence, fused-mask parity, shape bucketing, plan-cache LRU
bounds, and the autotune-cache round trip."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config.base import RippleConfig
from repro.core import dispatch
from repro.core.collapse import collapsed_attention
from repro.core.dispatch import (attention_dispatch, autotune_attention,
                                 dense_attention, get_policy, resolve_plan,
                                 shape_bucket)
from repro.core.reuse import compute_reuse
from repro.kernels.reuse_mask.ops import (fused_compute_reuse,
                                          fused_reuse_eligible)

GRID = (4, 4, 6)
N = GRID[0] * GRID[1] * GRID[2]
D = 16

CFG = RippleConfig(enabled=True, theta_min=0.2, theta_max=0.5,
                   i_min=2, i_max=6)


def _qkv(seed=0, shape=(2, 3, N, D)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, shape) for k in ks)


class TestBackendEquivalence:
    """Dispatch output matches the paper pipeline built from first
    principles (compute_reuse snap → backend math) — no shim."""

    STEP = jnp.asarray(5)

    def _dispatch(self, backend, cfg=CFG, **kw):
        q, k, v = _qkv(1)
        return attention_dispatch(q, k, v, grid=GRID, cfg=cfg,
                                  step=self.STEP, total_steps=10,
                                  backend=backend, **kw)

    def _snapped(self, q, k, cfg=CFG):
        thetas = get_policy("ripple").thetas_for(cfg, self.STEP, 10)
        rq = compute_reuse(q, GRID, thetas, window=cfg.window)
        rk = compute_reuse(k, GRID, thetas, window=cfg.window)
        return rq.snapped, rk.snapped

    def test_reference_matches_manual_snapped_dense(self):
        q, k, v = _qkv(1)
        q_s, k_s = self._snapped(q, k)
        direct = dense_attention(q_s, k_s, v, 1.0 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(self._dispatch("reference")),
                                   np.asarray(direct), atol=1e-6)

    def test_collapse_matches_manual_collapsed(self):
        q, k, v = _qkv(1)
        q_s, k_s = self._snapped(q, k)
        direct = collapsed_attention(q_s, k_s, v, window=CFG.window,
                                     scale=1.0 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(self._dispatch("collapse")),
                                   np.asarray(direct), atol=3e-5)

    def test_pallas_matches_reference(self):
        ref = self._dispatch("reference")
        np.testing.assert_allclose(np.asarray(self._dispatch("pallas")),
                                   np.asarray(ref), atol=3e-5)

    def test_backends_agree_with_each_other(self):
        ref = self._dispatch("reference")
        for b in ("collapse", "pallas"):
            np.testing.assert_allclose(np.asarray(self._dispatch(b)),
                                       np.asarray(ref), atol=3e-5)

    def test_dense_backend_bypasses_pipeline(self):
        q, k, v = _qkv(1)
        out = self._dispatch("dense")
        ref = dense_attention(q, k, v, 1.0 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)

    def test_inactive_cfg_is_dense(self):
        q, k, v = _qkv(2)
        out = attention_dispatch(q, k, v, grid=GRID, cfg=RippleConfig())
        ref = dense_attention(q, k, v, 1.0 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError):
            self._dispatch("cudnn")

    def test_grid_slice_and_stats(self):
        L = 8
        q, k, v = _qkv(3, (1, 2, L + N, D))
        out, stats = attention_dispatch(
            q, k, v, grid=GRID, cfg=CFG, step=self.STEP, total_steps=10,
            grid_slice=(L, N), with_stats=True)
        # manual reference: snap only the grid segment, dense attention
        thetas = get_policy("ripple").thetas_for(CFG, self.STEP, 10)

        def snap_seg(x):
            seg = x[..., L:, :]
            r = compute_reuse(seg, GRID, thetas, window=CFG.window)
            return jnp.concatenate([x[..., :L, :], r.snapped], axis=-2), \
                r.mask
        q_s, q_mask = snap_seg(q)
        k_s, k_mask = snap_seg(k)
        ref = dense_attention(q_s, k_s, v, 1.0 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)
        from repro.core.savings import partial_score_savings
        pad_q = jnp.concatenate(
            [jnp.zeros((*q.shape[:-2], L, D), jnp.bool_), q_mask], axis=-2)
        pad_k = jnp.concatenate(
            [jnp.zeros((*k.shape[:-2], L, D), jnp.bool_), k_mask], axis=-2)
        assert float(stats.savings) == pytest.approx(
            float(partial_score_savings(pad_q, pad_k)))


class TestFusedMask:
    """The fused Pallas Δ-check/snap kernel is bit-exact vs the host."""

    @pytest.mark.parametrize("grid,lead", [
        ((4, 4, 6), (2, 3)),
        ((1, 4, 8), (1, 2)),   # single frame: t check never fires
        ((2, 2, 2), ()),
    ])
    @pytest.mark.parametrize("granularity", ["channel", "token"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_host_pipeline(self, grid, lead, granularity, dtype):
        n = grid[0] * grid[1] * grid[2]
        x = jax.random.normal(jax.random.PRNGKey(0), (*lead, n, D), dtype)
        th = {a: jnp.asarray(0.6, jnp.float32) for a in ("t", "x", "y")}
        assert fused_reuse_eligible(grid, granularity=granularity)
        r = compute_reuse(x, grid, th, granularity=granularity)
        s, m = fused_compute_reuse(x, grid, th, granularity=granularity)
        np.testing.assert_array_equal(np.asarray(m), np.asarray(r.mask))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(r.snapped))

    def test_axis_priority_matches_host(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (1, N, D))
        th = {a: jnp.asarray(0.9, jnp.float32) for a in ("t", "x", "y")}
        for axes in (("t", "x", "y"), ("y", "t", "x"), ("x",)):
            r = compute_reuse(x, GRID, th, axes=axes)
            s, m = fused_compute_reuse(x, GRID, th, axes=axes)
            np.testing.assert_array_equal(np.asarray(s), np.asarray(r.snapped))

    def test_ineligible_shapes_fall_back(self):
        # odd spatial dims / odd frame counts / group granularity
        assert not fused_reuse_eligible((4, 3, 4))
        assert not fused_reuse_eligible((3, 4, 4))      # odd T with t check
        assert fused_reuse_eligible((3, 4, 4), axes=("x", "y"))
        assert not fused_reuse_eligible((4, 4, 4), granularity="group")
        assert not fused_reuse_eligible((4, 4, 4), window=4)

    def test_dispatch_fused_on_equals_host_path(self):
        q, k, v = _qkv(4)
        kw = dict(grid=GRID, step=jnp.asarray(5), total_steps=10)
        host = attention_dispatch(
            q, k, v, cfg=dataclasses.replace(CFG, fused_mask="off"), **kw)
        fused = attention_dispatch(
            q, k, v, cfg=dataclasses.replace(CFG, fused_mask="on"), **kw)
        np.testing.assert_array_equal(np.asarray(host), np.asarray(fused))


class TestPlansAndBuckets:
    def test_shape_bucket_powers_of_two(self):
        assert shape_bucket(1) == 64
        assert shape_bucket(96) == 128
        assert shape_bucket(128) == 128
        assert shape_bucket(129) == 256
        assert shape_bucket(32768) == 32768

    def test_nearby_shapes_share_plan(self):
        p1 = resolve_plan((2, 3, 96, D), (2, 3, 96, D), CFG)
        p2 = resolve_plan((2, 3, 100, D), (2, 3, 100, D), CFG)
        assert p1 is p2  # same bucket -> same cached plan object

    def test_auto_backend_on_cpu_follows_execution(self):
        p = resolve_plan((1, 1, N, D), (1, 1, N, D), CFG)
        assert p.backend == "reference"
        cfg = dataclasses.replace(CFG, execution="collapse")
        p = resolve_plan((1, 1, N, D), (1, 1, N, D), cfg)
        assert p.backend == "collapse"

    def test_inactive_resolves_dense(self):
        p = resolve_plan((1, 1, N, D), (1, 1, N, D), RippleConfig())
        assert p.backend == "dense"

    def test_plan_summary_prints(self):
        s = resolve_plan((1, 1, N, D), (1, 1, N, D), CFG).summary()
        assert "reference" in s


class TestSparseBackendResolution:
    """Plan resolution for the block-sparse masked flash backend
    (DESIGN.md §12): block-map policies land on it, explicit 'sparse'
    is honoured, and its block sizes come from the autotune cache."""

    def test_svg_auto_resolves_sparse(self):
        dispatch.clear_plan_cache()
        try:
            p = resolve_plan((1, 1, N, D), (1, 1, N, D), CFG, policy="svg")
            assert p.backend == "sparse"
            assert "sparse" in p.summary()
        finally:
            dispatch.clear_plan_cache()

    def test_explicit_sparse_honoured_for_any_policy(self):
        dispatch.clear_plan_cache()
        try:
            p = resolve_plan((1, 1, N, D), (1, 1, N, D), CFG,
                             backend="sparse")
            assert p.backend == "sparse" and p.policy == "ripple"
        finally:
            dispatch.clear_plan_cache()

    def test_sparse_dispatch_matches_reference(self):
        q, k, v = _qkv(9)
        kw = dict(grid=GRID, cfg=CFG, step=jnp.asarray(5), total_steps=10)
        out = attention_dispatch(q, k, v, backend="sparse", **kw)
        ref = attention_dispatch(q, k, v, backend="reference", **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)

    def test_sparse_blocks_come_from_autotune_cache(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "autotune.json"))
        dispatch.clear_plan_cache()
        try:
            n, d = 64, 8
            q, k, v = _qkv(0, (1, 1, n, d))
            entry = autotune_attention(q, k, v, backend="sparse",
                                       candidates=((16, 16), (32, 32)),
                                       repeats=1)
            plan = resolve_plan((1, 1, n, d), (1, 1, n, d), CFG,
                                backend="sparse")
            assert plan.tuned
            assert (plan.block_q, plan.block_k) == (entry["block_q"],
                                                    entry["block_k"])
            # ripple's pallas kernel never reads the sparse entry
            plan_p = resolve_plan((1, 1, n, d), (1, 1, n, d), CFG,
                                  backend="pallas")
            assert not plan_p.tuned
        finally:
            dispatch.clear_plan_cache()


class TestBucketProperties:
    """Property coverage for the shape-bucket map (fixed examples when
    hypothesis is absent, randomized search otherwise)."""

    @settings(deadline=None, max_examples=60)
    @given(n=st.integers(1, 1 << 16))
    def test_bucket_covers_and_is_power_of_two(self, n):
        b = shape_bucket(n)
        assert b >= n and b >= 64
        assert b & (b - 1) == 0          # power of two
        assert b < 2 * max(n, 64)        # tight: never over-doubles

    @settings(deadline=None, max_examples=60)
    @given(n1=st.integers(1, 1 << 16), n2=st.integers(1, 1 << 16))
    def test_bucket_monotonic(self, n1, n2):
        if n1 > n2:
            n1, n2 = n2, n1
        assert shape_bucket(n1) <= shape_bucket(n2)

    @settings(deadline=None, max_examples=25)
    @given(n=st.integers(65, 128), m=st.integers(65, 128))
    def test_shapes_in_one_bucket_share_one_plan(self, n, m):
        dispatch.clear_plan_cache()
        try:
            p1 = resolve_plan((1, 1, n, D), (1, 1, n, D), CFG)
            p2 = resolve_plan((1, 1, m, D), (1, 1, m, D), CFG)
            assert p1 is p2  # same (64, 128] bucket -> same cached plan
        finally:
            dispatch.clear_plan_cache()


class TestPlanCacheLRU:
    """The plan cache is a bounded LRU: it never exceeds its cap and
    eviction discards the coldest entry, keeping the hottest."""

    def _with_cap(self, cap):
        old = dispatch._PLAN_CACHE_CAP
        dispatch._PLAN_CACHE_CAP = cap
        dispatch.clear_plan_cache()
        return old

    @settings(deadline=None, max_examples=10)
    @given(cap=st.integers(2, 8), extra=st.integers(1, 24))
    def test_bounded_and_keeps_hottest(self, cap, extra):
        old = self._with_cap(cap)
        try:
            hot_shape = (1, 1, 64, D)
            hot = resolve_plan(hot_shape, hot_shape, CFG)
            for i in range(extra):
                # distinct buckets: distinct n buckets per iteration
                n = 64 * (i + 2)
                resolve_plan((1, 1, n, D), (1, 1, n, D), CFG)
                # re-touch the hot entry so it stays MRU
                assert resolve_plan(hot_shape, hot_shape, CFG) is hot
                assert len(dispatch._PLAN_CACHE) <= cap
            # the hottest entry survived every eviction
            assert resolve_plan(hot_shape, hot_shape, CFG) is hot
        finally:
            dispatch._PLAN_CACHE_CAP = old
            dispatch.clear_plan_cache()

    def test_cold_entries_are_evicted(self):
        old = self._with_cap(2)
        try:
            cold = resolve_plan((1, 1, 64, D), (1, 1, 64, D), CFG)
            resolve_plan((1, 1, 256, D), (1, 1, 256, D), CFG)
            resolve_plan((1, 1, 1024, D), (1, 1, 1024, D), CFG)
            assert len(dispatch._PLAN_CACHE) == 2
            # the first (coldest) entry was evicted -> fresh object now
            assert resolve_plan((1, 1, 64, D), (1, 1, 64, D), CFG) is not cold
        finally:
            dispatch._PLAN_CACHE_CAP = old
            dispatch.clear_plan_cache()


class TestAutotuneCache:
    def test_round_trip(self, tmp_path, monkeypatch):
        path = str(tmp_path / "autotune.json")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
        dispatch.clear_plan_cache()
        try:
            n, d = 64, 8
            q, k, v = _qkv(0, (1, 1, n, d))
            entry = autotune_attention(
                q, k, v, candidates=((16, 16), (32, 32)), repeats=1)
            assert (entry["block_q"], entry["block_k"]) in ((16, 16), (32, 32))
            assert len(entry["candidates"]) == 2

            # persisted on disk, keyed by the shape bucket
            disk = json.load(open(path))
            key = dispatch.autotune_key("pallas", shape_bucket(n), d, d)
            assert disk[key]["block_q"] == entry["block_q"]

            # a fresh in-memory cache resolves the tuned plan from disk
            dispatch.clear_plan_cache()
            plan = resolve_plan((1, 1, n, d), (1, 1, n, d), CFG,
                                backend="pallas")
            assert plan.tuned
            assert (plan.block_q, plan.block_k) == (entry["block_q"],
                                                    entry["block_k"])

            # second autotune call is a cache hit (no re-timing)
            again = autotune_attention(q, k, v,
                                       candidates=((16, 16), (32, 32)))
            assert again == disk[key]
        finally:
            dispatch.clear_plan_cache()  # drop tmp-path state for other tests

    def test_untuned_shapes_use_defaults(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "empty.json"))
        dispatch.clear_plan_cache()
        try:
            plan = resolve_plan((1, 1, 512, 32), (1, 1, 512, 32), CFG,
                                backend="pallas")
            assert not plan.tuned
            assert (plan.block_q, plan.block_k) == (128, 128)
        finally:
            dispatch.clear_plan_cache()
