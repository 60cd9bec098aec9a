"""Context-parallel ring attention tier (DESIGN.md §14).

Property-based parity: the ring path (token axis sharded over the
``seq`` mesh axis) must match the single-device dispatch for random
grids, windows, and policies at 2/4/8-way seq shards — bitwise for the
snap policies (ripple, equal_mse) and for dense's fallback, and to the
documented ~1e-5 relative tolerance for svg (hop order rotates the
online-softmax reduction per shard).

Also here, always-on (single-device): the sparse kernel's ring-hop
carry convention — chaining calls over K column slices equals one
full-width call bitwise, and a fully-masked query row finalizes to
zeros, never NaN.  Multi-device tests skip unless the backend exposes
enough devices (CI's multi-device job forces 8 virtual CPU devices).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import require_devices
from repro.config.base import RippleConfig
from repro.core import decision_cache as dc
from repro.core import dispatch
from repro.core.dispatch import (attention_dispatch, dispatch_mesh,
                                 resolve_plan)

# Grid/window pairs the property sweep draws from: (4,4,4)@4-way puts
# the window across a whole shard (t_local=1 < window, the multi-hop
# halo case), (8,4,4) has a window-misaligned shard boundary at 3, and
# (8,8,8) divides evenly at every way count.
GRIDS = [(4, 4, 4), (8, 4, 4), (8, 8, 8)]
WINDOWS = (2, 3, 2)
# Order matters for the fixed-example fallback (it draws lo/mid/hi =
# indices 0, 1, 3): ripple, equal_mse and svg must all be hit; dense's
# never-rings fallback has its own test below.
POLICIES = ("ripple", "equal_mse", "dense", "svg")
# Snap policies ring only on the reference backend (the bitwise
# contract); svg auto-resolves to the sparse backend.
BACKENDS = {"ripple": "reference", "equal_mse": "reference",
            "svg": None, "dense": None}


def _cfg(window=2, **kw):
    return RippleConfig(enabled=True, theta_min=0.2, theta_max=0.5,
                        i_min=2, i_max=6, window=window, **kw)


def _qkv(seed, n, d=16, lead=(2, 2)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (*lead, n, d)) for k in ks)


def _seq_mesh(ways):
    return jax.make_mesh((1, 1, ways), ("data", "model", "seq"))


def _run(q, k, v, grid, cfg, policy, backend, step=5):
    return np.asarray(attention_dispatch(
        q, k, v, grid=grid, cfg=cfg, step=jnp.asarray(step),
        total_steps=10, policy=policy, backend=backend))


@pytest.mark.parametrize("ways", [2, 4, 8])
class TestRingParity:
    @settings(max_examples=9, deadline=None)
    @given(gi=st.integers(0, 2), pi=st.integers(0, 3))
    def test_matches_single_device(self, ways, gi, pi):
        require_devices(ways)
        grid, window = GRIDS[gi], WINDOWS[gi]
        policy = POLICIES[pi]
        backend = BACKENDS[policy]
        cfg = _cfg(window=window)
        n = grid[0] * grid[1] * grid[2]
        q, k, v = _qkv(17 * gi + pi, n)
        dispatch.clear_plan_cache()
        ref = _run(q, k, v, grid, cfg, policy, backend)
        with dispatch_mesh(_seq_mesh(ways)):
            dispatch.clear_plan_cache()
            plan = resolve_plan(q.shape, v.shape, cfg, backend=backend,
                                policy=policy, grid=grid)
            expect_ring = (policy != "dense" and grid[0] % ways == 0)
            assert (plan.seq_shards == ways) == expect_ring, plan.summary()
            out = _run(q, k, v, grid, cfg, policy, backend)
        if expect_ring and policy == "svg":
            # hop order rotates the softmax reduction (DESIGN.md §14)
            np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)
        else:
            np.testing.assert_array_equal(out, ref)


class TestWindowLargerThanShard:
    def test_multi_hop_halo_bitwise(self):
        """(4,4,4) at 4-way: one frame per shard, window 2 — the halo
        exchange needs a whole neighbor block per side, and the decision
        must still be bitwise-equal to single-device."""
        require_devices(4)
        grid, n = (4, 4, 4), 64
        cfg = _cfg(window=2)
        q, k, v = _qkv(23, n)
        dispatch.clear_plan_cache()
        ref = _run(q, k, v, grid, cfg, "ripple", "reference")
        with dispatch_mesh(_seq_mesh(4)):
            dispatch.clear_plan_cache()
            plan = resolve_plan(q.shape, v.shape, cfg, backend="reference",
                                policy="ripple", grid=grid)
            assert plan.seq_shards == 4
            out = _run(q, k, v, grid, cfg, "ripple", "reference")
        np.testing.assert_array_equal(out, ref)


class TestElidedHops:
    def test_svg_ring_elides_dead_hops(self):
        """With random operands every head classifies spatial, so the
        shard hops that carry neither the sink frame nor local frames
        are all-SKIP — the ring must skip them and count them."""
        require_devices(2)
        grid, n = (8, 8, 8), 512
        cfg = dataclasses.replace(_cfg(), reuse_every=2)
        q, k, v = _qkv(3, n)
        with dispatch_mesh(_seq_mesh(2)):
            dispatch.clear_plan_cache()
            plan = resolve_plan(q.shape, v.shape, cfg, policy="svg",
                                grid=grid)
            assert plan.seq_shards == 2 and plan.backend == "sparse"
            state = dc.initial_state(q.shape, grid=grid, cfg=cfg,
                                     policy="svg", backend="sparse")
            for s in range(3):
                out, state = attention_dispatch(
                    q, k, v, grid=grid, cfg=cfg, step=jnp.asarray(s),
                    total_steps=6, policy="svg", cached_decision=state,
                    return_decision=True)
        elided = np.asarray(state.elided)
        assert elided.shape == (2,)  # one running counter per seq shard
        assert elided.sum() > 0
        assert (elided <= 3 * 2).all()  # <= steps x hops per shard

    def test_svg_hit_replays_bitwise(self):
        """A cache-hit step re-applies the cached bias verbatim, so with
        identical inputs the hit output equals a forced refresh bitwise
        — the §13 replay contract extended to the ring."""
        require_devices(2)
        grid, n = (8, 8, 8), 512
        q, k, v = _qkv(11, n)
        outs = {}
        with dispatch_mesh(_seq_mesh(2)):
            for every in (2, 1):  # step 1: cache hit vs forced refresh
                cfg = dataclasses.replace(_cfg(), reuse_every=every)
                dispatch.clear_plan_cache()
                state = dc.initial_state(q.shape, grid=grid, cfg=cfg,
                                         policy="svg", backend="sparse")
                for s in range(2):
                    out, state = attention_dispatch(
                        q, k, v, grid=grid, cfg=cfg, step=jnp.asarray(s),
                        total_steps=6, policy="svg",
                        cached_decision=state, return_decision=True)
                outs[every] = np.asarray(out)
        np.testing.assert_array_equal(outs[2], outs[1])


class TestDriftRefreshIndependence:
    def test_one_shard_refresh_does_not_desync_others(self):
        """Regression (DESIGN.md §14): a drift-forced refresh on one seq
        shard must stay local — the other shard keeps replaying its
        cached plan, bitwise-untouched, and only its hit counter moves.
        Collectives run outside the refresh cond, which is what makes
        this safe."""
        require_devices(2)
        grid, n = (4, 4, 4), 64
        cfg = dataclasses.replace(_cfg(window=2), drift_tol=0.5,
                                  reuse_every=10)
        q, k, v = _qkv(9, n)
        with dispatch_mesh(_seq_mesh(2)):
            dispatch.clear_plan_cache()
            plan = resolve_plan(q.shape, v.shape, cfg, backend="reference",
                                policy="ripple", grid=grid)
            assert plan.seq_shards == 2
            state = dc.initial_state(q.shape, grid=grid, cfg=cfg,
                                     policy="ripple", backend="reference")
            _, s1 = attention_dispatch(
                q, k, v, grid=grid, cfg=cfg, step=jnp.asarray(0),
                total_steps=20, backend="reference", policy="ripple",
                cached_decision=state, return_decision=True)
            # Perturb only the second shard's token slice: its drift
            # statistic blows past drift_tol, the first shard's doesn't.
            q2 = q.at[..., n // 2:, :].multiply(5.0)
            k2 = k.at[..., n // 2:, :].multiply(5.0)
            _, s2 = attention_dispatch(
                q2, k2, v, grid=grid, cfg=cfg, step=jnp.asarray(1),
                total_steps=20, backend="reference", policy="ripple",
                cached_decision=s1, return_decision=True)
        refr, hits = np.asarray(s2.refreshes), np.asarray(s2.hits)
        assert (refr[..., 1] == 2).all()  # perturbed shard refreshed
        assert (refr[..., 0] == 1).all()  # the other shard did not
        assert (hits[..., 0] == 1).all()  # ... it replayed its plan
        assert (hits[..., 1] == 0).all()
        # and its cached snap-source rows are bitwise-untouched
        np.testing.assert_array_equal(
            np.asarray(s2.q_idx)[..., : n // 2, :],
            np.asarray(s1.q_idx)[..., : n // 2, :])


class TestKernelCarry:
    """Single-device contracts the ring executors are built on —
    always-on tier-1, no multi-device backend needed."""

    def test_hop_chain_equals_full_width_call(self):
        """Chaining the sparse kernel over aligned K column slices via
        the (m, l, acc) carry equals one full-width call bitwise — the
        online-softmax recurrence visits the same blocks in the same
        order either way."""
        from repro.kernels.sparse.ops import sparse_attention_pallas

        n, d = 16, 8
        q, k, v = _qkv(7, n, d=d, lead=(1, 2))
        full = np.asarray(sparse_attention_pallas(q, k, v, block_q=4,
                                                  block_k=4))
        state, out = None, None
        for lo, hi in ((0, 8), (8, 16)):
            out, state = sparse_attention_pallas(
                q, k[..., lo:hi, :], v[..., lo:hi, :], block_q=4,
                block_k=4, carry=state, return_state=True)
        np.testing.assert_array_equal(np.asarray(out), full)

    def test_fully_masked_query_row_is_zeros_not_nan(self):
        """A query row whose bias is -inf everywhere accumulates l=0;
        both the kernel's own finalize and the ring's cross-hop
        ``acc / where(l > 0, l, 1)`` must emit zeros, not NaN."""
        from repro.kernels.sparse.ops import (block_map_from_keep,
                                              sparse_attention_pallas)

        n, d = 8, 4
        q, k, v = _qkv(5, n, d=d, lead=(1, 1))
        keep = jnp.ones((n, n), bool).at[2].set(False)
        bias = jnp.where(keep, 0.0, -jnp.inf).astype(jnp.float32)
        bmap = block_map_from_keep(keep, 4, 4)
        out, (m, l, acc) = sparse_attention_pallas(
            q, k, v, bias=bias, block_map=bmap, block_q=4, block_k=4,
            return_state=True)
        final = acc / jnp.where(l > 0.0, l, 1.0)[..., None]
        for arr in (np.asarray(out), np.asarray(final)):
            assert np.isfinite(arr).all()
            np.testing.assert_array_equal(arr[0, 0, 2], 0.0)
