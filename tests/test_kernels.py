"""Per-kernel validation: shape/dtype sweeps, allclose vs the pure-jnp
ref.py oracles (kernels execute in interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.adaln.ops import adaln_modulate
from repro.kernels.adaln.ref import adaln_modulate_ref
from repro.kernels.flash.ops import flash_attention
from repro.kernels.flash.ref import attention_ref
from repro.kernels.reuse_mask.ops import reuse_snap
from repro.kernels.reuse_mask.ref import reuse_snap_ref
from repro.kernels.ripple.ops import ripple_attention_pallas, ripple_block_stats
from repro.kernels.ripple.ref import ripple_attention_ref
from repro.kernels.sparse.ops import (FULL, PARTIAL, SKIP,
                                      block_map_from_keep,
                                      sparse_attention_pallas,
                                      sparse_block_stats)
from repro.kernels.sparse.ref import expand_block_map, sparse_attention_ref


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 3e-5


class TestFlashKernel:
    @pytest.mark.parametrize("B,H,Nq,Nk,d,dv", [
        (1, 1, 128, 128, 64, 64),
        (2, 3, 256, 256, 32, 32),
        (1, 2, 200, 333, 16, 48),   # unaligned both dims
        (1, 1, 64, 512, 128, 128),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, B, H, Nq, Nk, d, dv, dtype):
        ks = jax.random.split(jax.random.PRNGKey(Nq + Nk + d), 3)
        q = jax.random.normal(ks[0], (B, H, Nq, d), dtype)
        k = jax.random.normal(ks[1], (B, H, Nk, d), dtype)
        v = jax.random.normal(ks[2], (B, H, Nk, dv), dtype)
        out = flash_attention(q, k, v, block_q=128, block_k=128)
        ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=_tol(dtype),
                                   rtol=1e-2)

    def test_extreme_logits_stable(self):
        q = 30.0 * jax.random.normal(jax.random.PRNGKey(0), (1, 1, 128, 32))
        k = 30.0 * jax.random.normal(jax.random.PRNGKey(1), (1, 1, 128, 32))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 128, 32))
        out = flash_attention(q, k, v)
        assert np.isfinite(np.asarray(out)).all()


def _snapped_operand(key, B, H, N, d, frac):
    x = jax.random.normal(key, (B, H, N, d))
    e, o = x[..., 0::2, :], x[..., 1::2, :]
    coll = jax.random.uniform(jax.random.fold_in(key, 1),
                              (B, H, N // 2, 1)) < frac
    return jnp.stack([e, jnp.where(coll, e, o)], 3).reshape(B, H, N, d)


class TestRippleKernel:
    @pytest.mark.parametrize("N,d,frac", [
        (256, 32, 0.0), (256, 32, 0.6), (256, 32, 1.0),
        (512, 64, 0.9), (130, 16, 1.0),  # unaligned pairs
    ])
    def test_matches_snapped_oracle(self, N, d, frac):
        q = _snapped_operand(jax.random.PRNGKey(1), 1, 2, N, d, frac)
        k = _snapped_operand(jax.random.PRNGKey(2), 1, 2, N, d, frac)
        v = jax.random.normal(jax.random.PRNGKey(3), (1, 2, N, d))
        out = ripple_attention_pallas(q, k, v, block_q=64, block_k=64)
        ref = ripple_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)

    def test_structural_savings_reach_75_when_fully_collapsed(self):
        q = _snapped_operand(jax.random.PRNGKey(4), 1, 1, 512, 32, 1.0)
        k = _snapped_operand(jax.random.PRNGKey(5), 1, 1, 512, 32, 1.0)
        s = float(ripple_block_stats(q, k, block_q=64, block_k=64))
        assert abs(s - 0.75) < 1e-6

    def test_zero_savings_when_nothing_collapses(self):
        q = _snapped_operand(jax.random.PRNGKey(6), 1, 1, 512, 32, 0.0)
        k = _snapped_operand(jax.random.PRNGKey(7), 1, 1, 512, 32, 0.0)
        assert float(ripple_block_stats(q, k)) == 0.0

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_property_random_pair_structure(self, seed):
        key = jax.random.PRNGKey(seed)
        frac = float(jax.random.uniform(key))
        q = _snapped_operand(jax.random.fold_in(key, 1), 1, 1, 128, 16, frac)
        k = _snapped_operand(jax.random.fold_in(key, 2), 1, 1, 128, 16, frac)
        v = jax.random.normal(jax.random.fold_in(key, 3), (1, 1, 128, 16))
        out = ripple_attention_pallas(q, k, v, block_q=32, block_k=32)
        ref = ripple_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)


def _sparse_qkv(seed, B=1, H=2, N=256, D=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(kk, (B, H, N, D)) for kk in ks)


class TestSparseKernel:
    """Block-sparse masked flash kernel vs its pure-jnp oracle for every
    block-map state, plus the block-map-from-keep consistency contract
    (DESIGN.md §12)."""

    def test_all_full_matches_dense(self):
        q, k, v = _sparse_qkv(0)
        bmap = jnp.full((4, 4), FULL, jnp.int32)
        out = sparse_attention_pallas(q, k, v, block_map=bmap,
                                      block_q=64, block_k=64)
        ref = attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)
        assert float(sparse_block_stats(bmap)) == 0.0

    def test_all_skip_emits_zeros(self):
        q, k, v = _sparse_qkv(1)
        bmap = jnp.full((4, 4), SKIP, jnp.int32)
        out = sparse_attention_pallas(q, k, v, block_map=bmap,
                                      block_q=64, block_k=64)
        assert not np.asarray(out).any()
        ref = sparse_attention_ref(q, k, v, block_map=bmap,
                                   block_q=64, block_k=64)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        assert float(sparse_block_stats(bmap)) == 1.0

    def test_mixed_map_matches_oracle(self):
        q, k, v = _sparse_qkv(2)
        keep = jax.random.bernoulli(jax.random.PRNGKey(3), 0.5,
                                    (1, 2, 256, 256))
        keep = keep.at[..., :64, :64].set(True)    # a FULL tile
        keep = keep.at[..., 64:128, :64].set(False)  # a SKIP tile
        bias = jnp.where(keep, 0.0, -jnp.inf).astype(jnp.float32)
        bmap = block_map_from_keep(keep, 64, 64)
        assert {int(s) for s in np.unique(np.asarray(bmap))} \
            == {SKIP, FULL, PARTIAL}
        out = sparse_attention_pallas(q, k, v, bias=bias, block_map=bmap,
                                      block_q=64, block_k=64)
        ref = sparse_attention_ref(q, k, v, bias=bias, block_map=bmap,
                                   block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)
        # a map consistent with its bias also matches the plain dense
        # masked softmax (every row keeps at least one key here)
        dense = sparse_attention_ref(q, k, v, bias=bias,
                                     block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=3e-5)

    def test_partial_bias_applied_in_kernel(self):
        q, k, v = _sparse_qkv(4)
        bias = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 256, 256))
        bmap = jnp.full((4, 4), PARTIAL, jnp.int32)
        out = sparse_attention_pallas(q, k, v, bias=bias, block_map=bmap,
                                      block_q=64, block_k=64)
        s = (jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32)
             * (1.0 / np.sqrt(q.shape[-1]))) + bias
        p = jax.nn.softmax(s, axis=-1)
        ref = jnp.einsum("...qk,...kv->...qv", p, v.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)

    @pytest.mark.parametrize("N", [200, 130])
    def test_unaligned_tokens_padded_correctly(self, N):
        q, k, v = _sparse_qkv(6, N=N)
        keep = jax.random.bernoulli(jax.random.PRNGKey(7), 0.6,
                                    (1, 2, N, N))
        keep = keep.at[..., 64:128, :64].set(False)
        bias = jnp.where(keep, 0.0, -jnp.inf).astype(jnp.float32)
        bmap = block_map_from_keep(keep, 64, 64)
        out = sparse_attention_pallas(q, k, v, bias=bias, block_map=bmap,
                                      block_q=64, block_k=64)
        ref = sparse_attention_ref(q, k, v, bias=bias, block_map=bmap,
                                   block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)

    def test_no_map_degrades_to_masked_dense(self):
        """block_map=None + bias: every tile runs PARTIAL (dense masked
        flash); block_map=None + no bias: plain flash."""
        q, k, v = _sparse_qkv(8)
        keep = jax.random.bernoulli(jax.random.PRNGKey(9), 0.7,
                                    (1, 2, 256, 256))
        bias = jnp.where(keep, 0.0, -jnp.inf).astype(jnp.float32)
        out = sparse_attention_pallas(q, k, v, bias=bias,
                                      block_q=64, block_k=64)
        ref = sparse_attention_ref(q, k, v, bias=bias,
                                   block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)
        out2 = sparse_attention_pallas(q, k, v, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out2),
                                   np.asarray(attention_ref(q, k, v)),
                                   atol=3e-5)

    def test_row_chunked_calls_match_one_call(self, monkeypatch):
        """Long sequences split the (batch·head) rows over several
        kernel calls so each call's packed table fits SMEM; the chunked
        result (and the ring-hop carry) equals the single call's."""
        from repro.kernels.sparse import kernel as sparse_kernel

        q, k, v = _sparse_qkv(10, B=2, H=3)
        keep = jax.random.bernoulli(jax.random.PRNGKey(11), 0.5,
                                    (2, 3, 256, 256))
        bias = jnp.where(keep, 0.0, -jnp.inf).astype(jnp.float32)
        bmap = block_map_from_keep(keep, 64, 64)
        kw = dict(bias=bias, block_map=bmap, block_q=64, block_k=64,
                  return_state=True)
        one = sparse_attention_pallas(q, k, v, **kw)
        # 2 rows' tables per call: chunks of 2, 2, 2 rows
        monkeypatch.setattr(sparse_kernel, "_TABLE_BYTES", 2 * 16 * 4)
        jax.clear_caches()
        chunked = sparse_attention_pallas(q, k, v, **kw)
        jax.clear_caches()
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(chunked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_property_block_map_consistency(self, seed):
        """For any keep-mask: FULL tiles keep everything, SKIP tiles
        nothing, and the kernel on (map, bias) matches the dense masked
        softmax wherever a row keeps at least one key."""
        key = jax.random.PRNGKey(seed)
        density = float(jax.random.uniform(key, minval=0.05, maxval=0.95))
        N, blk = 128, 32
        keep = jax.random.bernoulli(jax.random.fold_in(key, 1), density,
                                    (1, 1, N, N))
        bmap = block_map_from_keep(keep, blk, blk)
        st_tok = np.asarray(expand_block_map(bmap, N, N, blk, blk))
        keep_np = np.asarray(keep)
        assert keep_np[st_tok == FULL].all()
        assert not keep_np[st_tok == SKIP].any()
        q, k, v = _sparse_qkv(seed + 1, H=1, N=N, D=16)
        bias = jnp.where(keep, 0.0, -jnp.inf).astype(jnp.float32)
        out = np.asarray(sparse_attention_pallas(
            q, k, v, bias=bias, block_map=bmap, block_q=blk, block_k=blk))
        ref = np.asarray(sparse_attention_ref(
            q, k, v, bias=bias, block_q=blk, block_k=blk))
        rows_alive = keep_np.any(axis=-1)
        np.testing.assert_allclose(out[rows_alive], ref[rows_alive],
                                   atol=3e-5)
        # fully-masked rows: the kernel's zero convention, never NaN
        assert np.isfinite(out).all()
        assert not out[~rows_alive].any()


class TestReuseSnapKernel:
    @pytest.mark.parametrize("N,d", [(256, 16), (300, 32), (64, 128)])
    @pytest.mark.parametrize("theta", [0.0, 0.3, 10.0])
    def test_matches_oracle(self, N, d, theta):
        x = jax.random.normal(jax.random.PRNGKey(N + d), (2, 2, N, d))
        snapped, mask = reuse_snap(x, theta, block=64)
        ref_o, ref_m = reuse_snap_ref(x[..., 0::2, :], x[..., 1::2, :], theta)
        np.testing.assert_allclose(np.asarray(snapped[..., 1::2, :]),
                                   np.asarray(ref_o))
        np.testing.assert_array_equal(np.asarray(mask[..., 1::2, :]),
                                      np.asarray(ref_m))
        # representatives untouched, never masked
        np.testing.assert_array_equal(np.asarray(snapped[..., 0::2, :]),
                                      np.asarray(x[..., 0::2, :]))
        assert not np.asarray(mask[..., 0::2, :]).any()


class TestAdaLNKernel:
    @pytest.mark.parametrize("B,Ntok,d", [(2, 256, 64), (1, 100, 128),
                                          (4, 64, 32)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, B, Ntok, d, dtype):
        x = jax.random.normal(jax.random.PRNGKey(0), (B, Ntok, d), dtype)
        sh = jax.random.normal(jax.random.PRNGKey(1), (B, d), dtype)
        sc = jax.random.normal(jax.random.PRNGKey(2), (B, d), dtype)
        out = adaln_modulate(x, sh, sc, block_t=64)
        ref = adaln_modulate_ref(x, sh, sc)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=_tol(dtype), rtol=1e-2)
