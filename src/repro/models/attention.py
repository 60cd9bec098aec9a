"""Attention layers.

Two entry points:

* :func:`gqa_attention` — decoder-side attention for the LM family:
  grouped-query heads, optional qk-norm, causal / sliding-window masks,
  KV-cache prefill and decode.
* :func:`mha_attention` — bidirectional attention for the diffusion /
  vision families, routed through the unified dispatch layer
  (``core.dispatch``, DESIGN.md §8): when a :class:`RippleConfig` is
  active the post-RoPE Q/K go through the reuse pipeline (snap →
  collapse/kernel) and the dispatcher picks the execution backend;
  otherwise it runs the plain dense path.

All activations flow through :class:`ShardCtx` constraints so the same
code serves 1 CPU device and the 512-chip production mesh.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import RippleConfig
from repro.core.dispatch import attention_dispatch
from repro.distributed.sharding import NULL_CTX, ShardCtx
from repro.models.common import rmsnorm, rmsnorm_defs
from repro.models.params import ParamDef, fan_in
from repro.utils.loops import in_cost_probe, map_chunks

_NEG = -2.3819763e38  # matches XLA's mask constant; safely below any logit


def attention_defs(d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   qk_norm: bool = False, bias: bool = False):
    defs = {
        "wq": ParamDef((d_model, n_heads * head_dim), ("embed", "heads"), fan_in()),
        "wk": ParamDef((d_model, n_kv * head_dim), ("embed", "kv"), fan_in()),
        "wv": ParamDef((d_model, n_kv * head_dim), ("embed", "kv"), fan_in()),
        "wo": ParamDef((n_heads * head_dim, d_model), ("heads", "embed"), fan_in()),
    }
    if qk_norm:
        defs["q_norm"] = rmsnorm_defs(head_dim)
        defs["k_norm"] = rmsnorm_defs(head_dim)
    return defs


def _project(params, x, n_heads, n_kv, head_dim, ctx: ShardCtx):
    dt = x.dtype
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(dt))
    q = ctx.c(q.reshape(B, S, n_heads, head_dim),
              ("batch", "attn_seq", "heads", None))
    k = ctx.c(k.reshape(B, S, n_kv, head_dim),
              ("batch", "attn_seq", "kv", None))
    v = ctx.c(v.reshape(B, S, n_kv, head_dim),
              ("batch", "attn_seq", "kv", None))
    if "q_norm" in params:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    return q, k, v


# Above this many logits per (batch·head) the core switches to the
# query-chunked (Rabe-Staats) path so 32k-token prefill never
# materializes an (S, S) map.
_CHUNK_LOGIT_BUDGET = 4096 * 8192
_Q_CHUNK = 1024


def _gqa_core_dense(q, k, v, mask, ctx: ShardCtx = NULL_CTX):
    """Flat-head GQA: K/V are repeated to Hq at compute time so the head
    dim shards cleanly over 'model' even when Hkv doesn't divide it
    (e.g. 8 kv heads on a 16-way model axis).  The repeat is a transient
    bf16 view; caches stay at Hkv."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        k = ctx.c(k, ("batch", "kv_seq", "heads", None))
        v = ctx.c(v, ("batch", "kv_seq", "heads", None))
    logits = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if mask is not None:
        logits = logits + mask  # (B|1, 1, S, Skv)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", probs, v)
    return out


def _gqa_core(q, k, v, mask, ctx: ShardCtx):
    """q: (B,S,Hq,hd); k,v: (B,Skv,Hkv,hd); mask additive (B|1,1,S,Skv)."""
    B, S, Hq, hd = q.shape
    Skv = k.shape[1]
    if S * Skv <= _CHUNK_LOGIT_BUDGET or S % _Q_CHUNK != 0 \
            or in_cost_probe():
        return _gqa_core_dense(q, k, v, mask, ctx)

    nchunks = S // _Q_CHUNK
    qc = q.reshape(B, nchunks, _Q_CHUNK, Hq, hd)
    if mask is not None:
        mb = jnp.broadcast_to(mask, (mask.shape[0], 1, S, Skv))
        mb = mb.reshape(mask.shape[0], 1, nchunks, _Q_CHUNK, Skv)

    def chunk(i):
        m_i = None if mask is None else mb[:, :, i]
        return _gqa_core_dense(qc[:, i], k, v, m_i, ctx)

    out = map_chunks(chunk, nchunks)  # (nchunks, B, qc, Hq, hd)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, Hq, hd)


def causal_mask(S_q: int, S_kv: int, q_offset, sliding_window=0):
    """Additive causal (+ optional sliding window) mask (1, 1, S_q, S_kv).

    ``q_offset`` is the absolute position of query row 0 (scalar or
    traced) — used at decode time where S_q == 1 and the cache holds
    S_kv past positions.  ``sliding_window`` may be a traced scalar
    (scan-over-layers with a local:global interleave); <= 0 disables it."""
    qi = jnp.arange(S_q)[:, None] + q_offset
    kj = jnp.arange(S_kv)[None, :]
    ok = kj <= qi
    window = jnp.asarray(sliding_window)
    win_ok = jnp.logical_or(window <= 0, kj > qi - window)
    ok = jnp.logical_and(ok, win_ok)
    return jnp.where(ok, 0.0, _NEG)[None, None].astype(jnp.float32)


def valid_mask(S_q: int, S_kv: int, kv_len):
    """Mask for decode against a partially-filled cache: keys ≥ kv_len
    are invalid. kv_len: scalar or (B,)."""
    kj = jnp.arange(S_kv)[None, :]
    kv_len = jnp.asarray(kv_len)
    if kv_len.ndim == 0:
        kv_len = kv_len[None]
    ok = kj < kv_len[:, None]
    return jnp.where(ok, 0.0, _NEG)[:, None, None].astype(jnp.float32)


def gqa_attention(
    params: Dict,
    x: jax.Array,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    positions: jax.Array,
    rope_theta: float = 10000.0,
    sliding_window=0,
    cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,
    ctx: ShardCtx = NULL_CTX,
):
    """LM attention. x: (B, S, d).

    Modes:
      * train/prefill: ``cache is None`` → causal self-attention; returns
        (out, (k, v)) so callers can keep the cache.
      * decode: ``cache=(k_cache, v_cache)`` of shape (B, S_max, Hkv, hd)
        and ``cache_index`` = current length; S must be 1.  Returns
        (out, updated_cache).
    """
    from repro.models.common import apply_rope_1d

    B, S, _ = x.shape
    q, k, v = _project(params, x, n_heads, n_kv, head_dim, ctx)
    q = apply_rope_1d(q, positions, rope_theta)
    k = apply_rope_1d(k, positions, rope_theta)

    if cache is None:
        mask = causal_mask(S, S, 0, sliding_window)
        out = _gqa_core(q, k, v, mask, ctx)
        new_cache = (k, v)
    else:
        k_cache, v_cache = cache
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), cache_index, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), cache_index, axis=1)
        k_cache = ctx.c(k_cache, ("batch", "kv_seq", "kv", None))
        v_cache = ctx.c(v_cache, ("batch", "kv_seq", "kv", None))
        S_kv = k_cache.shape[1]
        mask = valid_mask(S, S_kv, cache_index + S) \
            + causal_mask(S, S_kv, cache_index, sliding_window)
        out = _gqa_core(q, k_cache, v_cache, mask, ctx)
        new_cache = (k_cache, v_cache)

    out = ctx.c(out, ("batch", "attn_seq", "heads", None))
    B, S, Hq, hd = out.shape
    out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, Hq * hd),
                     params["wo"].astype(x.dtype))
    return ctx.c(out, ("batch", "seq", "embed")), new_cache


def mha_attention(
    params: Dict,
    x: jax.Array,
    *,
    n_heads: int,
    head_dim: int,
    grid: Tuple[int, int, int],
    ripple: RippleConfig,
    step: Optional[jax.Array] = None,
    total_steps: Optional[int] = None,
    rope_cos: Optional[jax.Array] = None,
    rope_sin: Optional[jax.Array] = None,
    grid_slice: Optional[Tuple[int, int]] = None,
    encoder_out: Optional[jax.Array] = None,
    backend: Optional[str] = None,
    cached_decision=None,
    return_decision: bool = False,
    with_stats: bool = False,
    ctx: ShardCtx = NULL_CTX,
):
    """Bidirectional MHA through the dispatch layer. x: (B, N, d).

    ``encoder_out`` switches to cross-attention (K/V from the encoder;
    ripple never applies — no grid on text tokens — so the dispatcher is
    forced onto its dense backend).  ``backend`` overrides
    ``ripple.backend`` for this call.  ``rope_cos/sin`` are precomputed
    factorized 3-D RoPE tables (``common.rope_3d_angles``); None means
    no RoPE (e.g. DiT's absolute sin-cos embeddings).

    ``cached_decision`` / ``return_decision`` thread the cross-step
    decision cache (DESIGN.md §13) through to ``attention_dispatch``;
    when either is set the layer returns ``(out, CachedDecision)`` so
    the model can carry per-layer decision state across denoising
    steps.  Self-attention only (cross-attention has no grid).
    ``with_stats`` appends the call's
    :class:`~repro.core.policy.RippleStats` to the return."""
    from repro.models.common import apply_rope_precomputed

    dt = x.dtype
    B, N, _ = x.shape
    kv_src = encoder_out if encoder_out is not None else x
    Nk = kv_src.shape[1]
    # Named scopes for the profiler: ``proj`` (q/k/v with their norms and
    # RoPE, and the output projection), ``attn`` (the dispatch call).
    with jax.named_scope("proj"):
        q = jnp.einsum("bnd,dh->bnh", x, params["wq"].astype(dt))
        k = jnp.einsum("bnd,dh->bnh", kv_src, params["wk"].astype(dt))
        v = jnp.einsum("bnd,dh->bnh", kv_src, params["wv"].astype(dt))
        q = q.reshape(B, N, n_heads, head_dim)
        k = k.reshape(B, Nk, n_heads, head_dim)
        v = v.reshape(B, Nk, n_heads, head_dim)
        if "q_norm" in params:
            q = rmsnorm(params["q_norm"], q)
            k = rmsnorm(params["k_norm"], k)
        if rope_cos is not None:
            q = apply_rope_precomputed(q, rope_cos, rope_sin)
            k = apply_rope_precomputed(k, rope_cos, rope_sin)

    # Cross-attention has no grid to snap: force the dense backend so
    # the dispatcher bypasses the reuse pipeline entirely.
    eff_backend = "dense" if encoder_out is not None else backend
    want_cache = cached_decision is not None or return_decision
    if want_cache and encoder_out is not None:
        raise ValueError("decision caching applies to grid self-attention "
                         "only, not cross-attention")
    with jax.named_scope("attn"):
        # (B, H, N, hd) layout for the ripple/core path
        q = ctx.c(q.transpose(0, 2, 1, 3),
                  ("batch", "heads", "attn_seq", None))
        k = ctx.c(k.transpose(0, 2, 1, 3), ("batch", "heads", None, None))
        v = ctx.c(v.transpose(0, 2, 1, 3), ("batch", "heads", None, None))
        res = attention_dispatch(
            q, k, v, grid=grid, cfg=ripple, step=step,
            total_steps=total_steps, grid_slice=grid_slice,
            backend=eff_backend, cached_decision=cached_decision,
            return_decision=want_cache, with_stats=with_stats)
        out, *extra = res if (want_cache or with_stats) else (res,)
        out = out.transpose(0, 2, 1, 3).reshape(B, N, n_heads * head_dim)

    with jax.named_scope("proj"):
        out = jnp.einsum("bnh,hd->bnd", out, params["wo"].astype(dt))
    out = ctx.c(out, ("batch", "seq", "embed"))
    return (out, *extra) if extra else out
