"""Flux-style MMDiT: double-stream joint blocks + single-stream blocks.

Matches the assigned ``flux-dev`` topology: 19 double blocks (separate
img/txt streams, joint attention), 38 single blocks (fused stream),
d=3072, 24 heads, rectified-flow conditioning vector (timestep +
guidance + pooled text).  Factorized RoPE with axes_dim (16, 56, 56).

TimeRipple applies to the image-grid tokens inside joint attention
(2-D mode, x/y axes); text tokens are never snapped (DESIGN.md §6).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import MMDiTConfig, RippleConfig
from repro.core.dispatch import attention_dispatch
from repro.distributed.sharding import NULL_CTX, ShardCtx
from repro.utils.loops import scan_layers
from repro.models.common import (layernorm, linear, linear_defs,
                                 rope_3d_angles, sincos_timestep_embed,
                                 apply_rope_precomputed)
from repro.models.params import (ParamDef, fan_in, normal, zeros,
                                 stack_layer_defs)

_RIPPLE_OFF = RippleConfig()


def _stream_defs(d: int, n_heads: int, mlp_ratio: float, qk_norm=True):
    hd = d // n_heads
    defs = {
        "mod": linear_defs(d, 6 * d, axes=("embed", None), init=zeros),
        "wqkv": ParamDef((d, 3 * d), ("embed", "heads"), fan_in()),
        "wo": ParamDef((d, d), ("heads", "embed"), fan_in()),
        "mlp_in": ParamDef((d, int(d * mlp_ratio)), ("embed", "mlp"), fan_in()),
        "mlp_in_b": ParamDef((int(d * mlp_ratio),), ("mlp",), zeros),
        "mlp_out": ParamDef((int(d * mlp_ratio), d), ("mlp", "embed"), fan_in()),
        "mlp_out_b": ParamDef((d,), ("embed",), zeros),
    }
    if qk_norm:
        defs["q_norm"] = {"scale": ParamDef((hd,), (None,), lambda k, s, t: jnp.ones(s, t))}
        defs["k_norm"] = {"scale": ParamDef((hd,), (None,), lambda k, s, t: jnp.ones(s, t))}
    return defs


def _single_defs(d: int, n_heads: int, mlp_ratio: float):
    hd = d // n_heads
    F = int(d * mlp_ratio)
    return {
        "mod": linear_defs(d, 3 * d, axes=("embed", None), init=zeros),
        "lin1": ParamDef((d, 3 * d + F), ("embed", "heads"), fan_in()),
        "lin1_b": ParamDef((3 * d + F,), ("heads",), zeros),
        "lin2": ParamDef((d + F, d), ("heads", "embed"), fan_in()),
        "lin2_b": ParamDef((d,), ("embed",), zeros),
        "q_norm": {"scale": ParamDef((hd,), (None,), lambda k, s, t: jnp.ones(s, t))},
        "k_norm": {"scale": ParamDef((hd,), (None,), lambda k, s, t: jnp.ones(s, t))},
    }


def mmdit_defs(cfg: MMDiTConfig):
    d = cfg.d_model
    p = cfg.patch
    return {
        "img_in": linear_defs(p * p * cfg.in_channels, d, axes=(None, "embed")),
        "txt_in": linear_defs(cfg.txt_dim, d, axes=(None, "embed")),
        "t_mlp1": linear_defs(256, d, axes=(None, "embed")),
        "t_mlp2": linear_defs(d, d, axes=("embed", "embed")),
        "vec_in": linear_defs(768, d, axes=(None, "embed")),
        "double": {
            "img": stack_layer_defs(
                _stream_defs(d, cfg.num_heads, cfg.mlp_ratio), cfg.n_double_blocks),
            "txt": stack_layer_defs(
                _stream_defs(d, cfg.num_heads, cfg.mlp_ratio), cfg.n_double_blocks),
        },
        "single": stack_layer_defs(
            _single_defs(d, cfg.num_heads, cfg.mlp_ratio), cfg.n_single_blocks),
        "final_mod": linear_defs(d, 2 * d, axes=("embed", None), init=zeros),
        "final": linear_defs(d, p * p * cfg.in_channels, axes=("embed", None),
                             init=zeros),
    }


def _rmsn(scale, x):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6)
            * scale["scale"].astype(jnp.float32)).astype(x.dtype)


def _qkv(bp, x, n_heads, hd):
    B, N, d = x.shape
    qkv = jnp.einsum("bnd,dh->bnh", x, bp["wqkv"].astype(x.dtype))
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = _rmsn(bp["q_norm"], q.reshape(B, N, n_heads, hd))
    k = _rmsn(bp["k_norm"], k.reshape(B, N, n_heads, hd))
    v = v.reshape(B, N, n_heads, hd)
    return q, k, v


def _joint_attention(q, k, v, rope_cos, rope_sin, grid, grid_slice, ripple,
                     step, total_steps, ctx, with_stats=False):
    """q/k/v: (B, N, H, hd) already normed; returns (B, N, H*hd), and
    the call's :class:`~repro.core.policy.RippleStats` with
    ``with_stats``."""
    with jax.named_scope("proj"):
        q = apply_rope_precomputed(q, rope_cos, rope_sin)
        k = apply_rope_precomputed(k, rope_cos, rope_sin)
    with jax.named_scope("attn"):
        qT = ctx.c(q.transpose(0, 2, 1, 3),
                   ("batch", "heads", "attn_seq", None))
        kT = ctx.c(k.transpose(0, 2, 1, 3), ("batch", "heads", None, None))
        vT = ctx.c(v.transpose(0, 2, 1, 3), ("batch", "heads", None, None))
        res = attention_dispatch(qT, kT, vT, grid=grid, cfg=ripple,
                                 step=step, total_steps=total_steps,
                                 grid_slice=grid_slice,
                                 with_stats=with_stats)
        out, stats = res if with_stats else (res, None)
        B, H, N, hd = out.shape
        out = out.transpose(0, 2, 1, 3).reshape(B, N, H * hd)
    return (out, stats) if with_stats else out


def mmdit_apply(
    params: Dict,
    latents: jax.Array,    # (B, H_lat, W_lat, C)
    t: jax.Array,          # (B,)
    txt: jax.Array,        # (B, L, txt_dim)
    vec: jax.Array,        # (B, 768) pooled conditioning
    cfg: MMDiTConfig,
    *,
    ripple: RippleConfig = _RIPPLE_OFF,
    step: Optional[jax.Array] = None,
    total_steps: Optional[int] = None,
    ctx: ShardCtx = NULL_CTX,
    compute_dtype=jnp.bfloat16,
    remat: bool = False,
    with_stats: bool = False,
) -> jax.Array:
    """Apply the MMDiT.  ``with_stats`` returns ``(out, RippleStats)``
    whose fields are the means over every block's attention call."""
    dt = compute_dtype
    B, Hl, Wl, C = latents.shape
    p = cfg.patch
    h, w = Hl // p, Wl // p
    grid = (1, h, w)
    L = txt.shape[1]
    n_img = h * w
    hd = cfg.d_model // cfg.num_heads

    with jax.named_scope("embed"):
        img = latents.astype(dt).reshape(B, h, p, w, p, C) \
            .transpose(0, 1, 3, 2, 4, 5)
        img = img.reshape(B, n_img, p * p * C)
        img = linear(params["img_in"], img)
        txt_tok = linear(params["txt_in"], txt.astype(dt))

        temb = sincos_timestep_embed(t, 256).astype(dt)
        c = linear(params["t_mlp2"],
                   jax.nn.silu(linear(params["t_mlp1"], temb)))
        c = jax.nn.silu(c + linear(params["vec_in"], vec.astype(dt)))

        cos_g, sin_g = rope_3d_angles(grid, cfg.axes_dim)
        ang_t = (1 + jnp.arange(L))[:, None].astype(jnp.float32) * (
            1.0 / (10000.0 ** (jnp.arange(cfg.axes_dim[0] // 2,
                                          dtype=jnp.float32)
                               / (cfg.axes_dim[0] // 2))))
        rest = jnp.zeros((L, (cfg.axes_dim[1] + cfg.axes_dim[2]) // 2))
        rope_cos = jnp.concatenate(
            [jnp.cos(jnp.concatenate([ang_t, rest], -1)), cos_g], axis=0)
        rope_sin = jnp.concatenate(
            [jnp.sin(jnp.concatenate([ang_t, rest], -1)), sin_g], axis=0)

    # Named scopes label the step's device work for the profiler: ``ada``
    # (modulation, norms, gates), ``proj``, ``attn`` and ``mlp``.
    def mod6(bp, x_):
        m = linear(bp["mod"], jax.nn.silu(c))
        return jnp.split(m, 6, axis=-1)

    def stream_pre(bp, x_):
        with jax.named_scope("ada"):
            sh, sc, g, sh2, sc2, g2 = mod6(bp, x_)
            h_ = layernorm({}, x_) * (1 + sc[:, None]) + sh[:, None]
        return h_, (g, sh2, sc2, g2)

    def stream_post(bp, x_, attn_out, mods):
        g, sh2, sc2, g2 = mods
        with jax.named_scope("proj"):
            o = jnp.einsum("bnh,hd->bnd", attn_out, bp["wo"].astype(dt))
        with jax.named_scope("ada"):
            x_ = x_ + g[:, None] * o
            h_ = layernorm({}, x_) * (1 + sc2[:, None]) + sh2[:, None]
        with jax.named_scope("mlp"):
            m = jax.nn.gelu(
                jnp.einsum("bnd,df->bnf", h_, bp["mlp_in"].astype(dt))
                + bp["mlp_in_b"].astype(dt))
            m = jnp.einsum("bnf,fd->bnd", m, bp["mlp_out"].astype(dt)) \
                + bp["mlp_out_b"].astype(dt)
        with jax.named_scope("ada"):
            x_ = x_ + g2[:, None] * m
        return ctx.c(x_, ("batch", "seq", "embed"))

    def attend(q, k, v):
        res = _joint_attention(q, k, v, rope_cos, rope_sin, grid, (L, n_img),
                               ripple, step, total_steps, ctx,
                               with_stats=with_stats)
        return res if with_stats else (res, None)

    def double_body(carry, bp):
        txt_x, img_x = carry
        ti, im = bp["txt"], bp["img"]
        th, tmods = stream_pre(ti, txt_x)
        ih, imods = stream_pre(im, img_x)
        with jax.named_scope("proj"):
            tq, tk, tv = _qkv(ti, th, cfg.num_heads, hd)
            iq, ik, iv = _qkv(im, ih, cfg.num_heads, hd)
        with jax.named_scope("attn"):
            q = jnp.concatenate([tq, iq], axis=1)
            k = jnp.concatenate([tk, ik], axis=1)
            v = jnp.concatenate([tv, iv], axis=1)
        out, stats = attend(q, k, v)
        txt_x = stream_post(ti, txt_x, out[:, :L], tmods)
        img_x = stream_post(im, img_x, out[:, L:], imods)
        return (txt_x, img_x), stats

    def single_body(x_, bp):
        with jax.named_scope("ada"):
            m = linear(bp["mod"], jax.nn.silu(c))
            sh, sc, g = jnp.split(m, 3, axis=-1)
            h_ = layernorm({}, x_) * (1 + sc[:, None]) + sh[:, None]
        F = int(cfg.d_model * cfg.mlp_ratio)
        # One matmul projects q/k/v and the MLP's hidden layer together,
        # and one projects attention and MLP out: both count as ``mlp``.
        with jax.named_scope("mlp"):
            fused = jnp.einsum("bnd,dh->bnh", h_, bp["lin1"].astype(dt)) \
                + bp["lin1_b"].astype(dt)
        with jax.named_scope("proj"):
            qkv = fused[..., :3 * cfg.d_model]
            B_, N_ = h_.shape[:2]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = _rmsn(bp["q_norm"], q.reshape(B_, N_, cfg.num_heads, hd))
            k = _rmsn(bp["k_norm"], k.reshape(B_, N_, cfg.num_heads, hd))
            v = v.reshape(B_, N_, cfg.num_heads, hd)
        attn, stats = attend(q, k, v)
        with jax.named_scope("mlp"):
            mlp_h = fused[..., 3 * cfg.d_model:]
            both = jnp.concatenate([attn, jax.nn.gelu(mlp_h)], axis=-1)
            out = jnp.einsum("bnh,hd->bnd", both, bp["lin2"].astype(dt)) \
                + bp["lin2_b"].astype(dt)
        with jax.named_scope("ada"):
            x_ = x_ + g[:, None] * out
        return ctx.c(x_, ("batch", "seq", "embed")), stats

    if remat:
        double_body = jax.checkpoint(double_body)
        single_body = jax.checkpoint(single_body)

    (txt_x, img_x), d_stats = scan_layers(double_body, (txt_tok, img),
                                          params["double"])
    x = jnp.concatenate([txt_x, img_x], axis=1)
    x, s_stats = scan_layers(single_body, x, params["single"])
    img_x = x[:, L:]

    with jax.named_scope("final"):
        sh, sc = jnp.split(linear(params["final_mod"], jax.nn.silu(c)), 2,
                           axis=-1)
        img_x = layernorm({}, img_x) * (1 + sc[:, None]) + sh[:, None]
        out = linear(params["final"], img_x)  # (B, n_img, p*p*C)
        out = out.reshape(B, h, w, p, p, C).transpose(0, 1, 3, 2, 4, 5)
        out = out.reshape(B, Hl, Wl, C)
    if with_stats:
        # Mean over every block's attention call, double and single.
        return out, jax.tree.map(
            lambda a, b: jnp.mean(jnp.concatenate([a, b])),
            d_stats, s_stats)
    return out
