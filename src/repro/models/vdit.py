"""The paper's native setting: a video diffusion transformer (vDiT).

3-D (t, x, y) latent token grid, factorized RoPE whose channel groups
carry temporal / x / y information (paper §3.1 — HunyuanVideo splits the
128-dim head into 16/56/56), text tokens joined to the sequence for
joint self-attention (MMDiT-lite), adaLN conditioning on the timestep.

TimeRipple runs in full 3-D mode here: Δ checks along all three axes,
Eq. 4 threshold schedule over denoising steps, text tokens excluded from
snapping via ``grid_slice``.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.config.base import RippleConfig, VDiTConfig
from repro.distributed.sharding import NULL_CTX, ShardCtx
from repro.utils.loops import scan_layers
from repro.models.attention import attention_defs, mha_attention
from repro.models.common import (layernorm, linear, linear_defs, mlp,
                                 mlp_defs, rope_3d_angles,
                                 sincos_timestep_embed)
from repro.models.params import ParamDef, fan_in, normal, zeros, stack_layer_defs

_RIPPLE_OFF = RippleConfig()


def _block_defs(cfg: VDiTConfig):
    d = cfg.d_model
    hd = d // cfg.num_heads
    return {
        "attn": attention_defs(d, cfg.num_heads, cfg.num_heads, hd,
                               qk_norm=True),
        "mlp": mlp_defs(d, int(d * cfg.mlp_ratio), gated=True),
        "ada": {"w": ParamDef((d, 6 * d), ("embed", None), zeros),
                "b": ParamDef((6 * d,), (None,), zeros)},
    }


def vdit_defs(cfg: VDiTConfig):
    d = cfg.d_model
    p = cfg.patch
    tp = cfg.t_patch
    in_dim = tp * p * p * cfg.in_channels
    return {
        "patch": {"w": ParamDef((in_dim, d), (None, "embed"), fan_in()),
                  "b": ParamDef((d,), ("embed",), zeros)},
        "txt_proj": linear_defs(cfg.txt_dim, d, axes=(None, "embed")),
        "t_mlp1": linear_defs(256, d, axes=("embed", "mlp")),
        "t_mlp2": linear_defs(d, d, axes=("mlp", "embed")),
        "blocks": stack_layer_defs(_block_defs(cfg), cfg.num_layers),
        "final_ada": {"w": ParamDef((d, 2 * d), ("embed", None), zeros),
                      "b": ParamDef((2 * d,), (None,), zeros)},
        "final": linear_defs(d, in_dim, axes=("embed", None), init=zeros),
    }


def patchify_3d(x, t_patch, patch):
    """(B, T, H, W, C) -> (B, T/tp * H/p * W/p, tp*p*p*C), (t,y,x) order."""
    B, T, H, W, C = x.shape
    tp, p = t_patch, patch
    x = x.reshape(B, T // tp, tp, H // p, p, W // p, p, C)
    x = x.transpose(0, 1, 3, 5, 2, 4, 6, 7)
    return x.reshape(B, (T // tp) * (H // p) * (W // p), tp * p * p * C)


def unpatchify_3d(x, t_patch, patch, tg, hg, wg, out_ch):
    B = x.shape[0]
    tp, p = t_patch, patch
    x = x.reshape(B, tg, hg, wg, tp, p, p, out_ch)
    x = x.transpose(0, 1, 4, 2, 5, 3, 6, 7)
    return x.reshape(B, tg * tp, hg * p, wg * p, out_ch)


def vdit_apply(
    params: Dict,
    latents: jax.Array,    # (B, T_lat, H_lat, W_lat, C)
    t: jax.Array,          # (B,) diffusion time
    txt: jax.Array,        # (B, L_txt, txt_dim) — precomputed text embeds
    cfg: VDiTConfig,
    *,
    ripple: RippleConfig = _RIPPLE_OFF,
    step: Optional[jax.Array] = None,
    total_steps: Optional[int] = None,
    ctx: ShardCtx = NULL_CTX,
    compute_dtype=jnp.bfloat16,
    remat: bool = False,
    decision_state=None,
    with_stats: bool = False,
) -> jax.Array:
    """Apply the vDiT.  ``decision_state`` (optional) is the per-layer
    cross-step decision-cache state (DESIGN.md §13): a stacked
    :class:`~repro.core.decision_cache.CachedDecision` whose leading dim
    is ``num_layers`` (``launch.workloads.vdit_decision_state`` builds
    it).  Each layer's slice rides the scan-over-layers as a per-layer
    input and the updated slices are restacked, so the sampler can carry
    the whole thing through its denoising scan; the function then
    returns ``(out, new_decision_state)``.  ``with_stats`` appends a
    :class:`~repro.core.policy.RippleStats` whose fields are the means
    over the layers' attention calls."""
    dt = compute_dtype
    B, T, H, W, C = latents.shape
    tg, hg, wg = T // cfg.t_patch, H // cfg.patch, W // cfg.patch
    grid = (tg, hg, wg)
    n_img = tg * hg * wg
    L_txt = txt.shape[1]

    with jax.named_scope("embed"):
        img = patchify_3d(latents.astype(dt), cfg.t_patch, cfg.patch)
        img = jnp.einsum("bnd,df->bnf", img,
                         params["patch"]["w"].astype(dt)) \
            + params["patch"]["b"].astype(dt)
        txt_tok = linear(params["txt_proj"], txt.astype(dt))
        x = jnp.concatenate([txt_tok, img], axis=1)  # text first, then grid
        x = ctx.c(x, ("batch", "seq", "embed"))

        temb = sincos_timestep_embed(t, 256).astype(dt)
        c = jax.nn.silu(linear(params["t_mlp2"],
                               jax.nn.silu(linear(params["t_mlp1"], temb))))

        # Factorized 3-D RoPE; text tokens sit at grid origin with a pure
        # temporal index beyond the video range so they never alias a
        # frame.
        cos_g, sin_g = rope_3d_angles(grid, cfg.axes_dim)
        txt_pos = tg + jnp.arange(L_txt)
        ang_t = txt_pos[:, None].astype(jnp.float32) * (1.0 / (
            10000.0 ** (jnp.arange(cfg.axes_dim[0] // 2, dtype=jnp.float32)
                        / (cfg.axes_dim[0] // 2))))
        ang_rest = jnp.zeros((L_txt,
                              (cfg.axes_dim[1] + cfg.axes_dim[2]) // 2))
        cos_t = jnp.cos(jnp.concatenate([ang_t, ang_rest], axis=-1))
        sin_t = jnp.sin(jnp.concatenate([ang_t, ang_rest], axis=-1))
        rope_cos = jnp.concatenate([cos_t, cos_g], axis=0)
        rope_sin = jnp.concatenate([sin_t, sin_g], axis=0)
    hd = cfg.d_model // cfg.num_heads

    def block(x, bp, dcache):
        # Named scopes (``ada``, ``proj``/``attn`` inside mha_attention,
        # ``mlp``) label the step's device work for the profiler.
        with jax.named_scope("ada"):
            ada = linear(bp["ada"], c)
            sh1, sc1, g1, sh2, sc2, g2 = jnp.split(ada, 6, axis=-1)
            h_ = layernorm({}, x) * (1 + sc1[:, None]) + sh1[:, None]
        res = mha_attention(
            bp["attn"], h_, n_heads=cfg.num_heads, head_dim=hd, grid=grid,
            ripple=ripple, step=step, total_steps=total_steps,
            rope_cos=rope_cos, rope_sin=rope_sin,
            grid_slice=(L_txt, n_img), cached_decision=dcache,
            return_decision=dcache is not None, with_stats=with_stats,
            ctx=ctx)
        attn, *extra = res if (dcache is not None or with_stats) else (res,)
        if dcache is not None:
            dcache = extra.pop(0)
        stats = extra.pop(0) if with_stats else None
        with jax.named_scope("ada"):
            x = x + g1[:, None] * attn
            h_ = layernorm({}, x) * (1 + sc2[:, None]) + sh2[:, None]
        with jax.named_scope("mlp"):
            m = mlp(bp["mlp"], h_)
        with jax.named_scope("ada"):
            x = x + g2[:, None] * m
        return ctx.c(x, ("batch", "seq", "embed")), (dcache, stats)

    if decision_state is None:
        def body(x, bp):
            return block(x, bp, None)
        xs = params["blocks"]
    else:
        def body(x, layer_in):
            return block(x, layer_in[0], layer_in[1])
        xs = (params["blocks"], decision_state)

    if remat:
        body = jax.checkpoint(body)
    x, (new_state, stats) = scan_layers(body, x, xs)

    with jax.named_scope("final"):
        sh, sc = jnp.split(linear(params["final_ada"], c), 2, axis=-1)
        x = layernorm({}, x[:, L_txt:]) * (1 + sc[:, None]) + sh[:, None]
        x = linear(params["final"], x)
        out = unpatchify_3d(x, cfg.t_patch, cfg.patch, tg, hg, wg, C)
    res = (out,)
    if decision_state is not None:
        res += (new_state,)
    if with_stats:
        res += (jax.tree.map(jnp.mean, stats),)
    return res if len(res) > 1 else out
