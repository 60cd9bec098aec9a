"""Plain float32 reference of ``vdit-paper``: one denoising step.

The vDiT as the configuration file states it: video latents patched
1x2x2 onto a (t, y, x) token grid, text embeddings projected and joined in
front, a sinusoidal timestep embedding through a two-layer SiLU MLP, and
uniform blocks of adaLN-modulated joint self-attention (RMS-normed q and
k, factorized 3-D RoPE over 16/56/56 head channels) and a gated SiLU MLP,
then an adaLN-modulated output projection.  Attention inputs go through
TimeRipple's Δ-check and snap (``refops.snap``) on the grid tokens; the
sampler is deterministic DDIM.  The served path applies no
classifier-free guidance, so neither does this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import refops as R
from bench.weights import layer_value


def grid(m):
    return (m["frames"] // m["t_vae_factor"] // m["t_patch"],
            m["img_res"] // m["vae_factor"] // m["patch"],
            m["img_res"] // m["vae_factor"] // m["patch"])


def latent_shape(m):
    T, H, W = grid(m)
    return (T * m["t_patch"], H * m["patch"], W * m["patch"],
            m["in_channels"])


def param_table(m):
    """{path: (shape, stacked over layers)} in the served tree's layout."""
    d, L = m["d_model"], m["num_layers"]
    F = int(d * m["mlp_ratio"])
    hd = d // m["num_heads"]
    pin = m["t_patch"] * m["patch"] ** 2 * m["in_channels"]
    top = {"patch/w": (pin, d), "patch/b": (d,),
           "txt_proj/w": (m["txt_dim"], d), "txt_proj/b": (d,),
           "t_mlp1/w": (256, d), "t_mlp1/b": (d,),
           "t_mlp2/w": (d, d), "t_mlp2/b": (d,),
           "final_ada/w": (d, 2 * d), "final_ada/b": (2 * d,),
           "final/w": (d, pin), "final/b": (pin,)}
    blk = {"attn/wq": (d, d), "attn/wk": (d, d), "attn/wv": (d, d),
           "attn/wo": (d, d), "attn/q_norm/scale": (hd,),
           "attn/k_norm/scale": (hd,), "mlp/wi_gate": (d, F),
           "mlp/wi_up": (d, F), "mlp/wo": (F, d), "ada/w": (d, 6 * d),
           "ada/b": (6 * d,)}
    out = {p: (s, False) for p, s in top.items()}
    out.update({f"blocks/{p}": ((L,) + s, True) for p, s in blk.items()})
    return out


def _weights(key, prefix, table, layer=None):
    return {p[len(prefix):]: layer_value(key, p, s if layer is None
                                         else s[1:], layer)
            for p, (s, st) in table.items()
            if p.startswith(prefix) and st == (layer is not None)}


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _embed(key, x, txt, temb, m, quant):
    mdict = dict(m)
    w = _weights(key, "", param_table(mdict))
    T, H, W = grid(mdict)
    tp, p, C = mdict["t_patch"], mdict["patch"], mdict["in_channels"]
    img = x.reshape(T, tp, H, p, W, p, C).transpose(0, 2, 4, 1, 3, 5, 6)
    img = img.reshape(T * H * W, tp * p * p * C)
    img = R.linear(w["patch/w"], w["patch/b"], img, quant)
    tok = R.linear(w["txt_proj/w"], w["txt_proj/b"], txt, quant)
    c = R.silu(R.linear(w["t_mlp1/w"], w["t_mlp1/b"], temb, quant))
    c = R.silu(R.linear(w["t_mlp2/w"], w["t_mlp2/b"], c, quant))
    return jnp.concatenate([tok, img], 0), c


@functools.partial(jax.jit, static_argnames=("m", "quant", "axes"))
def _block(key, layer, x, c, cos, sin, theta, m, quant, axes):
    mdict = dict(m)
    w = _weights(key, "blocks/", param_table(mdict), layer)
    nh = mdict["num_heads"]
    d = mdict["d_model"]
    hd = d // nh
    L = mdict["txt_tokens"]
    N = x.shape[0]
    sh1, sc1, g1, sh2, sc2, g2 = jnp.split(
        R.linear(w["ada/w"], w["ada/b"], c, quant), 6)
    h = R.layernorm(x) * (1 + sc1) + sh1
    q = R.mm("nd,df->nf", h, w["attn/wq"], quant).reshape(N, nh, hd)
    k = R.mm("nd,df->nf", h, w["attn/wk"], quant).reshape(N, nh, hd)
    v = R.mm("nd,df->nf", h, w["attn/wv"], quant).reshape(N, nh, hd)
    q = R.rope(R.rmsnorm(q, w["attn/q_norm/scale"]), cos, sin)
    k = R.rope(R.rmsnorm(k, w["attn/k_norm/scale"]), cos, sin)
    q, k, v = (a.transpose(1, 0, 2) for a in (q, k, v))
    g = grid(mdict)
    q = q.at[:, L:].set(R.snap(q[:, L:], g, theta, axes))
    k = k.at[:, L:].set(R.snap(k[:, L:], g, theta, axes))
    a = R.attention(q, k, v, quant).transpose(1, 0, 2).reshape(N, d)
    x = x + g1 * R.mm("nd,df->nf", a, w["attn/wo"], quant)
    h = R.layernorm(x) * (1 + sc2) + sh2
    u = R.silu(R.mm("nd,df->nf", h, w["mlp/wi_gate"], quant)) \
        * R.mm("nd,df->nf", h, w["mlp/wi_up"], quant)
    return x + g2 * R.mm("nf,fd->nd", u, w["mlp/wo"], quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _final(key, x, c, m, quant):
    mdict = dict(m)
    w = _weights(key, "", param_table(mdict))
    sh, sc = jnp.split(R.linear(w["final_ada/w"], w["final_ada/b"], c, quant), 2)
    x = R.layernorm(x[mdict["txt_tokens"]:]) * (1 + sc) + sh
    out = R.linear(w["final/w"], w["final/b"], x, quant)
    T, H, W = grid(mdict)
    tp, p, C = mdict["t_patch"], mdict["patch"], mdict["in_channels"]
    out = out.reshape(T, H, W, tp, p, p, C).transpose(0, 3, 1, 4, 2, 5, 6)
    return out.reshape(latent_shape(mdict))


def model_out(key, m, ripple, x, step, total, txt, quant=None):
    """The model's eps for one request at ``step`` of ``total``, and the
    DDIM coefficients (a, b) with x_next = a x + b eps."""
    t, a, b = R.ddim_coeffs(step, total)
    mk = R.freeze(m)
    T, H, W = grid(m)
    cos, sin = R.rope_tables((T, H, W), m["axes_dim"],
                             T + np.arange(m["txt_tokens"]))
    theta = jnp.float32(R.eq4_theta(step, total, ripple))
    h, c = _embed(key, jnp.asarray(x, jnp.float32),
                  jnp.asarray(txt, jnp.float32), R.timestep_embed(t),
                  m=mk, quant=quant)
    for layer in range(m["num_layers"]):
        h = _block(key, layer, h, c, cos, sin, theta, m=mk, quant=quant,
                   axes=tuple(ripple["axes"]))
    return _final(key, h, c, m=mk, quant=quant), a, b
