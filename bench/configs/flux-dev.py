"""Plain float32 reference of ``flux-dev``: one denoising step.

FLUX.1's MMDiT as the configuration file states it: image latents patched
2x2 (64 input channels), text embeddings projected, a conditioning vector
from the sinusoidal timestep embedding through a two-layer SiLU MLP plus
the projected pooled-text vector; double-stream blocks (separate image
and text weights, adaLN-modulated, joint attention over text then image
tokens with RMS-normed q and k and 3-D RoPE, tanh-GELU MLPs) then
single-stream blocks (one fused input projection for q, k, v and the
MLP, one fused output projection), then an adaLN-modulated output
projection.  Attention inputs go through TimeRipple's Δ-check and snap
(``refops.snap``, x and y axes) on the image tokens; the sampler is the
rectified-flow Euler step.  The server feeds a zero pooled-text vector,
and the model has no guidance embedding, so neither does this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import refops as R
from bench.weights import layer_value

POOLED = 768


def grid(m):
    s = m["latent_res"] // m["patch"]
    return (1, s, s)


def latent_shape(m):
    r = m["latent_res"]
    return (r, r, m["in_channels"])


def param_table(m):
    """{path: (shape, stacked over layers)} in the served tree's layout."""
    d = m["d_model"]
    F = int(d * m["mlp_ratio"])
    hd = d // m["num_heads"]
    pin = m["patch"] ** 2 * m["in_channels"]
    top = {"img_in/w": (pin, d), "img_in/b": (d,),
           "txt_in/w": (m["txt_dim"], d), "txt_in/b": (d,),
           "t_mlp1/w": (256, d), "t_mlp1/b": (d,),
           "t_mlp2/w": (d, d), "t_mlp2/b": (d,),
           "vec_in/w": (POOLED, d), "vec_in/b": (d,),
           "final_mod/w": (d, 2 * d), "final_mod/b": (2 * d,),
           "final/w": (d, pin), "final/b": (pin,)}
    stream = {"mod/w": (d, 6 * d), "mod/b": (6 * d,),
              "wqkv": (d, 3 * d), "wo": (d, d), "mlp_in": (d, F),
              "mlp_in_b": (F,), "mlp_out": (F, d), "mlp_out_b": (d,),
              "q_norm/scale": (hd,), "k_norm/scale": (hd,)}
    single = {"mod/w": (d, 3 * d), "mod/b": (3 * d,),
              "lin1": (d, 3 * d + F), "lin1_b": (3 * d + F,),
              "lin2": (d + F, d), "lin2_b": (d,),
              "q_norm/scale": (hd,), "k_norm/scale": (hd,)}
    out = {p: (s, False) for p, s in top.items()}
    nd, ns = m["n_double_blocks"], m["n_single_blocks"]
    for side in ("img", "txt"):
        out.update({f"double/{side}/{p}": ((nd,) + s, True)
                    for p, s in stream.items()})
    out.update({f"single/{p}": ((ns,) + s, True) for p, s in single.items()})
    return out


def _weights(key, prefix, table, layer=None):
    return {p[len(prefix):]: layer_value(key, p, s if layer is None
                                         else s[1:], layer)
            for p, (s, st) in table.items()
            if p.startswith(prefix) and st == (layer is not None)}


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _embed(key, x, txt, temb, m, quant):
    m = dict(m)
    w = _weights(key, "", param_table(m))
    _, h, wd = grid(m)
    p, C = m["patch"], m["in_channels"]
    img = x.reshape(h, p, wd, p, C).transpose(0, 2, 1, 3, 4)
    img = R.linear(w["img_in/w"], w["img_in/b"],
                   img.reshape(h * wd, p * p * C), quant)
    tok = R.linear(w["txt_in/w"], w["txt_in/b"], txt, quant)
    c = R.linear(w["t_mlp2/w"], w["t_mlp2/b"], R.silu(
        R.linear(w["t_mlp1/w"], w["t_mlp1/b"], temb, quant)), quant)
    vec = jnp.zeros((POOLED,), jnp.float32)
    c = R.silu(c + R.linear(w["vec_in/w"], w["vec_in/b"], vec, quant))
    return tok, img, c


def _qkv(q, k, v, qs, ks, nh):
    N = q.shape[0]
    hd = q.shape[-1] // nh
    q = R.rmsnorm(q.reshape(N, nh, hd), qs)
    k = R.rmsnorm(k.reshape(N, nh, hd), ks)
    return q, k, v.reshape(N, nh, hd)


def _joint(q, k, v, cos, sin, m, theta, axes, quant):
    """q, k, v: (N, H, hd) text first; returns (N, H*hd)."""
    L = m["txt_tokens"]
    q, k = R.rope(q, cos, sin), R.rope(k, cos, sin)
    q, k, v = (a.transpose(1, 0, 2) for a in (q, k, v))
    g = grid(m)
    q = q.at[:, L:].set(R.snap(q[:, L:], g, theta, axes))
    k = k.at[:, L:].set(R.snap(k[:, L:], g, theta, axes))
    out = R.attention(q, k, v, quant)
    return out.transpose(1, 0, 2).reshape(q.shape[1], -1)


@functools.partial(jax.jit, static_argnames=("m", "quant", "axes"))
def _double(key, layer, tok, img, c, cos, sin, theta, m, quant, axes):
    m = dict(m)
    table = param_table(m)
    nh = m["num_heads"]
    L = tok.shape[0]
    sc = R.silu(c)
    pre, mods, qkv = {}, {}, {}
    w = {}
    for side, x in (("txt", tok), ("img", img)):
        w[side] = _weights(key, f"double/{side}/", table, layer)
        ws = w[side]
        sh, s1, g, sh2, s2, g2 = jnp.split(
            R.linear(ws["mod/w"], ws["mod/b"], sc, quant), 6)
        mods[side] = (g, sh2, s2, g2)
        h = R.layernorm(x) * (1 + s1) + sh
        q, k, v = jnp.split(R.mm("nd,df->nf", h, ws["wqkv"], quant), 3, -1)
        qkv[side] = _qkv(q, k, v, ws["q_norm/scale"], ws["k_norm/scale"],
                         nh)
    q, k, v = (jnp.concatenate([qkv["txt"][i], qkv["img"][i]], 0)
               for i in range(3))
    a = _joint(q, k, v, cos, sin, m, theta, axes, quant)
    out = {}
    for side, x, part in (("txt", tok, a[:L]), ("img", img, a[L:])):
        ws = w[side]
        g, sh2, s2, g2 = mods[side]
        x = x + g * R.mm("nd,df->nf", part, ws["wo"], quant)
        h = R.layernorm(x) * (1 + s2) + sh2
        u = R.gelu_tanh(R.mm("nd,df->nf", h, ws["mlp_in"], quant)
                        + ws["mlp_in_b"])
        out[side] = x + g2 * (R.mm("nf,fd->nd", u, ws["mlp_out"], quant)
                              + ws["mlp_out_b"])
    return out["txt"], out["img"]


@functools.partial(jax.jit, static_argnames=("m", "quant", "axes"))
def _single(key, layer, x, c, cos, sin, theta, m, quant, axes):
    m = dict(m)
    w = _weights(key, "single/", param_table(m), layer)
    d, nh = m["d_model"], m["num_heads"]
    sh, s1, g = jnp.split(R.linear(w["mod/w"], w["mod/b"], R.silu(c),
                                   quant), 3)
    h = R.layernorm(x) * (1 + s1) + sh
    fused = R.mm("nd,df->nf", h, w["lin1"], quant) + w["lin1_b"]
    q, k, v = jnp.split(fused[:, :3 * d], 3, -1)
    q, k, v = _qkv(q, k, v, w["q_norm/scale"], w["k_norm/scale"], nh)
    a = _joint(q, k, v, cos, sin, m, theta, axes, quant)
    both = jnp.concatenate([a, R.gelu_tanh(fused[:, 3 * d:])], -1)
    return x + g * (R.mm("nf,fd->nd", both, w["lin2"], quant) + w["lin2_b"])


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _final(key, img, c, m, quant):
    m = dict(m)
    w = _weights(key, "", param_table(m))
    sh, s1 = jnp.split(R.linear(w["final_mod/w"], w["final_mod/b"],
                                R.silu(c), quant), 2)
    out = R.linear(w["final/w"], w["final/b"],
                   R.layernorm(img) * (1 + s1) + sh, quant)
    _, h, wd = grid(m)
    p, C = m["patch"], m["in_channels"]
    out = out.reshape(h, wd, p, p, C).transpose(0, 2, 1, 3, 4)
    return out.reshape(latent_shape(m))


def model_out(key, m, ripple, x, step, total, txt, quant=None):
    """The model's velocity for one request at ``step`` of ``total``, and
    the Euler coefficients (a, b) with x_next = a x + b v."""
    t, a, b = R.euler_coeffs(step, total)
    mk = R.freeze(m)
    L = m["txt_tokens"]
    cos, sin = R.rope_tables(grid(m), m["axes_dim"], 1 + np.arange(L))
    theta = jnp.float32(R.eq4_theta(step, total, ripple))
    axes = tuple(ripple["axes"])
    tok, img, c = _embed(key, jnp.asarray(x, jnp.float32),
                         jnp.asarray(txt, jnp.float32), R.timestep_embed(t),
                         m=mk, quant=quant)
    for layer in range(m["n_double_blocks"]):
        tok, img = _double(key, layer, tok, img, c, cos, sin, theta, m=mk,
                           quant=quant, axes=axes)
    h = jnp.concatenate([tok, img], 0)
    for layer in range(m["n_single_blocks"]):
        h = _single(key, layer, h, c, cos, sin, theta, m=mk, quant=quant,
                    axes=axes)
    return _final(key, h[L:], c, m=mk, quant=quant), a, b
