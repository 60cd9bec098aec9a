"""Plain float32 building blocks shared by the configurations' references.

Nothing here imports the program.  Every matrix product runs at
``Precision.HIGHEST``, since a float32 product on a TPU otherwise runs in
one bfloat16 pass.  ``quant="fp8"`` rounds both inputs of every product
to float8 (e4m3, one scale per tensor) first: the control, one precision
below the bfloat16 the configurations compute in.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def freeze(m: Dict):
    """A hashable form of a configuration dict (lists become tuples), for
    ``static_argnames``."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(spec: str, a, b, quant: Optional[str] = None):
    if quant == "fp8":
        a, b = fp8(a), fp8(b)
    elif quant is not None:
        raise ValueError(f"unknown quantization {quant!r}")
    return jnp.einsum(spec, a, b, precision=HI)


def linear(w, b, x, quant=None):
    out = mm("...d,df->...f", x, w, quant)
    return out if b is None else out + b


def layernorm(x, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def rmsnorm(x, scale, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def timestep_embed(t: float, dim: int = 256):
    """Sinusoidal embedding ``[cos(t f), sin(t f)]``, ``f = 10000^(-i/half)``."""
    half = dim // 2
    f = np.exp(-math.log(10000.0) * np.arange(half) / half)
    a = float(t) * f
    return jnp.asarray(np.concatenate([np.cos(a), np.sin(a)]), jnp.float32)


def rope_tables(grid, axes_dim: Sequence[int], txt_pos: np.ndarray):
    """(cos, sin) of shape (L + T*H*W, sum(axes_dim)/2): text tokens first,
    rotating only their temporal channels at ``txt_pos``; grid tokens in
    (t, y, x) row-major order rotate their t, x and y channel groups."""
    T, H, W = grid
    tt, yy, xx = np.meshgrid(np.arange(T), np.arange(H), np.arange(W),
                             indexing="ij")

    def freqs(dim):
        return 1.0 / 10000.0 ** (np.arange(0, dim, 2) / dim)

    ang = np.concatenate(
        [c.reshape(-1)[:, None] * freqs(d)
         for c, d in zip((tt, xx, yy), axes_dim)], axis=1)
    rest = (axes_dim[1] + axes_dim[2]) // 2
    ang_txt = np.concatenate(
        [txt_pos[:, None] * freqs(axes_dim[0]),
         np.zeros((len(txt_pos), rest))], axis=1)
    ang = np.concatenate([ang_txt, ang], axis=0)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def rope(x, cos, sin):
    """x: (N, H, hd); the two halves of the head dim rotate as pairs."""
    c, s = cos[:, None], sin[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def eq4_theta(step: int, total: int, ripple: Dict) -> float:
    """Paper Eq. 4 as the configuration states it: dense before ``i_min``
    and on the last step, a linear ramp theta_min -> theta_max over
    [i_min, i_max], theta_max after."""
    if ripple.get("policy", "ripple") != "ripple":
        return 0.0
    i_min, i_max = ripple["i_min"], ripple["i_max"]
    if not (i_min <= step < total - 1):
        return 0.0
    lo, hi = ripple["theta_min"], ripple["theta_max"]
    th = lo + (step - i_min) * (hi - lo) / max(i_max - i_min, 1)
    return float(min(max(th, min(lo, hi)), max(lo, hi)))


_AXIS = {"t": 1, "y": 2, "x": 3}  # in (H, T, Hg, Wg, hd)


def snap(x, grid, theta: float, axes: Sequence[str]):
    """TimeRipple's Δ-check and snap on grid tokens x: (H, N, hd).

    Along each axis the tokens pair up (0, 1), (2, 3), ...; where half the
    absolute difference of a pair's entries (the window's population
    standard deviation) is below theta, the second entry takes the
    first's value.  The first axis in ``axes`` that passes supplies the
    value; every check reads the unsnapped operand."""
    H, N, hd = x.shape
    g = x.reshape(H, *grid, hd)
    out, claimed = g, jnp.zeros(g.shape, bool)
    for a in axes:
        ax = _AXIS[a]
        n = g.shape[ax]
        if n < 2:
            continue
        m = (n // 2) * 2
        head = jax.lax.slice_in_dim(g, 0, m, axis=ax)
        a0 = jax.lax.slice_in_dim(head, 0, m, 2, axis=ax)
        a1 = jax.lax.slice_in_dim(head, 1, m, 2, axis=ax)
        ok = jnp.abs(a0 - a1) / 2 < theta
        mask = jnp.stack([jnp.zeros_like(ok), ok], ax + 1)
        rep = jnp.stack([a0, a0], ax + 1)
        mask = mask.reshape(head.shape)
        rep = rep.reshape(head.shape)
        if m < n:
            tail = [(0, 0)] * g.ndim
            tail[ax] = (0, n - m)
            mask = jnp.pad(mask, tail)
            rep = jnp.pad(rep, tail)
        take = mask & ~claimed
        out = jnp.where(take, rep, out)
        claimed = claimed | mask
    return out.reshape(H, N, hd)


def attention(q, k, v, quant=None, chunk: int = 256):
    """softmax(q k^T / sqrt(hd)) v over (H, N, hd), in query chunks so the
    (H, chunk, N) logits are all that is ever held."""
    H, N, hd = q.shape
    pad = (-N) % chunk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    qc = qp.reshape(H, -1, chunk, hd).transpose(1, 0, 2, 3)
    kq = fp8(k) if quant == "fp8" else k
    vq = fp8(v) if quant == "fp8" else v

    def one(qi):
        if quant == "fp8":
            qi = fp8(qi)
        s = jnp.einsum("hqd,hkd->hqk", qi, kq, precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            p = fp8(p)
        return jnp.einsum("hqk,hkd->hqd", p, vq, precision=HI)

    out = jax.lax.map(one, qc)  # (chunks, H, chunk, hd)
    return out.transpose(1, 0, 2, 3).reshape(H, N + pad, hd)[:, :N]


# -- samplers: float64 coefficients on the served float32 time grids --------


def _grid(start: float, stop: float, num: int) -> np.ndarray:
    """The time grid as the served sampler states it: ``linspace`` in
    float32 (so the DDIM grid 999..0 over 10 steps holds 665, not 666,
    after truncation to integers)."""
    return np.asarray(jnp.linspace(start, stop, num, dtype=jnp.float32))


def ddim_coeffs(step: int, total: int, train_steps: int = 1000,
                beta_start: float = 1e-4, beta_end: float = 0.02):
    """Deterministic DDIM (eta 0) from a linear-beta DDPM schedule on the
    integer timesteps ``int(linspace(999, 0, total))``: returns (t, a, b)
    with x_next = a x + b eps."""
    ab = np.cumprod(1.0 - np.linspace(beta_start, beta_end, train_steps))
    ts = _grid(train_steps - 1, 0, total).astype(np.int32)
    t = int(ts[step])
    ab_t = ab[t]
    ab_prev = ab[int(ts[step + 1])] if step + 1 < total else 1.0
    a = math.sqrt(ab_prev) / math.sqrt(ab_t)
    b = math.sqrt(1.0 - ab_prev) - a * math.sqrt(1.0 - ab_t)
    return float(t), a, b


def euler_coeffs(step: int, total: int):
    """Rectified-flow Euler step on ``linspace(1, 0, total + 1)``:
    returns (t, 1, t_next - t) with x_next = x + (t_next - t) v."""
    ts = _grid(1.0, 0.0, total + 1).astype(np.float64)
    return float(ts[step]), 1.0, float(ts[step + 1] - ts[step])
