"""Seeded random weights, made by the benchmark and not by the program.

Every leaf is drawn from the run's seed and the leaf's path alone, so the
program's parameter tree and the plain reference's table of shapes get
the same values without either taking anything from the other.  A leaf
stacked over layers is drawn layer by layer, so the reference can make
one layer's weights at a time.

Rules, by the leaf's last path component and its per-layer rank:

* a query or key norm's gain (``q_norm/scale``, ``k_norm/scale``):
  ``QK_GAIN (1 + 0.1 N(0, 1))``;
* any other name ending in ``scale`` (a norm's gain): ``1 + 0.1 N(0, 1)``;
* any other rank-1 leaf (a bias): ``0.02 N(0, 1)``;
* a matrix ``(d_in, d_out)``: ``N(0, g^2 / d_in)``, with ``g`` the
  leaf's entry in ``GAINS`` or 1.

No leaf is zero, unlike a fresh DiT init whose adaLN gates and final
projection start at zero and would make every output zero whatever the
attention computes.

``QK_GAIN`` sets how sharp attention is.  Query and key rows are
RMS-normed, so with unit gains the logits ``q.k / sqrt(head_dim)`` have
unit variance, and softmax over tens of thousands of random tokens is
all but uniform: attention would return the mean of the values whatever
the kernel or the snap did, and no comparison could see either.  A
trained model's attention is far from uniform; with gains of ``QK_GAIN``
the logits' standard deviation is ``QK_GAIN ** 2``.

``GAINS["patch/w"]`` scales the vDiT's latent input projection.  Random
weights predict no noise, so DDIM multiplies the served latents by up to
``1 / sqrt(alpha_bar_T)``, about 156: their RMS is about 10 at step 2
and 200 by the end.  At unit gain the embedded latents then outweigh
every block's output in the residual stream, and the model's output
hardly depends on attention at all.  At 0.1 the embedded latents of the
compared steps have about the scale of the blocks' outputs, as a trained
model's unit-RMS latents would.
"""

from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

QK_GAIN = 2.0
QK_NORMS = ("q_norm/scale", "k_norm/scale")
GAINS: Dict[str, float] = {"patch/w": 0.1}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` alone keeps
    only the low 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def layer_value(key: jax.Array, path: str, shape: Tuple[int, ...],
                layer: int | jax.Array | None = None) -> jax.Array:
    """One leaf (or one layer of a stacked leaf) in float32."""
    k = _leaf_key(key, path)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if path.endswith(QK_NORMS):
        return QK_GAIN * (1.0 + 0.1 * z)
    if path.endswith("scale"):
        return 1.0 + 0.1 * z
    if len(shape) == 1:
        return 0.02 * z
    return z * GAINS.get(path, 1.0) * (1.0 / shape[0]) ** 0.5


def leaf_value(key: jax.Array, path: str, shape: Tuple[int, ...],
               stacked: bool) -> jax.Array:
    if not stacked:
        return layer_value(key, path, shape)
    return jax.vmap(lambda i: layer_value(key, path, shape[1:], i))(
        jnp.arange(shape[0]))


def make(shapes: Dict[str, Tuple[Tuple[int, ...], bool]], seed: int):
    """All leaves ``{path: (shape, stacked)}`` in one jitted call on the
    default device; returns ``{path: array}``."""
    items = sorted(shapes.items())

    @jax.jit
    def build(key):
        return {p: leaf_value(key, p, s, st) for p, (s, st) in items}

    return build(seed_key(seed))
