"""Compute-savings accounting for TimeRipple.

The paper quantifies its benefit as the fraction of *partial attention
scores* (per-channel products ``q_{i,c}·k_{j,c}`` of the QKᵀ matmul)
obtained by copying instead of computing — e.g. "TIMERIPPLE_85%".  A
partial product (i, j, c) must be computed only when **neither** operand
entry is a snapped copy:

    computed(c) = (1 − fq_c) · (1 − fk_c)
    saved       = 1 − mean_c computed(c)

where ``fq_c``/``fk_c`` are the snapped fractions of Q/K at channel c.
(If ``q[i,c]`` is a copy of ``q[i',c]`` the whole row i of the channel-c
partial map equals row i'; if ``k[j,c]`` is a copy, column j equals its
representative column.)

We additionally report the *structural* savings realized by the TPU
collapse path (DESIGN.md §4), which also saves softmax+AV work for fully
collapsed pairs — the paper's accounting never includes AV.  The two
numbers are kept separate everywhere.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def snapped_fractions(mask: jax.Array) -> jax.Array:
    """Per-channel snapped fraction (..., d) of a boolean snap mask
    (..., N, d): the ``fq_c`` / ``fk_c`` above."""
    return jnp.mean(mask.astype(jnp.float32), axis=-2)


def savings_from_fractions(fq: jax.Array, fk: jax.Array) -> jax.Array:
    """:func:`partial_score_savings` from per-channel snapped fractions."""
    computed = jnp.mean((1.0 - fq) * (1.0 - fk), axis=-1)
    return 1.0 - jnp.mean(computed)


def partial_score_savings(q_mask: jax.Array, k_mask: jax.Array) -> jax.Array:
    """Paper-faithful savings ratio from boolean snap masks (..., N, d)."""
    return savings_from_fractions(snapped_fractions(q_mask),
                                  snapped_fractions(k_mask))


def pair_collapse_fractions(q_mask: jax.Array, k_mask: jax.Array,
                            window: int = 2) -> Tuple[jax.Array, jax.Array]:
    """Fractions of Q windows / K windows whose followers are fully snapped.

    A window collapses only when every non-representative member is
    snapped on **all** channels; masks are (..., N, d) with tokens in
    pair-major order along the collapse axis (caller's responsibility).
    """

    def frac(mask):
        *lead, N, d = mask.shape
        n = N // window
        m = mask[..., : n * window, :].reshape(*lead, n, window, d)
        followers = m[..., 1:, :]  # representative is never "snapped"
        full = jnp.all(followers, axis=(-1, -2))
        return jnp.mean(full.astype(jnp.float32))

    return frac(q_mask), frac(k_mask)


def collapse_savings(q_mask: jax.Array, k_mask: jax.Array, window: int = 2) -> jax.Array:
    """Structural FLOP savings of the collapse execution path.

    QKᵀ cost scales with rows_computed × cols_computed; AV with
    rows_computed × cols_computed as well (collapsed columns carry
    pair-summed V).  With fraction pq of Q windows and pk of K windows
    collapsed, each collapsed window does 1/window of the work:

        rows = 1 − pq·(window−1)/window,  cols = 1 − pk·(window−1)/window
        savings = 1 − rows · cols
    """
    pq, pk = pair_collapse_fractions(q_mask, k_mask, window)
    shrink = (window - 1) / window
    rows = 1.0 - pq * shrink
    cols = 1.0 - pk * shrink
    return 1.0 - rows * cols


def attention_flops(n_q: int, n_k: int, d: int, d_v: int, heads: int,
                    batch: int = 1) -> int:
    """Dense self-attention matmul FLOPs (QKᵀ + AV), multiply+add = 2."""
    qk = 2 * n_q * n_k * d
    av = 2 * n_q * n_k * d_v
    return batch * heads * (qk + av)


def theoretical_speedup(attn_fraction: float, savings: jax.Array) -> jax.Array:
    """End-to-end speedup the paper reports: self-attention is
    ``attn_fraction`` of total latency (paper Fig. 4: ~0.78 on average)
    and ``savings`` of it is skipped; the rest of the model is untouched.
    """
    return 1.0 / (1.0 - attn_fraction * savings)
